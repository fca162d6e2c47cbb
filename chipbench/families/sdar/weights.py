"""Weights of the ``sdar`` family from ``--seed``, a layer at a time.

At the published widths a layer is 0.623 B parameters (2.5 GB in float32),
so the weights are never all held: ``make`` returns a ``Weights`` that
makes one layer, or the top (embedding, final norm, head), on the device
when asked, always the same arrays for the same seed. The plain reference
asks in float32 and frees each layer after it; the family's
``program.py`` asks in the dtype of each program leaf, and the rounding
happens inside the jitted maker.

Layout: dense weights are ``(in, out)``; the experts are stacked on a
leading axis. Initialisation (``assumed`` in the configuration): every
matrix Normal(0, ``initializer_range``) rounded to bfloat16, the dtype the
model is published in (so the reference's float32 arrays and the
program's bfloat16 leaves hold the same values); RMSNorm gains 1 (the
block's two, QK-norm's two, the final one). The router's weight is drawn
alike and kept float32, never rounded: the program routes in float32.

``sizes(model)`` reads the ``model`` group of a configuration: the
published ``config.json`` keys with the depth cut, and the block-diffusion
settings the config does not give.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: leaves the program keeps in float32 whatever its dtype
FLOAT32_IN_PROGRAM = ("router",)


def sizes(model):
    if model["mlp_only_layers"] or int(model["decoder_sparse_step"]) != 1:
        raise SystemExit("chipbench: the sdar family routes every layer")
    if model["tie_word_embeddings"] or model["attention_bias"]:
        raise SystemExit("chipbench: the sdar family has an untied head "
                         "and no bias")
    return {
        "V": int(model["vocab_size"]), "D": int(model["hidden_size"]),
        "L": int(model["num_hidden_layers"]),
        "H": int(model["num_attention_heads"]),
        "Hkv": int(model["num_key_value_heads"]),
        "dh": int(model["head_dim"]),
        "E": int(model["num_experts"]), "K": int(model["num_experts_per_tok"]),
        "FE": int(model["moe_intermediate_size"]),
        "norm_topk": bool(model["norm_topk_prob"]),
        "theta": float(model["rope_theta"]),
        "eps": float(model["rms_norm_eps"]),
        "std": float(model["initializer_range"]),
        "B": int(model["block_length"]), "steps": int(model["denoise_steps"]),
        "mask": int(model["mask_token_id"]),
    }


def layer_leaves(s, layer):
    """``[(name, shape, kind)]`` of one layer; ``kind`` is how it is
    drawn: ``normal`` (the initializer's range) or ``one``."""
    del layer                                  # every layer is alike
    d, dh, e, f = s["D"], s["dh"], s["E"], s["FE"]
    return [("attn_norm", (d,), "one"), ("w_q", (d, s["H"] * dh), "normal"),
            ("w_k", (d, s["Hkv"] * dh), "normal"),
            ("w_v", (d, s["Hkv"] * dh), "normal"),
            ("q_norm", (dh,), "one"), ("k_norm", (dh,), "one"),
            ("w_o", (s["H"] * dh, d), "normal"), ("ffn_norm", (d,), "one"),
            ("router", (d, e), "normal"), ("e_gate", (e, d, f), "normal"),
            ("e_up", (e, d, f), "normal"), ("e_down", (e, f, d), "normal")]


def top_leaves(s):
    return [("embed", (s["V"], s["D"]), "normal"),
            ("final_norm", (s["D"],), "one"),
            ("head", (s["D"], s["V"]), "normal")]


def parameter_count(s):
    n = sum(int(np.prod(sh)) for _, sh, _ in top_leaves(s))
    for i in range(s["L"]):
        n += sum(int(np.prod(sh)) for _, sh, _ in layer_leaves(s, i))
    return n


def key_data(seed, stream):
    """Raw key data (uint32[4]) from any whole number: ``--seed`` may
    pass 2**31, which a 32-bit ``PRNGKey`` argument cannot hold."""
    return jnp.asarray(np.random.SeedSequence(
        [int(seed), int(stream)]).generate_state(4), dtype=jnp.uint32)


@functools.lru_cache(maxsize=None)
def _maker(leaves, std, low):
    """One jitted call that makes ``leaves``: the float32 leaves as they
    are drawn, every other rounded to what bfloat16 holds and, with
    ``low``, a bfloat16 array."""
    def make(key_data):
        # the "rbg" generator: XLA's own bit generator, a layer's 0.62 B
        # values in a fraction of threefry's time on the chip
        keys = jax.random.split(
            jax.random.wrap_key_data(key_data, impl="rbg"), len(leaves))
        out = {}
        for k, (name, shape, kind) in zip(keys, leaves):
            if kind == "one":
                a = jnp.ones(shape, jnp.float32)
            else:
                a = std * jax.random.normal(k, shape, jnp.float32)
            if name not in FLOAT32_IN_PROGRAM:
                a = a.astype(jnp.bfloat16)
                if not low:
                    a = a.astype(jnp.float32)
            out[name] = a
        return out
    return jax.jit(make)


class Weights:
    """The seeded weights, made a part at a time. ``for_program=True``
    gives each leaf in the dtype the program holds it in; the reference
    takes the same values in float32."""

    def __init__(self, model, seed):
        self.s, self.seed = sizes(model), seed

    def layer(self, i, for_program=False):
        return _maker(tuple(layer_leaves(self.s, i)), self.s["std"],
                      bool(for_program))(key_data(self.seed, 1 + i))

    def top(self, for_program=False):
        return _maker(tuple(top_leaves(self.s)), self.s["std"],
                      bool(for_program))(key_data(self.seed, 0))


def make(model, seed):
    return Weights(model, seed)
