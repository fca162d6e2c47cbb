"""Weights of the ``xing4`` family from ``--seed``, a layer at a time.

At the published widths a routed layer is 0.745 B parameters (3 GB in
float32), so the weights are never all held: ``make`` returns a ``Weights``
that makes one layer, or the top (embedding, final norm, head), on the
device when asked, always the same arrays for the same seed. The plain
reference asks in float32 and frees each layer after it; the family's
``program.py`` asks in the dtype of each program leaf, and the rounding
happens inside the jitted maker.

Layout: dense weights are ``(in, out)``; the routed experts are stacked on
a leading axis. Initialisation (``assumed`` in the configuration): every
matrix Normal(0, ``initializer_range``) rounded to bfloat16, the dtype the
model is published in (so the reference's float32 arrays and the
program's bfloat16 leaves hold the same values); RMSNorm gains 1. Float32
leaves, never rounded: the router's weight and bias and each
hyper-connection's ``phi`` Normal(0, ``initializer_range``), its ``alpha``
1, its ``b`` Normal(0, 1), so that the three maps are far from constant and
follow the token.

``sizes(model)`` reads the ``model`` group of a configuration: the
published ``config.json`` keys with the depth cut.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: leaves the program keeps in float32 whatever its dtype
FLOAT32_IN_PROGRAM = ("a_phi", "a_alpha", "a_b", "f_phi", "f_alpha", "f_b",
                      "router", "router_bias")


def sizes(model):
    ys = model["rope_scaling"]
    if int(model["num_nextn_predict_layers"]) != 0:
        raise SystemExit("chipbench: the xing4 family builds no "
                         "next-token-prediction module")
    return {
        "V": int(model["vocab_size"]), "D": int(model["hidden_size"]),
        "L": int(model["num_hidden_layers"]),
        "H": int(model["num_attention_heads"]),
        "dn": int(model["qk_nope_head_dim"]),
        "dr": int(model["qk_rope_head_dim"]),
        "dv": int(model["v_head_dim"]),
        "rq": int(model["q_lora_rank"]), "rkv": int(model["kv_lora_rank"]),
        "theta": float(model["rope_theta"]),
        "yarn": {"factor": float(ys["factor"]),
                 "original": int(ys["original_max_position_embeddings"]),
                 "beta_fast": float(ys["beta_fast"]),
                 "beta_slow": float(ys["beta_slow"]),
                 "mscale": float(ys["mscale"]),
                 "mscale_all_dim": float(ys["mscale_all_dim"])},
        "n": int(model["hc_mult"]), "iters": int(model["hc_sinkhorn_iters"]),
        "hc_eps": float(model["hc_eps"]),
        "clamp": [float(model["mhc_h_res_clamp_min"]),
                  float(model["mhc_h_res_clamp_max"])],
        "F": int(model["intermediate_size"]),
        "FE": int(model["moe_intermediate_size"]),
        "E": int(model["n_routed_experts"]),
        "K": int(model["num_experts_per_tok"]),
        "shared": int(model["n_shared_experts"]),
        "first_dense": int(model["first_k_dense_replace"]),
        "gate_scale": float(model["routed_scaling_factor"]),
        "eps": float(model["rms_norm_eps"]),
        "std": float(model["initializer_range"]),
    }


def layer_leaves(s, layer):
    """``[(name, shape, kind)]`` of one layer; ``kind`` is how it is
    drawn: ``normal`` (the initializer's range), ``unit`` (Normal(0, 1)),
    ``one``."""
    d, n = s["D"], s["n"]
    maps = 2 * n + n * n
    out = []
    for w in ("a", "f"):
        out += [(w + "_phi", (maps, n * d), "normal"),
                (w + "_alpha", (3,), "one"), (w + "_b", (maps,), "unit")]
    out += [("attn_norm", (d,), "one"), ("w_dq", (d, s["rq"]), "normal"),
            ("q_norm", (s["rq"],), "one"),
            ("w_uq", (s["rq"], s["H"] * (s["dn"] + s["dr"])), "normal"),
            ("w_dkv", (d, s["rkv"] + s["dr"]), "normal"),
            ("kv_norm", (s["rkv"],), "one"),
            ("w_uk", (s["rkv"], s["H"] * s["dn"]), "normal"),
            ("w_uv", (s["rkv"], s["H"] * s["dv"]), "normal"),
            ("w_o", (s["H"] * s["dv"], d), "normal"),
            ("ffn_norm", (d,), "one")]
    if layer < s["first_dense"]:
        f = s["F"]
        out += [("w_gate", (d, f), "normal"), ("w_up", (d, f), "normal"),
                ("w_down", (f, d), "normal")]
    else:
        e, f, fs = s["E"], s["FE"], s["FE"] * s["shared"]
        out += [("router", (d, e), "normal"),
                ("router_bias", (e,), "normal"),
                ("e_gate", (e, d, f), "normal"),
                ("e_up", (e, d, f), "normal"),
                ("e_down", (e, f, d), "normal"),
                ("s_gate", (d, fs), "normal"), ("s_up", (d, fs), "normal"),
                ("s_down", (fs, d), "normal")]
    return out


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
        # the "rbg" generator: XLA's own bit generator, a layer's 0.75 B
        # values in a fraction of threefry's time on the chip
        keys = jax.random.split(
            jax.random.wrap_key_data(key_data, impl="rbg"), len(leaves))
        out = {}
        for k, (name, shape, kind) in zip(keys, leaves):
            if kind == "one":
                a = jnp.ones(shape, jnp.float32)
            else:
                a = (std if kind == "normal" else 1.0) \
                    * jax.random.normal(k, shape, jnp.float32)
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
