"""Weights of the ``phi4flash`` family from ``--seed``, a layer at a time.

At the published widths the model is 3.85 B parameters: 7.7 GB as the
program holds them, 15.4 GB in float32, which does not lie beside the
program on one chip. So ``make`` returns a ``Weights`` that makes one
layer, or the top (the embedding, which is also the head, and the final
norm), on the device when asked, always the same arrays for the same
seed. The plain reference asks in float32 and frees each layer after it;
the family's ``program.py`` asks in the dtype of each program leaf.

Layout: dense weights are ``(in, out)``; ``a_log`` is ``(d_state,
channels)``, the state held channels minor-most. A layer's kind follows
from its index (``kind``). Initialisation (``assumed`` in the
configuration): every matrix and every attention bias Normal(0,
``initializer_range``); the Mamba layer's own, by its reference
implementation (arXiv:2312.00752): the convolution's weight and bias
Uniform(+-``d_conv``^-1/2), the step's projection Uniform(+-
``dt_rank``^-1/2), its bias the inverse softplus of a step log-uniform in
[1e-3, 1e-1], ``A_log = log(1..d_state)``, ``D = 1``; the ``lam`` vectors
Normal(0, 0.1); LayerNorm and sub-norm gains 1, LayerNorm biases 0. Every
value is rounded to bfloat16, the dtype the model is published in (so the
reference's float32 arrays and the program's bfloat16 leaves hold the
same values), but for the leaves the program keeps in float32
(``FLOAT32_IN_PROGRAM``).

``sizes(model)`` reads the ``model`` group of a configuration: the
published ``config.json`` keys, and beside them what the config leaves to
the family's convention (``d_state``, ``d_conv``, ``expand``, ``dt_rank``,
``initializer_range``) and the widest prefill chunk the window layers'
ring is built for (``prefill_chunk``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

SSM, SWA, FULL, GMU, CROSS = "ssm", "swa", "full", "gmu", "cross"
#: leaves the program keeps in float32 whatever its dtype
FLOAT32_IN_PROGRAM = ("a_log", "d_skip", "b_dt", "lam_q1", "lam_k1",
                      "lam_q2", "lam_k2")
LAMBDA_STD = 0.1


def sizes(model):
    d = int(model["hidden_size"])
    layers = int(model["num_hidden_layers"])
    if int(model["mb_per_layer"]) != 2 or layers % 4 or layers < 8:
        raise SystemExit("chipbench: a phi4flash model alternates state-"
                         "space and attention layers in two halves: "
                         "num_hidden_layers a multiple of 4, at least 8")
    hq = int(model["num_attention_heads"])
    return {
        "V": int(model["vocab_size"]), "D": d, "L": layers,
        "Hq": hq, "Hkv": int(model["num_key_value_heads"]),
        "dh": d // hq, "F": int(model["intermediate_size"]),
        "window": int(model["sliding_window"]),
        "eps": float(model["layer_norm_eps"]),
        "N": int(model.get("d_state", 16)),
        "K": int(model.get("d_conv", 4)),
        "C": int(model.get("expand", 2)) * d,
        "R": int(model.get("dt_rank", -(-d // 16))),
        "std": float(model.get("initializer_range", 0.02)),
        "chunk": int(model.get("prefill_chunk", 512)),
    }


def kind(s, layer):
    """The mixer of layer ``layer`` (0-based)."""
    half = s["L"] // 2
    if layer % 2 == 0:
        return SSM if layer <= half else GMU
    return SWA if layer < half else FULL if layer == half + 1 else CROSS


def group_layers(s):
    """The program's groups of leaves -> the layers each holds: the
    self-decoder's Mamba/window pairs and the cross-decoder's GMU/cross
    pairs stacked, the two layers between them (``mid_*``) on their own."""
    half, n = s["L"] // 2, s["L"]
    return {"self_m": list(range(0, half, 2)),
            "self_a": list(range(1, half, 2)),
            "mid_m": [half], "mid_a": [half + 1],
            "cross_g": list(range(half + 2, n, 2)),
            "cross_a": list(range(half + 3, n, 2))}


def lambda_init(layer):
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def layer_leaves(s, layer):
    """``[(name, shape, how it is drawn)]`` of one layer: its mixer's
    leaves, then the block's two LayerNorms and MLP."""
    d, c, n, dh, r = s["D"], s["C"], s["N"], s["dh"], s["R"]
    kv = s["Hkv"] * dh
    lam = [(f"lam_{x}", (dh,), "lam") for x in ("q1", "k1", "q2", "k2")]
    out = lam + [("sub_g", (2 * dh,), "one"), ("w_o", (d, d), "normal"),
                 ("b_o", (d,), "normal")]
    # fan-in: the taps of a depthwise filter, the rank of the projection
    conv, dt_w = ("uniform", s["K"] ** -0.5), ("uniform", r ** -0.5)
    mixer = {
        SSM: [("w_in", (d, 2 * c), "normal"),
              ("conv_w", (s["K"], c), conv), ("conv_b", (c,), conv),
              ("w_x", (c, r + 2 * n), "normal"), ("w_dt", (r, c), dt_w),
              ("b_dt", (c,), "dt_b"), ("a_log", (n, c), "a_log"),
              ("d_skip", (c,), "one"), ("w_out", (c, d), "normal")],
        GMU: [("w_in", (d, c), "normal"), ("w_out", (c, d), "normal")],
        CROSS: [("w_q", (d, d), "normal"), ("b_q", (d,), "normal")] + out,
    }
    mixer[SWA] = mixer[FULL] = [("w_qkv", (d, d + 2 * kv), "normal"),
                                ("b_qkv", (d + 2 * kv,), "normal")] + out
    return mixer[kind(s, layer)] + [
        ("ln1_g", (d,), "one"), ("ln1_b", (d,), "zero"),
        ("ln2_g", (d,), "one"), ("ln2_b", (d,), "zero"),
        ("w_gate_up", (d, 2 * s["F"]), "normal"),
        ("w_down", (s["F"], d), "normal")]


def top_leaves(s):
    return [("embed", (s["V"], s["D"]), "normal"),
            ("final_g", (s["D"],), "one"), ("final_b", (s["D"],), "zero")]


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


def _draw(key, shape, how, std):
    if how == "normal":
        return std * jax.random.normal(key, shape, jnp.float32)
    if how == "lam":
        return LAMBDA_STD * jax.random.normal(key, shape, jnp.float32)
    if isinstance(how, tuple):                   # ("uniform", bound)
        return jax.random.uniform(key, shape, jnp.float32, -how[1], how[1])
    if how == "dt_b":
        step = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                       * (math.log(1e-1) - math.log(1e-3))
                       + math.log(1e-3))
        step = jnp.maximum(step, 1e-4)
        return step + jnp.log(-jnp.expm1(-step))    # softplus^-1(step)
    if how == "a_log":
        n = jnp.arange(1, shape[0] + 1, dtype=jnp.float32)
        return jnp.broadcast_to(jnp.log(n)[:, None], shape)
    return jnp.full(shape, 1.0 if how == "one" else 0.0, jnp.float32)


@functools.lru_cache(maxsize=None)
def _maker(leaves, std, low):
    """One jitted call that makes ``leaves``. Every leaf but those of
    ``FLOAT32_IN_PROGRAM`` is rounded to what bfloat16 holds; with
    ``low`` those are bfloat16 arrays, else float32 arrays of the same
    values."""
    def make(key_data):
        # the "rbg" generator: XLA's own bit generator, which makes a
        # layer's values in a fraction of threefry's time on the chip
        # (the same key gives the same values on the same backend)
        keys = jax.random.split(
            jax.random.wrap_key_data(key_data, impl="rbg"), len(leaves))
        out = {}
        for k, (name, shape, how) in zip(keys, leaves):
            a = _draw(k, shape, how, std)
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
