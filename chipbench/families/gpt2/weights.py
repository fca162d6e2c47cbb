"""Weights from ``--seed``, made on the device in one jitted call.

The benchmark makes the weights; the program and the plain reference are
each given them. Nothing the program computes (a cast copy, a scale, a
table) ever reaches the reference: after the window the reference calls
``make`` again with the same seed and gets the same arrays.

Layout: one dict of float32 arrays, the per-layer leaves stacked on a
leading layer axis (what the reference's ``lax.scan`` wants).
``program_leaves`` cuts the same dict into the program's parameter names.
GPT-2's initialisation: Normal(0, ``initializer_range``) for every matrix,
embedding and bias; LayerNorm scale 1, shift 0; the LM head starts as a
copy of the token embedding (the program keeps two leaves).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: stacked per-layer leaves: name -> (shape after the layer axis, kind)
#: D = n_embd, F = n_inner. Dense weights are (out, in), as the program
#: stores them.
_LAYER_LEAVES = (
    ("ln1_g", ("D",), "one"), ("ln1_b", ("D",), "zero"),
    ("q_w", ("D", "D"), "normal"), ("q_b", ("D",), "normal"),
    ("k_w", ("D", "D"), "normal"), ("k_b", ("D",), "normal"),
    ("v_w", ("D", "D"), "normal"), ("v_b", ("D",), "normal"),
    ("o_w", ("D", "D"), "normal"), ("o_b", ("D",), "normal"),
    ("ln2_g", ("D",), "one"), ("ln2_b", ("D",), "zero"),
    ("f1_w", ("F", "D"), "normal"), ("f1_b", ("F",), "normal"),
    ("f2_w", ("D", "F"), "normal"), ("f2_b", ("D",), "normal"),
)
LAYER_NAMES = tuple(n for n, _, _ in _LAYER_LEAVES)
TOP_NAMES = ("wte", "wpe", "lnf_g", "lnf_b", "head")

#: the program's parameter name of each stacked leaf
_PROGRAM_LAYER = {
    "ln1_g": "ln1.gamma", "ln1_b": "ln1.beta",
    "q_w": "q_proj.weight", "q_b": "q_proj.bias",
    "k_w": "k_proj.weight", "k_b": "k_proj.bias",
    "v_w": "v_proj.weight", "v_b": "v_proj.bias",
    "o_w": "out_proj.weight", "o_b": "out_proj.bias",
    "ln2_g": "ln2.gamma", "ln2_b": "ln2.beta",
    "f1_w": "ffn1.weight", "f1_b": "ffn1.bias",
    "f2_w": "ffn2.weight", "f2_b": "ffn2.bias",
}
_PROGRAM_TOP = {"wte": "word_embed.weight", "wpe": "position_weight",
                "lnf_g": "ln_f.gamma", "lnf_b": "ln_f.beta",
                "head": "lm_head.weight"}


def sizes(model):
    """The sizes the leaves need, from a GPT-2 ``config.json`` group."""
    d = int(model["n_embd"])
    return {"D": d, "F": int(model.get("n_inner") or 4 * d),
            "L": int(model["n_layer"]), "V": int(model["vocab_size"]),
            "P": int(model["n_positions"]), "H": int(model["n_head"]),
            "std": float(model["initializer_range"])}


def key_data(seed, stream=0):
    """A raw threefry key (uint32[2]) from any whole number: ``--seed``
    may pass 2**31, which a 32-bit ``PRNGKey`` argument cannot hold."""
    return jnp.asarray(np.random.SeedSequence(
        [int(seed), int(stream)]).generate_state(2), dtype=jnp.uint32)


@functools.lru_cache(maxsize=None)
def _maker(d, f, l, v, p, std):
    dims = {"D": d, "F": f}

    def make(key):
        keys = iter(jax.random.split(key, len(_LAYER_LEAVES) + 2))
        out = {"wte": std * jax.random.normal(next(keys), (v, d)),
               "wpe": std * jax.random.normal(next(keys), (p, d)),
               "lnf_g": jnp.ones((d,)), "lnf_b": jnp.zeros((d,))}
        for name, shape, kind in _LAYER_LEAVES:
            full = (l,) + tuple(dims[s] for s in shape)
            k = next(keys)
            if kind == "normal":
                out[name] = std * jax.random.normal(k, full)
            else:
                out[name] = jnp.full(full, 1.0 if kind == "one" else 0.0)
        return out

    return jax.jit(make)


def make(model, seed):
    """The stacked float32 weights of ``model`` from ``seed``. ``head`` is
    a buffer of its own (the program's train step donates its leaves)."""
    s = sizes(model)
    w = _maker(s["D"], s["F"], s["L"], s["V"], s["P"], s["std"])(
        key_data(seed))
    w["head"] = jnp.copy(w["wte"])
    return w


@functools.lru_cache(maxsize=None)
def _cutter(n_layers):
    def cut(w):
        out = {_PROGRAM_TOP[n]: w[n] for n in TOP_NAMES}
        for i in range(n_layers):
            for n in LAYER_NAMES:
                out[f"layers.{i}.{_PROGRAM_LAYER[n]}"] = w[n][i]
        return out
    return jax.jit(cut)


def program_leaves(w):
    """The same weights under the program's parameter names, cut in one
    jitted call (one program instead of 16 slices per layer)."""
    return _cutter(int(w["q_w"].shape[0]))(w)


def program_leaf_index(n_layers):
    """``{program name: (stacked name, layer or None)}`` — how a per-leaf
    reading of the program lines up with the reference's stacked one."""
    idx = {_PROGRAM_TOP[n]: (n, None) for n in TOP_NAMES}
    for i in range(n_layers):
        for n in LAYER_NAMES:
            idx[f"layers.{i}.{_PROGRAM_LAYER[n]}"] = (n, i)
    return idx
