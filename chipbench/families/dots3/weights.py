"""Weights of the ``dots3`` family from ``--seed``, a layer at a time.

At the published widths one expert layer's share is 0.87 B parameters
(3.5 GB in float32), so the weights are never all held: ``make`` returns a
``Weights`` that makes one layer, or the top (embedding, final norm, head),
on the device when asked, always the same arrays for the same seed. The
plain reference asks in float32 and frees each layer after it; the
family's ``program.py`` asks in the dtype of each program leaf, and the
rounding happens inside the jitted maker (no float32 copy of a layer ever
lies beside the program's).

Layout: dense weights are ``(in, out)``; the routed experts held here are
stacked on a leading axis. Initialisation (``assumed`` in the
configuration): every matrix Normal(0, ``initializer_range`` or 0.02)
rounded to bfloat16, the dtype the model is published in (so the
reference's float32 arrays and the program's bfloat16 leaves hold the same
values); RMSNorm gains 1, the indexer's LayerNorm 1 and 0; the router's
weight and bias Normal(0, 0.02) in float32.

``sizes(model)`` reads the ``model`` group of a configuration: the
published ``config.json`` keys with the depth cut (``num_hidden_layers``,
``layer_types``), ``vocab_size`` the rows held, ``n_routed_experts`` the
experts HELD, and beside them what the cut needs: ``router_experts`` (the
width the router scores over) and ``expert_rank`` (which contiguous share
is held).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

FULL, WINDOW = "full_attention", "sliding_attention"


def sizes(model):
    kinds = tuple(model["layer_types"])
    if len(kinds) != int(model["num_hidden_layers"]):
        raise SystemExit("chipbench: layer_types and num_hidden_layers "
                         "disagree")
    held = int(model["n_routed_experts"])
    return {
        "V": int(model["vocab_size"]), "D": int(model["hidden_size"]),
        "L": len(kinds), "kinds": kinds,
        "full": {"H": int(model["num_attention_heads"]),
                 "dn": int(model["qk_nope_head_dim"]),
                 "dr": int(model["qk_rope_head_dim"]),
                 "dv": int(model["v_head_dim"]),
                 "rq": int(model["q_lora_rank"]),
                 "rkv": int(model["kv_lora_rank"]),
                 "theta": float(model["rope_theta"])},
        "swa": {"H": int(model["swa_num_attention_heads"]),
                "dn": int(model["swa_qk_nope_head_dim"]),
                "dr": int(model["swa_qk_rope_head_dim"]),
                "dv": int(model["swa_v_head_dim"]),
                "rq": int(model["swa_q_lora_rank"]),
                "rkv": int(model["swa_kv_lora_rank"]),
                "theta": float(model["swa_rope_theta"])},
        "HI": int(model["index_n_heads"]),
        "DI": int(model["index_head_dim"]),
        "topk": int(model["index_topk"]),
        "window": int(model["sliding_window_size"]),
        "F": int(model["intermediate_size"]),
        "FE": int(model["moe_intermediate_size"]),
        "E_all": int(model["router_experts"]), "E_held": held,
        "E_lo": int(model["expert_rank"]) * held,
        "K": int(model["num_experts_per_tok"]),
        "shared": int(model["n_shared_experts"]),
        "first_dense": int(model["first_k_dense_replace"]),
        "eps": float(model["rms_norm_eps"]),
        "std": float(model.get("initializer_range", 0.02)),
    }


def layer_leaves(s, layer):
    """``[(name, shape, kind, float32 in the program?)]`` of one layer."""
    kind = s["kinds"][layer]
    g = s["full"] if kind == FULL else s["swa"]
    d = s["D"]
    out = [("attn_norm", (d,), "one"), ("w_dq", (d, g["rq"]), "normal"),
           ("q_norm", (g["rq"],), "one"),
           ("w_uq", (g["rq"], g["H"] * (g["dn"] + g["dr"])), "normal"),
           ("w_dkv", (d, g["rkv"] + g["dr"]), "normal"),
           ("kv_norm", (g["rkv"],), "one"),
           ("w_uk", (g["rkv"], g["H"] * g["dn"]), "normal"),
           ("w_uv", (g["rkv"], g["H"] * g["dv"]), "normal"),
           ("w_g", (d, g["H"]), "normal"),
           ("w_o", (g["H"] * g["dv"], d), "normal"),
           ("ffn_norm", (d,), "one")]
    if kind == FULL:
        out += [("wi_q", (g["rq"], s["HI"] * s["DI"]), "normal"),
                ("wi_k", (d, s["DI"]), "normal"),
                ("wi_k_g", (s["DI"],), "one"),
                ("wi_k_b", (s["DI"],), "zero"),
                ("wi_w", (d, s["HI"]), "normal")]
    if layer < s["first_dense"]:
        f = s["F"]
        out += [("w_gate", (d, f), "normal"), ("w_up", (d, f), "normal"),
                ("w_down", (f, d), "normal")]
    else:
        e, f, fs = s["E_held"], s["FE"], s["FE"] * s["shared"]
        out += [("router", (d, s["E_all"]), "normal"),
                ("router_bias", (s["E_all"],), "normal"),
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


#: leaves the program keeps in float32: the router, as published
#: implementations do, and in a full-attention layer the indexer's branch
#: (its own three matrices and the query latent's down-projection, which
#: it reads). Every other program leaf is bfloat16. Of these only the
#: router's values are not ones bfloat16 holds.
ROUTER = ("router", "router_bias")
FLOAT32_IN_PROGRAM = ROUTER + ("w_dq", "wi_q", "wi_k", "wi_w")


def float32_in_program(s, layer, name):
    return name in ROUTER or (name in FLOAT32_IN_PROGRAM
                              and s["kinds"][layer] == FULL)


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
def _maker(leaves, std, low, keep):
    """One jitted call that makes ``leaves``, every one but the router's
    rounded to what bfloat16 holds; with ``low`` they are bfloat16
    arrays but for those named in ``keep``, else float32 arrays of the
    same values."""
    def make(key_data):
        # the "rbg" generator: XLA's own bit generator, which makes a
        # layer's 0.9 B values in a fraction of threefry's time on the
        # chip (the same key gives the same values on the same backend)
        keys = jax.random.split(
            jax.random.wrap_key_data(key_data, impl="rbg"), len(leaves))
        out = {}
        for k, (name, shape, kind) in zip(keys, leaves):
            if kind == "normal":
                a = std * jax.random.normal(k, shape, jnp.float32)
            else:
                a = jnp.full(shape, 1.0 if kind == "one" else 0.0,
                             jnp.float32)
            if name not in ROUTER:
                # the checkpoint's dtype: every value is one bfloat16
                # holds, in the reference's float32 arrays too
                a = a.astype(jnp.bfloat16)
                if not low or name in keep:
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
        leaves = tuple(layer_leaves(self.s, i))
        keep = tuple(n for n, _, _ in leaves
                     if float32_in_program(self.s, i, n))
        return _maker(leaves, self.s["std"], bool(for_program), keep)(
            key_data(self.seed, 1 + i))

    def top(self, for_program=False):
        return _maker(tuple(top_leaves(self.s)), self.s["std"],
                      bool(for_program), ())(key_data(self.seed, 0))


def make(model, seed):
    return Weights(model, seed)
