"""Weights of the ``nemotronh`` family from ``--seed``, a layer at a time.

At the published widths a routed layer holds 0.66 B parameters (2.6 GB in
float32), so the weights are never all held: ``make`` returns a
``Weights`` that makes one layer, or the top (embedding, final norm,
head), on the device when asked, always the same arrays for the same
seed. The plain reference asks in float32 and frees each layer after it;
the family's ``program.py`` asks in the dtype of each program leaf.

Layout: dense weights are ``(in, out)``; the held routed experts are
stacked on a leading axis, ``e_up`` output-major ``(E, F, D)`` as the
program holds it; ``w_in`` is ``[z | xBC | dt]`` and ``xBC`` is
``[x | B | C]``, as the published ``in_proj`` and convolution. What each
leaf is drawn from (``assumed`` in the configuration): every matrix and
the embedding Normal(0, ``initializer_range``) rounded to bfloat16, the
dtype the model is published in; the depthwise convolution's taps and
bias U(-1/2, 1/2), PyTorch's default for a ``Conv1d`` of 4 taps a
channel, which the published initialisation leaves as it is; the norms'
gains 1; float32 leaves, never rounded: ``A_log = log U[1, 16]``, ``D =
1``, ``dt_bias`` the inverse softplus of a step drawn log-uniform in
[``time_step_min``, ``time_step_max``] and floored at
``time_step_floor`` (Mamba-2's initialisation), the router's weight
Normal(0, ``initializer_range``) and its score-correction bias too.

``sizes(model)`` reads the ``model`` group of a configuration: the
published ``config.json`` keys with the depth, the experts held and the
vocabulary cut, and ``ep_size`` / ``ep_rank``, the deployment's ranks
that share a layer and this chip's place among them.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

#: leaves the program keeps in float32 whatever its dtype
FLOAT32_IN_PROGRAM = ("router", "router_bias", "a_log", "d_skip",
                      "dt_bias")
#: the range ``A = -exp(A_log)`` is drawn from (Mamba-2's ``A_init_range``)
A_RANGE = (1.0, 16.0)
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def sizes(model):
    pattern = str(model["hybrid_override_pattern"])
    if len(pattern) != int(model["num_hidden_layers"]):
        raise SystemExit("chipbench: hybrid_override_pattern names "
                         f"{len(pattern)} layers, num_hidden_layers "
                         f"{model['num_hidden_layers']}")
    for key, want in (("n_group", 1), ("topk_group", 1),
                      ("norm_topk_prob", True), ("mlp_hidden_act", "relu2"),
                      ("mamba_hidden_act", "silu"), ("use_conv_bias", True),
                      ("n_shared_experts", 1)):
        if model[key] != want:
            raise SystemExit(f"chipbench: the nemotronh family builds "
                             f"{key} {want!r}, not {model[key]!r}")
    held, ranks = int(model["n_routed_experts"]), int(model["ep_size"])
    return {
        "V": int(model["vocab_size"]), "D": int(model["hidden_size"]),
        "L": len(pattern), "pattern": pattern,
        "Hq": int(model["num_attention_heads"]),
        "Hkv": int(model["num_key_value_heads"]),
        "dh": int(model["head_dim"]),
        "H": int(model["mamba_num_heads"]), "P": int(model["mamba_head_dim"]),
        "G": int(model["n_groups"]), "N": int(model["ssm_state_size"]),
        "K": int(model["conv_kernel"]), "chunk": int(model["chunk_size"]),
        "F": int(model["moe_intermediate_size"]),
        "Fs": int(model["moe_shared_expert_intermediate_size"]),
        "E_held": held, "E_all": held * ranks,
        "E_lo": held * int(model["ep_rank"]),
        "top_k": int(model["num_experts_per_tok"]),
        "gate_scale": float(model["routed_scaling_factor"]),
        "eps": float(model["norm_eps"]),
        "std": float(model["initializer_range"]),
        "dt_range": [float(model["time_step_min"]),
                     float(model["time_step_max"]),
                     float(model["time_step_floor"])],
    }


def layer_leaves(s, layer):
    """``[(name, shape, kind)]`` of one layer; ``kind`` is how it is
    drawn: ``normal`` (the initializer's range), ``one``, ``conv``
    (U(-1/2, 1/2) for 4 taps), ``a_log``, ``dt_bias``."""
    d, kind = s["D"], s["pattern"][layer]
    out = [("norm", (d,), "one")]
    if kind == MAMBA:
        di = s["H"] * s["P"]
        cd = di + 2 * s["G"] * s["N"]
        return out + [
            ("w_in", (d, di + cd + s["H"]), "normal"),
            ("conv_w", (s["K"], cd), "conv"), ("conv_b", (cd,), "conv"),
            ("dt_bias", (s["H"],), "dt_bias"), ("a_log", (s["H"],), "a_log"),
            ("d_skip", (s["H"],), "one"), ("gnorm", (di,), "one"),
            ("w_out", (di, d), "normal")]
    if kind == EXPERTS:
        e, f, fs = s["E_held"], s["F"], s["Fs"]
        return out + [
            ("router", (d, s["E_all"]), "normal"),
            ("router_bias", (s["E_all"],), "normal"),
            ("e_up", (e, f, d), "normal"), ("e_down", (e, f, d), "normal"),
            ("s_up", (d, fs), "normal"), ("s_down", (fs, d), "normal")]
    q, kv = s["Hq"] * s["dh"], s["Hkv"] * s["dh"]
    return out + [("w_q", (d, q), "normal"), ("w_k", (d, kv), "normal"),
                  ("w_v", (d, kv), "normal"), ("w_o", (q, d), "normal")]


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


def _draw(k, kind, shape, std, conv_taps, dt_range):
    if kind == "one":
        return jnp.ones(shape, jnp.float32)
    if kind == "normal":
        return std * jax.random.normal(k, shape, jnp.float32)
    if kind == "conv":
        bound = 1.0 / math.sqrt(conv_taps)
        return jax.random.uniform(k, shape, jnp.float32, -bound, bound)
    if kind == "a_log":
        return jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                          *A_RANGE))
    lo, hi, floor = dt_range
    dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32)
                 * (math.log(hi) - math.log(lo)) + math.log(lo))
    dt = jnp.maximum(dt, floor)
    return dt + jnp.log(-jnp.expm1(-dt))            # inverse softplus


@functools.lru_cache(maxsize=None)
def _maker(leaves, std, conv_taps, dt_range, low):
    """One jitted call that makes ``leaves``: the float32 leaves as they
    are drawn, every other rounded to what bfloat16 holds and, with
    ``low``, a bfloat16 array."""
    def make(key_data):
        # the "rbg" generator: XLA's own bit generator, a layer's 0.66 B
        # values in a fraction of threefry's time on the chip
        keys = jax.random.split(
            jax.random.wrap_key_data(key_data, impl="rbg"), len(leaves))
        out = {}
        for k, (name, shape, kind) in zip(keys, leaves):
            a = _draw(k, kind, shape, std, conv_taps, dt_range)
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

    def _make(self, leaves, stream, for_program):
        s = self.s
        return _maker(tuple(leaves), s["std"], s["K"], tuple(s["dt_range"]),
                      bool(for_program))(key_data(self.seed, stream))

    def layer(self, i, for_program=False):
        return self._make(layer_leaves(self.s, i), 1 + i, for_program)

    def top(self, for_program=False):
        return self._make(top_leaves(self.s), 0, for_program)


def make(model, seed):
    return Weights(model, seed)
