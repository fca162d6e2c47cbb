"""The ``xing4`` family's operations, as functions of shapes.

Each counts what the algorithm needs, whatever implements it, so that a
share of a peak cannot pass 100 %: two FLOPs a parameter a token
multiplies (``K`` of the routed experts and the shared ones, the head on
one row a prompt); attention per key and layer ``2 H (rkv + dr + rkv)``
in the absorbed form of a decoded token, ``2 H (dn + dr + dv)`` in the
plain form of a prompt, whose keys are up-projected once a position (the
parameters of ``W_uk`` and ``W_uv``, counted with the rest), causal
halves; a hyper-connection's coefficient product ``2 n D (2 n + n^2)`` a
sublayer. Left out: the streams' mixing sums, Sinkhorn, norms, rotary,
softmax, sigmoid, top-k, padding, masked-out work, gathers, the keys a
chunk up-projects again, empty slots. ``s`` is the dict of
``weights.sizes``.
"""
from __future__ import annotations


def _layer_params(s, layer):
    """Parameters a token multiplies in one layer."""
    d = s["D"]
    n = d * s["rq"] + s["rq"] * s["H"] * (s["dn"] + s["dr"])
    n += d * (s["rkv"] + s["dr"]) + s["rkv"] * s["H"] * (s["dn"] + s["dv"])
    n += s["H"] * s["dv"] * d
    n += 2 * s["n"] * d * (2 * s["n"] + s["n"] ** 2)
    if layer < s["first_dense"]:
        return n + 3 * d * s["F"]
    return n + d * s["E"] + 3 * d * s["FE"] * (s["shared"] + s["K"])


def _matmul_params(s):
    return sum(_layer_params(s, i) for i in range(s["L"]))


def token_forward_flops(s, keys, with_head):
    """One decoded token with ``keys`` positions in context (its own
    included): the absorbed form over every one of them."""
    f = 2 * _matmul_params(s) \
        + s["L"] * 2 * s["H"] * (2 * s["rkv"] + s["dr"]) * keys
    return f + (2 * s["V"] * s["D"] if with_head else 0)


def prompt_forward_flops(s, n):
    """A prompt of ``n`` tokens, each attending causally in the plain
    form; one row of logits."""
    per_key = s["L"] * 2 * s["H"] * (s["dn"] + s["dr"] + s["dv"])
    return (2 * _matmul_params(s) * n + per_key * n * (n + 1) // 2
            + 2 * s["V"] * s["D"])


def train_step_flops(s, batch, seq):
    raise SystemExit("chipbench: the xing4 family is served, not trained")
