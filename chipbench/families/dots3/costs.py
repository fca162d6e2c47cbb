"""The ``dots3`` family's operations and bytes, as functions of shapes.

Each counts what the algorithm needs, whatever implements it, so that a
share of a peak cannot pass 100 %: the plain (non-absorbed) attention over
the *selected* keys only (at most ``index_topk`` in a full-attention layer,
at most the window in a window layer), the indexer's scores over every key
in context, the held experts' picks only (``K x E_held / E_all`` of a
token's ``K``, the router's expectation), no padding, masked-out work,
gathers, layout copies or empty slots. Norms, rotary, softmax, sigmoid and
top-k are left out. ``s`` is the dict of ``weights.sizes``.
"""
from __future__ import annotations

from chipbench.families.dots3 import weights as W


def _geom(s, kind):
    return s["full"] if kind == W.FULL else s["swa"]


def held_picks_per_token(s):
    """Expected picks of a token that fall on the experts held here."""
    return s["K"] * s["E_held"] / s["E_all"]


def _matmul_params(s):
    """Parameters a token multiplies, summed over the layers (the
    per-key work of attention is counted apart)."""
    d, n = s["D"], 0.0
    for i, kind in enumerate(s["kinds"]):
        g = _geom(s, kind)
        n += d * g["rq"] + g["rq"] * g["H"] * (g["dn"] + g["dr"])
        n += d * (g["rkv"] + g["dr"]) + g["rkv"] * g["H"] * (g["dn"]
                                                            + g["dv"])
        n += d * g["H"] + g["H"] * g["dv"] * d
        if kind == W.FULL:
            n += g["rq"] * s["HI"] * s["DI"] + d * s["DI"] + d * s["HI"]
        if i < s["first_dense"]:
            n += 3 * d * s["F"]
        else:
            n += d * s["E_all"] + 3 * d * s["FE"] * (
                s["shared"] + held_picks_per_token(s))
    return n


def _per_key(s):
    """FLOPs per selected key and per indexed key, summed over layers of
    each kind: ``(full selected, full indexed, window)``."""
    sel = idx = win = 0.0
    for kind in s["kinds"]:
        g = _geom(s, kind)
        att = 2 * g["H"] * (g["dn"] + g["dr"] + g["dv"])
        if kind == W.FULL:
            sel += att
            idx += 2 * s["HI"] * s["DI"]
        else:
            win += att
    return sel, idx, win


def token_forward_flops(s, keys, with_head):
    """One token with ``keys`` positions in context (its own included)."""
    sel, idx, win = _per_key(s)
    f = 2 * _matmul_params(s) + sel * min(keys, s["topk"]) + idx * keys \
        + win * min(keys, s["window"])
    return f + (2 * s["V"] * s["D"] if with_head else 0)


def _sum_min(n, cap):
    """``sum_{t=1..n} min(t, cap)``."""
    m = min(n, cap)
    return m * (m + 1) // 2 + max(n - cap, 0) * cap


def prompt_forward_flops(s, n):
    """A prompt of ``n`` tokens, each attending causally under the
    selection; one row of logits."""
    sel, idx, win = _per_key(s)
    return (2 * _matmul_params(s) * n + sel * _sum_min(n, s["topk"])
            + idx * n * (n + 1) // 2 + win * _sum_min(n, s["window"])
            + 2 * s["V"] * s["D"])


def train_step_flops(s, batch, seq):
    raise SystemExit("chipbench: the dots3 family is served, not trained")
