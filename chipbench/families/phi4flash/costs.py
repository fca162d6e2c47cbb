"""The ``phi4flash`` family's operations and bytes, as functions of shapes.

Each counts what the algorithm needs, whatever implements it, so that a
share of a peak cannot pass 100 %. A decoded token passes every layer. A
prompt of ``n`` tokens is ``n`` rows through the self-decoder (layers ``0
.. half``, and layer ``half + 1``'s K and V projection, which the cache
needs of every position) and ONE row through layer ``half + 1``'s own
attention and MLP, the cross-decoder and the head: layers above ``half +
1`` read only layer ``half``'s memory at their own position and layer
``half + 1``'s keys, and nothing reads layer ``half + 1``'s output but
they. Attention per key: ``min(keys, window)`` in a window layer, every
key in the full layer and the cross layers; a query head's two products
are ``d`` and ``2 d`` wide (a differential head attends a pair of value
heads). The recurrence: one multiply-add a channel and state for the
update, one for the read-out; the convolution's taps. Norms, softmax,
``exp``, ``softplus``, the gates' products, padding, masked-out work,
gathers and empty slots are left out. ``s`` is the dict of
``weights.sizes``.
"""
from __future__ import annotations

from chipbench.families.phi4flash import weights as W


def _mlp(s):
    return 3 * s["D"] * s["F"]


def _mixer_params(s, kind):
    """Parameters a row multiplies in one mixer of ``kind``."""
    d, c = s["D"], s["C"]
    kv = s["Hkv"] * s["dh"]
    return {W.SSM: d * 2 * c + c * (s["R"] + 2 * s["N"]) + s["R"] * c
            + c * d,
            W.SWA: d * (d + 2 * kv) + d * d,
            W.FULL: d * (d + 2 * kv) + d * d,
            W.GMU: 2 * d * c, W.CROSS: 2 * d * d}[kind]


def _scan_flops(s):
    """One token through one Mamba layer's recurrence and convolution."""
    return 4 * s["C"] * s["N"] + 2 * s["K"] * s["C"]


def _per_key(s):
    """FLOPs a query row spends on one key in one attention layer."""
    return 2 * s["Hq"] * (s["dh"] + 2 * s["dh"])


def _count(s, *kinds):
    return sum(1 for i in range(s["L"]) if W.kind(s, i) in kinds)


def _row_flops(s, kinds):
    """One row through every layer of ``kinds``, attention's per-key
    work apart."""
    f = 0.0
    for kind in kinds:
        n = _count(s, kind)
        f += n * 2 * (_mixer_params(s, kind) + _mlp(s))
        if kind == W.SSM:
            f += n * _scan_flops(s)
    return f


def _sum_min(n, cap):
    """``sum_{t=1..n} min(t, cap)``."""
    m = min(n, cap)
    return m * (m + 1) // 2 + max(n - cap, 0) * cap


def token_forward_flops(s, keys, with_head):
    """One decoded token with ``keys`` positions in context (its own
    included): every layer."""
    f = _row_flops(s, (W.SSM, W.SWA, W.FULL, W.GMU, W.CROSS))
    f += _per_key(s) * (_count(s, W.SWA) * min(keys, s["window"])
                        + _count(s, W.FULL, W.CROSS) * keys)
    return f + (2 * s["V"] * s["D"] if with_head else 0)


def cross_decoder_rows(n):
    """Rows of a prompt of ``n`` tokens that pass the layers above the
    self-decoder: its last."""
    return 1 if n else 0


def prompt_forward_flops(s, n):
    """A prompt of ``n`` tokens: ``n`` rows through the self-decoder and
    the full layer's K and V projection, one row through the rest."""
    kv = s["Hkv"] * s["dh"]
    rows = cross_decoder_rows(n)
    f = n * _row_flops(s, (W.SSM, W.SWA))
    f += _per_key(s) * _count(s, W.SWA) * _sum_min(n, s["window"])
    f += n * 2 * s["D"] * 2 * kv
    f += rows * (2 * (2 * s["D"] * s["D"] + _mlp(s))
                 + _row_flops(s, (W.GMU, W.CROSS))
                 + _per_key(s) * _count(s, W.FULL, W.CROSS) * n
                 + 2 * s["V"] * s["D"])
    return f


def train_step_flops(s, batch, seq):
    raise SystemExit("chipbench: the phi4flash family is served, not "
                     "trained")


def ssm_scan_call(f):
    """A FLOOR of the ``ssm_chunk_scan`` kernel's work in a traced
    serving window ``f``. The window's facts count chunks and prompts,
    not prompt tokens; every chunk of a prompt but its last is
    ``prefill_chunk`` valid tokens wide, so ``(prefill_chunks - prefills)
    x prefill_chunk`` tokens passed each Mamba layer's scan at the least
    (the last chunks' tokens, and single-chunk prompts, are left out; a
    prompt that ended in the window after its first chunks ran before it
    takes one away). Bytes: the convolved input and the step read and
    the memory written, float32, a token and channel; ``B`` and ``C``;
    the state in and out once a chunk. No metric of ``BENCHMARK.json``
    names it yet: the cell's traced window holds no chunk (PERF.md
    section 7 has the metric's file for the PR that moves the trace)."""
    s, c = f["sizes"], f["counters"]
    chunks = max(c["serving.generate.prefill_chunks"]
                 - c["serving.generate.prefills"], 0) * _count(s, W.SSM)
    tokens = chunks * s["chunk"]
    flops = tokens * 4 * s["C"] * s["N"]
    nbytes = 4 * (tokens * (3 * s["C"] + 2 * s["N"])
                  + chunks * 2 * s["N"] * s["C"])
    return flops, nbytes
