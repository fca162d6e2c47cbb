"""The plain reference of the ``phi4flash`` family: ``jax.numpy`` float32,
every product at ``Precision.HIGHEST``, the recurrence a plain ``lax.scan``
over the positions, plain masked attention a head pair at a time, no
cache, no batching, no kernel. It imports nothing of the program, and it
runs the WHOLE forward, all layers on every position: the program runs
the cross-decoder on a prompt's last position only, and is held to this.

What it computes (0-based layer ``l`` of ``L``, ``half = L / 2``; ``x`` the
residual stream). Every layer: ``h = x + Mixer_l(LN(x))``, ``y = h +
MLP(LN'(h))``; ``LN`` LayerNorm with gain and bias; ``MLP(u) = W_down
(silu(g) * v)``, ``[g, v] = W_gate_up u``. No positional encoding. A final
LayerNorm; logits against the embedding. ``Mixer_l``:

* even ``l <= half``, Mamba-1: ``[x_t, z_t] = W_in u_t``; ``xc_t =
  silu(sum_k w_k * x_{t-K+1+k} + b_c)``; ``[r_t, B_t, C_t] = W_x xc_t``;
  ``dt_t = softplus(W_dt r_t + b_dt)``; ``h_t = exp(dt_t (x) A) * h_{t-1}
  + (dt_t * xc_t) (x) B_t``, ``A = -exp(A_log)``; ``m_t = h_t C_t + D *
  xc_t``; out ``W_out (m_t * silu(z_t))``. Layer ``half``'s ``m`` is the
  memory.
* odd ``l < half``, differential attention over the window ``t - window <
  s <= t``: ``[q, k, v] = W_qkv u + b``; query pair ``j`` (heads ``2j``,
  ``2j + 1``) reads K pair ``j // G`` (heads ``2p``, ``2p + 1``) and the
  two value heads of pair ``p`` side by side; ``a_i = softmax(q_i k_i^T /
  sqrt(d)) v``; ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0(l)``; ``o =
  RMSNorm(a_1 - lam a_2) (1 - lam0)``; out ``W_o o + b``.
* ``l = half + 1``: the same, causal with no window.
* even ``l >= half + 2``, gated memory unit: ``W_out (m_t * silu(W_in
  u_t))``.
* odd ``l >= half + 3``, differential cross-attention: ``q = W_q u + b``
  against layer ``half + 1``'s ``k`` and ``v``, causal.

The weights come a layer at a time (``weights.Weights``): a layer is
made, used and dropped. ``control`` (``"int8"`` / ``"fp8"``) computes the
same with the operands of every dense product, and what a cache would
hold (the K and V rows, the convolution's inputs), rounded to that
precision; the recurrence, softmax and norms stay float32, as in the
program. That is the control of how ``correct`` is decided, never part
of a benchmark run.

``FAULTS`` are controls of another kind: the float32 forward with one of
the family's mechanisms broken, to show that a limit sees the mechanism
and not the precision alone. ``state_cleared``: the recurrent state is
zeroed at every multiple of ``prefill_chunk`` positions (a chunk that does
not take up the state the last one left). ``ring_cleared``: a window
layer sees nothing that lies before the last multiple of ``2 window``
positions (a ring that lost its content at the wrap).
``memory_from_own_layer``: a gated memory unit reads a memory of zeros
(what its own layer holds: it computes none) instead of layer ``half``'s.
``lambda_dropped``: ``lam = 0`` in every differential layer.
"""
from __future__ import annotations

import functools
import json
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench.families.phi4flash import weights as W

HI = lax.Precision.HIGHEST
FAULTS = ("state_cleared", "ring_cleared", "memory_from_own_layer",
          "lambda_dropped")
NEG = -1e30
SUBNORM_EPS = 1e-5

#: a request is padded to a multiple of this many positions: the longest
#: context the family's cell serves, so that every request of it runs one
#: compiled shape. Short requests (tests) pad to a multiple of 64.
PAD_LONG, PAD_SHORT = 5120, 64


def _fq(x, lowp):
    """Round ``x`` to ``lowp`` along its last axis."""
    if lowp == "int8":
        scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
        scale = jnp.where(scale > 0, scale, 1.0)
        return jnp.clip(jnp.round(x / scale), -127, 127) * scale
    if lowp == "fp8":
        m, e = jnp.frexp(x)                   # m in [0.5, 1): 4 bits kept
        return jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)
    raise ValueError(f"no control precision {lowp!r}")


def _dense(x, w, lowp):
    if lowp:
        x, w = _fq(x, lowp), _fq(w, lowp)
    return jnp.dot(x, w, precision=HI)


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * g + b


def _mlp(s, lw, h, lowp):
    z = _layer_norm(h, lw["ln2_g"], lw["ln2_b"], s["eps"])
    gu = _dense(z, lw["w_gate_up"], lowp)
    return h + _dense(jax.nn.silu(gu[:, :s["F"]]) * gu[:, s["F"]:],
                      lw["w_down"], lowp)


def _mamba(s, lw, u, lowp, fault):
    """The Mamba mixer over the whole sequence ``u`` (T, D) from a zero
    state. Returns the mixer's output and the memory ``m`` (T, C)."""
    t, c, n, r = u.shape[0], s["C"], s["N"], s["R"]
    xz = _dense(u, lw["w_in"], lowp)
    x, z = xz[:, :c], xz[:, c:]
    if lowp:                                 # what a tail would hold
        x = _fq(x, lowp)
    xp = jnp.concatenate([jnp.zeros((s["K"] - 1, c), jnp.float32), x], 0)
    xc = jax.nn.silu(lw["conv_b"] + sum(
        lw["conv_w"][k] * xp[k:k + t] for k in range(s["K"])))
    rbc = _dense(xc, lw["w_x"], lowp)
    dt = jax.nn.softplus(_dense(rbc[:, :r], lw["w_dt"], lowp)
                         + lw["b_dt"])
    a = -jnp.exp(lw["a_log"])                                # (N, C)
    keep = jnp.ones((t,), jnp.float32)
    if fault == "state_cleared":
        keep = (jnp.arange(t) % s["chunk"] != 0).astype(jnp.float32)

    def step(h, args):
        x_t, dt_t, b_t, c_t, keep_t = args
        h = jnp.exp(dt_t[None, :] * a) * (h * keep_t) \
            + (dt_t * x_t)[None, :] * b_t[:, None]
        return h, jnp.sum(h * c_t[:, None], axis=0)

    _, hc = lax.scan(step, jnp.zeros((n, c), jnp.float32),
                     (xc, dt, rbc[:, r:r + n], rbc[:, r + n:], keep))
    m = hc + lw["d_skip"] * xc
    return _dense(m * jax.nn.silu(z), lw["w_out"], lowp), m


def _differential(s, lw, q, k, v, valid, lam0, lowp, fault):
    """``q`` (T, Hq d), ``k``, ``v`` (S, Hkv d) flat rows; ``valid``
    (T, S). A query pair at a time."""
    t, dh = q.shape[0], s["dh"]
    g = s["Hq"] // s["Hkv"]
    q = q.reshape(t, s["Hq"] // 2, 2, dh)
    k = k.reshape(-1, s["Hkv"] // 2, 2, dh)
    v = v.reshape(-1, s["Hkv"] // 2, 2 * dh)
    lam = jnp.exp(jnp.sum(lw["lam_q1"] * lw["lam_k1"])) \
        - jnp.exp(jnp.sum(lw["lam_q2"] * lw["lam_k2"])) + lam0
    if fault == "lambda_dropped":
        lam = 0.0

    def pair(j):
        kp, vp = k[:, j // g], v[:, j // g]

        def half(i):
            sc = jnp.dot(q[:, j, i], kp[:, i].T, precision=HI) \
                / math.sqrt(dh)
            p = jax.nn.softmax(jnp.where(valid, sc, NEG), axis=-1)
            return jnp.dot(p, vp, precision=HI)

        a = half(0) - lam * half(1)
        return a * lax.rsqrt(jnp.mean(jnp.square(a), -1, keepdims=True)
                             + SUBNORM_EPS) * lw["sub_g"] * (1.0 - lam0)

    o = lax.map(pair, jnp.arange(s["Hq"] // 2))              # (P, T, 2d)
    o = jnp.moveaxis(o, 0, 1).reshape(t, s["D"])
    return _dense(o, lw["w_o"], lowp) + lw["b_o"]


def _mixer(s, kind, lw, x, kv, memory, lam0, lowp, fault):
    """A layer of ``kind``'s mixer on the stream ``x``. ``kv`` and
    ``memory`` are layer ``half + 1``'s rows and layer ``half``'s memory
    (``None`` where the kind reads neither). Returns ``(out, made)``:
    ``made`` is what the layer hands on, a Mamba layer's memory or an
    attention layer's ``(k, v)`` rows."""
    t = x.shape[0]
    u = _layer_norm(x, lw["ln1_g"], lw["ln1_b"], s["eps"])
    pos = jnp.arange(t, dtype=jnp.int32)
    diff = pos[:, None] - pos[None, :]
    if kind == W.SSM:
        return _mamba(s, lw, u, lowp, fault)
    if kind == W.GMU:
        if fault == "memory_from_own_layer":
            memory = jnp.zeros_like(memory)
        gate = jax.nn.silu(_dense(u, lw["w_in"], lowp))
        return _dense(memory * gate, lw["w_out"], lowp), None
    if kind == W.CROSS:
        q = _dense(u, lw["w_q"], lowp) + lw["b_q"]
        return _differential(s, lw, q, kv[0], kv[1], diff >= 0, lam0,
                             lowp, fault), None
    qkv = _dense(u, lw["w_qkv"], lowp) + lw["b_qkv"]
    d, w = s["D"], s["Hkv"] * s["dh"]
    q, k, v = qkv[:, :d], qkv[:, d:d + w], qkv[:, d + w:]
    if lowp:                                 # what a cache would hold
        k, v = _fq(k, lowp), _fq(v, lowp)
    valid = diff >= 0
    if kind == W.SWA:
        valid &= diff < s["window"]
        if fault == "ring_cleared":
            ring = 2 * s["window"]
            valid &= pos[None, :] >= (pos[:, None] // ring) * ring
    return _differential(s, lw, q, k, v, valid, lam0, lowp, fault), (k, v)


@functools.lru_cache(maxsize=None)
def _layer_fn(sizes_json, kind, control):
    """One compiled program a kind of layer: ``lam0`` is an argument."""
    s = json.loads(sizes_json)
    lowp, fault = (None, control) if control in FAULTS else (control, None)

    def run(lw, x, kv, memory, lam0):
        out, made = _mixer(s, kind, lw, x, kv, memory, lam0, lowp, fault)
        return _mlp(s, lw, x + out, lowp), made

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _head_fn(sizes_json, n_rows, lowp):
    s = json.loads(sizes_json)

    def run(top, x, start):
        h = lax.dynamic_slice_in_dim(x, start, n_rows, 0)
        z = _layer_norm(h, top["final_g"], top["final_b"], s["eps"])
        return _dense(z, top["embed"].T, lowp)

    return jax.jit(run)


def logits_rows(model, weights, tokens, start, n_rows, control=None):
    """Float32 logits (n_rows, V) at positions ``[start, start + n_rows)``
    of the sequence ``tokens`` (T,), each position attending what precedes
    it; every layer runs on every position. The weights are made a layer
    at a time and dropped. ``control`` is a precision or one of
    ``FAULTS``."""
    s = weights.s
    key = json.dumps(s, sort_keys=True)
    top = weights.top()
    x = jnp.take(top["embed"], jnp.asarray(tokens, jnp.int32), axis=0)
    half = s["L"] // 2
    kv = memory = None
    for i in range(s["L"]):
        kind = W.kind(s, i)
        lw = weights.layer(i)
        x, made = _layer_fn(key, kind, control)(
            lw, x, kv if kind == W.CROSS else None,
            memory if kind == W.GMU else None,
            jnp.float32(W.lambda_init(i)))
        if i == half:
            memory = made
        elif i == half + 1:
            kv = made
        del lw, made
    return _head_fn(key, int(n_rows),
                    None if control in FAULTS else control)(
        top, x, jnp.int32(start))


def served_gaps(model, weights, prompt, served, n_max, control=None):
    """One request as it was served: ``prompt`` ids and the ``served``
    tokens (at most ``n_max``). Returns, per served token, its gap under
    the float32 reference's best logit at its position and whether it is
    the reference's first choice; with ``control`` (a precision, or one
    of ``FAULTS``) the tokens judged are the ones that forward puts first
    there. The sequence is padded (ids 0 after its end, which no judged
    position attends) to a multiple of ``PAD_LONG`` positions, or of
    ``PAD_SHORT`` where it is short."""
    t0 = time.perf_counter()
    p, n = len(prompt), len(served)
    q = PAD_LONG if p + n > PAD_LONG // 8 else PAD_SHORT
    t = max(-(-(p + n) // q) * q, -(-(p - 1 + n_max) // q) * q)
    row = np.zeros((t,), np.int32)
    row[:p] = prompt
    row[p:p + n] = served
    logits = logits_rows(model, weights, row, p - 1, n_max)[:n]
    served = jnp.asarray(np.asarray(served, np.int32))
    if control:
        served = jnp.argmax(
            logits_rows(model, weights, row, p - 1, n_max, control)[:n],
            -1).astype(jnp.int32)
    best = jnp.max(logits, -1)
    mine = jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
    gaps = np.asarray(best - mine)
    hits = np.asarray(jnp.argmax(logits, -1) == served)
    print(f"chipbench phi4flash reference: {p} + {n} tokens padded to "
          f"{t}, control {control}: widest gap {gaps.max():.4f}, mean "
          f"{gaps.mean():.4f}, {time.perf_counter() - t0:.1f} s",
          file=sys.stderr, flush=True)
    return gaps, hits


def _not_trained(*_a, **_k):
    raise SystemExit("chipbench: the phi4flash family is served, not "
                     "trained: its weights come a layer at a time "
                     "(chipbench/README.md, A model family)")


loss_and_grads = loss_only = leaf_norms = leaf_index = _not_trained
