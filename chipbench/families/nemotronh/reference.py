"""The plain reference of the ``nemotronh`` family: ``jax.numpy``
float32, every product at ``Precision.HIGHEST``, the whole forward on
every position, the Mamba-2 recurrence stepped position by position,
plain causal attention a head at a time, every held expert as a plain
function of every token under its gate, no cache, no batching, no kernel.
It imports nothing of the program.

What it computes (``N`` an RMSNorm with its own gain, eps
``norm_eps``). Each layer ``h = u + Mixer(N(u))``; after the
last a final ``N`` and the head over the held slice of the vocabulary.

* ``M``: ``[z | xBC | dt] = z W_in``; ``xBC`` through the depthwise causal
  convolution (``conv_kernel`` taps, the positions before the first
  zeros) and its bias, then ``silu``; ``x | B | C`` split (``H`` heads of
  ``P``, ``G`` groups of ``N``); ``dt = softplus(dt + dt_bias)``, no
  clamp (``time_step_limit`` (0, inf) as published); ``A = -exp(A_log)``.
  For head ``h`` of group ``g = h // (H / G)``: ``S_t = exp(dt_t A_h)
  S_{t-1} + dt_t x_t B_{t,g}^T``, ``y_t = S_t C_{t,g} + D_h x_t``. Then
  ``y * silu(z)`` normed over each of ``G`` groups of channels, times the
  gain, and ``W_out``.
* ``E``: ``s = sigmoid(z W_r)``, the top ``num_experts_per_tok`` by ``s +
  bias``, ``g_i = routed_scaling_factor s_i / sum_chosen s_j``; out ``=
  sum_{i chosen, held} g_i W_down,i relu(W_up,i z)^2 + W_down,sh
  relu(W_up,sh z)^2``.
* ``*``: ``q = z W_q``, ``k = z W_k``, ``v = z W_v``; query head ``j``
  reads K/V head ``j // (Hq / Hkv)``; causal softmax at scale
  ``head_dim^-1/2``; no position of any kind; ``W_o``.

Departures from the published code, each as the program has it: the
experts that other ranks hold are left out (their part of a routed layer
is the other ranks'); the vocabulary is the held slice (embedding rows
and head columns); weights are drawn from the seed (``weights.py``).

``control`` (``"int8"`` / ``"fp8"``) computes the same with the operands
of every dense product, and the cached K and V, rounded to that
precision; the recurrence, its convolution, the router's scores, norms
and softmax stay float32 as the program keeps them. That is the control
of how ``correct`` is decided, never part of a benchmark run.

**What a request reads.** Top-6-of-128 routing is discontinuous: where
the last expert chosen and the first left out score within a bfloat16
stream's noise of each other, a correct bfloat16 program and this
float32 forward pick differently, and one other pick moves that
position's stream by a fifth. The convolution, the Mamba-2 states and
attention carry that to the next 5-20 positions, so a correct program's
logits lie within ~0.07 of these at most positions and 0.2-3 away in
bursts that cover about three positions in ten (on a TPU v5e at the
cell's widths; the limits file has the readings). No router margin at
a position, or at the positions before it, tells the bursts apart. The
widest gap of single tokens reads such a burst, not the precision, so
``served_gaps`` reports for every served token the MEAN gap of its
request's served tokens: every token is judged, and the harness's
widest is the widest request mean. The controls and the faults are read
alike.

``FAULTS`` are controls of another kind: the float32 forward with one
mechanism broken, to show that a limit sees the mechanism and not the
precision alone. ``state_kept``: every Mamba-2 layer starts from the
state and tail a previous tenant of the slot left (a fixed sequence of
``PREVIOUS`` random ids), not from zeros. ``norm_ungrouped``: the gated
norm over all channels at once, not over each group. ``relu_unsquared``:
``relu`` for ``relu^2`` in every expert.
"""
from __future__ import annotations

import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
FAULTS = ("state_kept", "norm_ungrouped", "relu_unsquared")
NEG = -1e30
#: the previous tenant's sequence of ``state_kept``: this many ids
PREVIOUS = 512

#: a request is padded to a multiple of this many positions: the longest
#: context the family's cell serves, so that every request of it runs one
#: compiled shape. Short requests (tests) pad to a multiple of 64.
PAD_LONG, PAD_SHORT = 3072, 64



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


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                         + eps) * g


def _relu2(x, fault):
    r = jax.nn.relu(x)
    return r if fault == "relu_unsquared" else r * r


def mamba2(s, lw, z, h0, tail0, lowp=None, fault=None):
    """The Mamba-2 mixer over ``z`` (T, D) from state ``h0`` (H, P, N)
    and ``tail0`` (K-1, C): out (T, D), and the state and tail after the
    last row."""
    t, h, p, g, n = z.shape[0], s["H"], s["P"], s["G"], s["N"]
    di = h * p
    cd = di + 2 * g * n
    proj = _dense(z, lw["w_in"], lowp)
    gate, xbc, dt = proj[:, :di], proj[:, di:di + cd], proj[:, di + cd:]
    xp = jnp.concatenate([tail0, xbc], axis=0)
    k = s["K"]
    pre = lw["conv_b"] + sum(lw["conv_w"][j] * xp[j:j + t]
                             for j in range(k))
    xbc = jax.nn.silu(pre)
    x = xbc[:, :di].reshape(t, h, p)
    b = xbc[:, di:di + g * n].reshape(t, g, n)
    c = xbc[:, di + g * n:].reshape(t, g, n)
    dt = jax.nn.softplus(dt + lw["dt_bias"])
    a = -jnp.exp(lw["a_log"])
    per = h // g

    def step(state, args):                  # one position: state (H, P, N)
        x_t, dt_t, b_t, c_t = args
        b_h, c_h = jnp.repeat(b_t, per, 0), jnp.repeat(c_t, per, 0)
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :]
        y = jnp.sum(state * c_h[:, None, :], -1) + lw["d_skip"][:, None] \
            * x_t
        return state, y

    h_end, y = lax.scan(step, h0, (x, dt, b, c))
    y = y.reshape(t, di) * jax.nn.silu(gate)
    groups = 1 if fault == "norm_ungrouped" else g
    yg = y.reshape(t, groups, -1)
    yg = yg * lax.rsqrt(jnp.mean(jnp.square(yg), -1, keepdims=True)
                        + s["eps"])
    y = yg.reshape(t, di) * lw["gnorm"]
    return _dense(y, lw["w_out"], lowp), h_end, xp[t:]


def route(s, lw, z):
    """``ids`` (T, K) over all experts, their gates, and the margin of
    the choice (the K-th biased score less the next), float32."""
    sc = jax.nn.sigmoid(jnp.dot(z, lw["router"], precision=HI))
    best, ids = lax.top_k(sc + lw["router_bias"], s["top_k"] + 1)
    ids = ids[:, :s["top_k"]]
    chosen = jnp.take_along_axis(sc, ids, axis=-1)
    return (ids, s["gate_scale"] * chosen
            / jnp.sum(chosen, -1, keepdims=True), best[:, -2] - best[:, -1])


def routed(s, lw, z, lowp=None, fault=None):
    """``sum_{i chosen, held} g_i E_i(z)``: a loop over the held experts,
    each over every token, weighted by its gate (0 where it was not
    chosen)."""
    ids, gates, _ = route(s, lw, z)

    def expert(acc, ew):
        e, w_up, w_down = ew
        g_e = jnp.sum(jnp.where(ids == e, gates, 0.0), -1)
        out = _dense(_relu2(_dense(z, w_up.T, lowp), fault), w_down, lowp)
        return acc + g_e[:, None] * out, None

    return lax.scan(expert, jnp.zeros_like(z),
                    (s["E_lo"] + jnp.arange(s["E_held"]), lw["e_up"],
                     lw["e_down"]))[0]


def experts(s, lw, z, lowp=None, fault=None):
    shared = _dense(_relu2(_dense(z, lw["s_up"], lowp), fault),
                    lw["s_down"], lowp)
    return routed(s, lw, z, lowp, fault) + shared


def attention(s, lw, z, lowp=None):
    t, hq, hkv, dh = z.shape[0], s["Hq"], s["Hkv"], s["dh"]
    q = _dense(z, lw["w_q"], lowp).reshape(t, hq, dh)
    k = _dense(z, lw["w_k"], lowp).reshape(t, hkv, dh)
    v = _dense(z, lw["w_v"], lowp).reshape(t, hkv, dh)
    if lowp:                                 # what the pools would hold
        k, v = _fq(k, lowp), _fq(v, lowp)
    pos = jnp.arange(t)
    causal = pos[None, :] <= pos[:, None]
    kv_of = jnp.arange(hq) // (hq // hkv)

    def head(args):                          # one query head at a time
        q_h, j = args
        sc = jnp.dot(q_h, k[:, j].T, precision=HI) / np.sqrt(dh)
        p = jax.nn.softmax(jnp.where(causal, sc, NEG), axis=-1)
        return jnp.dot(p, v[:, j], precision=HI)

    o = lax.map(head, (jnp.moveaxis(q, 1, 0), kv_of))    # (Hq, T, dh)
    return _dense(jnp.moveaxis(o, 0, 1).reshape(t, hq * dh), lw["w_o"],
                  lowp)


@functools.lru_cache(maxsize=None)
def _layer_fn(sizes_json, kind, control):
    """One layer of ``kind``: ``(lw, x, h0, tail)`` -> the new stream,
    the Mamba-2 state and tail after it (``M``) and the router's margin
    a row (``E``; infinite elsewhere)."""
    s = json.loads(sizes_json)
    lowp, fault = (None, control) if control in FAULTS else (control, None)

    def run(lw, x, h0, tail):
        z = _rms(x, lw["norm"], s["eps"])
        margin = jnp.full((x.shape[0],), jnp.inf)
        if kind == "M":
            out, h0, tail = mamba2(s, lw, z, h0, tail, lowp, fault)
        elif kind == "E":
            out = experts(s, lw, z, lowp, fault)
            margin = route(s, lw, z)[2]
        else:
            out = attention(s, lw, z, lowp)
        return x + out, h0, tail, margin

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _head_fn(sizes_json, n_rows, lowp):
    s = json.loads(sizes_json)

    def run(top, x, start):
        h = lax.dynamic_slice_in_dim(x, start, n_rows, 0)
        return _dense(_rms(h, top["final_norm"], s["eps"]), top["head"],
                      lowp)

    return jax.jit(run)


def previous_tenant(s):
    """The ids the slot held before the request under ``state_kept``."""
    return np.random.default_rng(4099).integers(
        0, s["V"], PREVIOUS).astype(np.int32)


def logits_rows(model, weights, tokens, start, n_rows, control=None,
                margins=False):
    """Float32 logits (n_rows, V) at positions ``[start, start + n_rows)``
    of the sequence ``tokens`` (T,), each position attending what precedes
    it. The weights are made a layer at a time and dropped. With
    ``margins`` also, for the same rows, the reference's own narrowest
    router margin over the routed layers."""
    s = weights.s
    key = json.dumps(s, sort_keys=True)
    top = weights.top()
    x = jnp.take(top["embed"], jnp.asarray(tokens, jnp.int32), axis=0)
    kept = control == "state_kept"
    if kept:
        x_prev = jnp.take(top["embed"], jnp.asarray(previous_tenant(s)),
                          axis=0)
    di = s["H"] * s["P"]
    zero_h = jnp.zeros((s["H"], s["P"], s["N"]), jnp.float32)
    zero_tail = jnp.zeros((s["K"] - 1, di + 2 * s["G"] * s["N"]),
                          jnp.float32)
    route_m = jnp.full((x.shape[0],), jnp.inf)
    for i, kind in enumerate(s["pattern"]):
        lw = weights.layer(i)
        h0, tail = zero_h, zero_tail
        if kept:
            # the previous tenant's pass leaves this layer's state behind
            x_prev, h0, tail, _ = _layer_fn(key, kind, None)(
                lw, x_prev, h0, tail)
            if kind != "M":
                h0, tail = zero_h, zero_tail
        x, _, _, mr = _layer_fn(key, kind, control)(lw, x, h0, tail)
        route_m = jnp.minimum(route_m, mr)
        del lw
    out = _head_fn(key, int(n_rows), None if control in FAULTS else control)(
        top, x, jnp.int32(start))
    if margins:
        return out, route_m[start:start + n_rows]
    return out


def served_gaps(model, weights, prompt, served, n_max, control=None):
    """One request as it was served: ``prompt`` ids and the ``served``
    tokens (at most ``n_max``). Returns, per served token, the mean over
    the request's served tokens of the gap under the float32 reference's
    best logit at each position (the module's text says why the mean),
    and whether the token is the reference's first choice; with
    ``control`` (a precision, or one of ``FAULTS``) the tokens judged are
    the ones that forward puts first there. The sequence is padded (ids 0
    after its end, which no judged position reads: attention is causal
    and the recurrence runs forward) to a multiple of ``PAD_LONG``
    positions, or of ``PAD_SHORT`` where it is short."""
    t0 = time.perf_counter()
    p, n = len(prompt), len(served)
    q = PAD_LONG if max(p + n, p - 1 + n_max) > PAD_LONG // 8 \
        else PAD_SHORT
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
    every = np.asarray(best - mine)
    hits = np.asarray(jnp.argmax(logits, -1) == served)
    print(f"chipbench nemotronh reference: {p} + {n} tokens padded to {t}, "
          f"control {control}: mean gap {every.mean():.4f}, widest "
          f"{every.max():.4f}, {int((every > 0).sum())} of {n} not first, "
          f"{time.perf_counter() - t0:.1f} s", file=sys.stderr, flush=True)
    return np.full((n,), every.mean()), hits


def _not_trained(*_a, **_k):
    raise SystemExit("chipbench: the nemotronh family is served, not "
                     "trained: its weights come a layer at a time "
                     "(chipbench/README.md, A model family)")


loss_and_grads = loss_only = leaf_norms = leaf_index = _not_trained
