"""The plain reference of the ``dots3`` family: ``jax.numpy`` float32, every
product at ``Precision.HIGHEST``, plain masked attention in the
non-absorbed form, a dense loop over the held experts, no cache, no
batching, no kernel. It imports nothing of the program.

What it computes, layer by layer (``x`` the residual stream, ``N`` an
RMSNorm with its own gain, ``R`` rotary positions in the half-split
convention): ``h = x + A(N(x))``, ``y = h + F(N(h))``, logits ``N(y)
W_head`` over the rows of the vocabulary held.

* Attention, with ``u = N(x)``: ``c_q = a_q N(u W_dq)``, ``[q_n, q_r]_h =
  c_q W_uq``, ``q_r <- R(q_r, t)``; ``[c, k_r] = u W_dkv``, ``c_kv = a_kv
  N(c)``, ``k_r <- R(k_r, t)`` (one for all heads); ``k_n_h = c_kv W_uk_h``,
  ``v_h = c_kv W_uv_h``; ``score_h(t, s) = (q_n . k_n + q_r . k_r) /
  sqrt(d_n + d_r)``; ``o_h = sum_{s in S_t} softmax(score_h(t, .)) v_h(s)``;
  ``A = concat_h(sigmoid(u W_g)_h o_h) W_o``. ``a_q = sqrt(D / r_q)``,
  ``a_kv = sqrt(D / r_kv)``.
* ``S_t`` in a full-attention layer: the ``index_topk`` positions ``s <= t``
  of largest ``I(t, s) = sum_j w_j(t) H_I^-1/2 d_I^-1/2 relu(q^I_j(t) .
  k^I(s))`` (all of them while there are no more; equal scores lowest
  position first), with ``q^I = c_q
  W^I_q``, ``k^I = LayerNorm(u W^I_k)``, both rotated on their first
  ``d_r`` dims, ``w = u W^I_w``. In a window layer: ``t - window < s <= t``
  at the ``swa`` geometry.
* Feed-forward: ``E(z; W) = (silu(z W_gate) * (z W_up)) W_down``; dense in
  the first layers; then ``s = sigmoid(z W_r)``, the top ``K`` of ``s + b``
  chosen, ``g_i = s_i / sum_chosen s_j``, and THIS share's part ``sum_{i
  chosen, held} g_i E_i(z) + E_shared(z)``: what the other ranks' experts
  would add is left out, as in the program.

The weights come a layer at a time (``weights.Weights``): a layer is made,
used and dropped. ``control`` (``"int8"`` / ``"fp8"``) computes the same
with the operands of every dense product, and the cached latent and
rotary key, rounded to that precision, but for what the program keeps in
float32 whatever its dtype, which stays float32 here too: the router's
scores, and the indexer's whole branch (the query latent as the indexer
reads it, its queries, keys, head weights and scores). That is the control
of how ``correct`` is decided, never part of a benchmark run.

``FAULTS`` are controls of another kind: the float32 forward with one of
the family's mechanisms broken, to show that a limit sees the mechanism
and not the precision alone. ``recent_keys``: a full-attention layer
keeps the ``index_topk`` most recent positions, whatever the indexer
scores. ``ring_cleared``: a window layer sees nothing that lies before
the last multiple of ``2 (window - 1)`` positions, a ring that lost its
content at the wrap. ``experts_rolled``: each held expert answers the
picks of the next one.
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

from chipbench.families.dots3 import weights as W

HI = lax.Precision.HIGHEST
FAULTS = ("recent_keys", "ring_cleared", "experts_rolled")
NEG = -1e30

#: a request is padded to a multiple of this many positions: the longest
#: context the family's cell serves, so that every request of it runs one
#: compiled shape (a layer's program takes most of a minute to compile,
#: a request at full length under five seconds). Short requests (tests)
#: pad to a multiple of 64.
PAD_LONG, PAD_SHORT = 8448, 64


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


def _layer_norm(x, g, b, eps=1e-6):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * g + b


def _rope(x, pos, theta, dims=None):
    """Half-split rotary on the first ``dims`` of the last axis of ``x``
    (T, ..., d)."""
    d = x.shape[-1] if dims is None else dims
    half = d // 2
    inv = jnp.exp(-math.log(theta)
                  * (jnp.arange(half, dtype=jnp.float32) * 2.0 / d))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    a, b, rest = x[..., :half], x[..., half:d], x[..., d:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


def _swiglu(z, w_gate, w_up, w_down, lowp):
    h = jax.nn.silu(_dense(z, w_gate, lowp)) * _dense(z, w_up, lowp)
    return _dense(h, w_down, lowp)


def _selection(s, lw, c_q, u, pos):
    """(T, T) mask of the positions each row of a full-attention layer
    attends (the ``topk`` causal positions of largest index score) and
    each row's margin of that choice. Never rounded: the indexer's whole
    branch is float32 in the program too."""
    g, hi, di = s["full"], s["HI"], s["DI"]
    t = u.shape[0]
    q = _rope(_dense(c_q, lw["wi_q"], None).reshape(t, hi, di), pos,
              g["theta"], dims=g["dr"])
    k = _rope(_layer_norm(_dense(u, lw["wi_k"], None), lw["wi_k_g"],
                          lw["wi_k_b"]), pos, g["theta"], dims=g["dr"])
    w = _dense(u, lw["wi_w"], None) * (hi ** -0.5) * (di ** -0.5)

    def head(acc, qw):                       # one index head at a time
        qj, wj = qw
        return acc + wj[:, None] * jax.nn.relu(
            jnp.dot(qj, k.T, precision=HI)), None

    scores = lax.scan(head, jnp.zeros((t, t), jnp.float32),
                      (jnp.moveaxis(q, 1, 0), w.T))[0]
    causal = pos[None, :] <= pos[:, None]
    if t <= s["topk"]:
        return causal, jnp.full((t,), jnp.inf)
    scores = jnp.where(causal, scores, -jnp.inf)
    best = lax.top_k(scores, s["topk"] + 1)[0]
    kth, nxt = best[:, -2:-1], best[:, -1]
    # the margin of the choice: how far the last position kept lies
    # above the first left out (inf while every position is kept)
    margin = jnp.where(nxt > -jnp.inf, kth[:, 0] - nxt, jnp.inf)
    # equal scores (exactly 0 wherever every head's product is negative)
    # are kept lowest position first
    above = scores > kth
    tied = causal & (scores == kth)
    room = s["topk"] - jnp.sum(above, -1, keepdims=True)
    return above | (tied & (jnp.cumsum(tied, -1) <= room)), margin


def _attention(s, kind, lw, x, pos, lowp, fault=None):
    g = s["full"] if kind == W.FULL else s["swa"]
    d, t = s["D"], x.shape[0]
    u = _rms(x, lw["attn_norm"], s["eps"])
    c_q = math.sqrt(d / g["rq"]) * _rms(_dense(u, lw["w_dq"], lowp),
                                        lw["q_norm"], s["eps"])
    q = _dense(c_q, lw["w_uq"], lowp).reshape(t, g["H"], g["dn"] + g["dr"])
    q_n, q_r = q[..., :g["dn"]], _rope(q[..., g["dn"]:], pos, g["theta"])
    ckr = _dense(u, lw["w_dkv"], lowp)
    c = math.sqrt(d / g["rkv"]) * _rms(ckr[:, :g["rkv"]], lw["kv_norm"],
                                       s["eps"])
    k_r = _rope(ckr[:, g["rkv"]:], pos, g["theta"])
    if lowp:                                 # what a cache would hold
        c, k_r = _fq(c, lowp), _fq(k_r, lowp)
    margin = jnp.full((t,), jnp.inf)
    if kind == W.FULL:
        exact = c_q if not lowp else math.sqrt(d / g["rq"]) * _rms(
            _dense(u, lw["w_dq"], None), lw["q_norm"], s["eps"])
        valid, margin = _selection(s, lw, exact, u, pos)
        if fault == "recent_keys":
            diff = pos[:, None] - pos[None, :]
            valid = (diff >= 0) & (diff < s["topk"])
    else:
        diff = pos[:, None] - pos[None, :]
        valid = (diff >= 0) & (diff < s["window"])
        if fault == "ring_cleared":
            ring = 2 * (s["window"] - 1)
            valid &= pos[None, :] >= (pos[:, None] // ring) * ring
    scale = 1.0 / math.sqrt(g["dn"] + g["dr"])

    def head(args):                          # one head at a time
        wk, wv, qn, qr = args
        k_n, v = _dense(c, wk, lowp), _dense(c, wv, lowp)
        sc = (jnp.dot(qn, k_n.T, precision=HI)
              + jnp.dot(qr, k_r.T, precision=HI)) * scale
        p = jax.nn.softmax(jnp.where(valid, sc, NEG), axis=-1)
        return jnp.dot(p, v, precision=HI)

    o = lax.map(head, (
        jnp.moveaxis(lw["w_uk"].reshape(g["rkv"], g["H"], g["dn"]), 1, 0),
        jnp.moveaxis(lw["w_uv"].reshape(g["rkv"], g["H"], g["dv"]), 1, 0),
        jnp.moveaxis(q_n, 1, 0), jnp.moveaxis(q_r, 1, 0)))   # (H, T, dv)
    gate = jax.nn.sigmoid(_dense(u, lw["w_g"], lowp))         # (T, H)
    o = jnp.moveaxis(o, 0, 1) * gate[..., None]
    return _dense(o.reshape(t, g["H"] * g["dv"]), lw["w_o"], lowp), margin


def route(s, lw, z):
    """``ids`` (T, K) over all routed experts, their gates, and the margin
    of the choice (the K-th biased score less the next), float32."""
    sc = jax.nn.sigmoid(jnp.dot(z, lw["router"], precision=HI))
    best, ids = lax.top_k(sc + lw["router_bias"], s["K"] + 1)
    ids = ids[:, :s["K"]]
    chosen = jnp.take_along_axis(sc, ids, axis=-1)
    return (ids, chosen / jnp.sum(chosen, -1, keepdims=True),
            best[:, -2] - best[:, -1])


def routed_share(s, lw, z, lowp=None, lo=None, fault=None):
    """``sum_{i chosen, held} g_i E_i(z)``: a dense loop over the held
    experts, each over every token, weighted by its gate (0 where it was
    not chosen). ``lo`` is the first expert held (default: the share the
    configuration names)."""
    lo = s["E_lo"] if lo is None else lo
    ids, gates, _ = route(s, lw, z)

    def expert(acc, ew):
        e, wg, wu, wd = ew
        picked = (e + 1) % s["E_held"] if fault == "experts_rolled" else e
        g_e = jnp.sum(jnp.where(ids == lo + picked, gates, 0.0), -1)
        return acc + g_e[:, None] * _swiglu(z, wg, wu, wd, lowp), None

    return lax.scan(expert, jnp.zeros_like(z),
                    (jnp.arange(s["E_held"]), lw["e_gate"], lw["e_up"],
                     lw["e_down"]))[0]


def _feed_forward(s, layer, lw, h, lowp, fault=None):
    z = _rms(h, lw["ffn_norm"], s["eps"])
    if layer < s["first_dense"]:
        return (_swiglu(z, lw["w_gate"], lw["w_up"], lw["w_down"], lowp),
                jnp.full((h.shape[0],), jnp.inf))
    return (routed_share(s, lw, z, lowp, fault=fault) + _swiglu(
        z, lw["s_gate"], lw["s_up"], lw["s_down"], lowp),
        route(s, lw, z)[2])


@functools.lru_cache(maxsize=None)
def _layer_fn(sizes_json, layer, control):
    s = json.loads(sizes_json)
    kind = s["kinds"][layer]
    lowp, fault = (None, control) if control in FAULTS else (control, None)

    def run(lw, x):
        pos = jnp.arange(x.shape[0], dtype=jnp.int32)
        a, index_margin = _attention(s, kind, lw, x, pos, lowp, fault)
        h = x + a
        f, route_margin = _feed_forward(s, layer, lw, h, lowp, fault)
        return h + f, index_margin, route_margin

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _head_fn(sizes_json, n_rows, lowp):
    s = json.loads(sizes_json)

    def run(top, x, start):
        h = lax.dynamic_slice_in_dim(x, start, n_rows, 0)
        return _dense(_rms(h, top["final_norm"], s["eps"]), top["head"],
                      lowp)

    return jax.jit(run)


def logits_rows(model, weights, tokens, start, n_rows, lowp=None,
                margins=False):
    """Float32 logits (n_rows, V) at positions ``[start, start + n_rows)``
    of the sequence ``tokens`` (T,), each position attending what precedes
    it. The weights are made a layer at a time and dropped. With
    ``margins`` also, for the same rows, the reference's own narrowest
    margins over the layers: ``(index, route)``, how far the last
    position the indexer kept lies above the first it left out, and the
    last expert chosen above the first not chosen."""
    s = weights.s
    key = json.dumps(s, sort_keys=True)
    top = weights.top()
    x = jnp.take(top["embed"], jnp.asarray(tokens, jnp.int32), axis=0)
    for i in range(s["L"]):
        lw = weights.layer(i)
        # layers of one kind share a compiled program: the layer index
        # only tells dense from routed and full from window
        same = min(j for j in range(s["L"])
                   if s["kinds"][j] == s["kinds"][i]
                   and (j < s["first_dense"]) == (i < s["first_dense"]))
        x, mi, mr = _layer_fn(key, same, lowp)(lw, x)
        index_m = mi if i == 0 else jnp.minimum(index_m, mi)
        route_m = mr if i == 0 else jnp.minimum(route_m, mr)
        del lw
    out = _head_fn(key, int(n_rows), None if lowp in FAULTS else lowp)(
        top, x, jnp.int32(start))
    if margins:
        return out, (index_m[start:start + n_rows],
                     route_m[start:start + n_rows])
    return out


def served_gaps(model, weights, prompt, served, n_max, control=None):
    """One request as it was served: ``prompt`` ids and the ``served``
    tokens (at most ``n_max``). Returns, per served token, its gap under
    the float32 reference's best logit at its position and whether it is
    the reference's first choice; with ``control`` (a precision, or one
    of ``FAULTS``) the tokens judged are the ones that forward puts first
    there. The sequence is padded (ids 0
    after its end, which no judged position attends) to a multiple of
    ``PAD_LONG`` positions, or of ``PAD_SHORT`` where it is short."""
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
    print(f"chipbench dots3 reference: {p} + {n} tokens padded to {t}, "
          f"control {control}: widest gap {gaps.max():.4f}, mean "
          f"{gaps.mean():.4f}, {time.perf_counter() - t0:.1f} s",
          file=sys.stderr, flush=True)
    return gaps, hits


def _not_trained(*_a, **_k):
    raise SystemExit("chipbench: the dots3 family is served, not trained: "
                     "its weights come a layer at a time "
                     "(chipbench/README.md, A model family)")


loss_and_grads = loss_only = leaf_norms = leaf_index = _not_trained
