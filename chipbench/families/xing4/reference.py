"""The plain reference of the ``xing4`` family: ``jax.numpy`` float32, every
product at ``Precision.HIGHEST``, the whole forward on every position, plain
causal attention in the non-absorbed form, every expert as a plain SwiGLU
under its gate, Sinkhorn as a Python loop, no cache, no batching, no
kernel. It imports nothing of the program.

What it computes (``D`` the hidden size, ``n = hc_mult``, ``N`` an RMSNorm
with its own gain, ``R`` rotary positions in the half-split convention with
YaRN's frequencies). A token's residual is ``X`` (n, D), ``X_0`` n copies
of its embedding row. One hyper-connected sublayer ``F`` with its own
``phi`` (held maps-major, (2 n + n^2, n D)), ``alpha``, ``b``::

    x~ = vec(X) / sqrt(mean(vec(X)^2) + hc_eps);  [p | q | r] = x~ phi^T
    H_pre = sigmoid(alpha_0 p + b_pre);  H_post = 2 sigmoid(alpha_1 q + b_post)
    M_0 = exp(clip(alpha_2 mat(r) + b_res, clamp_min, clamp_max))
    M_t = rownorm(colnorm(M_{t-1})), t = 1..hc_sinkhorn_iters, each / (sum + hc_eps)
    u = H_pre X;   X' = M_iters X + H_post^T F(N(u))

Each layer is two of them: attention, then the feed-forward. After the
last layer ``h = sum_i X_i``, logits ``N(h) W_head``.

* Attention on ``z = N(u)``: ``c_q = N(z W_dq)``, ``[q_n | q_r]_h = c_q
  W_uq``, ``q_r <- R(q_r, t)``; ``[c | k_r] = z W_dkv``, ``c <- N(c)``,
  ``k_r <- R(k_r, t)`` (one for all heads); ``k_n_h = c W_uk_h``, ``v_h =
  c W_uv_h``; ``score_h(t, s) = (q_n . k_n + q_r . k_r) scale`` over every
  ``s <= t``; output ``concat_h(softmax v_h) W_o``.
* YaRN (DeepSeek-V3's ``yarn_find_correction_range`` and
  ``yarn_linear_ramp_mask``, transcribed): with ``f_i = theta^(-2i/dr)``
  and ``g_i`` one minus the linear ramp between the dimensions that turn
  ``beta_fast`` and ``beta_slow`` times in ``original`` positions, the
  frequency is ``(1 - g_i) f_i / factor + g_i f_i``; cos and sin are
  scaled by ``m(mscale) / m(mscale_all_dim)`` and ``scale = (dn + dr)^-1/2
  m(mscale_all_dim)^2`` with ``m(x) = 0.1 x ln(factor) + 1``.
* Feed-forward on ``z = N(u)``: ``E(z; W) = (silu(z W_gate) * (z W_up))
  W_down``; dense in the first layers; then ``s = sigmoid(z W_r)``, the
  top ``K`` of ``s + bias`` chosen, ``g_i = routed_scaling_factor s_i /
  sum_chosen s_j``, result ``sum_chosen g_i E_i(z) + E_shared(z)``.

The weights come a layer at a time (``weights.Weights``). ``control``
(``"int8"`` / ``"fp8"``) computes the same with the operands of every
dense product, and the cached latent and rotary key, rounded to that
precision, but for what the program keeps in float32 whatever its dtype,
which stays float32 here too: the router's scores and the
hyper-connections' coefficient path. That is the control of how
``correct`` is decided, never part of a benchmark run.

**Which served tokens are judged.** Top-k routing is discontinuous: where
the last expert chosen and the first left out score within a bfloat16
stream's noise of each other, a correct bfloat16 program and this float32
forward pick differently, and with random weights and gates of a half
one other pick moves that position's logits by up to 0.4, thirty times
what bfloat16 does elsewhere (my chip runs, PR 33: at the published
widths the bfloat16 program's logits lie within 0.016 of these with the
two dense layers alone, and within 0.018 with all six layers at the
positions whose picks are decided, but up to 0.43 away at the others,
where a served token then reads 0.2-0.3 under the best: as far as the
fp8 control and four of the six faults read there). So ``served_gaps``
judges a served token only where THIS forward's own narrowest router
margin over the routed layers (the last expert chosen above the first
not chosen, float32) is at least ``DECIDED``, several times the noise a
bfloat16 stream puts on a margin (about 1e-3 after five layers), and
reports a gap of 0 elsewhere: about one position in seventeen of a
request is judged at four routed layers (a margin is near exponential
with mean 0.012), 216-280 of a run's 3,300-6,000. The
controls and the faults are judged at the same positions.

``FAULTS`` are controls of another kind: the float32 forward with one
mechanism broken, to show that a limit sees the mechanism and not the
precision alone. ``sinkhorn_once``: one Sinkhorn round, not
``hc_sinkhorn_iters``. ``res_identity``: ``H_res = I``. ``post_uniform``:
``H_post = 1``. ``yarn_off``: unscaled rotary frequencies and ``scale =
(dn + dr)^-1/2``. ``gates_unscaled``: ``routed_scaling_factor`` 1.
``experts_rolled``: each expert answers the picks of the next one.
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

HI = lax.Precision.HIGHEST
FAULTS = ("sinkhorn_once", "res_identity", "post_uniform", "yarn_off",
          "gates_unscaled", "experts_rolled")
NEG = -1e30

#: a request is padded to a multiple of this many positions: the longest
#: context the family's cell serves, so that every request of it runs one
#: compiled shape. Short requests (tests) pad to a multiple of 64.
PAD_LONG, PAD_SHORT = 9216, 64

#: a served token is judged where the reference's own narrowest router
#: margin at its position is at least this (see the module's text)
DECIDED = 8e-3


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


def _swiglu(z, w_gate, w_up, w_down, lowp):
    h = jax.nn.silu(_dense(z, w_gate, lowp)) * _dense(z, w_up, lowp)
    return _dense(h, w_down, lowp)


# -- YaRN, as DeepSeek-V3's modeling code has it ------------------------------
def yarn_find_correction_dim(num_rotations, dim, base, max_position):
    return (dim * math.log(max_position / (num_rotations * 2 * math.pi))
            ) / (2 * math.log(base))


def yarn_find_correction_range(low_rot, high_rot, dim, base, max_position):
    low = math.floor(yarn_find_correction_dim(low_rot, dim, base,
                                              max_position))
    high = math.ceil(yarn_find_correction_dim(high_rot, dim, base,
                                              max_position))
    return max(low, 0), min(high, dim - 1)


def yarn_linear_ramp_mask(lo, hi, dim):
    if lo == hi:
        hi += 0.001
    return np.clip((np.arange(dim, dtype=np.float64) - lo) / (hi - lo),
                   0.0, 1.0)


def yarn_get_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rotary(s, fault=None):
    """``(inv_freq (dr / 2,) float64, factor on cos and sin, softmax
    scale)`` of the attention."""
    y, dim, base = s["yarn"], s["dr"], s["theta"]
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    plain = 1.0 / math.sqrt(s["dn"] + s["dr"])
    if fault == "yarn_off":
        return freq_extra, 1.0, plain
    freq_inter = freq_extra / y["factor"]
    low, high = yarn_find_correction_range(
        y["beta_fast"], y["beta_slow"], dim, base, y["original"])
    mask = 1.0 - yarn_linear_ramp_mask(low, high, dim // 2)
    inv = freq_inter * (1.0 - mask) + freq_extra * mask
    m_all = yarn_get_mscale(y["factor"], y["mscale_all_dim"])
    return (inv, yarn_get_mscale(y["factor"], y["mscale"]) / m_all,
            plain * m_all * m_all)


def _rope(x, pos, inv, on_cos_sin):
    """Half-split rotary on the last axis of ``x`` (T, ..., d)."""
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos = jnp.cos(ang).reshape(shape) * on_cos_sin
    sin = jnp.sin(ang).reshape(shape) * on_cos_sin
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


# -- the hyper-connection -------------------------------------------------------
def hc_maps(s, phi, alpha, b, x, fault=None):
    """``H_pre`` (T, n), ``H_post`` (T, n), ``H_res`` (T, n, n) of the
    streams ``x`` (T, n, D). Never rounded: the program keeps this path
    float32 whatever its dtype."""
    n, t = s["n"], x.shape[0]
    flat = x.reshape(t, -1)
    xn = flat * lax.rsqrt(jnp.mean(jnp.square(flat), -1, keepdims=True)
                          + s["hc_eps"])
    pqr = jnp.dot(xn, phi.T, precision=HI)
    h_pre = jax.nn.sigmoid(alpha[0] * pqr[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * pqr[:, n:2 * n] + b[n:2 * n])
    m = jnp.exp(jnp.clip(
        alpha[2] * pqr[:, 2 * n:].reshape(t, n, n) + b[2 * n:].reshape(n, n),
        s["clamp"][0], s["clamp"][1]))
    for _ in range(1 if fault == "sinkhorn_once" else s["iters"]):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + s["hc_eps"])
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + s["hc_eps"])
    if fault == "res_identity":
        m = jnp.broadcast_to(jnp.eye(n, dtype=m.dtype), m.shape)
    if fault == "post_uniform":
        h_post = jnp.ones_like(h_post)
    return h_pre, h_post, m


def _sublayer(s, lw, which, norm, x, fn, fault):
    """The new streams, and the sublayer's normed input ``N(u)``."""
    h_pre, h_post, h_res = hc_maps(
        s, lw[which + "_phi"], lw[which + "_alpha"], lw[which + "_b"], x,
        fault)
    # sums over the n streams written out: elementwise float32, no product
    # whose precision is the accelerator's to choose
    n = s["n"]
    u = sum(h_pre[:, j, None] * x[:, j] for j in range(n))
    z = _rms(u, lw[norm], s["eps"])
    mixed = sum(h_res[:, :, j, None] * x[:, None, j, :] for j in range(n))
    return mixed + h_post[:, :, None] * fn(z)[:, None, :], z


# -- attention and the feed-forward ----------------------------------------------
def _attention(s, lw, z, pos, lowp, fault):
    t = z.shape[0]
    inv, on_cos_sin, scale = rotary(s, fault)
    c_q = _rms(_dense(z, lw["w_dq"], lowp), lw["q_norm"], s["eps"])
    q = _dense(c_q, lw["w_uq"], lowp).reshape(t, s["H"], s["dn"] + s["dr"])
    q_n = q[..., :s["dn"]]
    q_r = _rope(q[..., s["dn"]:], pos, inv, on_cos_sin)
    ckr = _dense(z, lw["w_dkv"], lowp)
    c = _rms(ckr[:, :s["rkv"]], lw["kv_norm"], s["eps"])
    k_r = _rope(ckr[:, s["rkv"]:], pos, inv, on_cos_sin)
    if lowp:                                 # what a cache would hold
        c, k_r = _fq(c, lowp), _fq(k_r, lowp)
    causal = pos[None, :] <= pos[:, None]

    def head(args):                          # one head at a time
        wk, wv, qn, qr = args
        k_n, v = _dense(c, wk, lowp), _dense(c, wv, lowp)
        sc = (jnp.dot(qn, k_n.T, precision=HI)
              + jnp.dot(qr, k_r.T, precision=HI)) * scale
        p = jax.nn.softmax(jnp.where(causal, sc, NEG), axis=-1)
        return jnp.dot(p, v, precision=HI)

    o = lax.map(head, (
        jnp.moveaxis(lw["w_uk"].reshape(s["rkv"], s["H"], s["dn"]), 1, 0),
        jnp.moveaxis(lw["w_uv"].reshape(s["rkv"], s["H"], s["dv"]), 1, 0),
        jnp.moveaxis(q_n, 1, 0), jnp.moveaxis(q_r, 1, 0)))   # (H, T, dv)
    return _dense(jnp.moveaxis(o, 0, 1).reshape(t, s["H"] * s["dv"]),
                  lw["w_o"], lowp)


def route(s, lw, z, fault=None):
    """``ids`` (T, K), their gates, and the margin of the choice (the
    K-th biased score less the next), float32."""
    sc = jax.nn.sigmoid(jnp.dot(z, lw["router"], precision=HI))
    best, ids = lax.top_k(sc + lw["router_bias"], s["K"] + 1)
    ids = ids[:, :s["K"]]
    chosen = jnp.take_along_axis(sc, ids, axis=-1)
    factor = 1.0 if fault == "gates_unscaled" else s["gate_scale"]
    return (ids, factor * chosen / jnp.sum(chosen, -1, keepdims=True),
            best[:, -2] - best[:, -1])


def routed(s, lw, z, lowp=None, fault=None):
    """``sum_{i chosen} g_i E_i(z)``: a loop over the experts, each over
    every token, weighted by its gate (0 where it was not chosen)."""
    ids, gates, _ = route(s, lw, z, fault)

    def expert(acc, ew):
        e, wg, wu, wd = ew
        picked = (e + 1) % s["E"] if fault == "experts_rolled" else e
        g_e = jnp.sum(jnp.where(ids == picked, gates, 0.0), -1)
        return acc + g_e[:, None] * _swiglu(z, wg, wu, wd, lowp), None

    return lax.scan(expert, jnp.zeros_like(z),
                    (jnp.arange(s["E"]), lw["e_gate"], lw["e_up"],
                     lw["e_down"]))[0]


def _feed_forward(s, layer, lw, z, lowp, fault):
    if layer < s["first_dense"]:
        return _swiglu(z, lw["w_gate"], lw["w_up"], lw["w_down"], lowp)
    return routed(s, lw, z, lowp, fault) + _swiglu(
        z, lw["s_gate"], lw["s_up"], lw["s_down"], lowp)


@functools.lru_cache(maxsize=None)
def _layer_fn(sizes_json, layer, control):
    s = json.loads(sizes_json)
    lowp, fault = (None, control) if control in FAULTS else (control, None)

    def run(lw, x):
        pos = jnp.arange(x.shape[0], dtype=jnp.int32)
        x, _ = _sublayer(
            s, lw, "a", "attn_norm", x,
            lambda z: _attention(s, lw, z, pos, lowp, fault), fault)
        x, z = _sublayer(
            s, lw, "f", "ffn_norm", x,
            lambda z: _feed_forward(s, layer, lw, z, lowp, fault), fault)
        if layer < s["first_dense"]:
            return x, jnp.full((x.shape[0],), jnp.inf)
        return x, route(s, lw, z)[2]

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _head_fn(sizes_json, n_rows, lowp):
    s = json.loads(sizes_json)

    def run(top, x, start):
        h = jnp.sum(lax.dynamic_slice_in_dim(x, start, n_rows, 0), axis=1)
        return _dense(_rms(h, top["final_norm"], s["eps"]), top["head"],
                      lowp)

    return jax.jit(run)


def logits_rows(model, weights, tokens, start, n_rows, control=None,
                margins=False):
    """Float32 logits (n_rows, V) at positions ``[start, start + n_rows)``
    of the sequence ``tokens`` (T,), each position attending what precedes
    it. The weights are made a layer at a time and dropped. With
    ``margins`` also, for the same rows, the reference's own narrowest
    router margin over the layers (the last expert chosen above the first
    not chosen)."""
    s = weights.s
    key = json.dumps(s, sort_keys=True)
    top = weights.top()
    e = jnp.take(top["embed"], jnp.asarray(tokens, jnp.int32), axis=0)
    x = jnp.broadcast_to(e[:, None, :], (e.shape[0], s["n"], e.shape[1]))
    route_m = None
    for i in range(s["L"]):
        lw = weights.layer(i)
        # dense layers share one compiled program, routed layers another
        same = min(i, s["first_dense"])
        x, mr = _layer_fn(key, same, control)(lw, x)
        route_m = mr if route_m is None else jnp.minimum(route_m, mr)
        del lw
    out = _head_fn(key, int(n_rows), None if control in FAULTS else control)(
        top, x, jnp.int32(start))
    if margins:
        return out, route_m[start:start + n_rows]
    return out


def served_gaps(model, weights, prompt, served, n_max, control=None):
    """One request as it was served: ``prompt`` ids and the ``served``
    tokens (at most ``n_max``). Returns, per served token, its gap under
    the float32 reference's best logit at its position and whether it is
    the reference's first choice; with ``control`` (a precision, or one
    of ``FAULTS``) the tokens judged are the ones that forward puts first
    there. A token is judged where the float32 forward's own router
    margin is at least ``DECIDED``; elsewhere its gap is reported as 0
    (the module's text says why). The sequence is padded (ids 0 after its
    end, which no judged position attends) to a multiple of ``PAD_LONG``
    positions, or of ``PAD_SHORT`` where it is short."""
    t0 = time.perf_counter()
    p, n = len(prompt), len(served)
    q = PAD_LONG if p + n > PAD_LONG // 8 else PAD_SHORT
    t = max(-(-(p + n) // q) * q, -(-(p - 1 + n_max) // q) * q)
    row = np.zeros((t,), np.int32)
    row[:p] = prompt
    row[p:p + n] = served
    logits, margin = logits_rows(model, weights, row, p - 1, n_max,
                                 margins=True)
    logits, decided = logits[:n], np.asarray(margin[:n]) >= DECIDED
    served = jnp.asarray(np.asarray(served, np.int32))
    if control:
        served = jnp.argmax(
            logits_rows(model, weights, row, p - 1, n_max, control)[:n],
            -1).astype(jnp.int32)
    best = jnp.max(logits, -1)
    mine = jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
    every = np.asarray(best - mine)
    gaps = np.where(decided, every, 0.0)
    hits = np.asarray(jnp.argmax(logits, -1) == served)
    print(f"chipbench xing4 reference: {p} + {n} tokens padded to {t}, "
          f"control {control}: {int(decided.sum())} judged, widest gap "
          f"{gaps.max():.4f} (of all {n}: {every.max():.4f}, mean "
          f"{every.mean():.4f}), {time.perf_counter() - t0:.1f} s",
          file=sys.stderr, flush=True)
    return gaps, hits


def _not_trained(*_a, **_k):
    raise SystemExit("chipbench: the xing4 family is served, not trained: "
                     "its weights come a layer at a time "
                     "(chipbench/README.md, A model family)")


loss_and_grads = loss_only = leaf_norms = leaf_index = _not_trained
