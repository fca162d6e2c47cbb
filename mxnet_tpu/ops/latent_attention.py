"""Multi-head latent attention as pure functions, shared by the decoder
families that have it (``gluon/model_zoo/dots3.py``, ``xing4.py``).

A position caches one row: the normed key/value latent ``c`` (``rkv``
wide) and one rotary key ``k_r`` (``dr`` wide) shared by all heads, padded
to a multiple of 128. Queries come through a low-rank latent of their
own. Two forms compute the same attention: the *plain* one makes every
head's keys and values from the cached latents (``attend_plain``: many
queries of one slot, a prefill chunk) and the *absorbed* one folds
``W_uk`` into the query and ``W_uv`` behind the weighted latents
(``attend_absorbed``: one query a slot, a decode tick).

What differs between the families is an argument, not a copy: the
rescale of the two normed latents (``Geom.a_q`` / ``a_kv``; 1 unless a
family says otherwise), the rotary frequencies (``Geom.inv_freq``; YaRN
through ``yarn_inv_freq``), the softmax scale (``Geom.scale``;
``yarn_mscale`` squared folds in there), and whatever a family does to the
heads' outputs before its output projection (its own code). Each function
takes the layer's arrays ``p`` (short name -> array): ``w_dq``,
``q_norm``, ``w_uq``, ``w_dkv``, ``kv_norm``, ``w_uk``, ``w_uv``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as onp
from jax import lax

from .attention import NEG_INF

_F32 = jnp.float32


def pad128(n):
    return -(-n // 128) * 128


def rope_inv_freq(theta, d):
    """The ``d / 2`` rotary frequencies ``theta^(-2i/d)``, float32."""
    return jnp.exp(-math.log(theta)
                   * (jnp.arange(d // 2, dtype=_F32) * 2.0 / d))


def yarn_mscale(factor, mscale):
    """YaRN's attention temperature ``m(x) = 0.1 x ln(factor) + 1`` (1
    where nothing is scaled)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(theta, d, factor, original, beta_fast, beta_slow):
    """YaRN's ``d / 2`` rotary frequencies, float32 (host arithmetic in
    float64, as DeepSeek-V3's ``yarn_find_correction_range`` and
    ``yarn_linear_ramp_mask``): ``(1 - g_i) f_i / factor + g_i f_i`` with
    ``f_i = theta^(-2i/d)`` and ``g_i`` one minus the linear ramp between
    the dimensions that turn ``beta_fast`` times and ``beta_slow`` times in
    ``original`` positions (fast dimensions keep their frequency, slow ones
    are interpolated)."""
    half = d // 2
    f = theta ** (-onp.arange(half, dtype=onp.float64) * 2.0 / d)

    def turns_at(n):
        return d * math.log(original / (n * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = onp.clip((onp.arange(half, dtype=onp.float64) - low)
                    / (high - low), 0.0, 1.0)
    g = 1.0 - ramp
    return jnp.asarray((1.0 - g) * f / factor + g * f, _F32)


class Geom:
    """One attention geometry. ``inv_freq`` is called where a rotation is
    traced and returns the ``dims / 2`` frequencies; ``a_q`` / ``a_kv``
    multiply the normed query and key/value latents."""

    def __init__(self, heads, nope, rope, v, q_rank, kv_rank, theta,
                 a_q=1.0, a_kv=1.0, scale=None, inv_freq=None):
        self.h, self.dn, self.dr, self.dv = heads, nope, rope, v
        self.rq, self.rkv, self.theta = q_rank, kv_rank, float(theta)
        self.row = kv_rank + rope            # what one position caches
        self.row_pad = pad128(self.row)      # minor dimension of a pool
        self.a_q, self.a_kv = float(a_q), float(a_kv)
        self.scale = 1.0 / math.sqrt(nope + rope) if scale is None \
            else float(scale)
        self.inv_freq = inv_freq if inv_freq is not None \
            else (lambda d: rope_inv_freq(self.theta, d))


def rms32(x, g, eps):
    """RMSNorm in float32, left in float32."""
    x32 = x.astype(_F32)
    y = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    return y * g.astype(_F32)


def rms(x, g, eps):
    return rms32(x, g, eps).astype(x.dtype)


def dot32(a, w):
    """A float32 product of float32 operands, at full precision. Both
    operands have to BE float32: the TPU compiler folds a bfloat16
    array's conversion into the product, and a product with one bfloat16
    operand rounds the other to bfloat16 too, whatever precision it is
    asked for (my chip run, PR 27: a query latent off by 0.8 %). So the
    leaves such a branch multiplies are float32 leaves."""
    assert a.dtype == _F32 and w.dtype == _F32, (a.dtype, w.dtype)
    return jnp.dot(a, w, precision=lax.Precision.HIGHEST)


def rope(x, pos, inv_freq, dims=None):
    """Rotary positions in the half-split convention on the first
    ``dims`` of the last axis (all of it by default). ``x`` (T, ..., d),
    ``pos`` (T,); ``inv_freq(d)`` gives the ``d / 2`` frequencies."""
    d = x.shape[-1] if dims is None else dims
    half = d // 2
    inv = inv_freq(d)
    ang = pos.astype(_F32)[:, None] * inv[None, :]           # (T, half)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x32 = x.astype(_F32)
    a, b, rest = x32[..., :half], x32[..., half:d], x32[..., d:]
    out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)
    return out.astype(x.dtype)


def swiglu(z, w_gate, w_up, w_down):
    g = jnp.dot(z, w_gate, preferred_element_type=_F32)
    u = jnp.dot(z, w_up, preferred_element_type=_F32)
    h = (jax.nn.silu(g) * u).astype(z.dtype)
    return jnp.dot(h, w_down, preferred_element_type=_F32)


def queries(p, u32, pos, g, eps, exact=False):
    """The query latent in float32 (``exact``: a layer whose float32
    branch reads it, and whose ``w_dq`` is a float32 leaf; else ``None``)
    and the per-head queries ``q_n`` (T, H, dn), ``q_r`` (T, H, dr)
    rotated, in the dtype of ``w_uq``. ``u32`` is the layer's normed
    input in float32."""
    u = u32.astype(p["w_uq"].dtype)
    if exact:
        c_q32 = g.a_q * rms32(dot32(u32, p["w_dq"]), p["q_norm"], eps)
        c_q = c_q32.astype(u.dtype)
    else:
        c_q32 = None
        c_q = (g.a_q * rms32(jnp.dot(u, p["w_dq"]), p["q_norm"],
                             eps)).astype(u.dtype)
    q = jnp.dot(c_q, p["w_uq"]).reshape(-1, g.h, g.dn + g.dr)
    return c_q32, q[..., :g.dn], rope(q[..., g.dn:], pos, g.inv_freq)


def latent_rows(p, u, pos, g, eps):
    """What a position caches, (T, row_pad): the normed (and rescaled)
    key/value latent, the rotary key, zeros up to the pool's width."""
    ckr = jnp.dot(u, p["w_dkv"])
    c = rms(ckr[:, :g.rkv], p["kv_norm"], eps)
    c = (c.astype(_F32) * g.a_kv).astype(u.dtype)
    k_r = rope(ckr[:, g.rkv:], pos, g.inv_freq)
    pad = jnp.zeros((u.shape[0], g.row_pad - g.row), u.dtype)
    return jnp.concatenate([c, k_r, pad], -1)


def softmax_masked(s, valid):
    s = jnp.where(valid, s, NEG_INF)
    m = s.max(-1, keepdims=True)
    e = jnp.where(valid, jnp.exp(s - m), 0.0)
    l = e.sum(-1, keepdims=True)
    return e / jnp.where(l > 0, l, 1.0)


def head_block(g, t, s):
    """Heads a step of the plain attention: the (hb, T, S) float32
    scores stay near 256 MB."""
    hb = g.h
    while hb > 1 and hb * t * s * 4 > (1 << 28) and hb % 2 == 0:
        hb //= 2
    return hb


def attend_plain(p, q_n, q_r, rows, valid, g, head_block):
    """Plain (non-absorbed) attention of T queries over S cached rows
    under ``valid`` (T, S): keys and values are made from the latent,
    ``head_block`` heads at a time so that no (H, T, S) tensor is ever
    whole. Returns (T, H, dv)."""
    t = q_n.shape[0]
    c, k_r = rows[:, :g.rkv], rows[:, g.rkv:g.row]
    nb = g.h // head_block
    w_uk = p["w_uk"].reshape(g.rkv, nb, head_block, g.dn)
    w_uv = p["w_uv"].reshape(g.rkv, nb, head_block, g.dv)

    def block(args):
        wk, wv, qn, qr = args
        k_n = jnp.einsum("sr,rhd->shd", c, wk)
        v = jnp.einsum("sr,rhd->shd", c, wv)
        s = jnp.einsum("thd,shd->hts", qn, k_n,
                       preferred_element_type=_F32)
        s += jnp.einsum("thd,sd->hts", qr, k_r,
                        preferred_element_type=_F32)
        pr = softmax_masked(s * g.scale, valid[None]).astype(v.dtype)
        return jnp.einsum("hts,shd->thd", pr, v)

    out = lax.map(block, (
        jnp.moveaxis(w_uk, 1, 0), jnp.moveaxis(w_uv, 1, 0),
        jnp.moveaxis(q_n.reshape(t, nb, head_block, g.dn), 1, 0),
        jnp.moveaxis(q_r.reshape(t, nb, head_block, g.dr), 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(t, g.h, g.dv)


def attend_absorbed(p, q_n, q_r, rows, valid, g):
    """Absorbed attention of one query a row over its own K cached rows:
    ``q_n`` (B, H, dn), ``rows`` (B, K, row_pad), ``valid`` (B, K).
    Returns (B, H, dv)."""
    c, k_r = rows[..., :g.rkv], rows[..., g.rkv:g.row]
    q_abs = jnp.einsum("bhd,rhd->bhr", q_n,
                       p["w_uk"].reshape(g.rkv, g.h, g.dn))
    s = jnp.einsum("bhr,bkr->bhk", q_abs, c, preferred_element_type=_F32)
    s += jnp.einsum("bhd,bkd->bhk", q_r, k_r, preferred_element_type=_F32)
    pr = softmax_masked(s * g.scale, valid[:, None, :]).astype(c.dtype)
    ctx = jnp.einsum("bhk,bkr->bhr", pr, c)
    return jnp.einsum("bhr,rhd->bhd", ctx,
                      p["w_uv"].reshape(g.rkv, g.h, g.dv))
