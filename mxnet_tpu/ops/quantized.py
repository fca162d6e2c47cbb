"""Fused dequantize-matmul: the weight-only int8 decode kernel.

Decode at small batch is weight-bandwidth-bound: every step re-streams
the full parameter set from HBM, so shrinking the bytes — not the
FLOPs — raises the ceiling. Weights are stored per-output-channel
symmetric int8 (``quantize_channelwise``) and the projection matmul
dequantizes them ON THE FLY, one output-channel block at a time:

    y = (x @ wq.astype(f32).T) * scale[None, :]

with the converted block living only in VMEM (Pallas TPU kernel) or
cache (blocked jnp path) — the dequantized weight never materializes
in HBM. Contrast contrib/quantization.py, which quantizes the
ACTIVATIONS too and runs int8 x int8 contractions (the MXU inference
path): here activations stay fp32, so the only error source is the
weight rounding — the property the serving engine's bounded-divergence
gate (docs/SERVING.md "Low-precision decode") is built on.

Parity discipline: ``dequant_matmul`` (jnp) and
``dequant_matmul_pallas`` perform the IDENTICAL per-block computation
— same block boundaries, same convert-multiply-dot order, same
``preferred_element_type`` — so the pair is bitwise-identical on one
backend (tested in tests/test_quantized.py); the engine-level int8
claims then reduce to properties of ONE numerical path.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from .attention import _use_pallas

__all__ = ["quantize_channelwise", "dequant_matmul",
           "dequant_matmul_pallas", "kv_scale", "kv_quantize"]

_INT8_MAX = 127.0
#: default output-channel block: 512 f32-dequantized channels of a
#: K<=4096 weight stay comfortably inside VMEM (and L2 on CPU)
_BLOCK_N = 512


def quantize_channelwise(w, axis=0):
    """fp32 weight -> ``(int8 weight, fp32 scales)`` with a symmetric
    range per output channel (``axis``; Dense layout is ``(out, in)``
    so the default quantizes each output row against its own absmax —
    the error of one channel never inflates another's scale).
    ``dequant == wq.astype(f32) * scale`` broadcast over ``axis``;
    scales are returned flat ``(w.shape[axis],)``."""
    w = jnp.asarray(w)
    if w.dtype != jnp.float32:
        w = w.astype(jnp.float32)
    red = tuple(i for i in range(w.ndim) if i != axis)
    absmax = jnp.abs(w).max(axis=red, keepdims=True)
    # all-zero channels get scale eps/127, quantize to 0, dequantize
    # to exact 0 — never a div-by-zero NaN
    scale = (jnp.maximum(absmax, 1e-12) / _INT8_MAX).astype(jnp.float32)
    wq = jnp.clip(jnp.round(w / scale), -_INT8_MAX, _INT8_MAX) \
        .astype(jnp.int8)
    return wq, scale.reshape(-1)


def kv_scale(x, axes):
    """amax-derived symmetric int8 scale over ``axes`` (fp32) — the KV
    companion of ``quantize_channelwise``, kept here so the whole
    int8 convention (amax/127 range, eps floor, round-then-clip)
    lives in one module."""
    return (jnp.max(jnp.abs(x), axis=axes) / _INT8_MAX) \
        .astype(jnp.float32)


def kv_quantize(x, scale):
    """Quantize K/V values with a broadcast-ready ``scale``. The
    epsilon floor keeps an unwritten slot's zero scale from minting
    NaN int8 garbage — those rows are masked out of attention, but a
    NaN V row would still poison the ``p @ v`` accumulation
    (0 * NaN)."""
    s = jnp.maximum(scale, 1e-12)
    return jnp.clip(jnp.round(x / s), -_INT8_MAX, _INT8_MAX) \
        .astype(jnp.int8)


def _dequant_dot(x2, wq_blk, s_row):
    """The ONE canonical block computation both paths run: convert the
    int8 block, contract x's feature axis against the weight's ``in``
    axis in fp32, then scale each output channel (``s_row`` is
    ``(1, bn)``: the scale multiplies the product, not the weight, so
    it broadcasts along lanes and costs ``B * bn`` multiplies instead
    of ``bn * K``). Kept as a shared helper so the jnp/Pallas pair
    cannot drift apart numerically."""
    y = lax.dot_general(x2, wq_blk.astype(jnp.float32),
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    return y * s_row


def dequant_matmul(x, wq, scales, block_n=_BLOCK_N):
    """``x @ dequant(wq, scales).T`` — blocked jnp reference.

    ``x`` is ``(..., K)`` fp32, ``wq`` ``(N, K)`` int8 (the Dense
    ``(out, in)`` layout), ``scales`` ``(N,)`` fp32. Returns
    ``(..., N)`` fp32. The weight is converted ``block_n`` output
    channels at a time inside a ``lax.map`` — the converted block is
    consumed by its dot before the next one exists, so peak extra
    memory is one block, not the whole fp32 weight; channels past the
    last whole block (``N % block_n``) take one more dot. On TPU
    dispatches to the Pallas kernel (same per-block arithmetic)."""
    wq = jnp.asarray(wq)
    scales = jnp.asarray(scales)
    x = jnp.asarray(x)
    n, k = wq.shape
    if x.shape[-1] != k:
        raise ValueError(f"x features {x.shape[-1]} do not match "
                         f"quantized weight in-dim {k}")
    if scales.shape != (n,):
        raise ValueError(f"scales shape {scales.shape} must be ({n},)")
    if _use_pallas():
        return dequant_matmul_pallas(x, wq, scales, block_n=block_n)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, k).astype(jnp.float32)
    bn = min(int(block_n), n)
    nb = n // bn
    s_row = scales[None, :]

    def body(j):
        wq_blk = lax.dynamic_slice(wq, (j * bn, 0), (bn, k))
        s_blk = lax.dynamic_slice(s_row, (0, j * bn), (1, bn))
        return _dequant_dot(x2, wq_blk, s_blk)

    if nb == 1:
        out = _dequant_dot(x2, wq[:bn], s_row[:, :bn])
    else:
        out = lax.map(body, jnp.arange(nb))        # (nb, B, bn)
        out = out.transpose(1, 0, 2).reshape(x2.shape[0], nb * bn)
    if nb * bn < n:
        tail = _dequant_dot(x2, wq[nb * bn:], s_row[:, nb * bn:])
        out = jnp.concatenate([out, tail], axis=1)
    return out.reshape(*lead, n)


def _dequant_matmul_kernel(x_ref, wq_ref, s_ref, o_ref):
    """One output-channel-block grid step: the int8 weight block and
    its scales stream into VMEM, convert in-register, one fp32 dot.
    The fp32 weight exists ONLY as this block."""
    o_ref[...] = _dequant_dot(x_ref[...], wq_ref[...], s_ref[...])


def dequant_matmul_pallas(x, wq, scales, block_n=_BLOCK_N,
                          interpret=False):
    """Pallas fused dequant-matmul: grid over output-channel blocks;
    each step DMAs one ``(block_n, K)`` int8 block + its ``(1,
    block_n)`` scales row, converts in VMEM, and writes one fp32
    output block — per-block arithmetic identical to the jnp path
    (bitwise-parity tested). ``N`` need not divide by ``block_n``:
    the last grid step overhangs, its out-of-range channels compute
    on padding and are dropped at write-back. On TPU ``block_n`` must
    be a multiple of 128 (one lane tile) unless it covers ``N``."""
    import jax.experimental.pallas as pl

    n, k = wq.shape
    lead = x.shape[:-1]
    x2 = x.reshape(-1, k).astype(jnp.float32)
    b = x2.shape[0]
    bn = min(int(block_n), n)
    out = pl.pallas_call(
        _dequant_matmul_kernel,
        name="dequant_matmul",
        grid=(pl.cdiv(n, bn),),
        in_specs=[
            pl.BlockSpec((b, k), lambda j: (0, 0)),
            pl.BlockSpec((bn, k), lambda j: (j, 0)),
            pl.BlockSpec((1, bn), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((b, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        interpret=interpret,
    )(x2, wq, scales[None, :])
    return out.reshape(*lead, n)
