"""Attention kernels: Pallas flash attention + ring attention (sp).

The reference has NO long-context support (SURVEY.md §5: "ring
attention, context parallel — absent upstream"); these are first-class
here because they shape the core design on TPU:

- `flash_attention` — blockwise online-softmax attention. On TPU the
  forward runs as a Pallas kernel (one q-block per grid step, KV
  streamed through VMEM, fp32 accumulators — the MXU-friendly
  formulation); backward recomputes attention blockwise (flash-style
  rematerialization: O(S) memory, no S×S residuals).
- `ring_attention` — sequence parallelism over the 'sp' mesh axis:
  each device holds a sequence shard of Q/K/V; KV shards rotate
  around the ring via `lax.ppermute` while every device accumulates
  online-softmax partial results. Collective-permute overlaps with
  the next block's compute under XLA's latency-hiding scheduler, so
  the ring rides the ICI torus at full bandwidth.
- `decode_attention` — the autoregressive fast path: one query per
  sequence against a preallocated KV cache buffer, masked to each
  row's valid length (serving/generate.py slot batches). jnp path
  everywhere; Pallas TPU kernel (scalar-prefetched lengths, KV
  streamed through VMEM) behind the same `_use_pallas()` gate.
- `paged_decode_attention` — the same against a PAGED cache: a page
  pool `(n_pages, page_size, H * D)` (a page is `page_size` rows of
  all heads, row-major on the chip: "the paged pool's layout" below)
  and a per-slot page table. Each slot's view is gathered from the
  pool and attended on the jnp path, on every backend: no Pallas
  kernel (PERF.md §6, PRs 25 and 28). One query a slot (the decode
  tick) attends the gathered ROWS as they lie
  (`rows_decode_attention`); several queries a slot, and every program
  traced over a tp mesh, attend the view split into heads
  (`gather_pages`).

All shapes are (batch, heads, seq, head_dim). `kv_len` arguments mean
"only the first kv_len entries of the key/value buffer are real" —
the cache-backed convention: buffers are allocated at S_max, filled
left-to-right, and the padded tail must never contribute attention
mass. The causal offset is then end-aligned against the VALID prefix
(`offset = kv_len - seq_q`), so prefill over a cache buffer and
decode steps against the same buffer agree with `mha_reference` run
on the sliced cache.
"""
from __future__ import annotations

import contextlib
import functools
import math
import threading

import jax
import jax.numpy as jnp
from jax import lax

from .. import telemetry

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# blockwise reference (differentiable, fuses well under XLA)
# ---------------------------------------------------------------------------
def _attn_block(q, k, v, m_prev, l_prev, acc_prev, scale, mask=None):
    """One online-softmax accumulation step.

    q: (..., Sq, D); k/v: (..., Sk, D); m/l: (..., Sq); acc (..., Sq, D).
    """
    s = jnp.einsum("...qd,...kd->...qk", q, k).astype(jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + p.sum(axis=-1)
    acc_new = acc_prev * alpha[..., None] + jnp.einsum(
        "...qk,...kd->...qd", p.astype(v.dtype), v).astype(jnp.float32)
    return m_new, l_new, acc_new


def mha_reference(q, k, v, causal=False, scale=None):
    """Plain attention (for tests and tiny sequences)."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("...qd,...kd->...qk", q, k).astype(jnp.float32) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        row = lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        col = lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where(col <= row + (sk - sq), s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", p.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# Pallas TPU forward kernel
# ---------------------------------------------------------------------------
def _causal_valid(row, col, offset):
    """End-aligned causal convention (matches mha_reference):
    query row r may attend key col c iff c <= r + offset, offset =
    seq_k - seq_q (so the LAST query sees the whole key)."""
    return col <= row + offset


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale,
                      causal, block_k, seq_k_padded, kv_len, offset):
    """One (batch*head, q-block) grid step; stream KV through VMEM."""
    import jax.experimental.pallas as pl
    q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
    bq, d = q.shape
    nk = seq_k_padded // block_k
    q_block = pl.program_id(1)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :]   # (bk, d)
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k.astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bq, bk)
        row = lax.broadcasted_iota(jnp.int32, (bq, block_k), 0) \
            + q_block * bq
        col = lax.broadcasted_iota(jnp.int32, (bq, block_k), 1) \
            + j * block_k
        valid = col < kv_len                           # padding mask
        if causal:
            valid = valid & _causal_valid(row, col, offset)
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + p.sum(axis=-1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    m, l, acc = lax.fori_loop(0, nk, body, (m0, l0, acc0))
    l_safe = jnp.where(l > 0, l, 1.0)                  # padded q rows
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(l_safe)


def _pad_seq(x, block):
    s = x.shape[2]
    pad = (-s) % block
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))


def flash_attention_pallas(q, k, v, causal=False, scale=None,
                           block_q=128, block_k=128, interpret=False,
                           kv_len=None):
    """Pallas forward (see pallas_guide.md patterns); any seq length
    (inputs are block-padded, padding masked). ``kv_len`` marks the
    valid key prefix of a longer (cache) buffer — keys at or beyond
    it are masked and the causal diagonal is end-aligned against the
    valid prefix, not the buffer end. Returns (out, lse)."""
    import jax.experimental.pallas as pl

    b, h, sq, d = q.shape
    sk = k.shape[2]
    kv_len = sk if kv_len is None else int(kv_len)
    if not 0 < kv_len <= sk:
        raise ValueError(f"kv_len={kv_len} out of range for key "
                         f"buffer of length {sk}")
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    block_q = min(block_q, max(sq, 1))
    block_k = min(block_k, max(sk, 1))
    qp, kp, vp = _pad_seq(q, block_q), _pad_seq(k, block_k), \
        _pad_seq(v, block_k)
    sqp, skp = qp.shape[2], kp.shape[2]
    qr = qp.reshape(b * h, sqp, d)
    kr = kp.reshape(b * h, skp, d)
    vr = vp.reshape(b * h, skp, d)

    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal, block_k=block_k,
        seq_k_padded=skp, kv_len=kv_len, offset=kv_len - sq)
    out, lse = pl.pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=(b * h, sqp // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, skp, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, skp, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            # lse rides a unit middle axis: a (1, block_q) block of a
            # 2-D (b*h, sqp) array breaks the TPU (8, 128) tiling rule
            pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sqp, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, sqp), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return (out.reshape(b, h, sqp, d)[:, :, :sq],
            lse.reshape(b, h, sqp)[:, :, :sq])


# ---------------------------------------------------------------------------
# blockwise jnp forward (non-TPU path) — O(S·block) memory
# ---------------------------------------------------------------------------
def _blockwise_fwd(q, k, v, causal, scale, block=512, kv_len=None):
    sq, sk = q.shape[-2], k.shape[-2]
    kv_len = sk if kv_len is None else int(kv_len)
    offset = kv_len - sq
    kp, vp = _pad_seq(k, block), _pad_seq(v, block)
    nb = kp.shape[-2] // block

    def step(carry, j):
        m, l, acc = carry
        kj = lax.dynamic_slice_in_dim(kp, j * block, block, axis=-2)
        vj = lax.dynamic_slice_in_dim(vp, j * block, block, axis=-2)
        s = jnp.einsum("...qd,...kd->...qk", q, kj) \
            .astype(jnp.float32) * scale
        row = lax.broadcasted_iota(jnp.int32, (sq, block), 0)
        col = lax.broadcasted_iota(jnp.int32, (sq, block), 1) + j * block
        valid = col < kv_len
        if causal:
            valid = valid & _causal_valid(row, col, offset)
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "...qk,...kd->...qd", p.astype(vj.dtype), vj
        ).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full(q.shape[:-1], NEG_INF, jnp.float32)
    l0 = jnp.zeros(q.shape[:-1], jnp.float32)
    acc0 = jnp.zeros(q.shape, jnp.float32)
    (m, l, acc), _ = lax.scan(step, (m0, l0, acc0), jnp.arange(nb))
    l_safe = jnp.where(l > 0, l, 1.0)
    return (acc / l_safe[..., None]).astype(q.dtype), m + jnp.log(l_safe)


# ---------------------------------------------------------------------------
# public flash_attention with blockwise (O(S·block)) backward
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal=False, scale=None, kv_len=None):
    """``kv_len`` (static int) marks the valid key prefix of a longer
    cache buffer: keys beyond it are masked out of the softmax and the
    causal diagonal end-aligns to the valid prefix (the last query row
    sees keys [0, kv_len))."""
    return _flash_fwd(q, k, v, causal, scale, kv_len)[0]


#: threads currently tracing under jnp_only() — the SPMD-serving
#: escape hatch (see below)
_JNP_ONLY = threading.local()


@contextlib.contextmanager
def jnp_only():
    """Force the jnp paths while tracing under this context.

    Tensor-parallel serving compiles the generation closures SPMD over
    the device mesh (params and KV sharded by heads); a ``pallas_call``
    inside such a program would need an explicit ``shard_map`` wrapping
    it per shard, which the decode kernels do not have — so a
    mesh-sharded engine traces its closures under this context and the
    kernels stay on the (numerically identical) jnp paths, partitioned
    by GSPMD like any other op. Under it ``paged_decode_attention``
    also keeps the view split into heads for a one-query tick: a tp
    pool shards a row's width by heads, and the rows reader would
    contract over it. Scoped per thread (trace-time only): an unsharded
    engine tracing concurrently still takes Pallas."""
    prev = getattr(_JNP_ONLY, "on", False)
    _JNP_ONLY.on = True
    try:
        yield
    finally:
        _JNP_ONLY.on = prev


def _use_pallas():
    if getattr(_JNP_ONLY, "on", False):
        return False
    return jax.default_backend() == "tpu"


def _flash_fwd(q, k, v, causal, scale, kv_len=None):
    # validate here (not only in the Pallas path) so the jnp fallback
    # rejects a bad kv_len too instead of attending zero-padded keys
    if kv_len is not None and not 0 < int(kv_len) <= k.shape[2]:
        raise ValueError(f"kv_len={kv_len} out of range for key "
                         f"buffer of length {k.shape[2]}")
    scale_v = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if _use_pallas():
        out, lse = flash_attention_pallas(q, k, v, causal=causal,
                                          scale=scale_v, kv_len=kv_len)
    else:
        out, lse = _blockwise_fwd(q, k, v, causal, scale_v,
                                  kv_len=kv_len)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, kv_len, res, do):
    """Blockwise flash backward: rematerializes attention one KV (then
    one Q) block at a time — no S×S residual ever materializes.
    Masked-out cache tail (cols >= kv_len) gets p=0, so its dk/dv are
    exactly zero and dq ignores it."""
    q, k, v, o, lse = res
    scale_v = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    block = 512
    sq, sk = q.shape[-2], k.shape[-2]
    kv_len = sk if kv_len is None else int(kv_len)
    offset = kv_len - sq
    do32 = do.astype(jnp.float32)
    delta = (do32 * o.astype(jnp.float32)).sum(-1)          # (..., sq)

    kp, vp = _pad_seq(k, block), _pad_seq(v, block)
    nb_k = kp.shape[-2] // block

    def dq_step(dq_acc, j):
        kj = lax.dynamic_slice_in_dim(kp, j * block, block, axis=-2)
        vj = lax.dynamic_slice_in_dim(vp, j * block, block, axis=-2)
        s = jnp.einsum("...qd,...kd->...qk", q, kj) \
            .astype(jnp.float32) * scale_v
        row = lax.broadcasted_iota(jnp.int32, (sq, block), 0)
        col = lax.broadcasted_iota(jnp.int32, (sq, block), 1) + j * block
        valid = col < kv_len
        if causal:
            valid = valid & _causal_valid(row, col, offset)
        p = jnp.where(valid, jnp.exp(s - lse[..., None]), 0.0)
        dp = jnp.einsum("...qd,...kd->...qk", do32,
                        vj.astype(jnp.float32))
        ds = p * (dp - delta[..., None]) * scale_v
        dq_acc = dq_acc + jnp.einsum("...qk,...kd->...qd", ds,
                                     kj.astype(jnp.float32))
        return dq_acc, None

    dq, _ = lax.scan(dq_step, jnp.zeros(q.shape, jnp.float32),
                     jnp.arange(nb_k))

    qp = _pad_seq(q, block)
    dop = _pad_seq(do32, block)
    pad_q = qp.shape[-2] - sq
    lsep = jnp.pad(lse, [(0, 0)] * (lse.ndim - 1) + [(0, pad_q)])
    deltap = jnp.pad(delta, [(0, 0)] * (delta.ndim - 1) + [(0, pad_q)])
    nb_q = qp.shape[-2] // block

    def dkv_step(carry, i):
        dk_acc, dv_acc = carry
        qi = lax.dynamic_slice_in_dim(qp, i * block, block, axis=-2)
        doi = lax.dynamic_slice_in_dim(dop, i * block, block, axis=-2)
        lsei = lax.dynamic_slice_in_dim(lsep, i * block, block, axis=-1)
        deltai = lax.dynamic_slice_in_dim(deltap, i * block, block,
                                          axis=-1)
        s = jnp.einsum("...qd,...kd->...qk", qi, k) \
            .astype(jnp.float32) * scale_v
        row = lax.broadcasted_iota(jnp.int32, (block, sk), 0) + i * block
        col = lax.broadcasted_iota(jnp.int32, (block, sk), 1)
        valid = (row < sq) & (col < kv_len)
        if causal:
            valid = valid & _causal_valid(row, col, offset)
        p = jnp.where(valid, jnp.exp(s - lsei[..., None]), 0.0)
        dv_acc = dv_acc + jnp.einsum("...qk,...qd->...kd", p, doi)
        dp = jnp.einsum("...qd,...kd->...qk", doi, v.astype(jnp.float32))
        ds = p * (dp - deltai[..., None]) * scale_v
        dk_acc = dk_acc + jnp.einsum("...qk,...qd->...kd", ds,
                                     qi.astype(jnp.float32))
        return (dk_acc, dv_acc), None

    (dk, dv), _ = lax.scan(
        dkv_step,
        (jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32)),
        jnp.arange(nb_q))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# decode attention (single-query KV-cache attention, per-row lengths)
# ---------------------------------------------------------------------------
def _masked_softmax(s, valid):
    """Softmax of float32 scores over the positions ``valid`` allows,
    with the two guards ``masked_attention`` describes."""
    s = jnp.where(valid, s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.where(valid, jnp.exp(s - m), 0.0)
    l = p.sum(axis=-1, keepdims=True)
    l_safe = jnp.where(l > 0, l, 1.0)
    return p / l_safe


def masked_attention(q, k, v, valid, scale):
    """Single-pass masked-softmax attention: score, mask, softmax with
    the two non-obvious guards the cache paths need — RE-MASK after
    the exp (a fully-masked row's scores are all NEG_INF, so
    exp(s - m) would be exp(0)=1 across the board instead of 0) and an
    l_safe denominator (a fully-masked row — an empty serving slot —
    returns zeros, not NaN). Shared by decode attention and chunked
    prefill, which differ only in the validity predicate, and by a
    model whose predicate is its own (a ring of window positions).

    ``q`` (B, Hq, Sq, D), ``k`` (B, Hk, S, D), ``v`` (B, Hv, S, Dv),
    ``valid`` broadcastable to (B, Hq, Sq, S). With equal head counts
    this is plain multi-head attention. Otherwise the heads are GROUPED:
    ``Hk`` divides ``Hq`` and ``Hv`` divides ``Hq``, query head ``h``
    reads key head ``h // (Hq / Hk)`` and value head ``h // (Hq / Hv)``
    (grouped-query attention has ``Hk == Hv``; a differential layer
    reads a pair of value heads as one head twice as wide, so its
    ``Hv`` is half its ``Hk``), and the scores are float32 products."""
    hq, hk, hv = q.shape[1], k.shape[1], v.shape[1]
    if hq == hk == hv:
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    else:
        if hq % hk or hq % hv:
            raise ValueError(f"{hq} query heads are no multiple of {hk} "
                             f"key heads and {hv} value heads")
        qg = q.reshape(q.shape[0], hk, hq // hk, *q.shape[2:])
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k,
                       preferred_element_type=jnp.float32)
        s = s.reshape(q.shape[0], hq, q.shape[2], k.shape[2]) * scale
    p = _masked_softmax(s, valid).astype(v.dtype)
    if hq == hv:
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)
    pg = p.reshape(p.shape[0], hv, hq // hv, *p.shape[2:])
    o = jnp.einsum("bhgqk,bhkd->bhgqd", pg, v)
    return o.reshape(q.shape[0], hq, q.shape[2], v.shape[3])


def _decode_fwd_jnp(q, k, v, lengths, scale):
    """Masked single-pass attention: every query row of batch b attends
    keys [0, lengths[b]) of its cache row. Small S_max fits one score
    materialization (B, H, Sq, S_max) — the decode working set is tiny
    compared to prefill, and XLA fuses the chain."""
    shape = (*q.shape[:3], k.shape[2])
    col = lax.broadcasted_iota(jnp.int32, shape, 3)
    return masked_attention(q, k, v,
                            col < lengths[:, None, None, None], scale)


def _decode_fwd_kernel(len_ref, q_ref, k_ref, v_ref, *rest, scale,
                       block_k, nkb, quant=False):
    """One (batch, head, kv-block) grid step. ``len_ref`` is the
    scalar-prefetched per-slot length vector (SMEM); blocks at or past
    the slot's valid prefix skip compute entirely (their BlockSpec
    index map also re-requests the already-resident block, so no data
    moves for them). Online-softmax state lives in VMEM scratch, which
    persists across the innermost (kv-block) grid axis; the output
    block is written once, on the last grid step.

    ``quant=True`` (int8 KV cache) adds two whole-array SMEM scale
    inputs right after ``v_ref``, indexed ``[batch * H + head, 0]``:
    the resident int8 block is dequantized IN-REGISTER with its slot's
    per-head scale — the fp32 K/V never exist outside VMEM, so the
    cache's HBM footprint (and the DMA per step) is the int8 bytes."""
    import jax.experimental.pallas as pl
    if quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    kb = pl.program_id(2)
    length = len_ref[b]
    nblocks = (length + block_k - 1) // block_k   # this slot's valid blocks
    if quant:
        # program ids are read here: the interpreter cannot bind them
        # inside a pl.when body
        sc_row = b * pl.num_programs(1) + pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(kb < nblocks)
    def _accumulate():
        q = q_ref[0, 0].astype(jnp.float32) * scale    # (sq, d)
        sq = q.shape[0]
        k = k_ref[0, 0].astype(jnp.float32)            # (block_k, d)
        v = v_ref[0, 0].astype(jnp.float32)
        if quant:
            k = k * ks_ref[sc_row, 0]
            v = v * vs_ref[sc_row, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # (sq, bk)
        col = lax.broadcasted_iota(jnp.int32, (sq, block_k), 1) \
            + kb * block_k
        # masks both the final partial block of the valid prefix and
        # any cache tail past sk (the last grid block may overhang)
        s = jnp.where(col < length, s, NEG_INF)
        # v's overhang rows may hold garbage (even NaN): p is 0 there,
        # but 0 * NaN is NaN, so zero them before the accumulate
        vrow = lax.broadcasted_iota(jnp.int32, (block_k, 1), 0) \
            + kb * block_k
        v = jnp.where(vrow < length, v, 0.0)
        # m/l scratch is (sq, 128) with all lanes equal (TPU-friendly
        # layout); [:, :1] slices recover the per-row scalar
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        m_ref[...] = m_new
        l_ref[...] = alpha * l_prev + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kb == nkb - 1)
    def _finalize():
        l = l_ref[...][:, :1]
        l_safe = jnp.where(l > 0, l, 1.0)  # length==0: an empty slot
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def decode_attention_pallas(q, k, v, lengths, scale=None, block_k=128,
                            interpret=False, k_scale=None, v_scale=None):
    """Pallas decode kernel: grid over (batch, head, kv-block) with the
    per-slot lengths scalar-prefetched into the KV BlockSpec index
    maps. Blocks past a slot's valid prefix are clamped to its last
    valid block — the TPU pipeline elides the copy when the block
    index repeats — so a 40-token slot in a 2048-row cache MOVES
    ceil(40/block_k) KV blocks, not S_max rows; compute for those
    steps is skipped in the kernel. No host-side padding: a final
    partial block is masked in-kernel. ``k_scale``/``v_scale``
    ``(B, H)`` mark an int8 KV cache: the streamed int8 blocks are
    dequantized in VMEM with each slot's per-head scale."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk = k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    block_k = min(block_k, max(sk, 1))
    nkb = (sk + block_k - 1) // block_k
    quant = k_scale is not None

    def _kv_index(i, j, kb, lens):
        last = jnp.maximum((lens[i] + block_k - 1) // block_k - 1, 0)
        return (i, j, jnp.minimum(kb, last), 0)

    in_specs = [
        pl.BlockSpec((1, 1, sq, d),
                     lambda i, j, kb, lens: (i, j, 0, 0)),
        pl.BlockSpec((1, 1, block_k, d), _kv_index),
        pl.BlockSpec((1, 1, block_k, d), _kv_index),
    ]
    operands = [q, k, v]
    if quant:
        # per-(slot, head) scalars: whole in SMEM, read by program id
        # (a (1, 1) VMEM block breaks the TPU (8, 128) tiling rule)
        in_specs += [pl.BlockSpec(memory_space=pltpu.SMEM)] * 2
        operands += [k_scale.astype(jnp.float32).reshape(b * h, 1),
                     v_scale.astype(jnp.float32).reshape(b * h, 1)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, nkb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, sq, d),
                               lambda i, j, kb, lens: (i, j, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((sq, 128), jnp.float32),   # running max
            pltpu.VMEM((sq, 128), jnp.float32),   # running denominator
            pltpu.VMEM((sq, d), jnp.float32),     # running numerator
        ],
    )
    kernel = functools.partial(_decode_fwd_kernel, scale=scale,
                               block_k=block_k, nkb=nkb, quant=quant)
    return pl.pallas_call(
        kernel,
        name="decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), *operands)


# -- the paged pool's layout ---------------------------------------------
# A pool is (n_pages, page_size, H * Dh): a page is ``page_size`` rows,
# a row one position's heads one after another (head h at columns
# [h * Dh, (h + 1) * Dh)). The minor dimension is the model's width, so
# on a TPU the array is row-major in whole (8, 128) tiles and a scatter
# into it, a gather from it and a donated result all run on the layout
# the argument came in: no paged program copies a pool. The write of
# pages, the write of rows and the gather below are the only code that
# knows where a position lies; everything else hands them chunks, rows
# and tables.
def pool_page_size(pool):
    """Positions a page of ``pool`` holds."""
    return pool.shape[1]


def write_pages(pool, page_ids, chunk):
    """Scatter a (1, H, C, Dh) chunk of K or V, C a multiple of the
    page size, into whole pool pages ``page_ids`` (C / page_size,)."""
    h, c, d = chunk.shape[1:]
    ps = pool_page_size(pool)
    return pool.at[page_ids].set(
        chunk[0].transpose(1, 0, 2).reshape(c // ps, ps, h * d)
        .astype(pool.dtype))


def write_rows(pool, page, offset, rows):
    """Scatter single positions: ``rows`` (..., H, Dh) go to row
    ``offset`` of pool page ``page`` (both of shape ``...``: (B,) for a
    decode tick, (B, R) for a speculative verify)."""
    return pool.at[page, offset].set(
        rows.reshape(*page.shape, -1).astype(pool.dtype))


def gather_pages(pool, table, num_heads):
    """Materialize each slot's logical KV view from a paged pool, SPLIT
    INTO HEADS: ``pool`` (n_pages, page_size, H * D) + ``table``
    (B, P_max) int32 -> (B, H, P_max * page_size, D). Logical position
    ``t`` of slot ``b`` lives at
    ``pool[table[b, t // ps], t % ps, h * D:(h + 1) * D]``. Free table
    entries point at the reserved scrap page (id 0) — their rows are
    garbage that per-row length masking must exclude.

    On the chip the split is a re-tiling the compiler materializes
    (``H * D``-wide rows to ``D``-wide heads, half of every 128-lane
    tile empty at D = 64), which costs more than the attention after
    it. Who pays it: several queries a slot (a chunk, a speculative
    verify) and programs traced over a tp mesh, whose pools are sharded
    by heads; a one-query tick reads ``gather_rows`` instead
    (``paged_decode_attention``)."""
    g = gather_rows(pool, table)
    b, s, hd = g.shape
    return g.reshape(b, s, num_heads, hd // num_heads).transpose(0, 2, 1, 3)


def gather_rows(pool, table):
    """Each slot's logical view as ROWS: (B, P_max * page_size, H * D),
    a position's heads side by side as the pool holds them (what
    ``rows_decode_attention`` reads)."""
    g = pool[table]                       # (B, P_max, ps, H * D)
    b, pm, ps, hd = g.shape
    return g.reshape(b, pm * ps, hd)


def expand_page_scales(pool_scale, table, page_size):
    """Broadcast per-head-per-PAGE scales onto token positions:
    ``pool_scale`` (n_pages, H) + ``table`` (B, P_max) ->
    (B, H, P_max * page_size) — position ``t`` of slot ``b`` carries
    the scale of its page ``table[b, t // page_size]``. The dequant
    companion of ``gather_pages`` for an int8 pool."""
    g = pool_scale[table]                       # (B, P_max, H)
    return jnp.repeat(g.transpose(0, 2, 1), page_size, axis=2)


def gather_kv(k_pool, v_pool, table, num_heads, k_scale=None,
              v_scale=None):
    """Both pools' gathered views (``gather_pages``). ``k_scale``/
    ``v_scale`` (n_pages, H) mark an INT8 pool: its views come back
    fp32, every page dequantized with the scale it was written under."""
    k = gather_pages(k_pool, table, num_heads)
    v = gather_pages(v_pool, table, num_heads)
    if k_scale is not None:
        ps = pool_page_size(k_pool)
        k = k.astype(jnp.float32) \
            * expand_page_scales(k_scale, table, ps)[..., None]
        v = v.astype(jnp.float32) \
            * expand_page_scales(v_scale, table, ps)[..., None]
    return k, v


def rows_decode_attention(q, k_rows, v_rows, valid, kv_heads, scale=None,
                          k_scale=None, v_scale=None):
    """One query a row against K/V ROWS AS THEY LIE in a pool or a ring:
    ``q`` (B, Hq, D); ``k_rows`` (B, S, Hk * D) and ``v_rows`` (B, S, Hv *
    Dv), a position's heads side by side; ``valid`` (B, S); ``kv_heads``
    ``(Hk, Hv)``, the rows' head counts (heads grouped as in
    ``masked_attention``). Returns (B, Hq, Dv) float32. The one reader
    of every family's one-query tick: ``paged_decode_attention`` (GPT)
    and ``Phi4FlashModel`` call it.

    The rows are never split into heads: the query is laid out
    block-diagonally, ``(B, Hq, Hk * D)`` with head ``h`` in the columns
    of its key head and zeros elsewhere, so the scores are ONE product
    over whole rows, and the values likewise (a product with whole rows,
    of which each head keeps its own columns). That spends ``Hk`` (``Hv``)
    times the multiply-adds of the split form, which a decode tick has to
    spare (one query a row: the rows' bytes bound it), and saves the
    re-tiling of every view from ``H * D``-wide rows to ``D``-wide heads,
    which cost more than the attention itself (PERF.md section 5). Where
    ``valid`` is false K may hold anything, NaN too: its score is masked
    before the softmax, and the query's zeros only ever meet other
    heads' columns of positions that ARE valid. V has to be finite
    everywhere (0 * NaN is NaN).

    ``k_scale`` (B, Hk, S) and ``v_scale`` (B, Hv, S) mark INT8 rows, a
    position's scale a head (``expand_page_scales``). No dequantized view
    is built: the integers are contracted as they lie, K's scale
    multiplies the scores and V's the probabilities, which is the
    arithmetic of dequantizing each head's columns first."""
    hq, d = q.shape[1], q.shape[2]
    hk, hv = kv_heads
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if k_scale is not None:
        # every int8 value is a bf16 value: the product reads the pool's
        # bytes and converts on the way
        k_rows, v_rows = k_rows.astype(q.dtype), v_rows.astype(q.dtype)
    own_k = jnp.arange(hq)[:, None] // (hq // hk) == jnp.arange(hk)[None]
    own_v = jnp.arange(hq)[:, None] // (hq // hv) == jnp.arange(hv)[None]
    q_rows = jnp.where(own_k[None, :, :, None], q[:, :, None, :], 0) \
        .reshape(q.shape[0], hq, hk * d)
    s = jnp.einsum("bhc,bsc->bhs", q_rows, k_rows,
                   preferred_element_type=jnp.float32) * scale
    if k_scale is not None:
        s = s * jnp.repeat(k_scale, hq // hk, axis=1)
    p = _masked_softmax(s, valid[:, None, :])
    if v_scale is not None:
        p = p * jnp.repeat(v_scale, hq // hv, axis=1)
    o = jnp.einsum("bhs,bsc->bhc", p.astype(v_rows.dtype), v_rows,
                   preferred_element_type=jnp.float32)
    o = o.reshape(q.shape[0], hq, hv, -1)
    return jnp.sum(jnp.where(own_v[None, :, :, None], o, 0.0), axis=2)


#: the ``name=`` of the held-pages reader's ``pallas_call``: the name of its
#: HLO instruction, which the device trace shows
PAGED_ROWS_KERNEL = "paged_rows_attention"
#: bytes of K (and as many of V) a step of the held-pages reader copies:
#: as many whole pages as fit, at least one
_HELD_STEP_BYTES = 1 << 20


def _paged_rows_kernel(table_ref, nv_ref, q_ref, k_hbm, v_hbm, o_ref,
                       k_buf, v_buf, k_sem, v_sem, cur_ref, m_ref, l_ref,
                       acc_ref, *, scale, pages, page_size, p_max, hv):
    """One slot a grid step. Its held pages are copied ``pages`` at a
    time into one of two buffers while the other is contracted; the last
    block of a slot starts the copy of the next slot's first, so the
    copies run on across steps (the grid axis is sequential). A page at
    or past the slot's last held page is never copied: its rows in the
    buffer are whatever an earlier copy left there, or never written,
    and are masked like the rows past ``n_valid`` in the last held page."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    b, n_slots = pl.program_id(0), pl.num_programs(0)
    rows = pages * page_size

    def n_valid(slot):
        return jnp.minimum(nv_ref[slot], p_max * page_size)

    def each_page(slot, blk, buf, act):
        held = (n_valid(slot) + page_size - 1) // page_size
        for j in range(pages):
            at = blk * pages + j
            page = table_ref[slot * p_max + jnp.minimum(at, p_max - 1)]
            dst = pl.ds(j * page_size, page_size)

            @pl.when(at < held)
            def _(page=page, dst=dst):
                act(pltpu.make_async_copy(k_hbm.at[page], k_buf.at[buf, dst],
                                          k_sem.at[buf]))
                act(pltpu.make_async_copy(v_hbm.at[page], v_buf.at[buf, dst],
                                          v_sem.at[buf]))

    def start(slot, blk, buf):
        each_page(slot, blk, buf, lambda c: c.start())

    @pl.when(b == 0)
    def _first():
        cur_ref[0] = 0
        start(0, 0, 0)

    n = n_valid(b)
    n_blocks = (n + rows - 1) // rows
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    q = q_ref[0]

    def block(i, carry):
        buf = cur_ref[0]
        nxt = 1 - buf
        pl.when(i + 1 < n_blocks)(lambda: start(b, i + 1, nxt))
        pl.when((i + 1 == n_blocks) & (b + 1 < n_slots))(
            lambda: start(b + 1, 0, nxt))
        each_page(b, i, buf, lambda c: c.wait())
        s = jax.lax.dot_general(q, k_buf[buf], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        col = lax.broadcasted_iota(jnp.int32, s.shape, 1) + i * rows
        s = jnp.where(col < n, s, NEG_INF)
        # V past n_valid may be stale or NaN: p is 0 there, 0 * NaN is not
        row = lax.broadcasted_iota(jnp.int32, (rows, 1), 0) + i * rows
        v = v_buf[buf]
        v = jnp.where(row < n, v, jnp.zeros_like(v))
        # m/l scratch is (Hq, 128) with all lanes equal
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        m_ref[...] = m_new
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        cur_ref[0] = nxt
        return carry

    lax.fori_loop(0, n_blocks, block, 0)
    # a slot that holds nothing hands the free buffer to the next slot
    pl.when((n_blocks == 0) & (b + 1 < n_slots))(
        lambda: start(b + 1, 0, cur_ref[0]))

    l = l_ref[...][:, :1]
    inv = 1.0 / jnp.where(l > 0, l, 1.0)           # n_valid 0: zeros
    hq, dv = acc_ref.shape[0], o_ref.shape[-1]
    own = lax.broadcasted_iota(jnp.int32, (hq, 1), 0) // (hq // hv)
    o = jnp.zeros((hq, dv), jnp.float32)
    for j in range(hv):
        o = o + jnp.where(own == j, acc_ref[:, j * dv:(j + 1) * dv], 0.0)
    o_ref[0] = o * inv


def paged_rows_attention(q, k_pool, v_pool, table, n_valid, kv_heads,
                         scale=None, interpret=False):
    """``rows_decode_attention`` over each slot's HELD pages of a paged
    pool, read in place: ``q`` (B, Hq, D); ``k_pool`` (n_pages,
    page_size, Hk * D) and ``v_pool`` (n_pages, page_size, Hv * Dv) as the
    pool lies; ``table`` (B, P_max) int32; ``n_valid`` (B,) int32, each
    slot's valid prefix; ``kv_heads`` ``(Hk, Hv)``. Returns (B, Hq, Dv)
    float32, what ``rows_decode_attention(q, gather_rows(k_pool, table),
    gather_rows(v_pool, table), col < n_valid, kv_heads, scale)`` returns,
    to rounding: the softmax is an online one over blocks of pages, and
    each block's probabilities are rounded to V's dtype before P x V.

    A Pallas kernel (``PAGED_ROWS_KERNEL``) with ``table`` and ``n_valid``
    scalar-prefetched: a slot's pages up to its last held one are copied
    from the pool, several a step (``_HELD_STEP_BYTES``), and no view is
    gathered; the pages past them are never read. The query is laid out
    block-diagonally over whole rows as in ``rows_decode_attention``, in
    the pool's dtype.
    Rows past ``n_valid`` in the last held page may hold a former
    tenant's values, NaN too: their scores are masked and their V rows
    zeroed. A slot with ``n_valid`` 0 returns zeros."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    b, hq, d = q.shape
    hk, hv = kv_heads
    _, ps, wk = k_pool.shape
    wv = v_pool.shape[2]
    p_max = table.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    pages = min(max(1, _HELD_STEP_BYTES // (ps * wk * k_pool.dtype.itemsize)),
                p_max)
    telemetry.counter("ops.attention.paged_decode.held")
    own_k = jnp.arange(hq)[:, None] // (hq // hk) == jnp.arange(hk)[None]
    q_rows = jnp.where(own_k[None, :, :, None], q[:, :, None, :], 0) \
        .astype(k_pool.dtype).reshape(b, hq, wk)
    rows = pages * ps
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, hq, wk), lambda i, *_: (i, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, hq, wv // hv), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, rows, wk), k_pool.dtype),
            pltpu.VMEM((2, rows, wv), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),          # the buffer to contract
            pltpu.VMEM((hq, 128), jnp.float32),   # running max
            pltpu.VMEM((hq, 128), jnp.float32),   # running denominator
            pltpu.VMEM((hq, wv), jnp.float32),    # running numerator
        ],
    )
    kernel = functools.partial(_paged_rows_kernel, scale=scale, pages=pages,
                               page_size=ps, p_max=p_max, hv=hv)
    return pl.pallas_call(
        kernel,
        name=PAGED_ROWS_KERNEL,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, wv // hv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(jnp.asarray(table, jnp.int32).reshape(-1),
      jnp.asarray(n_valid, jnp.int32), q_rows, k_pool, v_pool)


def paged_decode_attention(q, k_pool, v_pool, table, lengths,
                           scale=None, k_scale=None, v_scale=None):
    """Decode attention against a PAGED KV cache.

    ``q`` is (B, H, Sq, D); ``k_pool``/``v_pool`` are the global page
    pools (n_pages, page_size, H * D); ``table`` (B, P_max) int32 maps
    each slot's logical page index to a physical pool page; ``lengths``
    (B,) int32 marks each slot's valid token prefix: every query row
    attends keys ``[0, length)`` (``Sq > 1`` is a speculative verify).
    Past a length K may hold anything (its scores are masked); V has to
    be finite there, as for the chunk programs: 0 * NaN is NaN. A slot
    of length 0 returns zeros. The result has ``q``'s shape and dtype.

    The gather moves all ``P_max`` pages of every slot whatever its
    length; a page is one contiguous block of ``page_size`` rows of all
    heads, read from the pool as the write left it: row-major, with no
    copy of a pool on either side (tests/test_chip_compile.py counts
    them). What attends the gathered view is chosen by what the trace
    can see, and counted where it is chosen (trace-time counters
    ``ops.attention.paged_decode.rows`` / ``.gathered``,
    docs/OBSERVABILITY.md):

    - **``Sq == 1``** (the decode tick, a multi-tick scan's tick,
      ``peek_paged``): ``rows_decode_attention`` over ``gather_rows``,
      the rows as they lie. No view is split into heads, so the program
      holds no ``[B, S, H, D]`` re-tiling, which was the largest device
      operation of a tick (PERF.md §6, PR 32). Scores are float32
      products; the dense cache's ``decode_attention`` rounds a bf16
      product to bf16 first, so the two agree to rounding, not bit for
      bit (float32: a few ulp).
    - **``Sq > 1``**, and **every program traced under ``jnp_only()``**
      (an engine over a tp mesh): ``gather_kv`` + the masked softmax of
      ``decode_attention``'s jnp path over the view split into heads.
      With ``Sq`` queries a row the block-diagonal form spends
      ``H * Sq`` rows of MXU work, and a tp pool shards a row's width by
      heads, so a contraction over whole rows would add an all-reduce of
      the scores a layer that the split form does not need.

    PR 25's Pallas kernel, which moved only held pages, lost to the
    gather because the pool then was ``(n_pages, H, page_size, D)``,
    which the TPU keeps pages minor-most, and a kernel's operand has to
    be row-major: a copy of each whole pool a layer cost more than the
    kernel saved (PERF.md §6). This pool is row-major already, so that
    reason is gone: ``paged_rows_attention`` is such a kernel, over held
    pages and contracting rows as they lie. Its one caller is
    ``Phi4FlashModel``'s decode tick on a TPU; this function, and so
    ``GPTModel``'s tick, keeps the gather (ROADMAP S4).

    ``k_scale``/``v_scale`` (n_pages, H) fp32 mark an INT8 pool (half
    the HBM per cached token vs bf16, a quarter vs fp32): the rows
    reader scales scores and probabilities by each page's per-head
    scale and builds no dequantized view; the gathered reader
    dequantizes the view to float32 after the gather."""
    scale_v = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    table = jnp.asarray(table, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    h = q.shape[1]
    # the counters are bumped as a program traces, never in steady state
    if q.shape[2] != 1 or getattr(_JNP_ONLY, "on", False):
        telemetry.counter("ops.attention.paged_decode.gathered")
        k, v = gather_kv(k_pool, v_pool, table, h, k_scale, v_scale)
        return _decode_fwd_jnp(q, k, v, lengths, scale_v)
    telemetry.counter("ops.attention.paged_decode.rows")
    k_rows, v_rows = gather_rows(k_pool, table), gather_rows(v_pool, table)
    col = lax.broadcasted_iota(jnp.int32, k_rows.shape[:2], 1)
    if k_scale is not None:
        ps = pool_page_size(k_pool)
        k_scale = expand_page_scales(k_scale, table, ps)
        v_scale = expand_page_scales(v_scale, table, ps)
    o = rows_decode_attention(q[:, :, 0], k_rows, v_rows,
                              col < lengths[:, None], (h, h), scale_v,
                              k_scale, v_scale)
    return o[:, :, None, :].astype(q.dtype)


def chunked_prefill_attention(q, k, v, start, scale=None):
    """Attention for one PREFILL CHUNK against a cache buffer.

    ``q`` (B, H, C, D) holds the chunk's queries at global positions
    ``start + i`` (``start`` is a (B,) int32 or scalar — traced, so
    every chunk of every prompt runs ONE compiled program); ``k``/``v``
    (B, H, S, D) are each row's gathered cache holding valid keys
    ``[0, start + C)`` (earlier chunks plus this one, already written).
    Row ``i`` attends keys ``[0, start + i]`` — the causal mask in
    global coordinates, which also masks every unwritten/garbage cache
    position since nothing beyond ``start + i`` is ever valid for that
    query. Single-pass masked softmax (the decode-attention
    formulation): the chunk working set is (C, S), tiny next to a
    monolithic prefill's (S, S)."""
    scale_v = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    start = jnp.asarray(start, jnp.int32)
    if start.ndim == 0:
        start = start[None]
    shape = (*q.shape[:3], k.shape[2])
    row = lax.broadcasted_iota(jnp.int32, shape, 2)
    col = lax.broadcasted_iota(jnp.int32, shape, 3)
    valid = col <= start[:, None, None, None] + row
    return masked_attention(q, k, v, valid, scale_v)


def decode_attention(q, k, v, lengths, scale=None, k_scale=None,
                     v_scale=None):
    """Autoregressive decode attention against a preallocated KV cache.

    ``q`` is (B, H, Sq, D) — Sq is 1 on the decode hot path; ``k``/``v``
    are the cache buffers (B, H, S_max, D) filled left-to-right;
    ``lengths`` (B,) int32 marks each slot's valid prefix INCLUDING the
    just-inserted token. Every query attends keys [0, lengths[b]) — no
    intra-query causal structure (the single new token sees the whole
    valid cache), matching ``mha_reference(q, k[:, :, :len],
    v[:, :, :len])`` per row. A row with lengths==0 (an empty serving
    slot riding along in the fixed-shape batch) returns zeros.

    ``k_scale``/``v_scale`` (B, H) fp32 mark an INT8 cache: the
    stored int8 K/V dequantize with each slot's per-head scale — in
    VMEM on the Pallas path (int8 is what streams from HBM), before
    the masked softmax on the jnp path.
    """
    scale_v = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    lengths = jnp.asarray(lengths, jnp.int32)
    if _use_pallas():
        return decode_attention_pallas(q, k, v, lengths, scale=scale_v,
                                       k_scale=k_scale, v_scale=v_scale)
    if k_scale is not None:
        k = k.astype(jnp.float32) * k_scale[:, :, None, None]
        v = v.astype(jnp.float32) * v_scale[:, :, None, None]
    return _decode_fwd_jnp(q, k, v, lengths, scale_v)


# ---------------------------------------------------------------------------
# ring attention (sequence parallel over 'sp')
# ---------------------------------------------------------------------------
def ring_attention_local(q, k, v, axis_name="sp", causal=False, scale=None):
    """Per-shard body to run under shard_map: q/k/v are the LOCAL
    sequence shards (b, h, s_local, d)."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    s_local = q.shape[2]
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, t):
        m, l, acc, kv = carry
        kc, vc = kv
        src = (my - t) % n                      # whose KV shard this is
        # global-position causal mask for this (q-shard, kv-shard) pair
        if causal:
            row = lax.broadcasted_iota(jnp.int32, (s_local, s_local), 0) \
                + my * s_local
            col = lax.broadcasted_iota(jnp.int32, (s_local, s_local), 1) \
                + src * s_local
            mask = col <= row
        else:
            mask = None
        m, l, acc = _attn_block(q, kc, vc, m, l, acc, scale, mask)
        kv = jax.tree.map(lambda x: lax.ppermute(x, axis_name, perm),
                          (kc, vc))
        return (m, l, acc, kv), None

    # init carries FROM q so their device-variance matches the loop
    # body's outputs (shard_map tracks varying-over-axis types)
    m0 = jnp.full_like(q[..., 0], NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros_like(q[..., 0], dtype=jnp.float32)
    acc0 = jnp.zeros_like(q, dtype=jnp.float32)
    (m, l, acc, _), _ = lax.scan(step, (m0, l0, acc0, (k, v)),
                                 jnp.arange(n))
    return (acc / l[..., None]).astype(q.dtype)


def ring_attention(q, k, v, mesh=None, axis_name="sp", causal=False,
                   scale=None):
    """Sequence-parallel attention: shards the sequence axis (2) of
    q/k/v over `axis_name` and runs the ring. Returns the same global
    array layout as the input."""
    from jax.sharding import PartitionSpec as P
    from .._shard_compat import shard_map
    from .. import parallel

    mesh = mesh or parallel.get_mesh()
    if mesh is None or axis_name not in mesh.shape:
        return flash_attention(q, k, v, causal, scale)
    if q.shape[2] % mesh.shape[axis_name] != 0:
        # sequence not divisible by the sp axis (e.g. a shape-inference
        # probe with a tiny sequence): single-device attention is exact
        return flash_attention(q, k, v, causal, scale)
    if not isinstance(q, jax.core.Tracer):
        # Eager call (e.g. the deferred-init shape probe): committing
        # the output to the mesh would poison later eager ops that mix
        # it with single-device weights. The ring engages inside jitted
        # programs (hybridize / TrainStep) — the production path.
        return flash_attention(q, k, v, causal, scale)
    spec = P(None, None, axis_name, None)
    fn = shard_map(
        functools.partial(ring_attention_local, axis_name=axis_name,
                          causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)
