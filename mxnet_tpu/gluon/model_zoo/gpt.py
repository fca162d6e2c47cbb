"""GPT — causal decoder model family (the autoregressive serving
workload; the model_zoo so far was encoder-only BERT).

TPU-first notes: training/full-forward runs causal Pallas flash
attention like every other block here, but GENERATION is a different
regime — one token per step against a growing KV prefix — so the model
exposes an explicit-cache API next to the ordinary ``forward``:

- ``init_cache(batch_size)`` — a preallocated, fixed-shape pytree
  ``{"k": (per-layer (B, H, S_max, Dh)), "v": (...), "len": (B,)}``.
  Fixed shape is the point: every decode step of every request runs
  the SAME compiled program (zero steady-state compiles), and per-layer
  arrays (rather than one stacked (L, ...) buffer) let XLA alias each
  donated input to its updated output — decode is in-place
  dynamic-update-slice, not an O(cache) copy per token.
- ``prefill(tokens, valid_length, cache, slots=...)`` — run the prompt
  through causal flash attention at a bucketed sequence length, write
  the K/V rows into the cache at the given slot indices, set ``len``,
  return last-valid-token logits. Causality makes the padded prompt
  tail harmless: positions < valid_length never attend it, and decode
  masks the cache by ``len``.
- ``decode_step(tokens, cache)`` — one token per slot: insert the new
  K/V at position ``len``, attend over ``[0, len]`` via
  ``ops.attention.decode_attention`` (Pallas on TPU), bump ``len``.
  The cache argument is DONATED to the jitted step — steady-state
  decode never allocates a second cache.

Beside the dense cache there is a PAGED cache API (the serving
engine's ``paged=True`` mode — docs/SERVING.md "Paged KV cache"):
``init_paged_cache`` allocates a global pool of fixed-size KV pages
per layer plus a static-shape ``(B, P_max)`` int32 page table, and the
paged closures grow the same contract — ``prefill_paged`` (whole short
prompt bitwise-equal to dense prefill, or fixed-width chunks appended
at a traced global offset), ``decode_step_paged`` (per-row paged
write + ``ops.attention.paged_decode_attention``; inactive rows'
writes are REDIRECTED to the reserved scrap page 0, because a freed
slot's stale table row may alias pages owned by another slot),
``peek_logits_paged`` (first token of a fully-cached prompt, zero
prefill, no donation), and the ``bind_slot_paged``/``copy_page_paged``
table/COW helpers. Two readers attend a gathered view, and
``paged_decode_attention`` chooses by what the trace can see: the
one-query programs (``decode_paged``, the multi-tick scan's tick,
``peek_paged``) contract the pool's ROWS as they lie
(``ops.attention.rows_decode_attention``: no re-tiling of a view into
heads; to float32 rounding the dense cache's arithmetic, not bit for
bit), while ``prefill_chunk`` and ``verify_paged`` (several queries a
slot: ``_gathered_attention``) and every program traced for a tp mesh
attend the view split into heads, as the dense cache's reader does. Page ownership (refcounts, prefix index, COW
arming) is the engine's job — serving/paging.py; the model layer only
guarantees fixed shapes and donated in-place pool updates.

For SPECULATIVE DECODING (serving/generate.py ``draft_model=``;
docs/SERVING.md) the family grows k-token verify closures beside the
one-token decode: ``verify_step``/``verify_step_paged`` write R
tokens per row at ``[len, len + R)`` and return logits at every
position (``ops.attention.chunked_prefill_attention`` under the
global causal mask — the chunk-prefill kernel reused), ``advance_len``
/``advance_len_paged`` move the ``len`` waterline (commit AND
rollback — a rejected tail simply dies above it), and the FUSED
fast-path closures ``propose_tokens`` (k chained draft steps + the
sampling head in one program) and ``verify_commit[_paged]``
(verify + accept rule + len advance in one program) cut a
speculative iteration to three dispatches. The sampling heads
(ops/sampling.py) ride inside these traces with explicit per-slot
PRNG keys.

All generation entry points are jitted closures over the parameter
NDArrays (the CachedOp ``raw_fn`` rebinding idiom, gluon/block.py), and
count ``model.gpt.trace`` each time they actually trace — the
telemetry hook tests and the serving engine use to assert zero
steady-state compiles.

TENSOR-PARALLEL serving (``GenerationEngine(mesh_layout="tp")``;
docs/SHARDING.md): every parameter carries NAMED LOGICAL AXES
(``Parameter.logical_axes`` — q/k/v/out by heads, ffn1/ffn2 by the
mlp dim, embeddings/lm_head by vocab) that
``parallel.partition.Partitioner`` resolves to mesh placements. The
generation closures are TP-aware by construction: parameters and the
KV cache (sharded over the HEADS axis) enter as COMMITTED sharded
arrays, so the same jitted closures compile SPMD over the mesh —
no second set of closures, and greedy output stays token-identical to
the unsharded engine (the ``tp`` partial-sum reduction order, and the
paged tick's reader, are the only numeric differences: traced under
``ops.attention.jnp_only()`` the tick keeps the view split into heads,
which a pool sharded by heads attends without a collective).
"""
from __future__ import annotations

import math

import numpy as onp
import jax
import jax.numpy as jnp
from jax import lax

from ... import autograd, telemetry, tracing
from ...ndarray.ndarray import NDArray
from ...ops import attention as _att
from ...ops import lora as _lora
from ...ops import quantized as _qz
from ...ops import sampling as _smp
from ...random_state import next_key, trace_rng
from .. import _deferred
from ..block import HybridBlock
from ..parameter import Parameter
from ..nn import Dense, Dropout, Embedding, HybridSequential, LayerNorm

__all__ = ["GPTBlock", "GPTModel", "gpt_small"]

#: the weight-only int8 target set: every per-block projection on the
#: decode hot path. Embeddings, LayerNorms and the lm_head stay fp32 —
#: they are small next to the projections and the head feeds the
#: greedy argmax directly.
_QUANTIZED_PROJECTIONS = ("q_proj", "k_proj", "v_proj", "out_proj",
                          "ffn1", "ffn2")

#: the batched-LoRA target set (``arm_lora``): the attention
#: projections of every block. Adapters must attach to projections
#: with NO fused activation (the low-rank delta adds to the
#: pre-activation output; q/k/v/out and ffn2 qualify, ffn1's gelu
#: does not) — validated at arm time.
_LORA_PROJECTIONS = ("q_proj", "k_proj", "v_proj", "out_proj")

# the ONE int8 convention (amax/127, eps floor, round-then-clip)
# lives in ops/quantized.py — KV quantization must never drift from
# the weight quantization the parity bounds are built on
_kv_scale = _qz.kv_scale
_kv_quantize = _qz.kv_quantize

# HLO metadata only: every operation of a generation or training
# program carries the scope it was traced under in its ``op_name``
# (``jit(gpt_paged_decode)/attn/...``), so a device trace says which
# lines of the model emitted it. The scopes are ``attn`` (projections,
# the attention call and whatever it reshapes), ``kv_write`` (the write
# into the cache or pool), ``mlp``, ``lm_head`` and ``sample``
# (docs/OBSERVABILITY.md).
_scope = jax.named_scope


def _cache_insert(cache, new, pos):
    """Write ``new`` (B, H, 1, Dh) into ``cache`` (B, H, S, Dh) at
    per-row sequence position ``pos`` (B,). vmapped dynamic-update so
    XLA can update a donated cache in place."""
    return jax.vmap(
        lambda c, n, p: lax.dynamic_update_slice_in_dim(c, n, p, axis=1)
    )(cache, new, pos)


def _as_i32(x):
    if isinstance(x, NDArray):
        x = x._data
    return jnp.asarray(x, jnp.int32)


def _write_chunk(k_pool, v_pool, k_scale, v_scale, page_ids, k, v):
    """Scatter a chunk's (1, H, C, Dh) K and V into pool pages
    ``page_ids`` (C / page_size of them). ``k_scale``/``v_scale``
    (n_pages, H) mark an INT8 pool: each written page gets its own
    per-head amax scale. Returns the two pools and the two scale
    tables (None where there are none). Where a page lies in a pool
    is ``ops.attention.write_pages``' to know."""
    if k_scale is None:
        return (_att.write_pages(k_pool, page_ids, k),
                _att.write_pages(v_pool, page_ids, v), None, None)
    ps = _att.pool_page_size(k_pool)

    def quantized(pool, scales, a):
        _one, h, c, d = a.shape
        a = a.astype(jnp.float32)
        sc = _kv_scale(a.reshape(h, c // ps, ps, d), (2, 3))  # (H, C/ps)
        aq = _kv_quantize(a, jnp.repeat(sc, ps, axis=1)[None, :, :, None])
        return (_att.write_pages(pool, page_ids, aq),
                scales.at[page_ids].set(sc.T))

    kp, ksp = quantized(k_pool, k_scale, k)
    vp, vsp = quantized(v_pool, v_scale, v)
    return kp, vp, ksp, vsp


def _gathered_attention(q, k_pool, v_pool, k_scale, v_scale, table,
                        start):
    """A chunk's or a verify step's queries over each row's gathered
    view of the pool (``table`` (B, P_max)), causal in global
    coordinates from ``start``. ``k_scale``/``v_scale`` (n_pages, H)
    mark an INT8 pool, whose every page is dequantized with the scale
    it was written under."""
    kg, vg = _att.gather_kv(k_pool, v_pool, table, q.shape[1],
                            k_scale, v_scale)
    if k_scale is None:
        kg, vg = kg.astype(q.dtype), vg.astype(q.dtype)
    return _att.chunked_prefill_attention(q, kg, vg, start)


class GPTBlock(HybridBlock):
    """Pre-norm causal transformer block with an explicit-KV decode
    path (``prefill`` / ``decode``) beside the plain ``forward``."""

    def __init__(self, units, num_heads, hidden_size=None, dropout=0.0,
                 dtype="float32"):
        super().__init__()
        assert units % num_heads == 0, \
            "units must be divisible by num_heads"
        self._units = units
        self._num_heads = num_heads
        self._head_dim = units // num_heads
        self.ln1 = LayerNorm()
        self.q_proj = Dense(units, flatten=False, dtype=dtype)
        self.k_proj = Dense(units, flatten=False, dtype=dtype)
        self.v_proj = Dense(units, flatten=False, dtype=dtype)
        self.out_proj = Dense(units, flatten=False, dtype=dtype)
        self.ln2 = LayerNorm()
        self.ffn1 = Dense(hidden_size or 4 * units, activation="gelu",
                          flatten=False, dtype=dtype)
        self.ffn2 = Dense(units, flatten=False, dtype=dtype)
        self.drop = Dropout(dropout) if dropout else None
        #: per-call quant binding installed by ``GPTModel._make_bind``
        #: while a quantized generation closure runs: ``{proj_name:
        #: (int8 weight, fp32 per-channel scales)}`` of TRACED buffers.
        #: None (the steady state outside generation and for fp32
        #: engines) keeps every projection on the fp32 Dense path.
        self._qbind = None
        #: per-call LoRA binding installed by ``GPTModel._make_bind``
        #: while a generation closure of a LoRA-armed model runs:
        #: ``({proj_name: bank}, (B,) adapter-index vector)`` of
        #: TRACED buffers. None keeps every projection base-only.
        self._lbind = None

    def _split(self, x):
        b, s, _ = x.shape
        return x.reshape(b, s, self._num_heads,
                         self._head_dim).transpose(0, 2, 1, 3)

    def _merge(self, out):
        b, h, s, d = out.shape
        return out.transpose(0, 2, 1, 3).reshape(b, s, h * d)

    def _proj(self, name, x):
        """One projection: the fp32 Dense, or — when the bound quant
        table carries ``name`` — the fused dequant-matmul over its
        int8 weights (ops/quantized.py: the fp32 weight never
        materializes outside VMEM/cache). Bias and activation follow
        the Dense's own, so the two paths differ ONLY in the weight
        rounding."""
        layer = getattr(self, name)
        q = self._qbind.get(name) if self._qbind else None
        if q is None:
            out = layer(x)
        else:
            wq, w_scale = q
            y = _qz.dequant_matmul(x._data, wq, w_scale)
            if layer.bias is not None:
                y = y + layer.bias.data()._data
            out = NDArray(y, ctx=x.ctx)
            if layer.act is not None:
                out = layer.act(out)
        if self._lbind is not None:
            tab, idx = self._lbind
            bank = tab.get(name)
            if bank is not None:
                # the per-slot low-rank delta, fp32 over either base
                # path (targeted projections carry no activation —
                # enforced by arm_lora — so post-layer == pre-act)
                out = NDArray(_lora.apply(out._data, x._data, bank,
                                          idx), ctx=x.ctx)
        return out

    def _qkv(self, x):
        with _scope("attn"):
            h = self.ln1(x)
            return (self._split(self._proj("q_proj", h)),
                    self._split(self._proj("k_proj", h)),
                    self._split(self._proj("v_proj", h)))

    def _finish(self, x, attn):
        with _scope("attn"):
            y = self._proj("out_proj", self._merge(attn))
            if self.drop is not None:
                y = self.drop(y)
            x = x + y
        with _scope("mlp"):
            y = self._proj("ffn2", self._proj("ffn1", self.ln2(x)))
            if self.drop is not None:
                y = self.drop(y)
            return x + y

    def forward(self, x):
        q, k, v = self._qkv(x)
        from ... import numpy_extension as npx
        with _scope("attn"):
            attn = npx.flash_attention(q, k, v, causal=True)
        return self._finish(x, attn)

    # -- generation (called inside the model's jitted closures) --------
    def prefill(self, x):
        """Causal attention over the (padded) prompt; returns the block
        output and the raw K/V rows to write into the cache."""
        q, k, v = self._qkv(x)
        with _scope("attn"):
            attn = NDArray(_att.flash_attention(
                q._data, k._data, v._data, True, None), ctx=x.ctx)
        return self._finish(x, attn), (k._data, v._data)

    def decode(self, x, k_cache, v_cache, pos, att_len, k_scale=None,
               v_scale=None):
        """One decode step: insert this token's K/V at ``pos``, attend
        over the valid prefix ``[0, att_len)``. ``k_cache``/``v_cache``
        are raw (B, H, S_max, Dh) buffers; returns updated buffers.
        ``k_scale``/``v_scale`` (B, H) mark an INT8 cache: the new
        token quantizes against its slot's per-head scale (fixed at
        prefill — K/V statistics are stationary across positions, and
        one slot row must share one scale) and attention dequantizes
        in the kernel."""
        q, k, v = self._qkv(x)
        with _scope("kv_write"):
            if k_scale is not None:
                kc = _cache_insert(k_cache, _kv_quantize(
                    k._data, k_scale[:, :, None, None]), pos)
                vc = _cache_insert(v_cache, _kv_quantize(
                    v._data, v_scale[:, :, None, None]), pos)
            else:
                kc = _cache_insert(k_cache,
                                   k._data.astype(k_cache.dtype), pos)
                vc = _cache_insert(v_cache,
                                   v._data.astype(v_cache.dtype), pos)
        with _scope("attn"):
            attn = NDArray(
                _att.decode_attention(q._data, kc, vc, att_len,
                                      k_scale=k_scale, v_scale=v_scale),
                ctx=x.ctx)
        return self._finish(x, attn), kc, vc

    def verify(self, x, k_cache, v_cache, pos, start, k_scale=None,
               v_scale=None):
        """One speculative VERIFY step: insert R tokens' K/V at the
        contiguous positions ``[pos, pos + R)`` per row and attend all
        R queries over the global causal mask in one pass —
        ``ops.attention.chunked_prefill_attention`` with per-row
        ``start`` (= each row's committed length), the same kernel the
        paged chunk-prefill path runs. The caller guarantees
        ``pos + R <= S_max`` (the engine reserves a ``spec_k`` scratch
        margin), so the write never clamps. ``k_scale``/``v_scale``
        (B, H) mark an INT8 cache: writes quantize against the slot's
        prefill-time scale and the attention view dequantizes with it
        (the decode-path convention — one slot row, one scale)."""
        q, k, v = self._qkv(x)
        with _scope("kv_write"):
            if k_scale is not None:
                kc = _cache_insert(k_cache, _kv_quantize(
                    k._data, k_scale[:, :, None, None]), pos)
                vc = _cache_insert(v_cache, _kv_quantize(
                    v._data, v_scale[:, :, None, None]), pos)
            else:
                kc = _cache_insert(k_cache,
                                   k._data.astype(k_cache.dtype), pos)
                vc = _cache_insert(v_cache,
                                   v._data.astype(v_cache.dtype), pos)
        with _scope("attn"):
            if k_scale is not None:
                kf = kc.astype(jnp.float32) * k_scale[:, :, None, None]
                vf = vc.astype(jnp.float32) * v_scale[:, :, None, None]
            else:
                kf, vf = kc, vc
            attn = NDArray(_att.chunked_prefill_attention(
                q._data, kf.astype(q._data.dtype),
                vf.astype(q._data.dtype), start), ctx=x.ctx)
        return self._finish(x, attn), kc, vc

    # -- paged-cache generation (serving/generate.py paged mode) --------
    def decode_paged(self, x, k_pool, v_pool, table, page, offset,
                     att_len, k_scale=None, v_scale=None,
                     prev_page=None):
        """One decode step against a PAGED cache: write this token's
        K/V into pool page ``page[b]`` at slot ``offset[b]`` per row,
        attend over each row's valid pages via the table. Inactive
        rows must arrive with ``page == 0`` (the reserved scrap page):
        a free slot's table row may alias pages now owned by another
        slot, so its write is redirected, never masked after the
        fact.

        ``k_scale``/``v_scale`` (n_pages, H) mark an INT8 pool. The
        write page's per-head scale quantizes the new token; a FRESH
        page (``offset == 0``) inherits ``prev_page``'s scale — the
        page's eventual tokens must share one scale, K/V statistics
        are stationary across positions, and the recycled pool page's
        stale scale must never leak in. Scale writes ride the same
        scrap-page redirection as the data. Returns the updated scale
        pools alongside the K/V pools."""
        q, k, v = self._qkv(x)
        ksp = vsp = None
        with _scope("kv_write"):
            if k_scale is not None:
                fresh = (offset == 0)[:, None]
                ks_eff = jnp.where(fresh, k_scale[prev_page],
                                   k_scale[page])
                vs_eff = jnp.where(fresh, v_scale[prev_page],
                                   v_scale[page])
                ksp = k_scale.at[page].set(ks_eff)
                vsp = v_scale.at[page].set(vs_eff)
                kp = _att.write_rows(k_pool, page, offset, _kv_quantize(
                    k._data[:, :, 0, :], ks_eff[:, :, None]))
                vp = _att.write_rows(v_pool, page, offset, _kv_quantize(
                    v._data[:, :, 0, :], vs_eff[:, :, None]))
            else:
                kp = _att.write_rows(k_pool, page, offset,
                                     k._data[:, :, 0, :])
                vp = _att.write_rows(v_pool, page, offset,
                                     v._data[:, :, 0, :])
        with _scope("attn"):
            attn = NDArray(
                _att.paged_decode_attention(q._data, kp, vp, table,
                                            att_len, k_scale=ksp,
                                            v_scale=vsp), ctx=x.ctx)
        return self._finish(x, attn), kp, vp, ksp, vsp

    def prefill_chunk(self, x, k_pool, v_pool, pages, page_ids, start,
                      k_scale=None, v_scale=None):
        """One prefill CHUNK against a paged cache: scatter the chunk's
        K/V into its pool pages (``page_ids``), then attend the chunk's
        queries over the slot's full gathered view (earlier chunks +
        shared prefix pages + this chunk) with the causal mask in
        global coordinates (``start`` is traced — every chunk of every
        prompt runs one compiled program per chunk width).
        ``k_scale``/``v_scale`` (n_pages, H) mark an INT8 pool: each
        written page gets its own per-head amax scale, and the
        gathered view dequantizes every page — shared-prefix pages
        included — with the scale that page was written under."""
        q, k, v = self._qkv(x)
        with _scope("kv_write"):
            kp, vp, ksp, vsp = _write_chunk(
                k_pool, v_pool, k_scale, v_scale, page_ids, k._data,
                v._data)
        with _scope("attn"):
            attn = NDArray(_gathered_attention(
                q._data, kp, vp, ksp, vsp, pages[None], start),
                ctx=x.ctx)
        return self._finish(x, attn), kp, vp, ksp, vsp

    def verify_paged(self, x, k_pool, v_pool, table, page, offset,
                     start, k_scale=None, v_scale=None, fresh=None,
                     anchor_page=None):
        """Speculative VERIFY against a PAGED cache: scatter R tokens'
        K/V per row into pool pages ``page``/``offset`` (B, R) —
        inactive rows and positions past a slot's reservation arrive
        redirected to scrap page 0, exactly the decode-write
        discipline — then attend the R queries over each row's full
        gathered table view under the global causal mask
        (``chunked_prefill_attention`` with per-row ``start``).

        ``k_scale``/``v_scale`` (n_pages, H) mark an INT8 pool:
        ``fresh`` (B, R) flags positions whose page holds no committed
        token yet — they quantize (and stamp the page) with
        ``anchor_page``'s scale (the page holding the row's last
        committed token), the multi-position generalization of
        ``decode_paged``'s predecessor-scale inheritance; positions in
        partially-committed pages reuse that page's scale."""
        q, k, v = self._qkv(x)
        ksp = vsp = None
        with _scope("kv_write"):
            kt = k._data.transpose(0, 2, 1, 3)        # (B, R, H, Dh)
            vt = v._data.transpose(0, 2, 1, 3)
            if k_scale is not None:
                ks_eff = jnp.where(fresh[..., None],
                                   k_scale[anchor_page][:, None, :],
                                   k_scale[page])         # (B, R, H)
                vs_eff = jnp.where(fresh[..., None],
                                   v_scale[anchor_page][:, None, :],
                                   v_scale[page])
                ksp = k_scale.at[page].set(ks_eff)
                vsp = v_scale.at[page].set(vs_eff)
                kp = _att.write_rows(k_pool, page, offset,
                                     _kv_quantize(kt, ks_eff[..., None]))
                vp = _att.write_rows(v_pool, page, offset,
                                     _kv_quantize(vt, vs_eff[..., None]))
            else:
                kp = _att.write_rows(k_pool, page, offset, kt)
                vp = _att.write_rows(v_pool, page, offset, vt)
        with _scope("attn"):
            attn = NDArray(_gathered_attention(
                q._data, kp, vp, ksp, vsp, table, start), ctx=x.ctx)
        return self._finish(x, attn), kp, vp, ksp, vsp

    def peek_paged(self, x, k_pool, v_pool, table, att_len,
                   k_scale=None, v_scale=None):
        """Logits-only attention for the LAST already-cached token of
        one slot (its K/V — including its own — is in the pool): no
        write, cache untouched. The prefix-reuse fast path: a request
        whose entire prompt is cached needs one of these per layer, and
        zero prefill compute."""
        q, _k, _v = self._qkv(x)
        with _scope("attn"):
            attn = NDArray(_att.paged_decode_attention(
                q._data, k_pool, v_pool, table, att_len,
                k_scale=k_scale, v_scale=v_scale), ctx=x.ctx)
        return self._finish(x, attn)


class GPTModel(HybridBlock):
    """Decoder-only transformer LM: token + learned position
    embeddings -> N pre-norm ``GPTBlock``s -> final LayerNorm -> LM
    head. ``forward`` gives full-sequence logits (training / parity);
    ``init_cache``/``prefill``/``decode_step`` are the generation fast
    path (see module docstring and serving/generate.py)."""

    def __init__(self, vocab_size, units=256, num_layers=4, num_heads=4,
                 hidden_size=None, max_length=256, dropout=0.0,
                 dtype="float32"):
        super().__init__()
        self._vocab_size = vocab_size
        self._units = units
        self._num_layers = num_layers
        self._num_heads = num_heads
        self._head_dim = units // num_heads
        self._max_length = max_length
        self._dtype = dtype
        self.word_embed = Embedding(vocab_size, units, dtype=dtype)
        self.position_weight = Parameter(
            "position_weight", shape=(max_length, units), dtype=dtype)
        self.embed_drop = Dropout(dropout) if dropout else None
        self.layers = HybridSequential()
        for _ in range(num_layers):
            self.layers.add(GPTBlock(units, num_heads,
                                     hidden_size=hidden_size,
                                     dropout=dropout, dtype=dtype))
        self.ln_f = LayerNorm()
        self.lm_head = Dense(vocab_size, use_bias=False, flatten=False,
                             dtype=dtype)
        self._annotate_logical_axes()
        self._gen = None  # (param_nds, prefill_jit, decode_jit, ...)
        self._paged = None  # paged-cache closures (_ensure_paged)
        #: fused speculative closures, keyed (kind, k, sampled) —
        #: _ensure_spec; cleared with the other generation closures
        self._spec_jits = None
        #: weight-only int8 tables (``quantize_params``): one dict per
        #: block, ``{proj_name: (int8 weight, fp32 scales)}`` of
        #: device arrays, passed to the jitted closures as RUNTIME
        #: arguments (so a rollover re-quantize installs new values
        #: without retracing — the dense-engine swap discipline).
        self._quant = None
        #: reduced-precision compute buffers (``cast_compute_params``):
        #: a shadow list of the parameter buffers cast to bf16, passed
        #: to the jitted closures as RUNTIME arguments in place of the
        #: fp32 masters — a rollover re-cast installs new values with
        #: zero retraces (the int8 quant-table discipline). The fp32
        #: parameters stay the source of truth.
        self._cast = None
        self._cast_dtype = None
        #: batched-LoRA adapter banks (``arm_lora``): one dict per
        #: block, ``{proj_name: {"A", "B", "scale"} stacked bank}``
        #: (ops/lora.py), passed to the jitted closures as RUNTIME
        #: arguments together with a per-row adapter-index vector —
        #: loading/refreshing/clearing an adapter slot installs new
        #: bank arrays with zero retraces; the first arm (or a
        #: rank/include/capacity change) invalidates the closures.
        self._lora = None
        self._lora_meta = None  # (n_adapters, rank, include tuple)
        #: per-batch-size cached all-zeros (B,) index vectors for the
        #: adapters=None case — the vector is a constant, and minting
        #: a fresh device array per decode tick would tax every
        #: engine's hot path (LoRA-free ones included)
        self._lora_zero_idx: dict = {}

    def _annotate_logical_axes(self):
        """Stamp every parameter with its NAMED LOGICAL AXES
        (``parallel/partition.py``): the partitioner's ordered rule
        list maps these to mesh axes, so one metadata set serves every
        layout — ``"tp"`` shards q/k/v/out by heads and ffn1/ffn2 by
        the mlp dim over ``tp`` and the embeddings/lm_head over the
        vocab dim; ``"fsdp"`` shards everything over ``dp`` along its
        first shardable dim. Dense weights are ``(out, in)``;
        Embedding weights ``(vocab, embed)``."""
        self.word_embed.weight.logical_axes = ("vocab", "embed")
        self.position_weight.logical_axes = (None, "embed")
        self.lm_head.weight.logical_axes = ("vocab", "embed")
        for ln in [self.ln_f]:
            ln.gamma.logical_axes = ("embed",)
            ln.beta.logical_axes = ("embed",)
        for blk in self._blocks():
            for name in ("q_proj", "k_proj", "v_proj"):
                layer = getattr(blk, name)
                layer.weight.logical_axes = ("heads", "embed")
                if layer.bias is not None:
                    layer.bias.logical_axes = ("heads",)
            blk.out_proj.weight.logical_axes = ("embed", "heads")
            if blk.out_proj.bias is not None:
                blk.out_proj.bias.logical_axes = ("embed",)
            blk.ffn1.weight.logical_axes = ("mlp", "embed")
            if blk.ffn1.bias is not None:
                blk.ffn1.bias.logical_axes = ("mlp",)
            blk.ffn2.weight.logical_axes = ("embed", "mlp")
            if blk.ffn2.bias is not None:
                blk.ffn2.bias.logical_axes = ("embed",)
            for ln in (blk.ln1, blk.ln2):
                ln.gamma.logical_axes = ("embed",)
                ln.beta.logical_axes = ("embed",)

    @property
    def max_length(self):
        return self._max_length

    @property
    def quantized(self) -> bool:
        """True once ``quantize_params`` armed the weight-only int8
        decode path."""
        return self._quant is not None

    def _blocks(self):
        return list(self.layers._children.values())

    def _embed(self, tokens, positions=None):
        x = self.word_embed(tokens)
        if positions is None:
            pos = self.position_weight.data()[:tokens.shape[-1]]
        else:
            pos = positions
        x = x + pos
        if self.embed_drop is not None:
            x = self.embed_drop(x)
        return x

    def _head(self, x):
        with _scope("lm_head"):
            return self.lm_head(self.ln_f(x))

    def forward(self, tokens):
        x = self._embed(tokens)
        for blk in self._blocks():
            x = blk(x)
        return self._head(x)

    # -- generation API ------------------------------------------------
    def _clear_cached_op(self):
        super()._clear_cached_op()
        self._gen = None  # params rebound/cast: jitted closures stale
        self._paged = None
        self._spec_jits = None
        # NOTE: self._quant survives — it is derived state an explicit
        # quantize_params() refresh owns (the serving engine re-calls
        # it under the swap lock on every weight rollover)
        # NOTE: self._lora survives too — adapter banks are tenant
        # state, not derived from the base parameters; a weight
        # rollover keeps the loaded adapters armed
        # NOTE: self._cast survives for the same reason as _quant —
        # an explicit cast_compute_params() refresh owns it (the
        # engine re-casts under the load_weights swap lock)

    def quantize_params(self, include=_QUANTIZED_PROJECTIONS):
        """Arm (or refresh) weight-only int8 decode: quantize every
        ``include`` projection of every block per-output-channel
        symmetric int8 (ops/quantized.py) and route the generation
        closures' projections through the fused dequant-matmul.

        The quantized tables are RUNTIME arguments of the jitted
        closures, so calling this again after a weight swap
        (``GenerationEngine.load_weights``) installs freshly-quantized
        values with ZERO retraces; the first call (or a change of
        ``include``) invalidates the closures — quantize before
        ``warmup()``. Embeddings, LayerNorms and the lm_head stay
        fp32. Training/plain ``forward`` is untouched — the fp32
        parameters remain the source of truth."""
        self._gen_params()   # materialize deferred parameters first
        tabs = []
        for blk in self._blocks():
            tab = {}
            for name in include:
                layer = getattr(blk, name, None)
                if not isinstance(layer, Dense):
                    raise ValueError(
                        f"unknown quantizable projection {name!r} "
                        f"(choose from {_QUANTIZED_PROJECTIONS})")
                wq, scale = _qz.quantize_channelwise(
                    layer.weight.data()._data)
                tab[name] = (wq, scale)
            tabs.append(tab)
        fresh = (self._quant is None
                 or [sorted(t) for t in self._quant]
                 != [sorted(t) for t in tabs])
        self._quant = tabs
        if fresh:   # pytree structure changed: closures must retrace
            self._gen = None
            self._paged = None
            self._spec_jits = None
        return self

    @property
    def compute_dtype(self) -> str:
        """The generation closures' parameter/activation compute dtype:
        ``"float32"`` (default — the fp32 masters run as-is) or
        ``"bfloat16"`` once :meth:`cast_compute_params` armed the
        reduced-precision path."""
        return self._cast_dtype or "float32"

    def cast_compute_params(self, dtype="bfloat16"):
        """Arm (or refresh) the reduced-precision compute path: cast
        every floating parameter buffer to ``dtype`` into a shadow
        list the generation closures consume IN PLACE of the fp32
        masters, which remain the source of truth (training, plain
        ``forward``, checkpoints and re-casts all read fp32).

        The cast buffers are RUNTIME arguments of the jitted closures,
        so calling this again after a weight swap
        (``GenerationEngine.load_weights``) installs freshly-cast
        values with ZERO retraces; the first call (or a dtype change)
        invalidates the closures — cast before ``warmup()``.
        ``cast_compute_params(None)`` disarms. Softmax and LayerNorm
        still accumulate in fp32 (``ops.nn.accum_dtype``), attention
        scores likewise, and every closure returns fp32 logits — the
        host sampler/argmax contract is dtype-invariant. Composes
        with an int8 KV cache (bf16 K/V quantize against the same
        per-slot scales) and with weight-only int8 (quantized
        projections dequantize to their own compute path; the
        remaining fp32 parameters are what this casts)."""
        if dtype is None:
            if self._cast is not None:
                self._cast = None
                self._cast_dtype = None
                self._gen = None
                self._paged = None
                self._spec_jits = None
            return self
        dt = jnp.zeros((), dtype).dtype   # canonicalize str/np/jnp
        if dt not in (jnp.bfloat16, jnp.float16):
            raise ValueError(
                f"compute dtype {dtype!r} not supported (bfloat16 or "
                f"float16)")
        params = self._gen_params()
        self._cast = [
            p._data.astype(dt)
            if jnp.issubdtype(p._data.dtype, jnp.floating) else p._data
            for p in params]
        fresh = self._cast_dtype != dt.name
        self._cast_dtype = dt.name
        if fresh:   # param avals changed: closures must retrace
            self._gen = None
            self._paged = None
            self._spec_jits = None
        return self

    def _param_call_datas(self, param_nds):
        """The parameter buffers a generation-closure CALL carries:
        the bf16 shadow list when :meth:`cast_compute_params` is
        armed, else the fp32 masters. One helper so every call site
        (dense/paged/spec/multi/HLO) agrees."""
        if self._cast is not None:
            return self._cast
        return [nd._data for nd in param_nds]

    def quantized_param_stats(self):
        """``(n_elements, bytes_saved)`` of the current quant tables
        (fp32 -> int8 is 3 bytes per element; the per-channel scales
        are counted against the saving)."""
        if self._quant is None:
            return 0, 0
        n = sum(int(wq.size) for tab in self._quant
                for wq, _s in tab.values())
        scale_bytes = sum(int(s.size) * 4 for tab in self._quant
                          for _wq, s in tab.values())
        return n, n * 3 - scale_bytes

    # -- batched multi-tenant LoRA (ops/lora.py; serving/generate.py) ---
    @property
    def lora_armed(self) -> bool:
        """True once ``arm_lora`` installed the stacked adapter banks."""
        return self._lora is not None

    def arm_lora(self, n_adapters, rank, include=_LORA_PROJECTIONS):
        """Arm batched multi-tenant LoRA: allocate an all-zeros stacked
        adapter bank (``n_adapters`` slots, slot 0 reserved as the
        base-model zero adapter) for every ``include`` projection of
        every block, and route the generation closures through the
        per-slot batched apply ``y += (x @ A[idx]) @ B[idx] *
        scale[idx]`` (ops/lora.py).

        The banks are RUNTIME arguments of the jitted closures (the
        quant-table discipline): :meth:`set_adapter` /
        :meth:`clear_adapter` install new bank arrays with ZERO
        retraces. The first arm — or a change of ``n_adapters``,
        ``rank`` or ``include`` — changes the closures' pytree
        structure and invalidates them; arm before ``warmup()``.
        Training/plain ``forward`` is untouched (adapters live only on
        the generation path)."""
        self._gen_params()   # materialize deferred parameter shapes
        include = tuple(include)
        if not include:
            raise ValueError("arm_lora needs at least one projection")
        for name in include:
            probe = getattr(self._blocks()[0], name, None)
            if not isinstance(probe, Dense):
                raise ValueError(
                    f"unknown LoRA projection {name!r} (choose from "
                    f"{_LORA_PROJECTIONS + ('ffn2',)}; ffn1 carries "
                    f"a fused activation and cannot take the delta)")
            if probe.act is not None:
                raise ValueError(
                    f"LoRA projection {name!r} carries a fused "
                    f"activation: the low-rank delta must add to the "
                    f"pre-activation output (choose projections "
                    f"without one, e.g. {_LORA_PROJECTIONS})")
        meta = (int(n_adapters), int(rank), tuple(sorted(include)))
        fresh = self._lora_meta != meta
        if not fresh:
            return self
        tabs = []
        for blk in self._blocks():
            tab = {}
            for name in include:
                d_out, d_in = getattr(blk, name).weight.data().shape
                tab[name] = _lora.init_bank(n_adapters, d_in, d_out,
                                            rank)
            tabs.append(tab)
        self._lora = tabs
        self._lora_meta = meta
        # pytree structure changed: the closures must retrace once
        self._gen = None
        self._paged = None
        self._spec_jits = None
        return self

    def set_adapter(self, idx, params, alpha=1.0):
        """Install one tenant's LoRA factors into bank slot ``idx``
        (1-based; slot 0 is the reserved base adapter). ``params`` is
        a flat mapping ``{"layers.<li>.<proj>.A": (d_in, rank),
        "layers.<li>.<proj>.B": (rank, d_out)}`` covering EXACTLY the
        armed include set of every block; ``alpha`` is the adapter's
        scaling numerator (applied as ``alpha / rank``). Shape or
        coverage mismatches raise before any slot is touched, so a bad
        adapter can never leave the bank half-written. Zero retraces —
        the banks are runtime arguments of the jitted closures."""
        if self._lora is None:
            raise RuntimeError("set_adapter before arm_lora")
        include = self._lora_meta[2]
        expect = {f"layers.{li}.{name}.{half}"
                  for li in range(self._num_layers)
                  for name in include for half in ("A", "B")}
        got = set(params)
        if got != expect:
            missing = sorted(expect - got)[:3]
            extra = sorted(got - expect)[:3]
            raise ValueError(
                f"adapter params must cover the armed include set "
                f"exactly (missing {missing}, unexpected {extra})")
        for key in sorted(got):
            # host-side check: the factors arrive as host arrays, and
            # this runs inside the engine's exclusive swap window — a
            # per-key device round-trip would stall decode for
            # 2*layers*projections syncs per load
            if not bool(onp.isfinite(onp.asarray(params[key])).all()):
                raise ValueError(
                    f"adapter param {key!r} contains non-finite "
                    f"values — a NaN/inf factor would poison every "
                    f"request bound to this slot; rejected before "
                    f"any install")
        new_tabs = []
        for li, tab in enumerate(self._lora):
            new_tab = dict(tab)
            for name in include:
                new_tab[name] = _lora.set_slot(
                    tab[name], idx, params[f"layers.{li}.{name}.A"],
                    params[f"layers.{li}.{name}.B"], alpha)
            new_tabs.append(new_tab)
        self._lora = new_tabs
        return self

    def clear_adapter(self, idx):
        """Zero bank slot ``idx`` back to the base (no-op) adapter —
        zero retraces, like :meth:`set_adapter`."""
        if self._lora is None:
            raise RuntimeError("clear_adapter before arm_lora")
        self._lora = [
            {name: _lora.clear_slot(bank, idx)
             for name, bank in tab.items()} for tab in self._lora]
        return self

    def _lora_arg(self):
        """The LoRA-bank runtime argument every closure call carries:
        the live banks, or an empty pytree for unarmed models (a
        stable structure either way — flipping it retraces, which is
        why ``arm_lora`` invalidates the closures)."""
        return self._lora if self._lora is not None else []

    def _lora_idx(self, adapters, batch):
        """Normalize a per-row adapter-index vector: ``None`` means
        all-base (index 0 — the reserved zero adapter; the constant
        vector is cached per batch size, not re-minted per step)."""
        if adapters is None:
            b = int(batch)
            z = self._lora_zero_idx.get(b)
            if z is None:
                z = self._lora_zero_idx.setdefault(
                    b, jnp.zeros((b,), jnp.int32))
            return z
        idx = _as_i32(adapters).reshape(-1)
        if idx.shape[0] != int(batch):
            raise ValueError(
                f"adapters must be one index per row ({int(batch)}), "
                f"got shape {idx.shape}")
        return idx

    # -- mesh-sharded generation state (docs/SHARDING.md) ---------------
    def set_force_jnp_attention(self, on):
        """Switch the generation closures' attention tracing mode:
        ``True`` traces the jnp kernel paths (``ops.attention.
        jnp_only`` — required inside SPMD programs, where a
        ``pallas_call`` cannot ride without its own ``shard_map``),
        ``False`` restores the backend default (Pallas on TPU). The
        ONE place the flag and its closure invalidation live: a mode
        flip invalidates every cached generation closure, because a
        closure traced under the other mode would silently keep the
        wrong kernel path. No-op (closures kept) when the mode is
        already set."""
        on = bool(on)
        if getattr(self, "_force_jnp_attention", False) == on:
            return self
        self._force_jnp_attention = on
        self._gen = None
        self._paged = None
        self._spec_jits = None
        return self

    def shard_generation_state(self, partitioner):
        """Place the DERIVED generation-state runtime arguments onto
        mesh shardings riding the same logical axes as the parameters
        they scale (``GenerationEngine(mesh_layout="tp")`` calls this
        after placing the parameters, and again after every rollover
        re-quantize):

        - int8 quant tables: ``wq`` follows its fp32 weight's resolved
          spec exactly (same shape, same axes); the per-output-channel
          ``scale`` vector follows the weight's dim-0 axis — a scale
          must live WITH the channels it scales or every dequant
          would gather it cross-device.
        - LoRA banks: ``A (n, d_in, r)`` shards ``d_in`` on the
          projection weight's input axis (the out-projection's heads
          axis under tp), ``B (n, r, d_out)`` shards ``d_out`` on the
          weight's output axis (q/k/v's heads axis), ``scale``
          replicates — so the per-slot bank gather stays per-device
          inside the one fixed-shape program.

        Zero retraces: the tables/banks are runtime arguments and
        ``device_put`` changes values' placement, not the pytree
        structure."""
        import jax as _jax
        from jax.sharding import NamedSharding as _NS, \
            PartitionSpec as _P
        mesh = partitioner.mesh

        def _wspec(blk, name):
            d = getattr(blk, name).weight.data()._data
            sh = getattr(d, "sharding", None)
            spec = tuple(sh.spec) if isinstance(sh, _NS) else ()
            return spec + (None,) * (d.ndim - len(spec))

        if self._quant is not None:
            tabs = []
            for blk, tab in zip(self._blocks(), self._quant):
                new = {}
                for name, (wq, sc) in tab.items():
                    spec = _wspec(blk, name)
                    new[name] = (
                        _jax.device_put(wq, _NS(mesh, _P(*spec))),
                        _jax.device_put(sc, _NS(mesh, _P(spec[0]))))
                tabs.append(new)
            self._quant = tabs
        if self._lora is not None:
            tabs = []
            for blk, tab in zip(self._blocks(), self._lora):
                new = {}
                for name, bank in tab.items():
                    spec = _wspec(blk, name)     # (d_out, d_in)
                    new[name] = {
                        "A": _jax.device_put(
                            bank["A"],
                            _NS(mesh, _P(None, spec[1], None))),
                        "B": _jax.device_put(
                            bank["B"],
                            _NS(mesh, _P(None, None, spec[0]))),
                        "scale": _jax.device_put(bank["scale"],
                                                 _NS(mesh, _P())),
                    }
                tabs.append(new)
            self._lora = tabs
        return self

    def decode_hlo(self, tokens, cache, active=None, adapters=None):
        """Compiled HLO text of the decode-step program serving these
        argument avals (dense when ``active`` is None, paged
        otherwise) — the serving analog of ``TrainStep.compiled_hlo``:
        ``GenerationEngine.warmup()`` under ``mesh_layout="tp"`` feeds
        it to ``partition.hlo_collectives`` to count the per-step
        cross-device collectives the telemetry counters report. This
        lowers/compiles a fresh executable for inspection (the live
        jit entry is untouched), so call it OUTSIDE any timed
        window."""
        tokens = _as_i32(tokens)
        b = tokens.shape[0]
        args = [self._quant_arg(), self._lora_arg(),
                self._lora_idx(adapters, b), tokens]
        if active is None:
            gen = self._ensure_gen()
            param_nds, jitfn = gen[0], gen[2]
        else:
            p = self._ensure_paged()
            param_nds, jitfn = p["params"], p["decode"]
            args.append(_as_i32(active))
        lowered = jitfn.lower(next_key(),
                              self._param_call_datas(param_nds),
                              *args, cache)
        return lowered.compile().as_text()

    def verify_commit_hlo(self, k, cache, paged=False, adapters=None):
        """Compiled HLO text of the fused greedy ``verify_commit``
        program — :meth:`decode_hlo`'s speculative sibling: a
        speculative engine's steady state runs THIS program per
        iteration, not the single-token decode, so its per-step
        collective counts must be measured from it (the sampled
        variant adds sampling ops on top of the same verify; the
        greedy program is the collective-structure reference). Lowers
        a fresh executable; call outside any timed window."""
        b = int(cache["len"].shape[0])
        kind = "verify_commit_paged" if paged else "verify_commit"
        param_nds, jitted = self._ensure_spec(kind, int(k), False)
        zb = jnp.zeros((b,), jnp.int32)
        dt = jnp.zeros((b, int(k)), jnp.int32)
        ones = jnp.ones((b,), jnp.int32)
        lowered = jitted.lower(next_key(),
                               self._param_call_datas(param_nds),
                               self._quant_arg(), self._lora_arg(),
                               self._lora_idx(adapters, b),
                               zb, dt, ones, cache)
        return lowered.compile().as_text()

    def init_cache(self, batch_size, max_length=None, dtype=None):
        """Preallocated fixed-shape KV cache pytree for ``batch_size``
        slots: ``{"k": tuple of L (B, H, S_max, Dh) arrays, "v": same,
        "len": (B,) int32 valid lengths}``. Explicit argument/result of
        ``prefill``/``decode_step`` (which DONATE it) — never mutated
        in place from Python.

        ``dtype="int8"`` allocates a QUANTIZED cache (a quarter the
        K/V bytes of fp32): the pytree grows ``k_scale``/``v_scale``
        tuples of (B, H) fp32 per-head-per-slot scales, set at prefill
        from each prompt's amax and reused by every decode write into
        that slot."""
        s = int(max_length) if max_length is not None else self._max_length
        if not 1 <= s <= self._max_length:
            raise ValueError(
                f"cache max_length {s} out of range (position table "
                f"holds {self._max_length})")
        shape = (int(batch_size), self._num_heads, s, self._head_dim)
        dt = onp.dtype(dtype or self._dtype)
        zeros = lambda: tuple(jnp.zeros(shape, dt)  # noqa: E731
                              for _ in range(self._num_layers))
        cache = {"k": zeros(), "v": zeros(),
                 "len": jnp.zeros((int(batch_size),), jnp.int32)}
        if dt == onp.int8:
            sc = lambda: tuple(  # noqa: E731
                jnp.zeros((int(batch_size), self._num_heads),
                          jnp.float32) for _ in range(self._num_layers))
            cache["k_scale"] = sc()
            cache["v_scale"] = sc()
        return cache

    def _gen_params(self):
        params = list(self.collect_params().values())
        if any(p._data is None for p in params):
            # materialize deferred shapes with one eager probe forward
            # (the CachedOp._abstract_init idiom)
            self.infer_shape(NDArray(jnp.zeros((1, 2), jnp.int32)))
            params = list(self.collect_params().values())
        return [p.data() for p in params]

    @staticmethod
    def _make_bind(param_nds, blocks, force_jnp=False):
        """Closure factory: run ``fn`` with the parameter NDArrays
        rebound to the traced buffers (gluon/block.py raw_fn idiom)
        and — for a quantized model — each block's ``_qbind`` table
        rebound to the traced int8 weights/scales, so ``_proj``
        dispatches to the fused dequant-matmul inside the trace; a
        LoRA-armed model additionally rebinds each block's ``_lbind``
        to its traced adapter banks plus the call's per-row adapter
        index vector. Shared by the dense and paged generation
        closures. ``force_jnp`` (a mesh-sharded serving engine sets
        ``model._force_jnp_attention``) traces the attention ops on
        their jnp paths — a ``pallas_call`` cannot ride inside an
        SPMD program without its own ``shard_map``.

        ``_bind(fn, name)`` names the wrapper ``name`` before it is
        jitted, so each role is its own ``jit_<name>`` program on the
        profiler's ``XLA Modules`` line (docs/OBSERVABILITY.md lists
        them; the benchmark's per-program device times match them)."""
        def _bind(fn, name):
            def wrapper(key, param_datas, quant_tabs, lora_tabs,
                        lora_idx, *args):
                telemetry.counter("model.gpt.trace")
                tracing.flight.record("compile", what="model.gpt")
                saved = [nd._data for nd in param_nds]
                saved_q = [blk._qbind for blk in blocks]
                saved_l = [blk._lbind for blk in blocks]
                scope = _deferred.trace_scope()
                rec = autograd._RecordingScope(False, False)
                import contextlib as _ctx
                att_ctx = _att.jnp_only() if force_jnp \
                    else _ctx.nullcontext()
                with scope, rec, trace_rng(key), att_ctx:
                    for nd, d in zip(param_nds, param_datas):
                        nd._data = d
                    for blk, tab in zip(
                            blocks, quant_tabs or [None] * len(blocks)):
                        blk._qbind = tab
                    for blk, tab in zip(
                            blocks, lora_tabs or [None] * len(blocks)):
                        blk._lbind = None if tab is None \
                            else (tab, lora_idx)
                    try:
                        return fn(*args)
                    finally:
                        for nd, s in zip(param_nds, saved):
                            nd._data = s
                        for blk, s in zip(blocks, saved_q):
                            blk._qbind = s
                        for blk, s in zip(blocks, saved_l):
                            blk._lbind = s
            wrapper.__name__ = wrapper.__qualname__ = name
            return wrapper
        return _bind

    def _quant_arg(self):
        """The quant-table runtime argument every closure call carries:
        the live tables, or an empty pytree for fp32 models (a STABLE
        structure either way — flipping it retraces, which is why
        ``quantize_params`` invalidates the closures on first arm)."""
        return self._quant if self._quant is not None else []

    def _verify_body(self, blocks, tokens, cache):
        """The dense k-token verify computation (shared by the
        ``verify_step`` closure and the fused ``verify_commit``):
        write R tokens per row at ``[len, len + R)``, attend all R
        queries under the global causal mask, return (B, R, V) logits
        with ``len`` UNCHANGED."""
        _b, r = tokens.shape
        quant_kv = cache["k"][0].dtype == jnp.int8
        ln = cache["len"]
        positions = ln[:, None] + jnp.arange(r, dtype=jnp.int32)
        pw = self.position_weight.data()._data
        x = NDArray(self.word_embed(NDArray(tokens))._data
                    + jnp.take(pw, positions, axis=0))
        if self.embed_drop is not None:
            x = self.embed_drop(x)
        ks, vs = [], []
        for li, blk in enumerate(blocks):
            x, kc, vc = blk.verify(
                x, cache["k"][li], cache["v"][li], ln, ln,
                k_scale=cache["k_scale"][li] if quant_kv else None,
                v_scale=cache["v_scale"][li] if quant_kv else None)
            ks.append(kc)
            vs.append(vc)
        logits = self._head(x)                       # (B, R, V)
        new_cache = {"k": tuple(ks), "v": tuple(vs), "len": ln}
        if quant_kv:
            new_cache["k_scale"] = cache["k_scale"]
            new_cache["v_scale"] = cache["v_scale"]
        return logits._data.astype(jnp.float32), new_cache

    def _verify_body_paged(self, blocks, tokens, active, cache):
        """The paged k-token verify computation (shared by the
        ``verify_step_paged`` closure and the fused
        ``verify_commit_paged``): scatter each ACTIVE row's R tokens
        through its page table (inactive rows redirect to scrap page
        0), attend the gathered view, return (B, R, V) logits with
        ``len`` unchanged."""
        b, r = tokens.shape
        ps = _att.pool_page_size(cache["k"][0])
        s_max = cache["table"].shape[1] * ps
        quant_kv = cache["k"][0].dtype == jnp.int8
        ln = cache["len"]
        live = active > 0
        rows = jnp.arange(b, dtype=jnp.int32)[:, None]
        pos = jnp.minimum(
            ln[:, None] + jnp.arange(r, dtype=jnp.int32), s_max - 1)
        lpage = pos // ps
        page = jnp.where(live[:, None], cache["table"][rows, lpage], 0)
        offset = jnp.where(live[:, None], pos % ps, 0)
        pw = self.position_weight.data()._data
        x = NDArray(self.word_embed(NDArray(tokens))._data
                    + jnp.take(pw, pos, axis=0))
        if self.embed_drop is not None:
            x = self.embed_drop(x)
        if quant_kv:
            # scale anchoring: a page with no committed token yet
            # inherits the scale of the page holding the row's last
            # committed token (decode_paged's predecessor rule,
            # generalized to a multi-position write)
            anchor = jnp.where(
                live,
                cache["table"][jnp.arange(b),
                               jnp.maximum(ln - 1, 0) // ps], 0)
            fresh = (lpage * ps) >= ln[:, None]
        else:
            anchor = fresh = None
        ks, vs, kscs, vscs = [], [], [], []
        for li, blk in enumerate(blocks):
            x, kp, vp, ksp, vsp = blk.verify_paged(
                x, cache["k"][li], cache["v"][li], cache["table"],
                page, offset, ln,
                k_scale=cache["k_scale"][li] if quant_kv else None,
                v_scale=cache["v_scale"][li] if quant_kv else None,
                fresh=fresh, anchor_page=anchor)
            ks.append(kp)
            vs.append(vp)
            kscs.append(ksp)
            vscs.append(vsp)
        logits = self._head(x)                       # (B, R, V)
        new_cache = {"k": tuple(ks), "v": tuple(vs),
                     "table": cache["table"], "len": ln}
        if quant_kv:
            new_cache["k_scale"] = tuple(kscs)
            new_cache["v_scale"] = tuple(vscs)
        return logits._data.astype(jnp.float32), new_cache

    def _decode_body(self, blocks, tokens, cache, live=None):
        """One decode step's computation (shared by the ``decode_step``
        closure, the fused k-step ``propose_tokens`` loop and the
        multi-tick ``decode_multi`` scan). ``live`` (B,) bool, when
        given, freezes dead rows IN-PROGRAM: their ``len`` stands
        still, so their (unavoidable — fixed shape) cache write lands
        at the frozen waterline, above which nothing is ever attended
        (the speculative rejected-tail discipline); without it every
        row advances (the classic single-step contract, where the
        HOST masks dead rows by ignoring them)."""
        s_max = cache["k"][0].shape[2]
        quant_kv = cache["k"][0].dtype == jnp.int8
        ln = cache["len"]
        pos = jnp.minimum(ln, s_max - 1)   # clamped write position
        att_len = pos + 1                  # incl. the new token
        emb = self.word_embed(NDArray(tokens))          # (B, U)
        pw = self.position_weight.data()._data
        x = NDArray((emb._data + jnp.take(pw, pos, axis=0))[:, None, :])
        if self.embed_drop is not None:
            x = self.embed_drop(x)
        ks, vs = [], []
        for li, blk in enumerate(blocks):
            x, kc, vc = blk.decode(
                x, cache["k"][li], cache["v"][li], pos, att_len,
                k_scale=cache["k_scale"][li] if quant_kv else None,
                v_scale=cache["v_scale"][li] if quant_kv else None)
            ks.append(kc)
            vs.append(vc)
        logits = self._head(x)                       # (B, 1, V)
        new_len = ln + 1 if live is None \
            else ln + live.astype(jnp.int32)
        new_cache = {"k": tuple(ks), "v": tuple(vs), "len": new_len}
        if quant_kv:   # per-slot scales are fixed at prefill
            new_cache["k_scale"] = cache["k_scale"]
            new_cache["v_scale"] = cache["v_scale"]
        return logits._data[:, 0, :].astype(jnp.float32), new_cache

    def _decode_body_paged(self, blocks, tokens, active, cache):
        """One PAGED decode step's computation (shared by the
        ``decode_step_paged`` closure and the fused multi-tick
        ``decode_multi_paged`` scan). ``active`` (B,) int32 masks
        rows: an inactive row runs the same fixed-shape program but
        its write is redirected into scrap page 0 and its ``len``
        stands still — which is exactly how the multi-tick scan
        freezes rows that hit eos/budget mid-scan."""
        ps = _att.pool_page_size(cache["k"][0])
        s_max = cache["table"].shape[1] * ps
        quant_kv = cache["k"][0].dtype == jnp.int8
        ln = cache["len"]
        b = ln.shape[0]
        pos = jnp.minimum(ln, s_max - 1)
        att_len = pos + 1
        live = active > 0
        # inactive rows write into scrap page 0 (their table rows
        # may alias pages now owned by OTHER slots — a masked-out
        # result is not enough, the write itself must be redirected)
        page = jnp.where(
            live, cache["table"][jnp.arange(b), pos // ps], 0)
        offset = jnp.where(live, pos % ps, 0)
        # the previous page (scale inheritance for a page whose
        # first token this step writes); same scrap redirection
        prev_page = jnp.where(
            live,
            cache["table"][jnp.arange(b),
                           jnp.maximum(pos // ps - 1, 0)], 0)
        emb = self.word_embed(NDArray(tokens))
        pw = self.position_weight.data()._data
        x = NDArray((emb._data + jnp.take(pw, pos, axis=0))[:, None, :])
        if self.embed_drop is not None:
            x = self.embed_drop(x)
        ks, vs, kscs, vscs = [], [], [], []
        for li, blk in enumerate(blocks):
            x, kp, vp, ksp, vsp = blk.decode_paged(
                x, cache["k"][li], cache["v"][li], cache["table"],
                page, offset, att_len,
                k_scale=cache["k_scale"][li] if quant_kv else None,
                v_scale=cache["v_scale"][li] if quant_kv else None,
                prev_page=prev_page if quant_kv else None)
            ks.append(kp)
            vs.append(vp)
            kscs.append(ksp)
            vscs.append(vsp)
        logits = self._head(x)
        new_cache = {"k": tuple(ks), "v": tuple(vs),
                     "table": cache["table"],
                     "len": ln + live.astype(jnp.int32)}
        if quant_kv:
            new_cache["k_scale"] = tuple(kscs)
            new_cache["v_scale"] = tuple(vscs)
        return logits._data[:, 0, :].astype(jnp.float32), new_cache

    def _ensure_gen(self):
        if self._gen is not None:
            return self._gen
        param_nds = self._gen_params()
        blocks = self._blocks()
        _bind = self._make_bind(
            param_nds, blocks,
            force_jnp=getattr(self, '_force_jnp_attention', False))

        def prefill_raw(tokens, valid_len, slots, cache):
            b, sb = tokens.shape
            x = self._embed(NDArray(tokens))
            ks, vs = [], []
            for blk in blocks:
                x, (k, v) = blk.prefill(x)
                ks.append(k)
                vs.append(v)
            # logits of the LAST VALID prompt token (predicts token 1)
            idx = jnp.clip(valid_len - 1, 0, sb - 1)
            last = x._data[jnp.arange(b), idx][:, None, :]   # (b, 1, U)
            logits = self._head(NDArray(last))
            with _scope("kv_write"):
                dt = cache["k"][0].dtype
                if dt == jnp.int8:
                    # int8 cache: per-head-per-slot scales from the
                    # prompt's amax (the bucket's pad rows contribute —
                    # harmless overestimate); decode reuses them
                    ksc = [_kv_scale(k, (2, 3)) for k in ks]     # (b, H)
                    vsc = [_kv_scale(v, (2, 3)) for v in vs]
                    new_cache = {
                        "k": tuple(
                            c.at[slots, :, :sb, :].set(
                                _kv_quantize(k, s[:, :, None, None]))
                            for c, k, s in zip(cache["k"], ks, ksc)),
                        "v": tuple(
                            c.at[slots, :, :sb, :].set(
                                _kv_quantize(v, s[:, :, None, None]))
                            for c, v, s in zip(cache["v"], vs, vsc)),
                        "k_scale": tuple(
                            c.at[slots].set(s)
                            for c, s in zip(cache["k_scale"], ksc)),
                        "v_scale": tuple(
                            c.at[slots].set(s)
                            for c, s in zip(cache["v_scale"], vsc)),
                        "len": cache["len"].at[slots].set(valid_len),
                    }
                else:
                    new_cache = {
                        "k": tuple(c.at[slots, :, :sb, :].set(k.astype(dt))
                                   for c, k in zip(cache["k"], ks)),
                        "v": tuple(c.at[slots, :, :sb, :].set(v.astype(dt))
                                   for c, v in zip(cache["v"], vs)),
                        "len": cache["len"].at[slots].set(valid_len),
                    }
            return logits._data[:, 0, :].astype(jnp.float32), new_cache

        def decode_raw(tokens, cache):
            return self._decode_body(blocks, tokens, cache)

        def verify_raw(tokens, cache):
            """Speculative verify: write the R tokens of every row at
            its contiguous positions ``[len, len + R)`` and return the
            logits at ALL R positions (B, R, V) in one fixed-shape
            program. ``len`` is NOT advanced — the engine commits the
            accepted prefix afterwards via ``advance_raw``, which is
            what clips the rejected tail out of the cache (positions
            past ``len`` are never attended and the next verify
            overwrites them). The caller keeps ``len + R <= S_max``
            (the engine's spec_k capacity margin)."""
            return self._verify_body(blocks, tokens, cache)

        def advance_raw(delta, cache):
            """Commit point: bump each row's valid length by ``delta``
            (the engine's accepted-token count; 0 leaves a row put).
            Everything in the cache past the new ``len`` is dead —
            the speculative rollback IS this counter."""
            new = dict(cache)
            new["len"] = cache["len"] + delta
            return new

        # wrapper args: (key, params, quant, lora_tabs, lora_idx,
        # *fn_args) — fn args start at 5, hence the donated cache
        # positions below
        self._gen = (
            param_nds,
            jax.jit(_bind(prefill_raw, "gpt_dense_prefill"),
                    donate_argnums=(8,)),
            jax.jit(_bind(decode_raw, "gpt_dense_decode"),
                    donate_argnums=(6,)),
            jax.jit(_bind(verify_raw, "gpt_dense_verify"),
                    donate_argnums=(6,)),
            jax.jit(_bind(advance_raw, "gpt_dense_advance"),
                    donate_argnums=(6,)),
        )
        return self._gen

    def prefill(self, tokens, valid_length, cache, slots=None,
                adapters=None):
        """Run the (padded) prompts ``tokens`` (B_req, S_bucket) int32
        through the model, write their K/V into ``cache`` at rows
        ``slots`` (default ``0..B_req-1``), set ``len`` to
        ``valid_length``. Returns ``(last_logits, cache)`` — raw
        ``(B_req, vocab)`` logits of each row's last valid token and
        the updated cache (the passed cache is donated; always use the
        returned one). ``adapters`` (B_req,) int32 selects each row's
        LoRA bank slot on an armed model (None/0 = base)."""
        param_nds, prefill_jit = self._ensure_gen()[:2]
        tokens = _as_i32(tokens)
        if tokens.ndim != 2:
            raise ValueError(f"prefill tokens must be (batch, seq), got "
                             f"shape {tokens.shape}")
        s_max = cache["k"][0].shape[2]
        if tokens.shape[1] > s_max:
            raise ValueError(
                f"prompt bucket {tokens.shape[1]} exceeds cache "
                f"max_length {s_max}")
        valid_length = _as_i32(valid_length)
        if slots is None:
            slots = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        else:
            slots = _as_i32(slots)
        return prefill_jit(next_key(),
                           self._param_call_datas(param_nds),
                           self._quant_arg(), self._lora_arg(),
                           self._lora_idx(adapters, tokens.shape[0]),
                           tokens, valid_length, slots, cache)

    def decode_step(self, tokens, cache, adapters=None):
        """One greedy-decoding step for EVERY cache slot: insert the
        K/V of ``tokens`` (B,) int32 at each row's ``len``, attend over
        the valid prefix, bump ``len``. Returns ``(logits, cache)`` —
        raw ``(B, vocab)`` next-token logits and the updated cache
        (input cache donated). Rows whose slot is free/unprefilled
        produce garbage logits that callers simply ignore — the POINT
        is that the program shape never changes with occupancy.
        ``adapters`` (B,) selects each row's LoRA bank slot — per-slot
        runtime data gathered inside the one fixed-shape program."""
        param_nds, _, decode_jit = self._ensure_gen()[:3]
        tokens = _as_i32(tokens)
        return decode_jit(next_key(),
                          self._param_call_datas(param_nds),
                          self._quant_arg(), self._lora_arg(),
                          self._lora_idx(adapters, tokens.shape[0]),
                          tokens, cache)

    def verify_step(self, tokens, cache, adapters=None):
        """Speculative VERIFY over every cache slot: insert the K/V of
        ``tokens`` (B, R) int32 — per row ``[last, d_1 .. d_{R-1}]``,
        the committed tail token plus the draft's R-1 proposals — at
        positions ``[len, len + R)`` and return the raw logits at all
        R positions ``(B, R, V)`` plus the updated cache (donated).
        ``len`` is unchanged: commit the accepted prefix with
        :meth:`advance_len`, which also rolls the rejected tail back
        (a rejected token lives above the ``len`` waterline, is never
        attended, and the next verify overwrites it). Rows must
        satisfy ``len + R <=`` cache capacity — the serving engine
        reserves a ``spec_k`` scratch margin for exactly this."""
        gen = self._ensure_gen()
        param_nds, verify_jit = gen[0], gen[3]
        tokens = _as_i32(tokens)
        if tokens.ndim != 2:
            raise ValueError(f"verify tokens must be (batch, R), got "
                             f"shape {tokens.shape}")
        return verify_jit(next_key(),
                          self._param_call_datas(param_nds),
                          self._quant_arg(), self._lora_arg(),
                          self._lora_idx(adapters, tokens.shape[0]),
                          tokens, cache)

    def advance_len(self, delta, cache):
        """Advance each row's valid length by ``delta`` (B,) int32 —
        the speculative COMMIT/ROLLBACK primitive (0 leaves a row
        put; the draft model's cache is rolled back to the accept
        point with a negative delta). Cache donated."""
        gen = self._ensure_gen()
        param_nds, advance_jit = gen[0], gen[4]
        return advance_jit(next_key(),
                           self._param_call_datas(param_nds),
                           self._quant_arg(), self._lora_arg(),
                           self._lora_idx(None, 1),  # no compute
                           _as_i32(delta), cache)

    # -- fused speculative fast path ------------------------------------
    def _ensure_spec(self, kind, k, sampled):
        """Jitted SPECULATIVE fast-path closures, cached per ``(kind,
        k, sampled)``: the whole draft/verify half-iteration runs as
        ONE program each, because at serving model sizes the per-call
        dispatch overhead of k separate draft steps plus separate
        sample/accept/advance calls costs more than the math itself.

        - ``propose``: k chained decode steps of THIS (draft) model,
          each feeding its sampled/greedy token to the next, inside
          one trace.
        - ``verify_commit`` / ``verify_commit_paged``: build the
          ``[last, d_1 .. d_k]`` rows, run the k-token verify, apply
          the accept rule (greedy or the residual-distribution rule —
          ops/sampling.py), and advance ``len`` by each active row's
          commit count, all in one program. Rows the engine will
          evict (budget/eos/capacity clip) keep the full-commit
          ``len`` — they are dead rows whose counter nobody reads.
        - ``decode_multi`` / ``decode_multi_paged``: k PLAIN decode
          iterations fused into one ``lax.scan`` with per-row
          eos/budget stop handling IN-PROGRAM — the multi-tick decode
          path (:meth:`decode_multi`). Cached here so every existing
          invalidation site (``_clear_cached_op``, quantize refresh,
          ``arm_lora``, attention-path flips) covers it for free.
        """
        if self._spec_jits is None:
            self._spec_jits = {}
        key_ = (kind, int(k), bool(sampled))
        hit = self._spec_jits.get(key_)
        if hit is not None:
            return hit
        param_nds = self._gen_params()
        blocks = self._blocks()
        _bind = self._make_bind(
            param_nds, blocks,
            force_jnp=getattr(self, '_force_jnp_attention', False))
        k = int(k)
        # a sampled variant is another program, and its name says so
        tail = "_sampled" if sampled else ""

        if kind == "propose":
            if sampled:
                def raw(tokens, keys, temps, tks, tps, cache):
                    cur = tokens
                    dts, qs = [], []
                    for _ in range(k):
                        logits, cache = self._decode_body(
                            blocks, cur, cache)
                        with _scope("sample"):
                            cur, q, keys = _smp.sample_with_probs(
                                keys, logits, temps, tks, tps)
                        dts.append(cur)
                        qs.append(q)
                    return (jnp.stack(dts, axis=1),
                            jnp.stack(qs, axis=1), keys, cache)
                jitted = jax.jit(_bind(raw, "gpt_dense_propose" + tail),
                                 donate_argnums=(10,))
            else:
                def raw(tokens, cache):
                    cur = tokens
                    dts = []
                    for _ in range(k):
                        logits, cache = self._decode_body(
                            blocks, cur, cache)
                        with _scope("sample"):
                            cur = jnp.argmax(logits, axis=-1) \
                                .astype(jnp.int32)
                        dts.append(cur)
                    return jnp.stack(dts, axis=1), cache
                jitted = jax.jit(_bind(raw, "gpt_dense_propose"),
                                 donate_argnums=(6,))
        elif kind in ("verify_commit", "verify_commit_paged"):
            paged = kind == "verify_commit_paged"
            name = ("gpt_paged" if paged else "gpt_dense") \
                + "_verify_commit" + tail

            def _verify(vt, active, cache):
                if paged:
                    return self._verify_body_paged(blocks, vt, active,
                                                   cache)
                return self._verify_body(blocks, vt, cache)

            if sampled:
                def raw(last, d_toks, q, keys, temps, tks, tps,
                        active, cache):
                    vt = jnp.concatenate([last[:, None], d_toks],
                                         axis=1)
                    logits, cache = _verify(vt, active, cache)
                    with _scope("sample"):
                        commit, n_commit, keys = \
                            _smp.speculative_accept(
                                keys, logits, d_toks, q, temps, tks, tps)
                    new = dict(cache)
                    new["len"] = cache["len"] \
                        + n_commit * (active > 0)
                    return commit, n_commit, keys, new
                jitted = jax.jit(_bind(raw, name), donate_argnums=(13,))
            else:
                def raw(last, d_toks, active, cache):
                    vt = jnp.concatenate([last[:, None], d_toks],
                                         axis=1)
                    logits, cache = _verify(vt, active, cache)
                    with _scope("sample"):
                        commit, n_commit = _smp.greedy_accept(logits,
                                                              d_toks)
                    new = dict(cache)
                    new["len"] = cache["len"] \
                        + n_commit * (active > 0)
                    return commit, n_commit, new
                jitted = jax.jit(_bind(raw, name), donate_argnums=(8,))
        elif kind in ("decode_multi", "decode_multi_paged"):
            paged = kind == "decode_multi_paged"
            name = ("gpt_paged" if paged else "gpt_dense") \
                + "_decode_multi"

            def raw(tokens, keys, temps, tks, tps, eos_ids, budgets,
                    cache):
                """k fused decode iterations under ``lax.scan``. A
                row goes dead in-trace when it emits its eos or
                exhausts its budget; dead rows keep scanning (fixed
                shape) but their ``len`` is frozen, their cache write
                lands at/above the frozen waterline (dense) or in
                scrap page 0 (paged) where nothing ever attends it,
                and their emissions are masked out of ``emitted``.
                Mixed greedy/stochastic batches are runtime DATA
                (temp <= 0 rows argmax raw logits, bit-equal to the
                host-side greedy pick), so they compile nothing."""
                def step(carry, _):
                    cur, live, budget, ks_, cache = carry
                    if paged:
                        logits, cache = self._decode_body_paged(
                            blocks, cur, live.astype(jnp.int32),
                            cache)
                    else:
                        logits, cache = self._decode_body(
                            blocks, cur, cache, live=live)
                    # the sampler's sort-based top-k/top-p warp is
                    # ~50x an argmax on small batches; an all-greedy
                    # batch (the common case) must not pay it every
                    # scanned step. Runtime cond, not a trace fork:
                    # mixed batches still compile ONE program. Key
                    # semantics match the k=1 engine exactly — keys
                    # advance per step iff ANY batch row samples
                    # (greedy rows' keys are never consumed).
                    with _scope("sample"):
                        tok, ks_ = lax.cond(
                            jnp.any(temps > 0.0),
                            lambda ks: _smp.sample_tokens(
                                ks, logits, temps, tks, tps),
                            lambda ks: (jnp.argmax(logits, axis=-1)
                                        .astype(jnp.int32), ks),
                            ks_)
                    # a dead row re-feeds its last token: its logits
                    # are garbage and its pick must not leak out
                    tok = jnp.where(live, tok, cur)
                    budget = budget - live.astype(jnp.int32)
                    live_n = live & (tok != eos_ids) & (budget > 0)
                    return (tok, live_n, budget, ks_, cache), \
                        (tok, live)
                live0 = budgets > 0
                carry = (tokens, live0, budgets, keys, cache)
                (_, _, _, keys, cache), (toks, emits) = lax.scan(
                    step, carry, None, length=k)
                # scan stacks along axis 0 (k, B) — callers commit
                # per-slot (B, k) blocks
                return (jnp.transpose(toks), jnp.transpose(emits),
                        keys, cache)
            jitted = jax.jit(_bind(raw, name), donate_argnums=(12,))
        else:
            raise ValueError(f"unknown speculative closure {kind!r}")
        entry = (param_nds, jitted)
        self._spec_jits[key_] = entry
        return entry

    def _spec_call(self, kind, k, sampled, adapters, batch, *args):
        param_nds, jitted = self._ensure_spec(kind, k, sampled)
        return jitted(next_key(), self._param_call_datas(param_nds),
                      self._quant_arg(), self._lora_arg(),
                      self._lora_idx(adapters, batch), *args)

    def propose_tokens(self, tokens, cache, k, keys=None, temps=None,
                       top_ks=None, top_ps=None):
        """DRAFT side of one speculative iteration: k chained decode
        steps in ONE jitted program, each feeding its token to the
        next. Greedy (no ``keys``): returns ``(draft_tokens (B, k)
        int32, cache)``. Sampled (explicit per-row ``keys`` + knob
        vectors): returns ``(draft_tokens, warped_probs (B, k, V),
        advanced keys, cache)`` — exactly what the accept rule needs.
        ``len`` advances by k on every row; the engine rolls back to
        the accept point with :meth:`advance_len`. Cache donated."""
        tokens = _as_i32(tokens)
        b = tokens.shape[0]
        if keys is None:
            return self._spec_call("propose", k, False, None, b,
                                   tokens, cache)
        return self._spec_call(
            "propose", k, True, None, b, tokens,
            jnp.asarray(keys, jnp.uint32),
            jnp.asarray(temps, jnp.float32),
            jnp.asarray(top_ks, jnp.int32),
            jnp.asarray(top_ps, jnp.float32), cache)

    def verify_commit(self, last, d_toks, active, cache, q=None,
                      keys=None, temps=None, top_ks=None,
                      top_ps=None, adapters=None):
        """TARGET side of one speculative iteration, fused: verify all
        ``k + 1`` positions (``verify_step``'s program), apply the
        accept rule, and advance every active row's ``len`` by its
        commit count — one dispatch. Greedy (no ``q``/``keys``):
        returns ``(commit (B, k+1), n_commit (B,), cache)``; sampled:
        ``(commit, n_commit, advanced keys, cache)``. Cache donated;
        rows the engine evicts mid-commit keep the full-commit
        ``len`` (dead rows). ``adapters`` (B,) selects each row's
        LoRA bank slot — the verify runs ADAPTED (the draft proposed
        with the base model; the accept rule makes the committed
        stream the adapted model's own)."""
        last = _as_i32(last)
        k = int(d_toks.shape[1])
        b = last.shape[0]
        if q is None:
            return self._spec_call("verify_commit", k, False, adapters,
                                   b, last, _as_i32(d_toks),
                                   _as_i32(active), cache)
        return self._spec_call(
            "verify_commit", k, True, adapters, b, last,
            _as_i32(d_toks), q, jnp.asarray(keys, jnp.uint32),
            jnp.asarray(temps, jnp.float32),
            jnp.asarray(top_ks, jnp.int32),
            jnp.asarray(top_ps, jnp.float32), _as_i32(active), cache)

    def verify_commit_paged(self, last, d_toks, active, cache, q=None,
                            keys=None, temps=None, top_ks=None,
                            top_ps=None, adapters=None):
        """Paged-cache :meth:`verify_commit` (the verify runs
        ``verify_step_paged``'s program; accept/advance identical)."""
        last = _as_i32(last)
        k = int(d_toks.shape[1])
        b = last.shape[0]
        if q is None:
            return self._spec_call("verify_commit_paged", k, False,
                                   adapters, b, last, _as_i32(d_toks),
                                   _as_i32(active), cache)
        return self._spec_call(
            "verify_commit_paged", k, True, adapters, b, last,
            _as_i32(d_toks), q, jnp.asarray(keys, jnp.uint32),
            jnp.asarray(temps, jnp.float32),
            jnp.asarray(top_ks, jnp.int32),
            jnp.asarray(top_ps, jnp.float32), _as_i32(active), cache)

    # -- fused multi-tick decode ----------------------------------------
    def decode_multi(self, tokens, budgets, cache, k, keys, temps,
                     top_ks, top_ps, eos_ids, adapters=None):
        """``k`` PLAIN decode iterations for every cache slot fused
        into ONE jitted ``lax.scan`` program — the multi-tick decode
        path: one dispatch and one host sync amortize over up to k
        emitted tokens per row. Per-row stop handling runs IN-PROGRAM:
        a row stops (stays in the scan with ``len`` frozen, write
        masked to its inactive position, emissions masked) once it
        emits ``eos_ids[row]`` (pass -1 for no eos) or its
        ``budgets[row]`` remaining-token budget hits zero; a row whose
        budget is 0 AT ENTRY never runs (free slots). Sampling knobs
        are per-row runtime data exactly as in :meth:`propose_tokens`
        — a temp<=0 row argmaxes raw logits, bit-equal to the
        single-step host-side greedy pick, so greedy multi-tick output
        is token-identical to k=1. Returns ``(tokens (B, k) int32,
        emitted (B, k) bool, advanced keys, cache)``: row i's emitted
        tokens are the prefix ``tokens[i, :emitted[i].sum()]`` (the
        live mask is monotone — once dead, dead). Every row's key
        advances once per scan step (the k=1 engine tick's sampler
        contract), so seeded streams are bitwise-reproducible across
        tick sizes. Cache donated. ``adapters`` (B,) selects each
        row's LoRA bank slot."""
        tokens = _as_i32(tokens)
        b = tokens.shape[0]
        return self._spec_call(
            "decode_multi", k, True, adapters, b, tokens,
            jnp.asarray(keys, jnp.uint32),
            jnp.asarray(temps, jnp.float32),
            jnp.asarray(top_ks, jnp.int32),
            jnp.asarray(top_ps, jnp.float32),
            _as_i32(eos_ids), _as_i32(budgets), cache)

    def decode_multi_paged(self, tokens, budgets, cache, k, keys,
                           temps, top_ks, top_ps, eos_ids,
                           adapters=None):
        """Paged-cache :meth:`decode_multi`: identical scan and stop
        semantics, with dead rows' writes redirected into scrap page
        0 through the ``decode_step_paged`` active-mask discipline
        (``len`` frozen, table untouched). Cache donated."""
        tokens = _as_i32(tokens)
        b = tokens.shape[0]
        return self._spec_call(
            "decode_multi_paged", k, True, adapters, b, tokens,
            jnp.asarray(keys, jnp.uint32),
            jnp.asarray(temps, jnp.float32),
            jnp.asarray(top_ks, jnp.int32),
            jnp.asarray(top_ps, jnp.float32),
            _as_i32(eos_ids), _as_i32(budgets), cache)

    # -- paged-cache generation API -------------------------------------
    def init_paged_cache(self, batch_size, n_pages, page_size,
                         max_length=None, dtype=None):
        """Preallocated PAGED KV cache: a global pool of ``n_pages``
        fixed-size pages per layer plus a static-shape page table —
        ``{"k": tuple of L (n_pages, page_size, H * Dh) arrays, "v":
        same, "table": (B, P_max) int32, "len": (B,) int32}`` with
        ``P_max = max_length // page_size``. A page is ``page_size``
        rows, a row one position's heads one after another: logical
        position ``t`` of slot ``b`` lives at ``pool[table[b, t // ps],
        t % ps, h * Dh:(h + 1) * Dh]`` (the layout, and why the chip
        keeps it: ``ops/attention.py``, "the paged pool's layout").
        Page 0 is the reserved SCRAP page: free table entries point at
        it and redirected writes land in it — callers must never
        allocate it to a slot. Explicit argument/result of the paged
        generation calls (which DONATE it, except ``peek``)."""
        s = int(max_length) if max_length is not None else self._max_length
        if not 1 <= s <= self._max_length:
            raise ValueError(
                f"cache max_length {s} out of range (position table "
                f"holds {self._max_length})")
        ps = int(page_size)
        if ps < 1 or s % ps != 0:
            raise ValueError(
                f"page_size {ps} must divide cache max_length {s}")
        if int(n_pages) < 2:
            raise ValueError("n_pages must be >= 2 (page 0 is the "
                             "reserved scrap page)")
        shape = (int(n_pages), ps, self._num_heads * self._head_dim)
        dt = onp.dtype(dtype or self._dtype)
        zeros = lambda: tuple(jnp.zeros(shape, dt)  # noqa: E731
                              for _ in range(self._num_layers))
        cache = {"k": zeros(), "v": zeros(),
                 "table": jnp.zeros((int(batch_size), s // ps),
                                    jnp.int32),
                 "len": jnp.zeros((int(batch_size),), jnp.int32)}
        if dt == onp.int8:
            # per-head-per-PAGE scales: a shared prefix page carries
            # its own scale wherever its refcount travels, and COW
            # copies it with the data
            sc = lambda: tuple(  # noqa: E731
                jnp.zeros((int(n_pages), self._num_heads), jnp.float32)
                for _ in range(self._num_layers))
            cache["k_scale"] = sc()
            cache["v_scale"] = sc()
        return cache

    def _ensure_paged(self):
        if self._paged is not None:
            return self._paged
        param_nds = self._gen_params()
        blocks = self._blocks()
        _bind = self._make_bind(
            param_nds, blocks,
            force_jnp=getattr(self, '_force_jnp_attention', False))

        def fresh_raw(tokens, n_valid, slot, pages, cache):
            """Whole-prompt prefill of one slot at bucket width W: the
            computation is EXACTLY the dense prefill's (same causal
            flash over the prompt block — bitwise-equal K/V and
            logits); only the cache write is page-shaped (and, for an
            int8 pool, quantized per page with per-head amax
            scales)."""
            _b, w = tokens.shape
            ps = _att.pool_page_size(cache["k"][0])
            x = self._embed(NDArray(tokens))
            ks, vs = [], []
            for blk in blocks:
                x, (k, v) = blk.prefill(x)
                ks.append(k)
                vs.append(v)
            idx = jnp.clip(n_valid - 1, 0, w - 1)
            last = x._data[0, idx][None, None, :]
            logits = self._head(NDArray(last))
            with _scope("kv_write"):
                quant_kv = cache["k"][0].dtype == jnp.int8
                page_ids = pages[:w // ps]          # start == 0: static
                kps, vps, kscs, vscs = zip(*(
                    _write_chunk(
                        cache["k"][li], cache["v"][li],
                        cache["k_scale"][li] if quant_kv else None,
                        cache["v_scale"][li] if quant_kv else None,
                        page_ids, ks[li], vs[li])
                    for li in range(len(blocks))))
                new_cache = {
                    "k": kps, "v": vps,
                    "table": cache["table"].at[slot].set(pages),
                    "len": cache["len"].at[slot].set(n_valid),
                }
                if quant_kv:
                    new_cache["k_scale"] = kscs
                    new_cache["v_scale"] = vscs
            return logits._data[:, 0, :].astype(jnp.float32), new_cache

        def chunk_raw(tokens, start, n_valid, slot, pages, cache):
            """One fixed-width prefill chunk of one slot, appended at
            global position ``start`` (a multiple of page_size;
            traced, so every chunk runs this one program)."""
            _b, c = tokens.shape
            ps = _att.pool_page_size(cache["k"][0])
            positions = start + jnp.arange(c, dtype=jnp.int32)
            pw = self.position_weight.data()._data
            x = NDArray(self.word_embed(NDArray(tokens))._data
                        + jnp.take(pw, positions, axis=0))
            if self.embed_drop is not None:
                x = self.embed_drop(x)
            page_ids = lax.dynamic_slice(pages, (start // ps,),
                                         (c // ps,))
            quant_kv = cache["k"][0].dtype == jnp.int8
            ks, vs, kscs, vscs = [], [], [], []
            for li, blk in enumerate(blocks):
                x, kp, vp, ksp, vsp = blk.prefill_chunk(
                    x, cache["k"][li], cache["v"][li], pages, page_ids,
                    start,
                    k_scale=cache["k_scale"][li] if quant_kv else None,
                    v_scale=cache["v_scale"][li] if quant_kv else None)
                ks.append(kp)
                vs.append(vp)
                kscs.append(ksp)
                vscs.append(vsp)
            idx = jnp.clip(n_valid - 1, 0, c - 1)
            last = x._data[0, idx][None, None, :]
            logits = self._head(NDArray(last))
            new_cache = {
                "k": tuple(ks), "v": tuple(vs),
                "table": cache["table"].at[slot].set(pages),
                "len": cache["len"].at[slot].set(start + n_valid),
            }
            if quant_kv:
                new_cache["k_scale"] = tuple(kscs)
                new_cache["v_scale"] = tuple(vscs)
            return logits._data[:, 0, :].astype(jnp.float32), new_cache

        def decode_raw(tokens, active, cache):
            return self._decode_body_paged(blocks, tokens, active,
                                           cache)

        def spec_verify_raw(tokens, active, cache):
            """Speculative verify against the paged pool: write each
            ACTIVE row's R tokens at positions ``[len, len + R)``
            through its page table (inactive rows' — and any position
            past a slot's reservation, whose table entry already
            points at scrap — writes land in scrap page 0) and return
            logits at all R positions. ``len`` unchanged; the engine
            commits via ``advance_raw``."""
            return self._verify_body_paged(blocks, tokens, active,
                                           cache)

        def advance_raw(delta, cache):
            new = dict(cache)
            new["len"] = cache["len"] + delta
            return new

        def peek_raw(token, slot, cache):
            """Logits of the last CACHED token of ``slot`` (position
            len-1, K/V already in the pool) — zero prefill compute, no
            cache write. The 100%-prefix-hit admission path."""
            quant_kv = cache["k"][0].dtype == jnp.int8
            ln = cache["len"][slot]
            pos = ln - 1
            pw = self.position_weight.data()._data
            x = NDArray((self.word_embed(NDArray(token[None]))._data
                         + jnp.take(pw, pos[None], axis=0))[:, None, :])
            if self.embed_drop is not None:
                x = self.embed_drop(x)
            table1 = cache["table"][slot][None]
            for li, blk in enumerate(blocks):
                x = blk.peek_paged(
                    x, cache["k"][li], cache["v"][li], table1, ln[None],
                    k_scale=cache["k_scale"][li] if quant_kv else None,
                    v_scale=cache["v_scale"][li] if quant_kv else None)
            logits = self._head(x)
            return logits._data[0, 0, :].astype(jnp.float32)

        def bind_raw(slot, pages, length, cache):
            new = dict(cache)   # int8 scale pools ride along untouched
            new["table"] = cache["table"].at[slot].set(pages)
            new["len"] = cache["len"].at[slot].set(length)
            return new

        def copy_raw(src, dst, cache):
            new = dict(cache)
            new["k"] = tuple(p.at[dst].set(p[src]) for p in cache["k"])
            new["v"] = tuple(p.at[dst].set(p[src]) for p in cache["v"])
            if "k_scale" in cache:   # a COW'd page keeps its scale
                new["k_scale"] = tuple(p.at[dst].set(p[src])
                                       for p in cache["k_scale"])
                new["v_scale"] = tuple(p.at[dst].set(p[src])
                                       for p in cache["v_scale"])
            return new

        # wrapper args: (key, params, quant, lora_tabs, lora_idx,
        # *fn_args) — fn args start at 5, hence the donated cache
        # positions below
        self._paged = {"params": param_nds}
        for role, fn, donate in (
                ("fresh", fresh_raw, (9,)), ("chunk", chunk_raw, (10,)),
                ("decode", decode_raw, (7,)), ("peek", peek_raw, ()),
                ("bind", bind_raw, (8,)), ("copy", copy_raw, (7,)),
                ("verify", spec_verify_raw, (7,)),
                ("advance", advance_raw, (6,))):
            self._paged[role] = jax.jit(_bind(fn, "gpt_paged_" + role),
                                        donate_argnums=donate)
        return self._paged

    def _paged_call(self, name, adapters, batch, *args):
        p = self._ensure_paged()
        return p[name](next_key(),
                       self._param_call_datas(p["params"]),
                       self._quant_arg(), self._lora_arg(),
                       self._lora_idx(adapters, batch), *args)

    def prefill_paged(self, tokens, n_valid, slot, pages, cache, *,
                      start=0, fresh=False, adapters=None):
        """Prefill one chunk (or, with ``fresh=True``, one whole short
        prompt) of ``slot`` into pool pages. ``tokens`` is (1, W) int32
        with W a multiple of the page size; ``pages`` is the slot's
        FULL (P_max,) physical-page row (entries past the slot's
        reservation must point at scrap page 0); ``start`` is the
        chunk's global offset (multiple of the page size; 0 when
        ``fresh``); ``n_valid`` counts real tokens in this chunk.
        Returns ``(last_valid_logits (1, V), cache)`` — cache donated.

        ``fresh=True`` runs the dense prefill computation (causal flash
        over the prompt block only) and is bitwise-identical to dense
        ``prefill`` — use it for unshared prompts that fit one chunk;
        the general path attends the gathered page view (shared prefix
        + earlier chunks) under the global causal mask."""
        tokens = _as_i32(tokens)
        if tokens.ndim != 2 or tokens.shape[0] != 1:
            raise ValueError(f"paged prefill tokens must be (1, W), "
                             f"got shape {tokens.shape}")
        ps = _att.pool_page_size(cache["k"][0])
        s_max = cache["table"].shape[1] * ps
        w = tokens.shape[1]
        if w % ps or w > s_max:
            raise ValueError(
                f"chunk width {w} must be a multiple of page_size "
                f"{ps} and fit cache capacity {s_max}")
        if int(start) % ps:
            raise ValueError(f"chunk start {start} must be a multiple "
                             f"of page_size {ps}")
        if fresh and int(start) != 0:
            raise ValueError("fresh prefill starts at 0 by definition")
        pages = _as_i32(pages)
        if fresh:
            return self._paged_call(
                "fresh", adapters, 1, tokens, jnp.int32(n_valid),
                jnp.int32(slot), pages, cache)
        return self._paged_call(
            "chunk", adapters, 1, tokens, jnp.int32(start),
            jnp.int32(n_valid), jnp.int32(slot), pages, cache)

    def decode_step_paged(self, tokens, active, cache, adapters=None):
        """One decode step for every slot of a PAGED cache: write each
        active row's K/V into its current page at ``len % page_size``,
        attend its valid pages, bump its ``len``. ``active`` (B,) masks
        rows: inactive rows run the same fixed-shape program but their
        writes are redirected to the scrap page and their ``len`` is
        not bumped (a freed slot's table row may alias pages owned by
        someone else — garbage logits are ignorable, stray writes are
        not). Returns ``(logits, cache)`` — cache donated.
        ``adapters`` (B,) selects each row's LoRA bank slot."""
        tokens = _as_i32(tokens)
        return self._paged_call("decode", adapters, tokens.shape[0],
                                tokens, _as_i32(active), cache)

    def peek_logits_paged(self, token, slot, cache, adapters=None):
        """Next-token logits for a slot whose ENTIRE prompt is already
        cached (prefix reuse): recompute the last prompt token's query
        at position ``len - 1`` and attend the cached pages — no
        prefill, no write. Cache is NOT donated (unchanged). Returns
        raw (vocab,) logits."""
        return self._paged_call("peek", adapters, 1,
                                jnp.asarray(token, jnp.int32),
                                jnp.int32(slot), cache)

    def bind_slot_paged(self, slot, pages, length, cache):
        """Install a slot's page-table row and valid length (the
        exact-prefix-hit admission: point the table at shared pages;
        no compute). Cache donated."""
        return self._paged_call("bind", None, 1, jnp.int32(slot),
                                _as_i32(pages), jnp.int32(length),
                                cache)

    def copy_page_paged(self, src, dst, cache):
        """Copy physical page ``src`` to ``dst`` across every layer's
        K and V pools — the copy half of copy-on-write at a shared
        divergence page. Cache donated."""
        return self._paged_call("copy", None, 1, jnp.int32(src),
                                jnp.int32(dst), cache)

    def verify_step_paged(self, tokens, active, cache, adapters=None):
        """Speculative VERIFY for every slot of a PAGED cache: write
        each active row's ``tokens`` (B, R) int32 — ``[last, d_1 ..
        d_{R-1}]`` — at positions ``[len, len + R)`` through its page
        table and return the raw logits at all R positions
        ``(B, R, V)`` plus the updated cache (donated). Inactive rows
        (``active == 0``) and positions past a slot's page reservation
        write into the reserved scrap page; ``len`` is unchanged —
        commit the accepted prefix with :meth:`advance_len_paged`."""
        tokens = _as_i32(tokens)
        if tokens.ndim != 2:
            raise ValueError(f"verify tokens must be (batch, R), got "
                             f"shape {tokens.shape}")
        return self._paged_call("verify", adapters, tokens.shape[0],
                                tokens, _as_i32(active), cache)

    def advance_len_paged(self, delta, cache):
        """Advance each paged row's valid length by ``delta`` (B,)
        int32 — the paged commit/rollback counterpart of
        :meth:`advance_len`. Cache donated."""
        return self._paged_call("advance", None, 1, _as_i32(delta),
                                cache)


def gpt_small(vocab_size=1000, units=64, num_layers=2, num_heads=4,
              max_length=128, dropout=0.0, dtype="float32", **kwargs):
    """Tiny configuration for tests/bench (the bert_small analog)."""
    return GPTModel(vocab_size=vocab_size, units=units,
                    num_layers=num_layers, num_heads=num_heads,
                    max_length=max_length, dropout=dropout, dtype=dtype,
                    **kwargs)
