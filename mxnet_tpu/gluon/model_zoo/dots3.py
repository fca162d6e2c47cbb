"""The ``dots3_note`` decoder family: latent attention with a learned
sparse indexer, latent window layers, sigmoid-routed experts.

A second decoder family beside ``gpt.py``, built for SERVING through
``serving.GenerationEngine``'s paged path (docs/SERVING.md "The
model-engine contract"). Layers are pre-norm residual blocks,
``h = x + A(N(x))``, ``y = h + F(N(h))``, ``N`` an RMSNorm:

* **full-attention layers**: multi-head latent attention (a low-rank
  query latent, one cached key/value latent ``c_kv`` and one rotary key
  ``k_r`` per position, shared by all heads), with a *lightning indexer*
  that scores every cached position and keeps the ``index_topk`` best
  for each query. Cached per position: the latent row (``kv_lora_rank +
  qk_rope_head_dim`` wide) and the indexer key (``index_head_dim``), in
  two paged pools under the engine's one page table.
* **window layers**: the same attention at a second geometry (the
  ``swa_*`` sizes) over the last ``sliding_window_size`` positions, the
  token's own among them, with no indexer. Cached: a ring of
  ``ring_size`` latent rows a slot, written at ``t mod ring_size``, never
  paged and never shared (so this family takes no prefix reuse).
* **feed-forward**: SwiGLU, dense in the first ``first_k_dense_replace``
  layers, then routed: sigmoid scores over all ``n_routed_experts`` in
  float32, the top ``num_experts_per_tok`` by score plus a bias, gates
  normalised over the chosen, plus shared experts. The model is told
  which routed experts it HOLDS (``experts_held``): it routes over all
  of them and computes its own experts' part (``ops/moe.py``), which is
  what one rank of an expert-parallel deployment computes.

The indexer's whole branch (the query latent it reads, its queries, keys,
head weights and scores, and the pool of its keys) is float32 at full
precision whatever the model's dtype: its scores decide a discrete
choice, and a choice made from rounded scores differs from the float32
one at some fifty of 8192 positions a query, which moves the first
layer's output (all attention: the embedding is small) by a tenth. The
router's scores are float32 for the same reason.

Decode runs the absorbed form (``q_n W_uk`` against ``c_kv``, the output
through ``W_uv``) over the gathered selected positions only; prefill
runs the plain form over the slot's whole view under a per-row
selection mask. Two flags of the published config carry no formula and
are read by public precedent: ``apply_mla_qkv_lora_rescale`` scales the
normed query and key/value latents by ``sqrt(hidden / rank)``
(LongCat-Flash), and ``attention_gate_type="headwise"`` multiplies each
head's output by ``sigmoid`` of one value a head computed from the
layer's normed input (Qiu et al. 2025).

Parameters are leaves of the model's ``dtype`` (bfloat16 unless told
otherwise) with ``grad_req="null"`` (float32 whatever the dtype: the
router's weight and bias, as published implementations keep them, and a
full-attention layer's four matrices of the indexer's branch, 0.4 % of
the parameters): no gradient buffer and
no cast shadow, so the bytes held are two a parameter. The caches and the
programs' activations are of the same dtype, and the engine is refused
any other ``compute_dtype``. Every generation program takes the
parameters as runtime arguments.
"""
from __future__ import annotations

import collections
import math

import jax
import jax.numpy as jnp
import numpy as onp
from jax import lax

from ... import telemetry, tracing
from ...ndarray.ndarray import NDArray
from ...ops import latent_attention as _la
from ...ops import moe as _moe
from ...ops.latent_attention import dot32 as _dot32
from ...ops.latent_attention import pad128 as _pad128
from ...ops.latent_attention import rms as _rms
from ...ops.latent_attention import rms32 as _rms32
from ...ops.latent_attention import rope as _rope
from ...ops.latent_attention import swiglu as _swiglu
from ..block import HybridBlock
from ..parameter import Parameter

__all__ = ["Dots3Model"]

_scope = jax.named_scope
_F32 = jnp.float32

#: counters that count a trace or a compile of a generation program
TRACE_COUNTER = "model.dots3.trace"


def _layer_norm32(x, g, b, eps=1e-6):
    x32 = x.astype(_F32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), -1, keepdims=True)
    y = (x32 - mu) * lax.rsqrt(var + eps)
    return y * g.astype(_F32) + b.astype(_F32)


def _gate_out(p, u, o, g):
    """Head-wise sigmoid gate from the layer's normed input, then the
    output projection. ``o`` (T, H, dv)."""
    gate = jax.nn.sigmoid(jnp.dot(u, p["w_g"], preferred_element_type=_F32))
    o = (o.astype(_F32) * gate[..., None]).astype(u.dtype)
    return jnp.dot(o.reshape(o.shape[0], g.h * g.dv), p["w_o"],
                   preferred_element_type=_F32)


def _index_queries(p, c_q32, u32, pos, idx, rope_dims, inv_freq):
    """The indexer's per-head queries (T, H_I, d_I), rotated on their
    first ``rope_dims``, and head weights (T, H_I) with both ``H_I^-1/2``
    and ``d_I^-1/2`` folded in. Float32 from float32 inputs: the
    indexer's whole branch is, so that the same input gives the same
    selection whatever the dtype of the rest."""
    hi, di = idx
    q = _dot32(c_q32, p["wi_q"]).reshape(-1, hi, di)
    q = _rope(q, pos, inv_freq, dims=rope_dims)
    return q, _dot32(u32, p["wi_w"]) * (hi ** -0.5) * (di ** -0.5)


def _index_keys(p, u32, pos, rope_dims, inv_freq):
    """The indexer's key of each position, float32 (cached as such)."""
    k = _layer_norm32(_dot32(u32, p["wi_k"]), p["wi_k_g"], p["wi_k_b"])
    return _rope(k, pos, inv_freq, dims=rope_dims)


def _index_scores(q, w, keys, head_block=8):
    """``I(t, s) = sum_j w_j(t) relu(q_j(t) . k(s))`` in float32,
    ``head_block`` index heads at a time. ``q`` (..., T, H_I, d_I),
    ``w`` (..., T, H_I), ``keys`` (..., S, d_I) -> (..., T, S)."""
    hi = q.shape[-2]
    head_block = math.gcd(hi, head_block)
    nb = hi // head_block
    qb = jnp.moveaxis(q.reshape(q.shape[:-2] + (nb, head_block,
                                                q.shape[-1])), -3, 0)
    wb = jnp.moveaxis(w.reshape(w.shape[:-1] + (nb, head_block)), -2, 0)

    def step(acc, args):
        qj, wj = args
        s = jnp.einsum("...tjd,...sd->...tjs", qj, keys,
                       precision=lax.Precision.HIGHEST,
                       preferred_element_type=_F32)
        # elementwise, not a product: a contraction would round its
        # float32 operands to the accelerator's default precision
        return acc + jnp.sum(jax.nn.relu(s) * wj[..., None], axis=-2), None

    acc0 = jnp.zeros(q.shape[:-2] + (keys.shape[-2],), _F32)
    return lax.scan(step, acc0, (qb, wb))[0]


def _select(scores, allowed, top_k):
    """The ``top_k`` allowed positions of largest score in each row, as a
    mask: every allowed position where a row has no more than ``top_k``.
    Equal scores are taken lowest position first, as ``lax.top_k`` takes
    them in the decode program (an index score is exactly 0 wherever
    every head's product is negative)."""
    s = jnp.where(allowed, scores, -jnp.inf)
    if s.shape[-1] <= top_k:
        return allowed
    kth = lax.top_k(s, top_k)[0][..., -1:]
    above = s > kth
    tied = allowed & (s == kth)
    room = top_k - jnp.sum(above, -1, keepdims=True)
    return above | (tied & (jnp.cumsum(tied, -1) <= room))


def _ring_positions(last, ring):
    """The position each ring entry holds once ``last`` is written: the
    largest ``p <= last`` with ``p mod ring == j`` (negative: none yet)."""
    j = jnp.arange(ring, dtype=jnp.int32)
    return last[..., None] - jnp.mod(last[..., None] - j, ring)


class Dots3Model(HybridBlock):
    """A ``dots3_note`` language model, from its ``config.json`` keys.

    ``vocab_size`` is the vocabulary HELD here (a slice of the published
    one serves ids, logits and sampling over the slice), ``layer_types``
    the kind of each layer (its length is the depth), ``n_routed_experts``
    the width the router scores over, and ``experts_held`` the contiguous
    range of routed experts whose weights live here (default: all).
    """

    @property
    def generation_support(self):
        """What ``serving.GenerationEngine`` may be asked for with this
        family; it refuses every other option by name."""
        return {
            "dense_cache": False, "paged": True, "prefix_cache": False,
            "quantize": False, "kv_dtype": False, "speculative": False,
            "decode_ticks": False, "mesh_layout": False, "lora": False,
            "cache_dtype": (self._dtype,), "compute_dtype": (self._dtype,),
            "prefill_chunk_max": self._chunk_max,
        }

    def __init__(self, vocab_size, hidden_size, layer_types,
                 num_attention_heads, q_lora_rank, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                 index_n_heads, index_head_dim, index_topk,
                 sliding_window_size, swa_num_attention_heads,
                 swa_q_lora_rank, swa_kv_lora_rank, swa_qk_nope_head_dim,
                 swa_qk_rope_head_dim, swa_v_head_dim, intermediate_size,
                 moe_intermediate_size, n_routed_experts,
                 num_experts_per_tok, n_shared_experts=1,
                 first_k_dense_replace=1, rope_theta=10000.0,
                 swa_rope_theta=10000.0, rms_norm_eps=1e-5,
                 experts_held=None, max_length=8192, prefill_chunk=512,
                 dtype="bfloat16", **kwargs):
        super().__init__(**kwargs)
        self._dtype = str(jnp.dtype(dtype))
        self._vocab_size = int(vocab_size)
        self._d = int(hidden_size)
        self._kinds = tuple(str(t) for t in layer_types)
        bad = set(self._kinds) - {"full_attention", "sliding_attention"}
        if bad:
            raise ValueError(f"unknown layer_types {sorted(bad)}")
        # ``apply_mla_qkv_lora_rescale``: the two normed latents times
        # sqrt(hidden / rank)
        self._full = _la.Geom(
            num_attention_heads, qk_nope_head_dim, qk_rope_head_dim,
            v_head_dim, q_lora_rank, kv_lora_rank, rope_theta,
            a_q=math.sqrt(self._d / q_lora_rank),
            a_kv=math.sqrt(self._d / kv_lora_rank))
        self._swa = _la.Geom(
            swa_num_attention_heads, swa_qk_nope_head_dim,
            swa_qk_rope_head_dim, swa_v_head_dim, swa_q_lora_rank,
            swa_kv_lora_rank, swa_rope_theta,
            a_q=math.sqrt(self._d / swa_q_lora_rank),
            a_kv=math.sqrt(self._d / swa_kv_lora_rank))
        self._idx = (int(index_n_heads), int(index_head_dim))
        self._topk = int(index_topk)
        self._window = int(sliding_window_size)
        self._f_dense = int(intermediate_size)
        self._f_moe = int(moe_intermediate_size)
        self._e_all = int(n_routed_experts)
        self._k = int(num_experts_per_tok)
        self._n_shared = int(n_shared_experts)
        self._first_dense = int(first_k_dense_replace)
        self._eps = float(rms_norm_eps)
        held = range(self._e_all) if experts_held is None \
            else range(*experts_held) if isinstance(experts_held, tuple) \
            else experts_held
        if len(held) < 1 or held.step != 1 or held.start < 0 \
                or held.stop > self._e_all:
            raise ValueError(
                f"experts_held {held!r} must be a contiguous range of the "
                f"{self._e_all} routed experts")
        self._held = held
        self._max_length = int(max_length)
        #: widest prefill chunk the ring leaves room for: a chunk is
        #: written before it is attended, and must not overwrite a
        #: position its first query still sees
        self._chunk_max = int(prefill_chunk)
        self._ring = 1 << (self._window + self._chunk_max - 2).bit_length()
        if "full_attention" not in self._kinds:
            raise ValueError("layer_types holds no full_attention layer: "
                             "the family's first layer is one")
        self._params = {}        # layer -> {short name: Parameter}
        self._build_parameters()
        self._progs = None
        self._forward = None     # the jitted whole forward
        self._host_len = None    # host mirror of cache["len"]
        self._hits = collections.deque()   # (counter, device count) a call

    # -- parameters ------------------------------------------------------
    def _add(self, layer, short, shape, dtype=None):
        dtype = dtype or self._dtype
        init = "ones" if short.endswith(("norm", "_g")) and len(shape) == 1 \
            else "zeros" if short.endswith(("_b", "bias")) else None
        p = Parameter(short, grad_req="null", shape=shape, dtype=dtype,
                      init=init)
        name = short if layer is None else f"layers_{layer}_{short}"
        setattr(self, name, p)
        self._params.setdefault(layer, {})[short] = p

    def _build_parameters(self):
        d, (hi, di) = self._d, self._idx
        self._add(None, "embed", (self._vocab_size, d))
        self._add(None, "final_norm", (d,))
        self._add(None, "head", (d, self._vocab_size))
        for li, kind in enumerate(self._kinds):
            full = kind == "full_attention"
            g = self._full if full else self._swa
            # the indexer's branch is float32 (``_dot32``), and with it
            # the query latent's down-projection, which it reads
            self._add(li, "w_dq", (d, g.rq), "float32" if full else None)
            for short, shape in (
                    ("attn_norm", (d,)), ("q_norm", (g.rq,)),
                    ("w_uq", (g.rq, g.h * (g.dn + g.dr))),
                    ("w_dkv", (d, g.row)), ("kv_norm", (g.rkv,)),
                    ("w_uk", (g.rkv, g.h * g.dn)),
                    ("w_uv", (g.rkv, g.h * g.dv)),
                    ("w_g", (d, g.h)), ("w_o", (g.h * g.dv, d)),
                    ("ffn_norm", (d,))):
                self._add(li, short, shape)
            if full:
                for short, shape in (("wi_q", (g.rq, hi * di)),
                                     ("wi_k", (d, di)), ("wi_w", (d, hi))):
                    self._add(li, short, shape, "float32")
                for short, shape in (("wi_k_g", (di,)), ("wi_k_b", (di,))):
                    self._add(li, short, shape)
            if li < self._first_dense:
                f = self._f_dense
                for short, shape in (("w_gate", (d, f)), ("w_up", (d, f)),
                                     ("w_down", (f, d))):
                    self._add(li, short, shape)
                continue
            e, f = len(self._held), self._f_moe
            self._add(li, "router", (d, self._e_all), "float32")
            self._add(li, "router_bias", (self._e_all,), "float32")
            for short, shape in (("e_gate", (e, d, f)), ("e_up", (e, d, f)),
                                 ("e_down", (e, f, d))):
                self._add(li, short, shape)
            fs = f * self._n_shared
            for short, shape in (("s_gate", (d, fs)), ("s_up", (d, fs)),
                                 ("s_down", (fs, d))):
                self._add(li, short, shape)

    @property
    def max_length(self):
        return self._max_length

    @property
    def ring_size(self):
        return self._ring

    def parameter_count(self):
        return sum(int(onp.prod(p.shape))
                   for p in self.collect_params().values())

    def _datas(self):
        """The parameters' buffers as the programs take them: a list
        (one dict a layer) and the top dict."""
        def grab(ps):
            return {k: p.data()._data for k, p in ps.items()}
        return ([grab(self._params[li]) for li in range(len(self._kinds))],
                grab(self._params[None]))

    # -- one layer ---------------------------------------------------------
    def _ffn(self, li, p, h, hit):
        """The feed-forward of layer ``li``; a routed layer appends to
        ``hit`` the number of held experts its rows fall on."""
        z32 = _rms32(h, p["ffn_norm"], self._eps)
        z = z32.astype(h.dtype)
        if li < self._first_dense:
            with _scope("mlp"):
                return _swiglu(z, p["w_gate"], p["w_up"], p["w_down"])
        with _scope("moe"):
            # the router reads the float32 input: see ``_dot32``
            routed, n_hit = _moe.routed_layer(z32, z, p, self._k,
                                              self._held.start)
            hit.append(n_hit)
            return routed + _swiglu(z, p["s_gate"], p["s_up"], p["s_down"])

    def _layer_prefill(self, li, p, x, pos, keys, hit):
        """One layer over a chunk of T tokens at positions ``pos``.
        ``keys(rows, ikeys)`` takes what the chunk caches (full layers:
        its latent rows and indexer keys; window layers: its latent
        rows) and returns what it attends: ``(rows_S, ikeys_S or None,
        key_pos (S,))`` — the write into the cache happens inside it."""
        full = self._kinds[li] == "full_attention"
        g = self._full if full else self._swa
        u32 = _rms32(x, p["attn_norm"], self._eps)
        u = u32.astype(x.dtype)
        with _scope("mla" if full else "swa"):
            c_q32, q_n, q_r = _la.queries(p, u32, pos, g, self._eps, full)
            rows = _la.latent_rows(p, u, pos, g, self._eps)
        ik = None
        if full:
            with _scope("indexer"):
                ik = _index_keys(p, u32, pos, g.dr, g.inv_freq)
        with _scope("kv_write"):
            rows_s, ik_s, key_pos = keys(rows, ik)
        causal = (key_pos[None, :] <= pos[:, None]) & (key_pos[None, :] >= 0)
        if full:
            with _scope("indexer"):
                qi, wi = _index_queries(p, c_q32, u32, pos, self._idx,
                                        g.dr, g.inv_freq)
                valid = _select(_index_scores(qi, wi, ik_s), causal,
                                self._topk)
        else:
            valid = causal & (pos[:, None] - key_pos[None, :]
                              < self._window)
        with _scope("mla" if full else "swa"):
            o = _la.attend_plain(p, q_n, q_r, rows_s, valid, g,
                                 _la.head_block(g, x.shape[0],
                                                rows_s.shape[0]))
            h = (x.astype(_F32) + _gate_out(p, u, o, g)).astype(x.dtype)
        return (h.astype(_F32) + self._ffn(li, p, h, hit)).astype(x.dtype)

    def _logits(self, top, x):
        with _scope("lm_head"):
            z = _rms(x, top["final_norm"], self._eps)
            return jnp.dot(z, top["head"], preferred_element_type=_F32)

    # -- the cache -----------------------------------------------------------
    def init_paged_cache(self, batch_size, n_pages, page_size,
                         max_length=None, dtype=None):
        """Three kinds of state under one slot: ``"lat"`` and ``"idx"``,
        a paged pool of latent rows and one of indexer keys for each
        full-attention layer (``(n_pages, page_size, width)``, both under
        ``"table"`` (B, P_max) and ``"len"`` (B,); page 0 is the scrap
        page), and ``"ring"``, for each window layer the slots' rings
        ``(B, ring_size, width)``. Minor dimensions are multiples of
        128."""
        s = int(max_length) if max_length is not None else self._max_length
        ps = int(page_size)
        if ps < 1 or s % ps:
            raise ValueError(f"page_size {ps} must divide cache "
                             f"max_length {s}")
        if int(n_pages) < 2:
            raise ValueError("n_pages must be >= 2 (page 0 is the "
                             "reserved scrap page)")
        if dtype is not None and str(dtype) != self._dtype:
            raise ValueError(f"cache dtype {dtype!r}: this model caches "
                             f"its own dtype, {self._dtype}, only")
        n_full = self._kinds.count("full_attention")
        n_swa = len(self._kinds) - n_full
        b = int(batch_size)
        bf = jnp.dtype(self._dtype)
        return {
            "lat": tuple(jnp.zeros((int(n_pages), ps, self._full.row_pad),
                                   bf) for _ in range(n_full)),
            # the indexer's keys stay float32, as its whole branch does
            "idx": tuple(jnp.zeros((int(n_pages), ps,
                                    _pad128(self._idx[1])), _F32)
                         for _ in range(n_full)),
            "ring": tuple(jnp.zeros((b, self._ring, self._swa.row_pad), bf)
                          for _ in range(n_swa)),
            "table": jnp.zeros((b, s // ps), jnp.int32),
            "len": jnp.zeros((b,), jnp.int32),
        }

    def _state_index(self):
        """Layer -> its index among the layers of its kind."""
        out, nf, ns = [], 0, 0
        for kind in self._kinds:
            if kind == "full_attention":
                out.append(nf)
                nf += 1
            else:
                out.append(ns)
                ns += 1
        return out

    # -- the programs ----------------------------------------------------------
    def _prefill_body(self, layers, top, tokens, start, n_valid, slot,
                      pages, cache, fresh):
        w = tokens.shape[1]
        ps = cache["lat"][0].shape[1]
        di = self._idx[1]
        pos = start + jnp.arange(w, dtype=jnp.int32)
        x = jnp.take(top["embed"], tokens[0], axis=0)
        lat, idx, ring = list(cache["lat"]), list(cache["idx"]), \
            list(cache["ring"])
        where = self._state_index()
        hit = []
        page_ids = lax.dynamic_slice(pages, (start // ps,), (w // ps,))
        last = start + w - 1
        for li, kind in enumerate(self._kinds):
            ci = where[li]
            if kind == "full_attention":
                def keys(rows, ik, ci=ci):
                    lat[ci] = lat[ci].at[page_ids].set(
                        rows.reshape(w // ps, ps, -1))
                    ikp = jnp.pad(ik, ((0, 0), (0, idx[ci].shape[-1] - di)))
                    idx[ci] = idx[ci].at[page_ids].set(
                        ikp.reshape(w // ps, ps, -1))
                    if fresh:
                        return rows, ik, pos
                    view = lat[ci][pages].reshape(-1, rows.shape[-1])
                    iview = idx[ci][pages].reshape(
                        -1, idx[ci].shape[-1])[:, :di]
                    return view, iview, jnp.arange(view.shape[0],
                                                   dtype=jnp.int32)
            else:
                def keys(rows, ik, ci=ci):
                    r = lax.dynamic_index_in_dim(ring[ci], slot, 0, False)
                    r = r.at[jnp.mod(pos, self._ring)].set(rows)
                    ring[ci] = lax.dynamic_update_index_in_dim(
                        ring[ci], r, slot, 0)
                    if fresh:
                        return rows, None, pos
                    return r, None, _ring_positions(last, self._ring)
            x = self._layer_prefill(li, layers[li], x, pos, keys, hit)
        row = x[jnp.clip(n_valid - 1, 0, w - 1)][None]
        new = {"lat": tuple(lat), "idx": tuple(idx), "ring": tuple(ring),
               "table": cache["table"].at[slot].set(pages),
               "len": cache["len"].at[slot].set(start + n_valid)}
        return self._logits(top, row), new, sum(hit)

    def _decode_body(self, layers, top, tokens, active, cache):
        b = tokens.shape[0]
        ps = cache["lat"][0].shape[1]
        di = self._idx[1]
        t = cache["len"]
        live = active > 0
        rows_b = jnp.arange(b)
        page = jnp.where(
            live, cache["table"][rows_b, jnp.minimum(
                t // ps, cache["table"].shape[1] - 1)], 0)
        flat = page * ps + t % ps
        x = jnp.take(top["embed"], tokens, axis=0)
        lat, idx, ring = list(cache["lat"]), list(cache["idx"]), \
            list(cache["ring"])
        where = self._state_index()
        hit = []
        for li, kind in enumerate(self._kinds):
            p, ci = layers[li], where[li]
            full = kind == "full_attention"
            g = self._full if full else self._swa
            u32 = _rms32(x, p["attn_norm"], self._eps)
            u = u32.astype(x.dtype)
            with _scope("mla" if full else "swa"):
                c_q32, q_n, q_r = _la.queries(p, u32, t, g, self._eps,
                                              full)
                rows = _la.latent_rows(p, u, t, g, self._eps)
            if full:
                with _scope("indexer"):
                    ik = _index_keys(p, u32, t, g.dr, g.inv_freq)
                with _scope("kv_write"):
                    pool = lat[ci].reshape(-1, g.row_pad).at[flat].set(rows)
                    lat[ci] = pool.reshape(lat[ci].shape)
                    wi_ = idx[ci].shape[-1]
                    ipool = idx[ci].reshape(-1, wi_).at[flat].set(
                        jnp.pad(ik, ((0, 0), (0, wi_ - di))))
                    idx[ci] = ipool.reshape(idx[ci].shape)
                with _scope("indexer"):
                    keys = idx[ci][cache["table"]].reshape(
                        b, -1, wi_)[..., :di]
                    qi, wi = _index_queries(p, c_q32, u32, t, self._idx,
                                            g.dr, g.inv_freq)
                    sc = _index_scores(qi[:, None], wi[:, None],
                                       keys)[:, 0]          # (B, S)
                    s_all = sc.shape[-1]
                    allowed = jnp.arange(s_all)[None, :] <= t[:, None]
                    sc = jnp.where(allowed, sc, -jnp.inf)
                    kk = min(self._topk, s_all)
                    top_sc, sel = lax.top_k(sc, kk)
                    valid = top_sc > -jnp.inf
                    sel_flat = (jnp.take_along_axis(
                        cache["table"], sel // ps, axis=1) * ps + sel % ps)
                with _scope("mla"):
                    got = pool[jnp.where(valid, sel_flat, 0)]
                    o = _la.attend_absorbed(p, q_n, q_r, got, valid, g)
            else:
                with _scope("kv_write"):
                    at = jnp.mod(t, self._ring)
                    old = ring[ci][rows_b, at]
                    ring[ci] = ring[ci].at[rows_b, at].set(
                        jnp.where(live[:, None], rows, old))
                with _scope("swa"):
                    kp = _ring_positions(t, self._ring)
                    valid = (kp >= 0) & (t[:, None] - kp < self._window)
                    o = _la.attend_absorbed(p, q_n, q_r, ring[ci], valid,
                                            g)
            with _scope("mla" if full else "swa"):
                h = (x.astype(_F32) + _gate_out(p, u, o, g)).astype(x.dtype)
            x = (h.astype(_F32) + self._ffn(li, p, h, hit)).astype(x.dtype)
        new = {"lat": tuple(lat), "idx": tuple(idx), "ring": tuple(ring),
               "table": cache["table"],
               "len": t + live.astype(jnp.int32)}
        return self._logits(top, x), new, sum(hit)

    def _ensure_programs(self):
        if self._progs is not None:
            return self._progs

        def named(fn, name):
            def wrapper(*args):
                telemetry.counter(TRACE_COUNTER)
                tracing.flight.record("compile", what="model.dots3")
                return fn(*args)
            wrapper.__name__ = wrapper.__qualname__ = name
            return wrapper

        def fresh(layers, top, tokens, n_valid, slot, pages, cache):
            return self._prefill_body(layers, top, tokens, jnp.int32(0),
                                      n_valid, slot, pages, cache, True)

        def chunk(layers, top, tokens, start, n_valid, slot, pages, cache):
            return self._prefill_body(layers, top, tokens, start, n_valid,
                                      slot, pages, cache, False)

        def advance(delta, cache):
            new = dict(cache)
            new["len"] = cache["len"] + delta
            return new

        self._progs = {
            "fresh": jax.jit(named(fresh, "dots3_paged_fresh"),
                             donate_argnums=(6,)),
            "chunk": jax.jit(named(chunk, "dots3_paged_chunk"),
                             donate_argnums=(7,)),
            "decode": jax.jit(named(self._decode_body,
                                    "dots3_paged_decode"),
                              donate_argnums=(4,)),
            "advance": jax.jit(named(advance, "dots3_paged_advance"),
                               donate_argnums=(1,)),
        }
        return self._progs

    # -- the calls the engine makes ----------------------------------------
    def _count_experts(self, counter, out):
        """``out`` is a program's ``(logits, cache, experts hit)``: the
        count stays on the device until it is ready (the engine's own
        sync of a later call has passed it by then: no wait is added),
        then goes into ``counter``."""
        logits, cache, hit = out
        self._hits.append((counter, hit))
        while self._hits and self._hits[0][1].is_ready():
            name, n = self._hits.popleft()
            telemetry.counter(name, int(n))
        return logits, cache

    def _note_len(self, cache):
        b = cache["len"].shape[0]
        if self._host_len is None or self._host_len.shape[0] != b:
            self._host_len = onp.zeros((b,), "i8")
        return self._host_len

    def prefill_paged(self, tokens, n_valid, slot, pages, cache, *,
                      start=0, fresh=False):
        """Prefill one chunk of ``slot`` (``tokens`` (1, W) int32, W a
        multiple of the page size and at most the ``prefill_chunk`` the
        model was built for; ``pages`` the slot's full page-table row;
        ``start`` the chunk's position, a multiple of the page size), or
        with ``fresh=True`` a whole prompt of at most one chunk. Returns
        ``(last valid logits (1, V), cache)``; the cache is donated."""
        tokens = jnp.asarray(tokens, jnp.int32)
        if tokens.ndim != 2 or tokens.shape[0] != 1:
            raise ValueError(f"paged prefill tokens must be (1, W), got "
                             f"shape {tokens.shape}")
        ps = cache["lat"][0].shape[1]
        w = tokens.shape[1]
        if w % ps or w > self._chunk_max:
            raise ValueError(
                f"chunk width {w} must be a multiple of page_size {ps} "
                f"and at most prefill_chunk {self._chunk_max} (the ring "
                f"of {self._ring} holds a window of {self._window} and "
                f"one chunk)")
        if int(start) % ps:
            raise ValueError(f"chunk start {start} must be a multiple of "
                             f"page_size {ps}")
        if fresh and int(start) != 0:
            raise ValueError("fresh prefill starts at 0 by definition")
        self._note_len(cache)[int(slot)] = int(start) + int(n_valid)
        pr = self._ensure_programs()
        layers, top = self._datas()
        pages = jnp.asarray(pages, jnp.int32)
        if fresh:
            out = pr["fresh"](layers, top, tokens, jnp.int32(n_valid),
                              jnp.int32(slot), pages, cache)
        else:
            out = pr["chunk"](layers, top, tokens, jnp.int32(start),
                              jnp.int32(n_valid), jnp.int32(slot), pages,
                              cache)
        return self._count_experts("model.dots3.experts_hit.prefill", out)

    def decode_step_paged(self, tokens, active, cache):
        """One decode step for every slot: each active row's token is
        written at its ``len`` (pools through the page table, rings at
        ``len mod ring_size``), attends, and ``len`` is bumped. Inactive
        rows ride along: their pool writes land in the scrap page, their
        ring and ``len`` stand still. Returns ``(logits (B, V) float32,
        cache)``; the cache is donated."""
        active_h = onp.asarray(active) > 0
        lens = self._note_len(cache)
        n_full = self._kinds.count("full_attention")
        ctx = lens[active_h] + 1
        telemetry.counter("model.dots3.keys_in_context",
                          int(ctx.sum()) * n_full)
        telemetry.counter("model.dots3.keys_selected",
                          int(onp.minimum(ctx, self._topk).sum()) * n_full)
        lens[active_h] += 1
        layers, top = self._datas()
        return self._count_experts(
            "model.dots3.experts_hit.decode",
            self._ensure_programs()["decode"](
                layers, top, jnp.asarray(tokens, jnp.int32),
                jnp.asarray(active, jnp.int32), cache))

    def advance_len_paged(self, delta, cache):
        """Advance each row's valid length by ``delta`` (B,) int32.
        Cache donated."""
        self._note_len(cache)[:] += onp.asarray(delta, "i8")
        return self._ensure_programs()["advance"](
            jnp.asarray(delta, jnp.int32), cache)

    # -- the whole forward, for a user who wants logits --------------------
    def forward(self, tokens):
        """Logits (B, T, V) float32 of ``tokens`` (B, T), every position
        attending as the generation programs do (no cache)."""
        toks = tokens._data if isinstance(tokens, NDArray) \
            else jnp.asarray(tokens)
        layers, top = self._datas()
        if self._forward is None:
            def dots3_forward(layers, top, toks):
                def one(row):
                    pos = jnp.arange(row.shape[0], dtype=jnp.int32)
                    x = jnp.take(top["embed"], row, axis=0)
                    for li in range(len(self._kinds)):
                        x = self._layer_prefill(
                            li, layers[li], x, pos,
                            lambda rows, ik: (rows, ik, pos), [])
                    return self._logits(top, x)
                return jnp.stack([one(r) for r in toks])
            self._forward = jax.jit(dots3_forward)
        return NDArray(self._forward(layers, top, toks.astype(jnp.int32)))
