"""The ``xing4_0`` decoder family: a residual of several streams under
manifold-constrained hyper-connections, dense multi-head latent attention
with YaRN-scaled rotary positions, sigmoid-routed experts held whole.

A fourth decoder family, built for SERVING through
``serving.GenerationEngine``'s paged path (docs/SERVING.md "The
model-engine contract") as pure functions of ``(params, state, x)`` like
``dots3.py`` and ``phi4flash.py``.

* **The residual** is not ``x + F(N(x))``. A token carries ``hc_mult``
  streams ``X`` (n, D), all copies of its embedding at the bottom and
  summed before the final norm. Every sublayer (a layer has two:
  attention, then the feed-forward) reads one input ``u = H_pre X``, and
  writes ``X' = H_res X + H_post^T F(N(u))``; the three maps are
  functions of the token's own streams, ``H_res`` projected onto doubly
  stochastic matrices by ``hc_sinkhorn_iters`` Sinkhorn-Knopp rounds
  (``ops/hyper_connection.py``; float32 whatever the model's dtype, with
  ``phi``, ``alpha``, ``b`` float32 leaves).
* **Attention**: latent attention (``ops/latent_attention.py``, shared
  with ``dots3.py``) over EVERY cached position up to the query's own: no
  indexer, no window, no gate, no latent rescale. Rotary frequencies are
  YaRN's, and ``mscale_all_dim`` enters the softmax scale squared. Cached
  a position and layer: one latent row (``kv_lora_rank +
  qk_rope_head_dim``, padded to 128) in a paged pool under the engine's
  one page table; nothing else, so all of this family's state is paged.
  Prefill runs the plain form over the slot's whole view under a causal
  mask; a decode tick the absorbed form, one query a slot over the
  slot's whole view (``ops.attention.gather_rows``) under a length mask.
* **Feed-forward**: SwiGLU, dense in the first ``first_k_dense_replace``
  layers, then ``num_experts_per_tok`` of ``n_routed_experts`` by sigmoid
  score plus a bias (``ops/moe.py``), gates normalised and scaled by
  ``routed_scaling_factor``, plus shared experts. Every routed expert is
  held (``ep_size`` 1).

The multi-token-prediction module (``num_nextn_predict_layers``) is not
built: the published config does not say how the streams enter it.

Parameters are leaves of the model's ``dtype`` (bfloat16 unless told
otherwise) with ``grad_req="null"``; float32 whatever the dtype: the
hyper-connections' ``phi``, ``alpha``, ``b`` and the router's weight and
bias (0.1 % of the parameters). Two bytes a parameter are held; the pools
and the programs' activations are of the same dtype, and the engine is
refused any other ``compute_dtype``. Every generation program takes the
parameters as runtime arguments.
"""
from __future__ import annotations

import collections
import math

import jax
import jax.numpy as jnp
import numpy as onp

from ... import telemetry, tracing
from ...ndarray.ndarray import NDArray
from ...ops import hyper_connection as _hc
from ...ops import latent_attention as _la
from ...ops import moe as _moe
from ...ops.attention import gather_rows
from ..block import HybridBlock
from ..parameter import Parameter

__all__ = ["Xing4Model"]

_scope = jax.named_scope
_F32 = jnp.float32

#: counts a trace or a compile of a generation program
TRACE_COUNTER = "model.xing4.trace"

#: leaves that are float32 whatever the model's dtype
FLOAT32_LEAVES = ("a_phi", "a_alpha", "a_b", "f_phi", "f_alpha", "f_b",
                  "router", "router_bias")


class Xing4Model(HybridBlock):
    """A ``xing4_0`` language model, from its ``config.json`` keys
    (``rope_scaling`` the published group, type ``yarn``)."""

    @property
    def generation_support(self):
        """What ``serving.GenerationEngine`` may be asked for with this
        family; it refuses every other option by name."""
        return {
            "dense_cache": False, "paged": True, "prefix_cache": False,
            "quantize": False, "kv_dtype": False, "speculative": False,
            "decode_ticks": False, "mesh_layout": False, "lora": False,
            "cache_dtype": (self._dtype,), "compute_dtype": (self._dtype,),
        }

    def __init__(self, vocab_size, hidden_size, num_hidden_layers,
                 num_attention_heads, q_lora_rank, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                 intermediate_size, moe_intermediate_size, n_routed_experts,
                 num_experts_per_tok, hc_mult, hc_sinkhorn_iters, hc_eps,
                 mhc_h_res_clamp_min, mhc_h_res_clamp_max, rope_scaling,
                 n_shared_experts=1, first_k_dense_replace=1,
                 routed_scaling_factor=1.0, rope_theta=10000.0,
                 rms_norm_eps=1e-6, max_length=8192, dtype="bfloat16",
                 **kwargs):
        super().__init__(**kwargs)
        self._dtype = str(jnp.dtype(dtype))
        self._vocab_size = int(vocab_size)
        self._d = int(hidden_size)
        self._n_layers = int(num_hidden_layers)
        ys = dict(rope_scaling)
        if ys.get("type") != "yarn":
            raise ValueError(f"rope_scaling type {ys.get('type')!r}: this "
                             f"family rotates with 'yarn'")
        if ys["mscale"] != ys["mscale_all_dim"]:
            raise ValueError(
                "rope_scaling mscale != mscale_all_dim would scale the "
                "rotary cos and sin by their ratio: not built, the "
                "published value is 1")
        m_all = _la.yarn_mscale(ys["factor"], ys["mscale_all_dim"])
        dr = int(qk_rope_head_dim)
        inv = _la.yarn_inv_freq(
            float(rope_theta), dr, ys["factor"],
            ys["original_max_position_embeddings"], ys["beta_fast"],
            ys["beta_slow"])
        self._geom = _la.Geom(
            int(num_attention_heads), int(qk_nope_head_dim), dr,
            int(v_head_dim), int(q_lora_rank), int(kv_lora_rank), rope_theta,
            scale=m_all * m_all / math.sqrt(qk_nope_head_dim + dr),
            inv_freq=lambda d: inv)
        self._n = int(hc_mult)
        self._hc = dict(iters=int(hc_sinkhorn_iters), eps=float(hc_eps),
                        clamp=(float(mhc_h_res_clamp_min),
                               float(mhc_h_res_clamp_max)))
        self._f_dense = int(intermediate_size)
        self._f_moe = int(moe_intermediate_size)
        self._e = int(n_routed_experts)
        self._k = int(num_experts_per_tok)
        self._n_shared = int(n_shared_experts)
        self._first_dense = int(first_k_dense_replace)
        self._gate_scale = float(routed_scaling_factor)
        self._eps = float(rms_norm_eps)
        self._max_length = int(max_length)
        self._params = {}        # layer -> {short name: Parameter}
        self._build_parameters()
        self._progs = None
        self._forward = None     # the jitted whole forward
        self._host_len = None    # host mirror of cache["len"]
        self._hits = collections.deque()   # (counter, device count) a call

    # -- parameters ------------------------------------------------------
    def _add(self, layer, short, shape):
        dtype = "float32" if short in FLOAT32_LEAVES else self._dtype
        init = "ones" if short.endswith(("norm", "alpha")) else None
        p = Parameter(short, grad_req="null", shape=shape, dtype=dtype,
                      init=init)
        name = short if layer is None else f"layers_{layer}_{short}"
        setattr(self, name, p)
        self._params.setdefault(layer, {})[short] = p

    def _build_parameters(self):
        d, g, n = self._d, self._geom, self._n
        maps = 2 * n + n * n
        self._add(None, "embed", (self._vocab_size, d))
        self._add(None, "final_norm", (d,))
        self._add(None, "head", (d, self._vocab_size))
        for li in range(self._n_layers):
            for short, shape in (
                    ("a_phi", (maps, n * d)), ("a_alpha", (3,)),
                    ("a_b", (maps,)), ("attn_norm", (d,)),
                    ("w_dq", (d, g.rq)), ("q_norm", (g.rq,)),
                    ("w_uq", (g.rq, g.h * (g.dn + g.dr))),
                    ("w_dkv", (d, g.row)), ("kv_norm", (g.rkv,)),
                    ("w_uk", (g.rkv, g.h * g.dn)),
                    ("w_uv", (g.rkv, g.h * g.dv)),
                    ("w_o", (g.h * g.dv, d)),
                    ("f_phi", (maps, n * d)), ("f_alpha", (3,)),
                    ("f_b", (maps,)), ("ffn_norm", (d,))):
                self._add(li, short, shape)
            if li < self._first_dense:
                f = self._f_dense
                for short, shape in (("w_gate", (d, f)), ("w_up", (d, f)),
                                     ("w_down", (f, d))):
                    self._add(li, short, shape)
                continue
            e, f = self._e, self._f_moe
            self._add(li, "router", (d, e))
            self._add(li, "router_bias", (e,))
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

    def parameter_count(self):
        return sum(int(onp.prod(p.shape))
                   for p in self.collect_params().values())

    def _datas(self):
        """The parameters' buffers as the programs take them: a list
        (one dict a layer) and the top dict."""
        def grab(ps):
            return {k: p.data()._data for k, p in ps.items()}
        return ([grab(self._params[li]) for li in range(self._n_layers)],
                grab(self._params[None]))

    # -- one layer ---------------------------------------------------------
    def _sublayer(self, p, which, norm, x, fn):
        """One hyper-connected sublayer over the streams ``x`` (T, n, D):
        ``fn`` takes the normed input in float32 and returns ``F`` (T, D)
        float32. ``which`` is ``"a"`` or ``"f"``: whose maps."""
        with _scope("mhc_coeff"):
            h_pre, h_post, h_res = _hc.coefficients(
                x, p[which + "_phi"], p[which + "_alpha"], p[which + "_b"],
                **self._hc)
        with _scope("mhc_mix"):
            u32 = _la.rms32(_hc.mix_in(h_pre, x), p[norm], self._eps)
        f = fn(u32)
        with _scope("mhc_mix"):
            return _hc.mix_out(h_res, h_post, x, f)

    def _ffn(self, li, p, z32, dtype, hit):
        """The feed-forward of layer ``li`` on the normed input; a routed
        layer appends to ``hit`` the number of experts its rows fall
        on."""
        z = z32.astype(dtype)
        if li < self._first_dense:
            with _scope("mlp"):
                return _la.swiglu(z, p["w_gate"], p["w_up"], p["w_down"])
        with _scope("moe"):
            routed, n_hit = _moe.routed_layer(z32, z, p, self._k, 0,
                                              self._gate_scale)
            hit.append(n_hit)
            return routed + _la.swiglu(z, p["s_gate"], p["s_up"],
                                       p["s_down"])

    def _out(self, p, o):
        g = self._geom
        return jnp.dot(o.reshape(o.shape[0], g.h * g.dv), p["w_o"],
                       preferred_element_type=_F32)

    def _layer_prefill(self, li, p, x, pos, keys, hit):
        """One layer over a chunk of T tokens' streams ``x`` (T, n, D) at
        positions ``pos``. ``keys(rows)`` takes the latent rows the chunk
        caches and returns what it attends, ``(rows_S, key_pos (S,))``:
        the write into the pool happens inside it."""
        g = self._geom

        def attention(u32):
            with _scope("mla_plain"):
                _, q_n, q_r = _la.queries(p, u32, pos, g, self._eps)
                rows = _la.latent_rows(p, u32.astype(x.dtype), pos, g,
                                       self._eps)
            with _scope("kv_write"):
                rows_s, key_pos = keys(rows)
            with _scope("mla_plain"):
                valid = key_pos[None, :] <= pos[:, None]
                o = _la.attend_plain(
                    p, q_n, q_r, rows_s, valid, g,
                    _la.head_block(g, x.shape[0], rows_s.shape[0]))
                return self._out(p, o)

        x = self._sublayer(p, "a", "attn_norm", x, attention)
        return self._sublayer(
            p, "f", "ffn_norm", x,
            lambda z32: self._ffn(li, p, z32, x.dtype, hit))

    def _streams(self, top, tokens):
        """``X_0``: every stream a copy of the token's embedding row."""
        e = jnp.take(top["embed"], tokens, axis=0)
        return jnp.broadcast_to(e[:, None, :],
                                (e.shape[0], self._n, e.shape[1]))

    def _logits(self, top, x):
        with _scope("lm_head"):
            h = jnp.sum(x.astype(_F32), axis=1)
            z = _la.rms32(h, top["final_norm"], self._eps).astype(x.dtype)
            return jnp.dot(z, top["head"], preferred_element_type=_F32)

    # -- the cache -----------------------------------------------------------
    def init_paged_cache(self, batch_size, n_pages, page_size,
                         max_length=None, dtype=None):
        """``"lat"``: one paged pool of latent rows a layer,
        ``(n_pages, page_size, row_pad)``, all under ``"table"``
        (B, P_max) and ``"len"`` (B,); page 0 is the scrap page."""
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
        b = int(batch_size)
        return {
            "lat": tuple(jnp.zeros((int(n_pages), ps, self._geom.row_pad),
                                   jnp.dtype(self._dtype))
                         for _ in range(self._n_layers)),
            "table": jnp.zeros((b, s // ps), jnp.int32),
            "len": jnp.zeros((b,), jnp.int32),
        }

    # -- the programs ----------------------------------------------------------
    def _prefill_body(self, layers, top, tokens, start, n_valid, slot,
                      pages, cache, fresh):
        w = tokens.shape[1]
        ps = cache["lat"][0].shape[1]
        pos = start + jnp.arange(w, dtype=jnp.int32)
        x = self._streams(top, tokens[0])
        lat = list(cache["lat"])
        hit = []
        page_ids = jax.lax.dynamic_slice(pages, (start // ps,), (w // ps,))
        for li in range(self._n_layers):
            def keys(rows, li=li):
                lat[li] = lat[li].at[page_ids].set(
                    rows.reshape(w // ps, ps, -1))
                if fresh:
                    return rows, pos
                view = lat[li][pages].reshape(-1, rows.shape[-1])
                return view, jnp.arange(view.shape[0], dtype=jnp.int32)
            x = self._layer_prefill(li, layers[li], x, pos, keys, hit)
        row = x[jnp.clip(n_valid - 1, 0, w - 1)][None]
        new = {"lat": tuple(lat),
               "table": cache["table"].at[slot].set(pages),
               "len": cache["len"].at[slot].set(start + n_valid)}
        return self._logits(top, row), new, sum(hit)

    def _decode_body(self, layers, top, tokens, active, cache):
        b = tokens.shape[0]
        g = self._geom
        ps = cache["lat"][0].shape[1]
        t = cache["len"]
        live = active > 0
        page = jnp.where(
            live, cache["table"][jnp.arange(b), jnp.minimum(
                t // ps, cache["table"].shape[1] - 1)], 0)
        flat = page * ps + t % ps
        x = self._streams(top, tokens)
        lat = list(cache["lat"])
        hit = []
        for li in range(self._n_layers):
            p = layers[li]

            def attention(u32, li=li, p=p):
                with _scope("mla_absorbed"):
                    _, q_n, q_r = _la.queries(p, u32, t, g, self._eps)
                    rows = _la.latent_rows(p, u32.astype(x.dtype), t, g,
                                           self._eps)
                with _scope("kv_write"):
                    pool = lat[li].reshape(-1, g.row_pad).at[flat].set(rows)
                    lat[li] = pool.reshape(lat[li].shape)
                with _scope("mla_absorbed"):
                    view = gather_rows(lat[li], cache["table"])
                    valid = jnp.arange(view.shape[1])[None, :] <= t[:, None]
                    return self._out(p, _la.attend_absorbed(
                        p, q_n, q_r, view, valid, g))

            x = self._sublayer(p, "a", "attn_norm", x, attention)
            x = self._sublayer(
                p, "f", "ffn_norm", x,
                lambda z32, li=li, p=p: self._ffn(li, p, z32, x.dtype, hit))
        new = {"lat": tuple(lat), "table": cache["table"],
               "len": t + live.astype(jnp.int32)}
        return self._logits(top, x), new, sum(hit)

    def _ensure_programs(self):
        if self._progs is not None:
            return self._progs

        def named(fn, name):
            def wrapper(*args):
                telemetry.counter(TRACE_COUNTER)
                tracing.flight.record("compile", what="model.xing4")
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
            "fresh": jax.jit(named(fresh, "xing4_paged_fresh"),
                             donate_argnums=(6,)),
            "chunk": jax.jit(named(chunk, "xing4_paged_chunk"),
                             donate_argnums=(7,)),
            "decode": jax.jit(named(self._decode_body,
                                    "xing4_paged_decode"),
                              donate_argnums=(4,)),
            "advance": jax.jit(named(advance, "xing4_paged_advance"),
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
        multiple of the page size; ``pages`` the slot's full page-table
        row; ``start`` the chunk's position, a multiple of the page
        size), or with ``fresh=True`` a whole prompt of one chunk.
        Returns ``(last valid logits (1, V), cache)``; the cache is
        donated."""
        tokens = jnp.asarray(tokens, jnp.int32)
        if tokens.ndim != 2 or tokens.shape[0] != 1:
            raise ValueError(f"paged prefill tokens must be (1, W), got "
                             f"shape {tokens.shape}")
        ps = cache["lat"][0].shape[1]
        if tokens.shape[1] % ps:
            raise ValueError(f"chunk width {tokens.shape[1]} must be a "
                             f"multiple of page_size {ps}")
        if int(start) % ps:
            raise ValueError(f"chunk start {start} must be a multiple of "
                             f"page_size {ps}")
        if fresh and int(start) != 0:
            raise ValueError("fresh prefill starts at 0 by definition")
        self._note_len(cache)[int(slot)] = int(start) + int(n_valid)
        telemetry.counter("model.xing4.hc_sublayer_rows.prefill",
                          int(n_valid) * 2 * self._n_layers)
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
        return self._count_experts("model.xing4.experts_hit.prefill", out)

    def decode_step_paged(self, tokens, active, cache):
        """One decode step for every slot: each active row's token is
        written at its ``len`` through the page table, attends every
        position up to its own, and ``len`` is bumped. Inactive rows ride
        along: their pool writes land in the scrap page and their ``len``
        stands still. Returns ``(logits (B, V) float32, cache)``; the
        cache is donated."""
        active_h = onp.asarray(active) > 0
        lens = self._note_len(cache)
        telemetry.counter("model.xing4.keys_attended",
                          int((lens[active_h] + 1).sum()) * self._n_layers)
        telemetry.counter("model.xing4.hc_sublayer_rows.decode",
                          int(active_h.sum()) * 2 * self._n_layers)
        lens[active_h] += 1
        layers, top = self._datas()
        return self._count_experts(
            "model.xing4.experts_hit.decode",
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
            def xing4_forward(layers, top, toks):
                def one(row):
                    pos = jnp.arange(row.shape[0], dtype=jnp.int32)
                    x = self._streams(top, row)
                    for li in range(self._n_layers):
                        x = self._layer_prefill(
                            li, layers[li], x, pos,
                            lambda rows: (rows, pos), [])
                    return self._logits(top, x)
                return jnp.stack([one(r) for r in toks])
            self._forward = jax.jit(xing4_forward)
        return NDArray(self._forward(layers, top, toks.astype(jnp.int32)))

