"""The ``sdar_moe`` decoder family: generation by diffusion over blocks,
grouped-query attention with QK-norm under a block-causal mask, and
softmax-routed experts.

A sixth decoder family, built for SERVING through
``serving.GenerationEngine``'s paged path (docs/SERVING.md "The
model-engine contract" and "Block-diffusion steps") as pure functions of
``(params, state, x)`` like ``xing4.py``. It is Qwen3-MoE's block, run
by SDAR's generation rule: a step of the engine yields a block of
tokens, not one.

Every layer (``D`` the hidden size, ``N`` an RMSNorm with its own gain and
``rms_norm_eps``, no bias anywhere)::

    h   = x + Attn(N(x))
    out = h + MoE(N(h))

* **Attention**: ``Hq`` query heads over ``Hkv`` K/V heads of ``dh``,
  query head ``j`` reading K/V head ``j // (Hq / Hkv)``. ``q = N_q(x W_q)``
  and ``k = N_k(x W_k)`` per head (QK-norm: an RMSNorm over ``dh`` with
  one gain a projection, before the rotation), both rotated by RoPE over
  the whole head in the half-split convention with ``theta =
  rope_theta`` (no scaling, no window), ``v = x W_v``; scores ``q.k /
  sqrt(dh)``; softmax in float32; ``W_o`` on the heads side by side.
* **The mask is block-causal** with block ``B``: query ``i`` sees key
  ``j`` iff ``floor(j / B) <= floor(i / B)``: bidirectional inside a
  block, causal across blocks.
* **MoE**: ``pi = softmax(z W_r)`` over all ``num_experts`` in float32,
  the top ``num_experts_per_tok`` kept and, with ``norm_topk_prob``,
  renormalised to sum to 1 (``ops/moe.py route_softmax_topk``); each
  expert ``W_down(silu(z W_gate) * (z W_up))`` of width
  ``moe_intermediate_size``; no shared expert; every expert held
  (``ops/moe.py expert_layer``).
* A final RMSNorm, then an untied head.

**Generation** (``generation_support["block_diffusion"]``, the
constructor's ``block_length``, ``denoise_steps``, ``mask_token_id``). A
prompt of ``p`` tokens: its first ``floor(p / B) B`` positions are
prefilled under the block-causal mask (``prefill_paged``);
the remaining ``p mod B`` open the first block, whose other positions
hold the mask token. A denoising step runs the block's ``B`` rows against
the cached prefix and the block's own rows; at each masked position it
takes the argmax and its softmax probability (the confidence), and
unmasks the ``k_s`` most confident masked positions, ``k_s`` LLaDA's
``get_num_transfer_tokens``: the block's masked count at its start split
over the steps, the remainder going to the first steps (``low_confidence_
static``). The engine counts ``k_s`` from the static schedule and hands
it in a step at a time. A masked position's own output predicts its token
(no shift).
When no mask is left the block's K/V are computed again from its final
tokens and written to the cache: that COMMIT rides in the slot's next
step (``denoise_step_paged``: each slot runs ``2 B`` rows, the finished
block's under its own mask beside the next block's), so a block costs
``steps`` passes over the weights, not ``steps + 1``. A request's last
block is never committed: nothing reads it.

State on the device (the cache dict, donated by every program): one K and
one V pool a layer ``(n_pages, page_size, Hkv dh)`` under ``"table"``;
and each slot's block: its tokens and which are filled (``"blk"``,
``"fill"``), its first position (``"start"``), and the finished block to
commit (``"prev"``, ``"commit"``). A slot entering its first
block is handed in by the engine (``fresh``); every other step runs from
the state alone, so the host reads tokens only when a block completes.

Parameters are leaves of the model's ``dtype`` (bfloat16 unless told
otherwise) with ``grad_req="null"``; float32 whatever the dtype: the
router's weight. The residual stream, norms, softmax, the confidence and
logits are float32. Every generation program takes the parameters as
runtime arguments.
"""
from __future__ import annotations

import collections
import math

import jax
import jax.numpy as jnp
import numpy as onp

from ... import telemetry, tracing
from ...ndarray.ndarray import NDArray
from ...ops import attention as _att
from ...ops import latent_attention as _la
from ...ops import moe as _moe
from ..block import HybridBlock
from ..parameter import Parameter

__all__ = ["SDARModel"]

_scope = jax.named_scope
_F32 = jnp.float32

#: counts a trace or a compile of a generation program
TRACE_COUNTER = "model.sdar.trace"

#: leaves that are float32 whatever the model's dtype
FLOAT32_LEAVES = ("router",)

#: the one unmasking rule (a configuration's ``remasking_strategy``)
SCHEDULE = "low_confidence_static"


class SDARModel(HybridBlock):
    """An ``sdar_moe`` language model, from its ``config.json`` keys and
    the block-diffusion settings the config does not give."""

    @property
    def generation_support(self):
        """What ``serving.GenerationEngine`` may be asked for with this
        family; it refuses every other option by name. ``block_diffusion``
        makes the engine run its block-diffusion step path."""
        return {
            "dense_cache": False, "paged": True, "prefix_cache": False,
            "quantize": False, "kv_dtype": False, "speculative": False,
            "decode_ticks": False, "mesh_layout": False, "lora": False,
            "cache_dtype": (self._dtype,), "compute_dtype": (self._dtype,),
            "block_diffusion": {
                "block_length": self._blk, "denoise_steps": self._steps},
        }

    def __init__(self, vocab_size, hidden_size, num_hidden_layers,
                 num_attention_heads, num_key_value_heads, head_dim,
                 moe_intermediate_size, num_experts, num_experts_per_tok,
                 mask_token_id, block_length=4, denoise_steps=2,
                 remasking_strategy=SCHEDULE, norm_topk_prob=True,
                 rope_theta=1000000.0, rms_norm_eps=1e-6, max_length=4096,
                 dtype="bfloat16", **kwargs):
        super().__init__(**kwargs)
        self._dtype = str(jnp.dtype(dtype))
        self._vocab_size = int(vocab_size)
        self._d = int(hidden_size)
        self._n_layers = int(num_hidden_layers)
        self._hq = int(num_attention_heads)
        self._hkv = int(num_key_value_heads)
        self._dh = int(head_dim)
        if self._hq % self._hkv:
            raise ValueError(f"{self._hq} query heads are no multiple of "
                             f"{self._hkv} K/V heads")
        self._f = int(moe_intermediate_size)
        self._e = int(num_experts)
        self._k = int(num_experts_per_tok)
        self._norm_topk = bool(norm_topk_prob)
        self._theta = float(rope_theta)
        self._eps = float(rms_norm_eps)
        self._max_length = int(max_length)
        self._blk = int(block_length)
        self._steps = int(denoise_steps)
        self._mask_id = int(mask_token_id)
        if remasking_strategy != SCHEDULE:
            raise ValueError(f"remasking_strategy {remasking_strategy!r}: "
                             f"this family unmasks by {SCHEDULE!r} only")
        if self._blk < 1 or self._steps < 1:
            raise ValueError("block_length and denoise_steps must be >= 1")
        if not 0 <= self._mask_id < self._vocab_size:
            raise ValueError(f"mask_token_id {mask_token_id} is not a row "
                             f"of the vocabulary")
        self._params = {}        # layer -> {short name: Parameter}
        self._build_parameters()
        self._progs = None
        self._forward = None     # the jitted whole forward
        self._pending = collections.deque()   # (counter, device count)

    # -- parameters ------------------------------------------------------
    def _add(self, layer, short, shape):
        dtype = "float32" if short in FLOAT32_LEAVES else self._dtype
        init = "ones" if short.endswith("norm") else None
        p = Parameter(short, grad_req="null", shape=shape, dtype=dtype,
                      init=init)
        name = short if layer is None else f"layers_{layer}_{short}"
        setattr(self, name, p)
        self._params.setdefault(layer, {})[short] = p

    def _build_parameters(self):
        d, dh, e, f = self._d, self._dh, self._e, self._f
        self._add(None, "embed", (self._vocab_size, d))
        self._add(None, "final_norm", (d,))
        self._add(None, "head", (d, self._vocab_size))
        for li in range(self._n_layers):
            for short, shape in (
                    ("attn_norm", (d,)), ("w_q", (d, self._hq * dh)),
                    ("w_k", (d, self._hkv * dh)),
                    ("w_v", (d, self._hkv * dh)), ("q_norm", (dh,)),
                    ("k_norm", (dh,)), ("w_o", (self._hq * dh, d)),
                    ("ffn_norm", (d,)), ("router", (d, e)),
                    ("e_gate", (e, d, f)), ("e_up", (e, d, f)),
                    ("e_down", (e, f, d))):
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
    def _qkv(self, p, x32, pos):
        """Queries (T, Hq, dh), keys and values (T, Hkv, dh) of the rows
        ``x32`` (T, D) float32 at positions ``pos`` (T,): QK-normed and
        rotated, in the model's dtype."""
        t, dh = x32.shape[0], self._dh
        dtype = jnp.dtype(self._dtype)
        h = _la.rms(x32, p["attn_norm"], self._eps).astype(dtype)

        def proj(w, heads):
            return jnp.dot(h, w, preferred_element_type=_F32).reshape(
                t, heads, dh)

        def inv(d):
            return _la.rope_inv_freq(self._theta, d)

        q = _la.rope(_la.rms32(proj(p["w_q"], self._hq), p["q_norm"],
                               self._eps), pos, inv).astype(dtype)
        k = _la.rope(_la.rms32(proj(p["w_k"], self._hkv), p["k_norm"],
                               self._eps), pos, inv).astype(dtype)
        return q, k, proj(p["w_v"], self._hkv).astype(dtype)

    def _out(self, p, o):
        return jnp.dot(o.reshape(o.shape[0], -1).astype(self._dtype),
                       p["w_o"], preferred_element_type=_F32)

    def _moe(self, p, x32, hit):
        """The routed experts on ``N(x32)``; appends to ``hit`` the number
        of experts its rows fall on."""
        with _scope("moe"):
            z32 = _la.rms32(x32, p["ffn_norm"], self._eps)
            ids, gates = _moe.route_softmax_topk(z32, p["router"], self._k,
                                                 self._norm_topk)
            y, n_hit = _moe.expert_layer(
                z32.astype(self._dtype), p["e_gate"], p["e_up"],
                p["e_down"], ids, gates, 0,
                _moe.tile_rows(x32.shape[0] * self._k))
            hit.append(n_hit)
            return y

    def _layer_rows(self, p, x32, pos, keys, hit):
        """One layer over rows ``x32`` (T, D) float32 at positions ``pos``
        that attend under the block-causal mask. ``keys(k, v)`` takes the
        rows' keys and values and returns what they attend, ``(k_S, v_S,
        key_pos (S,))``: the write into the pool happens inside it."""
        q, k, v = self._qkv(p, x32, pos)
        with _scope("kv_write"):
            k_s, v_s, key_pos = keys(k, v)
        with _scope("attention"):
            b = self._blk
            valid = key_pos[None, :] // b <= pos[:, None] // b
            o = _att.masked_attention(
                q.transpose(1, 0, 2)[None], k_s.transpose(1, 0, 2)[None],
                v_s.transpose(1, 0, 2)[None], valid[None, None],
                1.0 / math.sqrt(self._dh))
            x32 = x32 + self._out(p, o[0].transpose(1, 0, 2))
        return x32 + self._moe(p, x32, hit)

    def _logits(self, top, x32):
        with _scope("lm_head"):
            z = _la.rms(x32, top["final_norm"], self._eps).astype(
                self._dtype)
            return jnp.dot(z, top["head"], preferred_element_type=_F32)

    # -- the cache -----------------------------------------------------------
    def init_paged_cache(self, batch_size, n_pages, page_size,
                         max_length=None, dtype=None):
        """``"k"``, ``"v"``: one paged pool a layer, ``(n_pages, page_size,
        Hkv dh)``, all under ``"table"`` (B, P_max); page 0 is the scrap
        page. The block state a slot: ``"blk"`` / ``"fill"`` (B, block)
        tokens and which are filled, ``"start"`` (B,), ``"prev"`` (B,
        block) the finished block, ``"commit"`` (B,) whether it is still
        to commit."""
        s = int(max_length) if max_length is not None else self._max_length
        ps = int(page_size)
        if ps < 1 or s % ps:
            raise ValueError(f"page_size {ps} must divide cache "
                             f"max_length {s}")
        if ps % self._blk:
            raise ValueError(f"page_size {ps} must be a multiple of the "
                             f"block length {self._blk}")
        if int(n_pages) < 2:
            raise ValueError("n_pages must be >= 2 (page 0 is the "
                             "reserved scrap page)")
        if dtype is not None and str(dtype) != self._dtype:
            raise ValueError(f"cache dtype {dtype!r}: this model caches "
                             f"its own dtype, {self._dtype}, only")
        b, blk = int(batch_size), self._blk
        pool = (int(n_pages), ps, self._hkv * self._dh)
        i32 = jnp.int32
        return {
            "k": tuple(jnp.zeros(pool, self._dtype)
                       for _ in range(self._n_layers)),
            "v": tuple(jnp.zeros(pool, self._dtype)
                       for _ in range(self._n_layers)),
            "table": jnp.zeros((b, s // ps), i32),
            "blk": jnp.zeros((b, blk), i32),
            "fill": jnp.zeros((b, blk), jnp.bool_),
            "start": jnp.zeros((b,), i32),
            "prev": jnp.zeros((b, blk), i32),
            "commit": jnp.zeros((b,), i32),
        }

    # -- the programs ----------------------------------------------------------
    def _prefill_body(self, layers, top, tokens, start, n_valid, slot,
                      pages, cache, fresh):
        """One chunk of a prompt's aligned part: every row written into
        the slot's pages, attending the slot's cached prefix and the
        chunk under the block-causal mask. No logits: a prompt's first
        token comes from its first block."""
        del n_valid
        w = tokens.shape[1]
        ps = cache["k"][0].shape[1]
        pos = start + jnp.arange(w, dtype=jnp.int32)
        x = jnp.take(top["embed"], tokens[0], axis=0).astype(_F32)
        kp, vp = list(cache["k"]), list(cache["v"])
        hit = []
        page_ids = jax.lax.dynamic_slice(pages, (start // ps,), (w // ps,))
        for li in range(self._n_layers):
            def keys(k, v, li=li):
                kp[li] = kp[li].at[page_ids].set(k.reshape(w // ps, ps, -1))
                vp[li] = vp[li].at[page_ids].set(v.reshape(w // ps, ps, -1))
                if fresh:
                    return k, v, pos
                view = [_att.gather_rows(pl, pages[None])[0].reshape(
                    -1, self._hkv, self._dh) for pl in (kp[li], vp[li])]
                return view[0], view[1], jnp.arange(
                    view[0].shape[0], dtype=jnp.int32)
            x = self._layer_rows(layers[li], x, pos, keys, hit)
        new = dict(cache, k=tuple(kp), v=tuple(vp),
                   table=cache["table"].at[slot].set(pages))
        return new, sum(hit)

    def _attend_block(self, q, k_pool, v_pool, table, n_valid):
        """Each slot's ``B`` query rows ``q`` (S, B, Hq, dh) against its
        first ``n_valid`` (S,) positions of the pools: the rows folded
        head-major into the query-head axis (``h B + pos``), which the
        readers' grouping maps to K/V head ``h // (Hq / Hkv)``. Returns
        (S B, Hq dh) float32."""
        s, b, hq, dh = q.shape
        qf = q.transpose(0, 2, 1, 3).reshape(s, hq * b, dh)
        scale = 1.0 / math.sqrt(dh)
        heads = (self._hkv, self._hkv)
        if _att._use_pallas():
            o = _att.paged_rows_attention(qf, k_pool, v_pool, table,
                                          n_valid, heads, scale)
        else:
            k_rows = _att.gather_rows(k_pool, table)
            v_rows = _att.gather_rows(v_pool, table)
            col = jnp.arange(k_rows.shape[1], dtype=jnp.int32)
            o = _att.rows_decode_attention(qf, k_rows, v_rows,
                                           col[None] < n_valid[:, None],
                                           heads, scale)
        return o.reshape(s, hq, b, dh).transpose(0, 2, 1, 3).reshape(
            s * b, hq * dh)

    def _denoise_body(self, layers, top, take, fresh, fresh_blk,
                      fresh_fill, fresh_start, fresh_table, cache):
        """One denoising step of every slot's block (``take`` (S,) the
        positions each unmasks, 0 for an idle slot), and the commit of
        the block each slot finished at its last step. Returns the
        blocks' tokens after the step (S, B), the positions unmasked and
        the experts hit (device counts), and the cache."""
        nb, blk = take.shape[0], self._blk
        ps = cache["k"][0].shape[1]
        f = fresh > 0
        act = take > 0
        tok = jnp.where(f[:, None], fresh_blk, cache["blk"])
        fill = jnp.where(f[:, None], fresh_fill, cache["fill"])
        start = jnp.where(f, fresh_start, cache["start"])
        commit = act & (cache["commit"] > 0) & ~f
        table = jnp.where(f[:, None], fresh_table, cache["table"])

        # rows: the finished block (to commit), then the block being
        # denoised, each at its own positions
        lane = jnp.arange(blk, dtype=jnp.int32)[None, :]
        pos = jnp.concatenate([start[:, None] - blk + lane,
                               start[:, None] + lane], 1)        # (S, 2B)
        live = jnp.concatenate([jnp.broadcast_to(commit[:, None], (nb, blk)),
                                jnp.broadcast_to(act[:, None], (nb, blk))], 1)
        ids = jnp.concatenate(
            [cache["prev"], jnp.where(fill, tok, self._mask_id)], 1)
        at = jnp.maximum(pos, 0)
        page = jnp.where(live, jnp.take_along_axis(
            table, jnp.minimum(at // ps, table.shape[1] - 1), 1), 0)
        off = at % ps
        n_commit = jnp.where(commit, start, 0)
        n_block = jnp.where(act, start + blk, 0)

        x = jnp.take(top["embed"], ids.reshape(-1), axis=0).astype(_F32)
        flat_pos = pos.reshape(-1)
        kp, vp = list(cache["k"]), list(cache["v"])
        hit = []
        for li in range(self._n_layers):
            p = layers[li]
            q, k, v = self._qkv(p, x, flat_pos)
            with _scope("kv_write"):
                kp[li] = _att.write_rows(kp[li], page, off,
                                         k.reshape(nb, 2 * blk, -1))
                vp[li] = _att.write_rows(vp[li], page, off,
                                         v.reshape(nb, 2 * blk, -1))
            with _scope("attention"):
                q = q.reshape(nb, 2, blk, self._hq, self._dh)
                o = jnp.stack([
                    self._attend_block(q[:, g], kp[li], vp[li], table, n)
                    .reshape(nb, blk, -1)
                    for g, n in ((0, n_commit), (1, n_block))], 1)
                x = x + self._out(p, o.reshape(nb * 2 * blk, -1))
            x = x + self._moe(p, x, hit)

        with _scope("unmask"):
            rows = x.reshape(nb, 2, blk, -1)[:, 1].reshape(nb * blk, -1)
            logits = self._logits(top, rows).reshape(nb, blk, -1)
            best = jnp.max(logits, -1)
            conf = best - jax.nn.logsumexp(logits, -1)     # log confidence
            x0 = jnp.argmax(logits, -1).astype(jnp.int32)
            masked = ~fill
            c = jnp.where(masked, conf, -jnp.inf)
            before = (c[:, None, :] > c[:, :, None]) | (
                (c[:, None, :] == c[:, :, None])
                & (lane[:, None, :] < lane[:, :, None]))
            rank = jnp.sum(before & masked[:, None, :], -1)
            unmask = masked & (rank < take[:, None])
            new_tok = jnp.where(unmask, x0, tok)
            new_fill = fill | unmask
            done = act & jnp.all(new_fill, -1)

        new = dict(
            cache, k=tuple(kp), v=tuple(vp), table=table,
            blk=jnp.where(done[:, None], 0, new_tok),
            fill=jnp.where(done[:, None], False, new_fill),
            start=jnp.where(done, start + blk, start),
            prev=jnp.where(done[:, None], new_tok, cache["prev"]),
            commit=jnp.where(act, done, ~f & (cache["commit"] > 0))
            .astype(jnp.int32))
        return new_tok, jnp.sum(unmask, dtype=jnp.int32), sum(hit), new

    def _ensure_programs(self):
        if self._progs is not None:
            return self._progs

        def named(fn, name):
            def wrapper(*args):
                telemetry.counter(TRACE_COUNTER)
                tracing.flight.record("compile", what="model.sdar")
                return fn(*args)
            wrapper.__name__ = wrapper.__qualname__ = name
            return wrapper

        def fresh(layers, top, tokens, n_valid, slot, pages, cache):
            return self._prefill_body(layers, top, tokens, jnp.int32(0),
                                      n_valid, slot, pages, cache, True)

        def chunk(layers, top, tokens, start, n_valid, slot, pages, cache):
            return self._prefill_body(layers, top, tokens, start, n_valid,
                                      slot, pages, cache, False)

        self._progs = {
            "fresh": jax.jit(named(fresh, "sdar_paged_fresh"),
                             donate_argnums=(6,)),
            "chunk": jax.jit(named(chunk, "sdar_paged_chunk"),
                             donate_argnums=(7,)),
            "denoise": jax.jit(named(self._denoise_body,
                                     "sdar_paged_denoise"),
                               donate_argnums=(8,)),
        }
        return self._progs

    # -- the calls the engine makes ----------------------------------------
    def _count(self, *pending):
        """``(counter, device count)`` pairs: each count stays on the
        device until it is ready (a later sync of the engine has passed it
        by then: no wait is added), then goes into its counter."""
        self._pending.extend(pending)
        while self._pending and self._pending[0][1].is_ready():
            name, n = self._pending.popleft()
            telemetry.counter(name, int(n))

    def prefill_paged(self, tokens, n_valid, slot, pages, cache, *,
                      start=0, fresh=False):
        """Prefill one chunk of ``slot``'s aligned prompt part
        (``tokens`` (1, W) int32, W a multiple of the page size; ``pages``
        the slot's full page-table row; ``start`` a multiple of the page
        size), or with ``fresh=True`` a whole aligned part of one chunk.
        Returns ``(None, cache)``: the first token comes from the first
        block. The cache is donated."""
        tokens = jnp.asarray(tokens, jnp.int32)
        if tokens.ndim != 2 or tokens.shape[0] != 1:
            raise ValueError(f"paged prefill tokens must be (1, W), got "
                             f"shape {tokens.shape}")
        ps = cache["k"][0].shape[1]
        if tokens.shape[1] % ps or int(start) % ps:
            raise ValueError(f"chunk width {tokens.shape[1]} and start "
                             f"{start} must be multiples of page_size {ps}")
        if int(n_valid) % self._blk:
            raise ValueError(f"n_valid {n_valid} must be whole blocks of "
                             f"{self._blk}")
        if fresh and int(start) != 0:
            raise ValueError("fresh prefill starts at 0 by definition")
        pr = self._ensure_programs()
        layers, top = self._datas()
        pages = jnp.asarray(pages, jnp.int32)
        if fresh:
            cache, hit = pr["fresh"](layers, top, tokens, jnp.int32(n_valid),
                                     jnp.int32(slot), pages, cache)
        else:
            cache, hit = pr["chunk"](layers, top, tokens, jnp.int32(start),
                                     jnp.int32(n_valid), jnp.int32(slot),
                                     pages, cache)
        self._count(("model.sdar.experts_hit.prefill", hit))
        return None, cache

    def denoise_step_paged(self, take, fresh, fresh_blk, fresh_fill,
                           fresh_start, fresh_table, cache):
        """One denoising step of every slot's block, ``take`` (S,) the
        ``k_s`` of the slot's step (the masked positions it unmasks; 0 for
        an idle slot), and the commit of the block each finished at its
        last step. A slot with ``fresh`` set starts its first block here:
        its tokens ``fresh_blk`` (S, B) where ``fresh_fill`` (S, B) is set
        (the prompt's tail), the mask token elsewhere, at ``fresh_start``
        (S,), its page-table row ``fresh_table`` (S, P_max). Idle rows
        ride along: their writes land in the scrap page and their state
        stands still. Returns ``(tokens (S, B) int32 on the device, the
        blocks after this step, cache)``; the cache is donated."""
        layers, top = self._datas()
        tok, unmasked, hit, cache = self._ensure_programs()["denoise"](
            layers, top, onp.asarray(take, "i4"),
            onp.asarray(fresh, "i4"), onp.asarray(fresh_blk, "i4"),
            onp.asarray(fresh_fill, bool), onp.asarray(fresh_start, "i4"),
            onp.asarray(fresh_table, "i4"), cache)
        self._count(("model.sdar.tokens_unmasked", unmasked),
                    ("model.sdar.experts_hit.decode", hit))
        return tok, cache

    # -- the whole forward, for a user who wants logits --------------------
    def forward(self, tokens):
        """Logits (B, T, V) float32 of ``tokens`` (B, T), every position
        attending under the block-causal mask (no cache, no mask
        token: the sequence as it stands)."""
        toks = tokens._data if isinstance(tokens, NDArray) \
            else jnp.asarray(tokens)
        layers, top = self._datas()
        if self._forward is None:
            def sdar_forward(layers, top, toks):
                def one(row):
                    pos = jnp.arange(row.shape[0], dtype=jnp.int32)
                    x = jnp.take(top["embed"], row, axis=0).astype(_F32)
                    for li in range(self._n_layers):
                        x = self._layer_rows(layers[li], x, pos,
                                             lambda k, v: (k, v, pos), [])
                    return self._logits(top, x)
                return jnp.stack([one(r) for r in toks])
            self._forward = jax.jit(sdar_forward)
        return NDArray(self._forward(layers, top, toks.astype(jnp.int32)))
