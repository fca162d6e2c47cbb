"""The ``nemotron_h`` decoder family: Mamba-2 mixers, routed experts of
squared ReLU with a shared one, and grouped-query attention with no
positions, one mixer a layer.

A fifth decoder family, built for SERVING through
``serving.GenerationEngine``'s paged path (docs/SERVING.md "The
model-engine contract") as pure functions of ``(params, state, x)`` like
``dots3.py``, ``phi4flash.py`` and ``xing4.py``.

Every layer is ONE mixer behind its own pre-norm, ``h = u + Mixer_l(N(u))``
(``N`` an RMSNorm, no bias anywhere), the mixer named by the layer's
letter in ``hybrid_override_pattern``. A final RMSNorm, then an untied
head; the embedding is not scaled.

* ``M``, **Mamba-2** (``H`` heads of ``P`` channels, ``G`` groups of ``N``
  states, head ``h`` in group ``h // (H / G)``): ``[z, xBC, dt] = W_in u``;
  ``xBC = silu(causal_conv(xBC) + b)``, split into ``x`` (H P), ``B`` and
  ``C`` (G N each); ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``,
  a scalar a head; ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`` with
  ``S`` (P, N) float32 a head, ``y_t = S_t C_t + D x_t``; then ``y =
  N_G(y * silu(z))``, an RMSNorm over each of ``G`` groups of channels
  with one gain over all, and out ``= W_out y``. ``ops/ssm.py``: a chunk
  of one slot runs ``ssd_scan`` (the Pallas kernel ``ssd_chunk_scan`` on
  a TPU), a decode tick ``ssd_step``.
* ``E``, **routed experts**: sigmoid scores over all
  ``n_routed_experts`` in float32, the top ``num_experts_per_tok`` by
  score plus a bias, gates normalised over the chosen and scaled by
  ``routed_scaling_factor``; each expert ``W_down relu(W_up z)^2``, and a
  shared expert of the same form. The model is told which routed experts
  it HOLDS (``experts_held``) and computes their part
  (``ops/moe.py expert_layer`` without a gate), what one rank of an
  expert-parallel deployment computes before the exchange.
* ``*``, **attention**: ``Hq`` query heads over ``Hkv`` K/V heads of
  ``head_dim``, query head ``j`` reading K/V head ``j // (Hq / Hkv)``,
  causal softmax, no rotary or other position (the published modelling
  code applies none). One paged K and V pool a layer under the engine's
  one page table.

So a slot keeps two kinds of state: for each Mamba-2 layer a float32
state ``(H, P, N)`` and a tail of ``conv_kernel - 1`` inputs, and the
attention layers' paged rows. The recurrence follows the engine's rules
(docs/SERVING.md): a padded chunk stops state and tail at ``n_valid``, an
inactive tick row leaves them as they were, a chunk at ``start == 0``
starts from zeros. None of it can be shared, so the family takes no
prefix reuse.

Leaves are held a layer at a time (``layers_<l>_<name>``), not stacked
by kind: a layer's held experts are the operand of a Pallas kernel, and a
layer's slice of a stacked leaf would be copied before every call. For
the same reason the experts' ``W_up`` is held output-major, ``(E, F,
D)``: at ``F`` = 1856, no multiple of 128, a TPU lays ``(E, D, F)``
out with ``D`` minor-most, and the kernel was handed a copy of all of it
every call (on a v5e, 2.19 s of a traced 5 s of the benchmark's cell).
Parameters are leaves of the model's ``dtype`` (bfloat16 unless told
otherwise) with ``grad_req="null"``; float32 whatever the dtype: the
router's weight and bias, ``A_log``, ``D`` and ``dt_bias``. The
recurrence, its convolution, softplus, the norms, softmax and logits are
float32; the residual stream is the model's dtype. Every generation
program takes the parameters as runtime arguments.
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
from ...ops import attention as _att
from ...ops import moe as _moe
from ...ops import ssm as _ssm
from ...ops.latent_attention import rms32 as _rms32
from ..block import HybridBlock
from ..parameter import Parameter

__all__ = ["NemotronHModel"]

_scope = jax.named_scope
_F32 = jnp.float32

#: counts a trace or a compile of a generation program
TRACE_COUNTER = "model.nemotronh.trace"

#: leaves that are float32 whatever the model's dtype
FLOAT32_LEAVES = ("router", "router_bias", "a_log", "d_skip", "dt_bias")

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def _dot(a, w):
    return jnp.dot(a, w, preferred_element_type=_F32)


def _add(x, out32):
    """A residual add in float32, back in the stream's dtype."""
    return (x.astype(_F32) + out32).astype(x.dtype)


def _heads(rows, n):
    """``(B, S, n * d)`` rows of heads side by side -> ``(B, n, S, d)``."""
    b, s, w = rows.shape
    return rows.reshape(b, s, n, w // n).transpose(0, 2, 1, 3)


def grouped_gated_norm(y, z, g, groups, eps):
    """``RMSNorm`` of ``y * silu(z)`` over each of ``groups`` equal groups
    of the last axis, times the gain ``g`` over all of it. Float32."""
    v = y.astype(_F32) * jax.nn.silu(z.astype(_F32))
    vg = v.reshape(*v.shape[:-1], groups, -1)
    vg = vg * lax.rsqrt(jnp.mean(jnp.square(vg), -1, keepdims=True) + eps)
    return vg.reshape(v.shape) * g.astype(_F32)


class NemotronHModel(HybridBlock):
    """A ``nemotron_h`` language model, from its ``config.json`` keys,
    told which routed experts it holds (``experts_held``, a range of
    ``n_routed_experts``; all of them unless told)."""

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

    def __init__(self, vocab_size, hidden_size, hybrid_override_pattern,
                 num_attention_heads, num_key_value_heads, head_dim,
                 mamba_num_heads, mamba_head_dim, n_groups, ssm_state_size,
                 conv_kernel, chunk_size, moe_intermediate_size,
                 moe_shared_expert_intermediate_size, n_routed_experts,
                 num_experts_per_tok, routed_scaling_factor=1.0,
                 experts_held=None, norm_eps=1e-5,
                 max_length=4096, dtype="bfloat16", **kwargs):
        super().__init__(**kwargs)
        self._dtype = str(jnp.dtype(dtype))
        self._vocab_size = int(vocab_size)
        self._d = int(hidden_size)
        self._pattern = str(hybrid_override_pattern)
        if set(self._pattern) - {MAMBA, EXPERTS, ATTENTION}:
            raise ValueError(f"hybrid_override_pattern "
                             f"{hybrid_override_pattern!r}: this family "
                             f"builds the mixers M, E and * only")
        self._hq, self._hkv = int(num_attention_heads), \
            int(num_key_value_heads)
        if self._hq % self._hkv:
            raise ValueError(f"{self._hq} query heads over {self._hkv} "
                             f"key/value heads do not group")
        self._dh = int(head_dim)
        self._h, self._p = int(mamba_num_heads), int(mamba_head_dim)
        self._g, self._n = int(n_groups), int(ssm_state_size)
        if self._h % self._g:
            raise ValueError(f"{self._h} Mamba heads over {self._g} "
                             f"groups do not divide")
        self._di = self._h * self._p
        self._conv_dim = self._di + 2 * self._g * self._n
        self._k_conv = int(conv_kernel)
        self._chunk = int(chunk_size)
        self._f, self._fs = int(moe_intermediate_size), \
            int(moe_shared_expert_intermediate_size)
        self._e_all = int(n_routed_experts)
        held = range(self._e_all) if experts_held is None \
            else experts_held
        self._lo, self._e_held = int(held.start), len(held)
        if held.step != 1 or self._lo < 0 or held.stop > self._e_all:
            raise ValueError(f"experts_held {experts_held!r} is no range "
                             f"of the {self._e_all} routed experts")
        self._top_k = int(num_experts_per_tok)
        self._gate_scale = float(routed_scaling_factor)
        self._eps = float(norm_eps)
        self._max_length = int(max_length)
        #: each layer's index among the layers of its kind
        self._nth = [self._pattern[:i].count(c)
                     for i, c in enumerate(self._pattern)]
        self._params = {}        # layer -> {short name: Parameter}
        self._build_parameters()
        self._progs = None
        self._forward = None     # the jitted whole forward
        self._host_len = None    # host mirror of cache["len"]
        self._hits = collections.deque()   # (counter, device count) a call

    # -- parameters ------------------------------------------------------
    def _leaves(self, kind):
        """``[(short name, shape)]`` of one layer of ``kind``, its
        pre-norm first."""
        d = self._d
        out = [("norm", (d,))]
        if kind == MAMBA:
            h = self._h
            return out + [
                ("w_in", (d, self._di + self._conv_dim + h)),
                ("conv_w", (self._k_conv, self._conv_dim)),
                ("conv_b", (self._conv_dim,)), ("dt_bias", (h,)),
                ("a_log", (h,)), ("d_skip", (h,)), ("gnorm", (self._di,)),
                ("w_out", (self._di, d))]
        if kind == EXPERTS:
            e, f, fs = self._e_held, self._f, self._fs
            return out + [
                ("router", (d, self._e_all)),
                ("router_bias", (self._e_all,)),
                ("e_up", (e, f, d)), ("e_down", (e, f, d)),
                ("s_up", (d, fs)), ("s_down", (fs, d))]
        q, kv = self._hq * self._dh, self._hkv * self._dh
        return out + [("w_q", (d, q)), ("w_k", (d, kv)), ("w_v", (d, kv)),
                      ("w_o", (q, d))]

    def _add_param(self, layer, short, shape):
        dtype = "float32" if short in FLOAT32_LEAVES else self._dtype
        init = "ones" if short in ("norm", "gnorm", "final_norm",
                                   "d_skip") else None
        p = Parameter(short, grad_req="null", shape=shape, dtype=dtype,
                      init=init)
        name = short if layer is None else f"layers_{layer}_{short}"
        setattr(self, name, p)
        self._params.setdefault(layer, {})[short] = p

    def _build_parameters(self):
        self._add_param(None, "embed", (self._vocab_size, self._d))
        self._add_param(None, "final_norm", (self._d,))
        self._add_param(None, "head", (self._d, self._vocab_size))
        for li, kind in enumerate(self._pattern):
            for short, shape in self._leaves(kind):
                self._add_param(li, short, shape)

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
        return ([grab(self._params[li]) for li in range(len(self._pattern))],
                grab(self._params[None]))

    # -- the mixers --------------------------------------------------------
    def _mamba(self, p, u, conv, scan):
        """The Mamba-2 mixer around its two stateful steps: ``conv(xbc)``
        the causal convolution and ``scan(x, dt, a, b, c, d)`` the
        recurrence, each returning its output and its new state.
        ``u`` (..., D) -> (..., D) float32, and the two states."""
        di, cd, lead = self._di, self._conv_dim, u.shape[:-1]
        proj = _dot(u, p["w_in"])
        z, dt = proj[..., :di], proj[..., di + cd:]
        pre, tail = conv(proj[..., di:di + cd].astype(u.dtype))
        xbc = jax.nn.silu(pre)
        gn = self._g * self._n
        with _scope("ssd"):
            y, state = scan(
                xbc[..., :di].reshape(*lead, self._h, self._p),
                jax.nn.softplus(dt + p["dt_bias"]),
                -jnp.exp(p["a_log"]),
                xbc[..., di:di + gn].reshape(*lead, self._g, self._n),
                xbc[..., di + gn:].reshape(*lead, self._g, self._n),
                p["d_skip"])
        y = grouped_gated_norm(y.reshape(*lead, di), z, p["gnorm"],
                               self._g, self._eps)
        return _dot(y.astype(u.dtype), p["w_out"]), state, tail

    def _mamba_rows(self, p, u, h0, tail, n_valid):
        """``T`` rows of one sequence from state ``h0`` (H, P, N) and
        ``tail`` (K-1, C); both come back as after row ``n_valid - 1``."""
        return self._mamba(
            p, u,
            lambda x: _ssm.causal_conv_chunk(x, p["conv_w"], p["conv_b"],
                                             tail, n_valid),
            lambda *a: _ssm.ssd_scan(*a, h0, n_valid, self._chunk))

    def _mamba_tick(self, p, u, h, tail, live):
        """One position of every slot: ``u`` (B, D), ``h`` (B, H, P, N),
        ``tail`` (B, K-1, C); rows that are not ``live`` keep both."""
        return self._mamba(
            p, u,
            lambda x: _ssm.causal_conv_step(x, p["conv_w"], p["conv_b"],
                                            tail, live),
            lambda *a: _ssm.ssd_step(*a, h, live))

    def _experts(self, p, u32, dtype, hit):
        """The held experts' part of a routed layer and the shared
        expert, (T, D) float32; appends to ``hit`` the number of held
        experts the rows fall on."""
        z = u32.astype(dtype)
        with _scope("moe"):
            routed, n_hit = _moe.routed_layer(
                u32, z, p, self._top_k, self._lo, self._gate_scale,
                up_t=True)
            hit.append(n_hit)
            shared = _moe.relu2(_dot(z, p["s_up"])).astype(dtype)
            return routed + _dot(shared, p["s_down"])

    def _qkv(self, p, u):
        return tuple(_dot(u, p[w]).astype(u.dtype)
                     for w in ("w_q", "w_k", "w_v"))

    def _out(self, p, o):
        """``o`` (B, Hq, S, dh) -> (B, S, D) float32."""
        b, _, s, _ = o.shape
        o = o.transpose(0, 2, 1, 3).reshape(b, s, self._hq * self._dh)
        return _dot(o.astype(p["w_o"].dtype), p["w_o"])

    def _attend(self, p, q, k, v, valid):
        """Grouped causal attention of rows ``q`` (T, Hq dh) over K/V
        split into heads, (1, Hkv, S, dh), under ``valid`` (T, S)."""
        o = _att.masked_attention(
            _heads(q[None], self._hq), k, v, valid[None, None],
            1.0 / math.sqrt(self._dh))
        return self._out(p, o)[0]

    def _logits(self, top, x):
        with _scope("lm_head"):
            z = _rms32(x, top["final_norm"], self._eps).astype(x.dtype)
            return _dot(z, top["head"])

    # -- the cache -----------------------------------------------------------
    def init_paged_cache(self, batch_size, n_pages, page_size,
                         max_length=None, dtype=None):
        """Two kinds of state under one slot. ``"ssm"``: a float32 state
        ``(B, H, P, N)`` and ``"conv"`` a tail ``(B, K - 1, C)`` for each
        Mamba-2 layer (tuples, in the layers' order). ``"k"``, ``"v"``: a
        paged pool of rows ``(n_pages, page_size, Hkv dh)`` for each
        attention layer, all under ``"table"`` (B, P_max) and ``"len"``
        (B,); page 0 is the scrap page."""
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
        b, dt = int(batch_size), jnp.dtype(self._dtype)
        n_m = self._pattern.count(MAMBA)
        n_a = self._pattern.count(ATTENTION)
        pool = (int(n_pages), ps, self._hkv * self._dh)
        return {
            "ssm": tuple(jnp.zeros((b, self._h, self._p, self._n), _F32)
                         for _ in range(n_m)),
            "conv": tuple(jnp.zeros((b, self._k_conv - 1, self._conv_dim),
                                    dt) for _ in range(n_m)),
            "k": tuple(jnp.zeros(pool, dt) for _ in range(n_a)),
            "v": tuple(jnp.zeros(pool, dt) for _ in range(n_a)),
            "table": jnp.zeros((b, s // ps), jnp.int32),
            "len": jnp.zeros((b,), jnp.int32),
        }

    # -- the programs ----------------------------------------------------------
    def _prefill_body(self, layers, top, tokens, start, n_valid, slot,
                      pages, cache, fresh):
        """One chunk of ``slot``. ``fresh``: the prompt's only chunk, at
        position 0 (no state is read, the chunk attends itself)."""
        w = tokens.shape[1]
        ps = cache["k"][0].shape[1]
        pos = start + jnp.arange(w, dtype=jnp.int32)
        at_zero = start == 0
        ssm, conv = list(cache["ssm"]), list(cache["conv"])
        kp, vp = list(cache["k"]), list(cache["v"])
        page_ids = lax.dynamic_slice(pages, (start // ps,), (w // ps,))
        x = jnp.take(top["embed"], tokens[0], axis=0)
        hit = []
        for li, kind in enumerate(self._pattern):
            p, j = layers[li], self._nth[li]
            u32 = _rms32(x, p["norm"], self._eps)
            u = u32.astype(x.dtype)
            if kind == MAMBA:
                h0 = lax.dynamic_index_in_dim(ssm[j], slot, 0, False)
                tail = lax.dynamic_index_in_dim(conv[j], slot, 0, False)
                # a chunk at position 0 starts a sequence: nothing of the
                # slot's last tenant is read
                h0 = jnp.where(at_zero, 0.0, h0)
                tail = jnp.where(at_zero, jnp.zeros_like(tail), tail)
                out, h_end, tail = self._mamba_rows(p, u, h0, tail, n_valid)
                ssm[j] = lax.dynamic_update_index_in_dim(ssm[j], h_end,
                                                         slot, 0)
                conv[j] = lax.dynamic_update_index_in_dim(conv[j], tail,
                                                          slot, 0)
            elif kind == EXPERTS:
                out = self._experts(p, u32, x.dtype, hit)
            else:
                with _scope("attention"):
                    q, k, v = self._qkv(p, u)
                    k_new, v_new = _heads(k[None], self._hkv), \
                        _heads(v[None], self._hkv)
                    kp[j] = _att.write_pages(kp[j], page_ids, k_new)
                    vp[j] = _att.write_pages(vp[j], page_ids, v_new)
                    if fresh:
                        k_s, v_s, key_pos = k_new, v_new, pos
                    else:
                        k_s = _att.gather_pages(kp[j], pages[None],
                                                self._hkv)
                        v_s = _att.gather_pages(vp[j], pages[None],
                                                self._hkv)
                        key_pos = jnp.arange(k_s.shape[2], dtype=jnp.int32)
                    out = self._attend(p, q, k_s, v_s,
                                       key_pos[None, :] <= pos[:, None])
            x = _add(x, out)
        row = lax.dynamic_slice_in_dim(x, jnp.clip(n_valid - 1, 0, w - 1),
                                       1, 0)
        new = {"ssm": tuple(ssm), "conv": tuple(conv), "k": tuple(kp),
               "v": tuple(vp), "table": cache["table"].at[slot].set(pages),
               "len": cache["len"].at[slot].set(start + n_valid)}
        return self._logits(top, row), new, sum(hit)

    def _decode_body(self, layers, top, tokens, active, cache):
        b = tokens.shape[0]
        ps = cache["k"][0].shape[1]
        t = cache["len"]
        live = active > 0
        ssm, conv = list(cache["ssm"]), list(cache["conv"])
        kp, vp = list(cache["k"]), list(cache["v"])
        # an inactive row's write lands in the scrap page
        page = jnp.where(live, cache["table"][jnp.arange(b), jnp.minimum(
            t // ps, cache["table"].shape[1] - 1)], 0)
        seen = jnp.arange(cache["table"].shape[1] * ps,
                          dtype=jnp.int32)[None, :] <= t[:, None]
        x = jnp.take(top["embed"], tokens, axis=0)
        hit = []
        for li, kind in enumerate(self._pattern):
            p, j = layers[li], self._nth[li]
            u32 = _rms32(x, p["norm"], self._eps)
            u = u32.astype(x.dtype)
            if kind == MAMBA:
                out, ssm[j], conv[j] = self._mamba_tick(p, u, ssm[j],
                                                        conv[j], live)
            elif kind == EXPERTS:
                out = self._experts(p, u32, x.dtype, hit)
            else:
                with _scope("attention"):
                    q, k, v = self._qkv(p, u)
                    kp[j] = _att.write_rows(kp[j], page, t % ps, k)
                    vp[j] = _att.write_rows(vp[j], page, t % ps, v)
                    o = _att.rows_decode_attention(
                        q.reshape(b, self._hq, self._dh),
                        _att.gather_rows(kp[j], cache["table"]),
                        _att.gather_rows(vp[j], cache["table"]), seen,
                        (self._hkv, self._hkv), 1.0 / math.sqrt(self._dh))
                    out = self._out(p, o[:, :, None])[:, 0]
            x = _add(x, out)
        new = {"ssm": tuple(ssm), "conv": tuple(conv), "k": tuple(kp),
               "v": tuple(vp), "table": cache["table"],
               "len": t + live.astype(jnp.int32)}
        return self._logits(top, x), new, sum(hit)

    def _ensure_programs(self):
        if self._progs is not None:
            return self._progs

        def named(fn, name):
            def wrapper(*args):
                telemetry.counter(TRACE_COUNTER)
                tracing.flight.record("compile", what="model.nemotronh")
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
            "fresh": jax.jit(named(fresh, "nemotronh_paged_fresh"),
                             donate_argnums=(6,)),
            "chunk": jax.jit(named(chunk, "nemotronh_paged_chunk"),
                             donate_argnums=(7,)),
            "decode": jax.jit(named(self._decode_body,
                                    "nemotronh_paged_decode"),
                              donate_argnums=(4,)),
            "advance": jax.jit(named(advance, "nemotronh_paged_advance"),
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
        ps = cache["k"][0].shape[1]
        if tokens.shape[1] % ps:
            raise ValueError(f"chunk width {tokens.shape[1]} must be a "
                             f"multiple of page_size {ps}")
        if int(start) % ps:
            raise ValueError(f"chunk start {start} must be a multiple of "
                             f"page_size {ps}")
        if fresh and int(start) != 0:
            raise ValueError("fresh prefill starts at 0 by definition")
        n = int(n_valid)
        self._note_len(cache)[int(slot)] = int(start) + n
        telemetry.counter("model.nemotronh.ssd_token_layers.prefill",
                          n * self._pattern.count(MAMBA))
        pr = self._ensure_programs()
        layers, top = self._datas()
        pages = jnp.asarray(pages, jnp.int32)
        if fresh:
            out = pr["fresh"](layers, top, tokens, jnp.int32(n),
                              jnp.int32(slot), pages, cache)
        else:
            out = pr["chunk"](layers, top, tokens, jnp.int32(start),
                              jnp.int32(n), jnp.int32(slot), pages, cache)
        return self._count_experts("model.nemotronh.experts_hit.prefill",
                                   out)

    def decode_step_paged(self, tokens, active, cache):
        """One decode step for every slot: each active row's token is
        written at its ``len`` through the page table, attends every
        position up to its own, steps every Mamba-2 state, and ``len`` is
        bumped. Inactive rows ride along: their pool writes land in the
        scrap page; their states, tails and ``len`` stand still. Returns
        ``(logits (B, V) float32, cache)``; the cache is donated."""
        active_h = onp.asarray(active) > 0
        lens = self._note_len(cache)
        telemetry.counter("model.nemotronh.keys_attended",
                          int((lens[active_h] + 1).sum())
                          * self._pattern.count(ATTENTION))
        telemetry.counter("model.nemotronh.ssd_token_layers.decode",
                          int(active_h.sum()) * self._pattern.count(MAMBA))
        lens[active_h] += 1
        layers, top = self._datas()
        return self._count_experts(
            "model.nemotronh.experts_hit.decode",
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
        through every layer as the generation programs compute it (no
        cache: zero states, each row attending what precedes it)."""
        toks = tokens._data if isinstance(tokens, NDArray) \
            else jnp.asarray(tokens)
        layers, top = self._datas()
        if self._forward is None:
            def nemotronh_forward(layers, top, toks):
                return jnp.stack([self._forward_row(layers, top, r)
                                  for r in toks])
            self._forward = jax.jit(nemotronh_forward)
        return NDArray(self._forward(layers, top, toks.astype(jnp.int32)))

    def _forward_row(self, layers, top, row):
        t = row.shape[0]
        pos = jnp.arange(t, dtype=jnp.int32)
        x = jnp.take(top["embed"], row, axis=0)
        h0 = jnp.zeros((self._h, self._p, self._n), _F32)
        tail = jnp.zeros((self._k_conv - 1, self._conv_dim), x.dtype)
        for li, kind in enumerate(self._pattern):
            p = layers[li]
            u32 = _rms32(x, p["norm"], self._eps)
            u = u32.astype(x.dtype)
            if kind == MAMBA:
                out = self._mamba_rows(p, u, h0, tail, jnp.int32(t))[0]
            elif kind == EXPERTS:
                out = self._experts(p, u32, x.dtype, [])
            else:
                q, k, v = self._qkv(p, u)
                out = self._attend(p, q, _heads(k[None], self._hkv),
                                   _heads(v[None], self._hkv),
                                   pos[None, :] <= pos[:, None])
            x = _add(x, out)
        return self._logits(top, x)
