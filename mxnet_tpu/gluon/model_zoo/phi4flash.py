"""The ``phi4flash`` decoder family: state-space layers, differential
window attention, and a cross-decoder of gated memory units and
cross-attention that reads ONE layer's keys and values.

A third decoder family beside ``gpt.py`` and ``dots3.py``, built for
SERVING through ``serving.GenerationEngine``'s paged path
(docs/SERVING.md "The model-engine contract").

The layers (0-based ``l``, ``L`` of them, ``half = L / 2``). Every layer:
``h = x + Mixer_l(LN(x))``, ``y = h + MLP(LN'(h))``; ``LN`` is LayerNorm
with gain and bias; ``MLP(u) = W_down (silu(g) * v)``, ``[g, v] =
W_gate_up u``, no bias. No positional encoding of any kind. A final
LayerNorm; the head is the embedding transposed. ``Mixer_l``:

* **even ``l <= half``, Mamba-1.** Per token ``t``: ``[x_t, z_t] = W_in
  u_t`` (``C = expand * hidden`` each); ``xc_t = silu(sum_{k<K} w_k *
  x_{t-K+1+k} + b_c)`` (depthwise, causal, ``K = d_conv``); ``[r_t, B_t,
  C_t] = W_x xc_t`` (``dt_rank``, ``N``, ``N``); the step ``dt_t =
  softplus(W_dt r_t + b_dt)``; ``h_t = exp(dt_t (x) A) * h_{t-1} + (dt_t *
  xc_t) (x) B_t``, ``A = -exp(A_log)``; ``m_t = h_t C_t + D * xc_t``;
  out ``= W_out (m_t * silu(z_t))``. **Layer ``half``'s ``m_t``** (before
  the gate) is the cross-decoder's memory.
* **odd ``l < half``, differential window attention**, window
  ``sliding_window``, the token's own position counted. ``[q, k, v] =
  W_qkv u + b`` (``Hq``, ``Hkv``, ``Hkv`` heads of ``d``). Heads pair up:
  ``q1, q2`` the even and odd query heads, ``k1, k2`` likewise, ``v`` a
  pair's two value heads side by side (``2 d`` wide); query pair ``j``
  reads K/V pair ``j // (Hq / Hkv)``. ``a_i = softmax(q_i k_i^T /
  sqrt(d) + mask) v``; ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0``,
  four learned ``d``-vectors, ``lam0(l) = 0.8 - 0.6 exp(-0.3 l)``; ``o =
  RMSNorm_2d(a_1 - lam a_2) * (1 - lam0)``; out ``= W_o o + b``.
* **``l = half + 1``, differential full causal attention**: the same
  with no window. Its K and V rows are the ONE paged cache of the model.
* **even ``l >= half + 2``, gated memory unit**: out ``= W_out (m_t *
  silu(W_in u_t))``, ``m_t`` layer ``half``'s memory at the same
  position.
* **odd ``l >= half + 3``, differential cross-attention**: its own
  ``W_q``, ``lam``, sub-norm and ``W_o``; K and V are layer ``half +
  1``'s, causal.

So a position keeps three kinds of state under one slot: a recurrent
state ``(N, C)`` float32 and a tail of ``K - 1`` inputs for each of the
``L / 4 + 1`` Mamba layers, a ring of plain K/V rows for each of the
``L / 4`` window layers, and one paged K/V pool, layer ``half + 1``'s,
which ``L / 4`` layers read. A recurrence can be corrupted merely by
being computed, so: a chunk padded to its bucket stops state and tail at
``n_valid``; an inactive decode row leaves state, tail and ring as they
were; a chunk at ``start > 0`` takes state and tail up where the last
chunk left them, one at ``start == 0`` clears them. None of this state
can be shared between requests, so the family takes no prefix reuse.

**A prefill chunk does not need the whole depth.** Layers above ``half +
1`` read only layer ``half``'s memory at their own position and layer
``half + 1``'s keys, so a prompt's chunks run the self-decoder (layers
``0 .. half``, and layer ``half + 1``'s K/V projection into the pool)
and only the prompt's LAST position runs layer ``half + 1``'s own
attention, the cross-decoder and the head. The engine says which chunk
is last (``generation_support["prefill_last"]``).

Layers of one kind have one shape, so their leaves are STACKED on a
leading axis (``self_*``: ``L / 4`` Mamba/window pairs; ``cross_*``:
``L / 4 - 1`` GMU/cross pairs; ``mid_*``: layers ``half`` and ``half +
1``) and the programs ``lax.scan`` over the pairs: a program holds one
pair's code, whatever the depth. The recurrent state is held ``(N, C)``
and ``A_log`` likewise (``ops/ssm.py``: the minor dimension a multiple
of 128).

Parameters are leaves of the model's ``dtype`` (bfloat16 unless told
otherwise) with ``grad_req="null"``; float32 whatever the dtype:
``A_log``, ``D``, ``b_dt`` and the ``lam`` vectors. The recurrence,
``exp``, ``softplus``, softmax, residual adds and logits are float32.
Every generation program takes the parameters as runtime arguments.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as onp
from jax import lax

from ... import telemetry, tracing
from ...ndarray.ndarray import NDArray
from ...ops import attention as _att
from ...ops import ssm as _ssm
from ..block import HybridBlock
from ..parameter import Parameter

__all__ = ["Phi4FlashModel"]

_scope = jax.named_scope
_F32 = jnp.float32

#: counters that count a trace or a compile of a generation program
TRACE_COUNTER = "model.phi4flash.trace"
#: eps of the differential layers' sub-norm (Ye et al., arXiv:2410.05258)
SUBNORM_EPS = 1e-5

_FLOAT32_LEAVES = ("a_log", "d_skip", "b_dt", "lam_q1", "lam_k1", "lam_q2",
                   "lam_k2")


def lambda_init(layer):
    """``lam0`` of layer ``layer`` (0-based)."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


# ---------------------------------------------------------------------------
# pure pieces: each takes a layer's arrays ``p`` (short name -> array)
# ---------------------------------------------------------------------------
def _ln(x, g, b, eps):
    x32 = x.astype(_F32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), -1, keepdims=True)
    y = (x32 - mu) * lax.rsqrt(var + eps)
    return (y * g.astype(_F32) + b.astype(_F32)).astype(x.dtype)


def _dot(a, w):
    return jnp.dot(a, w, preferred_element_type=_F32)


def _add(x, out32):
    """A residual add in float32, back in the stream's dtype."""
    return (x.astype(_F32) + out32).astype(x.dtype)


def _mlp(p, h, eps):
    with _scope("mlp"):
        z = _ln(h, p["ln2_g"], p["ln2_b"], eps)
        gu = _dot(z, p["w_gate_up"])
        f = gu.shape[-1] // 2
        act = (jax.nn.silu(gu[..., :f]) * gu[..., f:]).astype(h.dtype)
        return _add(h, _dot(act, p["w_down"]))


def _ssm_inputs(p, xc32, n_state):
    """``B_t``, ``C_t`` and the step ``dt_t`` of each row, float32."""
    rank = p["w_dt"].shape[0]
    rbc = _dot(xc32.astype(p["w_x"].dtype), p["w_x"])
    dt = jax.nn.softplus(
        _dot(rbc[..., :rank].astype(p["w_dt"].dtype), p["w_dt"])
        + p["b_dt"].astype(_F32))
    return dt, rbc[..., rank:rank + n_state], rbc[..., rank + n_state:]


def _mamba(p, u, conv, recur, n_state):
    """The Mamba mixer around its two stateful steps: ``conv(x)`` is the
    causal convolution and ``recur(xc, dt, a, b, c, d)`` the recurrence,
    each returning its output and its new state. Returns the memory
    ``m`` float32 (before the gate), the mixer's output float32, and
    the two states."""
    c = p["w_out"].shape[0]
    xz = jnp.dot(u, p["w_in"])
    x, z = xz[..., :c], xz[..., c:]
    pre, new_tail = conv(x)
    xc = jax.nn.silu(pre)
    dt, b, cc = _ssm_inputs(p, xc, n_state)
    m, h = recur(xc, dt, -jnp.exp(p["a_log"].astype(_F32)), b, cc,
                 p["d_skip"])
    out = _dot((m * jax.nn.silu(z.astype(_F32))).astype(u.dtype),
               p["w_out"])
    return m, out, h, new_tail


def _mamba_rows(p, u, h0, tail, n_valid):
    """``T`` rows of one sequence from state ``h0`` (N, C) and ``tail``
    (K-1, C); state and tail come back as after row ``n_valid - 1``."""
    def recur(*args):
        with _scope("ssm_scan"):
            return _ssm.selective_scan(*args, h0, n_valid)

    return _mamba(p, u, lambda x: _ssm.causal_conv_chunk(
        x, p["conv_w"], p["conv_b"], tail, n_valid), recur, h0.shape[0])


def _mamba_tick(p, u, h, tail, live):
    """One position of every slot: ``u`` (B, D), ``h`` (B, N, C),
    ``tail`` (B, K-1, C); rows that are not ``live`` keep both."""
    def recur(*args):
        with _scope("ssm_step"):
            return _ssm.selective_step(*args, h, live)

    return _mamba(p, u, lambda x: _ssm.causal_conv_step(
        x, p["conv_w"], p["conv_b"], tail, live), recur, h.shape[1])


def _gmu(p, u, m):
    """The gated memory unit: ``W_out (m * silu(W_in u))``."""
    with _scope("gmu"):
        gate = jax.nn.silu(_dot(u, p["w_in"]))
        return _dot((m.astype(_F32) * gate).astype(u.dtype), p["w_out"])


def _heads(rows, n):
    """``(B, S, n * d)`` rows of heads side by side -> ``(B, n, S, d)``."""
    b, s, w = rows.shape
    return rows.reshape(b, s, n, w // n).transpose(0, 2, 1, 3)


def _diff_queries(q, hq, hkv):
    """``q`` (B, Sq, Hq * d) -> (B, Hq, Sq, d) with the heads REORDERED so
    that grouped attention (``ops.attention.masked_attention``: query
    head ``h'`` reads key head ``h' // G`` and, of value heads twice as
    wide, head ``h' // 2G``, ``G = Hq / Hkv``) is the differential
    pairing: head ``h = 2 (G p + g) + i`` (pair ``G p + g``, half ``i``)
    goes to ``h' = 2 G p + G i + g``."""
    b, sq, w = q.shape
    g = hq // hkv
    q = q.reshape(b, sq, hkv // 2, g, 2, w // hq)
    return q.transpose(0, 2, 4, 3, 1, 5).reshape(b, hq, sq, w // hq)


def _diff_combine(p, o, lam0, hq, hkv):
    """``o`` (B, Hq, Sq, 2 d), in ``_diff_queries``' order -> the mixer's
    output (B, Sq, D) float32: ``W_o (RMSNorm(a_1 - lam a_2) (1 - lam0))
    + b``."""
    b, _, sq, d2 = o.shape
    g = hq // hkv
    o = o.astype(_F32).reshape(b, hkv // 2, 2, g, sq, d2)
    lam = jnp.exp(jnp.sum(p["lam_q1"] * p["lam_k1"])) \
        - jnp.exp(jnp.sum(p["lam_q2"] * p["lam_k2"])) + lam0
    a = o[:, :, 0] - lam * o[:, :, 1]              # (B, P, G, Sq, 2d)
    a = a * lax.rsqrt(jnp.mean(jnp.square(a), -1, keepdims=True)
                      + SUBNORM_EPS) * p["sub_g"].astype(_F32)
    a = (a * (1.0 - lam0)).transpose(0, 3, 1, 2, 4).reshape(
        b, sq, (hq // 2) * d2)
    return _dot(a.astype(p["w_o"].dtype), p["w_o"]) \
        + p["b_o"].astype(_F32)


def _ring_positions(last, ring):
    """The position each ring entry holds once ``last`` is written: the
    largest ``p <= last`` with ``p mod ring == j`` (negative: none yet)."""
    j = jnp.arange(ring, dtype=jnp.int32)
    return last[..., None] - jnp.mod(last[..., None] - j, ring)


class Phi4FlashModel(HybridBlock):
    """A ``phi4flash`` language model, from its ``config.json`` keys and
    the Mamba sizes the config leaves to the family's convention
    (``d_state`` 16, ``d_conv`` 4, ``expand`` 2, ``dt_rank`` ``ceil(hidden
    / 16)``)."""

    @property
    def generation_support(self):
        """What ``serving.GenerationEngine`` may be asked for with this
        family; it refuses every other option by name. ``prefill_last``
        asks the engine to say which chunk is a prompt's last."""
        return {
            "dense_cache": False, "paged": True, "prefix_cache": False,
            "quantize": False, "kv_dtype": False, "speculative": False,
            "decode_ticks": False, "mesh_layout": False, "lora": False,
            "cache_dtype": (self._dtype,), "compute_dtype": (self._dtype,),
            "prefill_chunk_max": self._chunk_max, "prefill_last": True,
        }

    def __init__(self, vocab_size, hidden_size, num_hidden_layers,
                 num_attention_heads, num_key_value_heads,
                 intermediate_size, sliding_window, mb_per_layer=2,
                 layer_norm_eps=1e-5, d_state=16, d_conv=4, expand=2,
                 dt_rank=None, max_length=8192, prefill_chunk=512,
                 dtype="bfloat16", **kwargs):
        super().__init__(**kwargs)
        self._dtype = str(jnp.dtype(dtype))
        self._vocab_size = int(vocab_size)
        self._d = int(hidden_size)
        self._layers = int(num_hidden_layers)
        if int(mb_per_layer) != 2 or self._layers % 4 or self._layers < 8:
            raise ValueError(
                "the family alternates a state-space and an attention "
                "layer (mb_per_layer 2) in two halves of equal depth: "
                f"num_hidden_layers {num_hidden_layers} must be a "
                "multiple of 4, at least 8")
        self._hq, self._hkv = int(num_attention_heads), \
            int(num_key_value_heads)
        if self._d % self._hq or self._hq % self._hkv or self._hkv % 2:
            raise ValueError(
                f"{self._hq} query heads over {self._hkv} key/value heads "
                f"of {self._d} units do not pair up")
        self._dh = self._d // self._hq
        self._f = int(intermediate_size)
        self._window = int(sliding_window)
        self._eps = float(layer_norm_eps)
        self._n, self._k = int(d_state), int(d_conv)
        self._c = int(expand) * self._d
        self._rank = int(dt_rank) if dt_rank is not None \
            else -(-self._d // 16)
        self._n_self = self._layers // 4
        self._n_cross = self._layers // 4 - 1
        self._max_length = int(max_length)
        #: widest prefill chunk the ring leaves room for: a chunk is
        #: written before it is attended, and must not overwrite a
        #: position its first query still sees
        self._chunk_max = int(prefill_chunk)
        self._ring = 1 << (self._window + self._chunk_max - 2).bit_length()
        self._params = {}        # group -> {short name: Parameter}
        self._build_parameters()
        self._progs = None
        self._forward = None     # the jitted whole forward
        self._host_len = None    # host mirror of cache["len"]

    # -- parameters ------------------------------------------------------
    def _leaves(self, kind):
        """``[(short name, shape)]`` of one layer of ``kind``: its mixer's
        leaves, then the block's (two LayerNorms and the MLP)."""
        d, c, n, dh = self._d, self._c, self._n, self._dh
        kv = self._hkv * dh
        lam = [(f"lam_{x}", (dh,)) for x in ("q1", "k1", "q2", "k2")]
        attn_out = lam + [("sub_g", (2 * dh,)), ("w_o", (d, d)),
                          ("b_o", (d,))]
        mixer = {
            "ssm": [("w_in", (d, 2 * c)), ("conv_w", (self._k, c)),
                    ("conv_b", (c,)), ("w_x", (c, self._rank + 2 * n)),
                    ("w_dt", (self._rank, c)), ("b_dt", (c,)),
                    ("a_log", (n, c)), ("d_skip", (c,)),
                    ("w_out", (c, d))],
            "attn": [("w_qkv", (d, d + 2 * kv)), ("b_qkv", (d + 2 * kv,))]
            + attn_out,
            "gmu": [("w_in", (d, c)), ("w_out", (c, d))],
            "cross": [("w_q", (d, d)), ("b_q", (d,))] + attn_out,
        }[kind]
        return mixer + [("ln1_g", (d,)), ("ln1_b", (d,)), ("ln2_g", (d,)),
                        ("ln2_b", (d,)), ("w_gate_up", (d, 2 * self._f)),
                        ("w_down", (self._f, d))]

    def _groups(self):
        """Group of leaves -> (mixer kind, the layers stacked in it;
        ``None``: one layer, not stacked)."""
        half = self._layers // 2
        return {
            "self_m": ("ssm", range(0, half, 2)),
            "self_a": ("attn", range(1, half, 2)),
            "mid_m": ("ssm", None), "mid_a": ("attn", None),
            "cross_g": ("gmu", range(half + 2, self._layers, 2)),
            "cross_a": ("cross", range(half + 3, self._layers, 2)),
        }

    def _build_parameters(self):
        def add(group, short, shape):
            init = "ones" if short.endswith("_g") or short == "d_skip" \
                else "zeros" if short.startswith("b_") \
                or short.endswith("_b") else None
            dtype = "float32" if short in _FLOAT32_LEAVES else self._dtype
            p = Parameter(short, grad_req="null", shape=shape, dtype=dtype,
                          init=init)
            name = short if group is None else f"{group}_{short}"
            setattr(self, name, p)
            self._params.setdefault(group, {})[short] = p

        add(None, "embed", (self._vocab_size, self._d))
        add(None, "final_g", (self._d,))
        add(None, "final_b", (self._d,))
        for group, (kind, layers) in self._groups().items():
            lead = () if layers is None else (len(layers),)
            for short, shape in self._leaves(kind):
                add(group, short, lead + shape)

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
        """The parameters' buffers as the programs take them: group ->
        {short name: array} (``"top"`` the embedding and final norm)."""
        return {("top" if g is None else g):
                {k: p.data()._data for k, p in ps.items()}
                for g, ps in self._params.items()}

    def _lam0(self, group):
        """``lam0`` of each layer stacked in ``group``."""
        return jnp.asarray([lambda_init(l) for l in
                            self._groups()[group][1]], _F32)

    @property
    def _lam0_mid(self):
        """``lam0`` of the one full-attention layer, ``half + 1``."""
        return jnp.float32(lambda_init(self._layers // 2 + 1))

    # -- layers ------------------------------------------------------------
    def _qkv(self, p, u):
        """Flat rows ``q`` (.., Hq d), ``k``, ``v`` (.., Hkv d)."""
        qkv = (_dot(u, p["w_qkv"]) + p["b_qkv"].astype(_F32)).astype(
            u.dtype)
        kv = self._hkv * self._dh
        return qkv[..., :self._d], qkv[..., self._d:self._d + kv], \
            qkv[..., self._d + kv:]

    def _kv_only(self, p, u):
        """The K and V rows of ``_qkv`` without its queries."""
        kv = (_dot(u, p["w_qkv"][:, self._d:])
              + p["b_qkv"][self._d:].astype(_F32)).astype(u.dtype)
        return kv[..., :kv.shape[-1] // 2], kv[..., kv.shape[-1] // 2:]

    def _attend(self, p, q, k, v, valid, lam0):
        """Differential attention of flat queries ``q`` (B, Sq, Hq d)
        over flat rows ``k``, ``v`` (B, S, Hkv d) under ``valid``
        (broadcastable to (B, Hq, Sq, S)) -> (B, Sq, D) float32."""
        o = _att.masked_attention(
            _diff_queries(q, self._hq, self._hkv), _heads(k, self._hkv),
            _heads(v, self._hkv // 2), valid, 1.0 / math.sqrt(self._dh))
        return _diff_combine(p, o, lam0, self._hq, self._hkv)

    def _attend_rows(self, p, q, k_rows, v_rows, valid, lam0):
        """One query a row, ``q`` (B, Hq d), over K/V rows as a pool or
        a ring holds them, (B, S, Hkv d), under ``valid`` (B, S) -> (B, D)
        float32 (``ops.attention.rows_decode_attention``: the rows are
        never split into heads)."""
        o = _att.rows_decode_attention(
            _diff_queries(q[:, None], self._hq, self._hkv)[:, :, 0],
            k_rows, v_rows, valid, (self._hkv, self._hkv // 2),
            1.0 / math.sqrt(self._dh))
        return _diff_combine(p, o[:, :, None], lam0, self._hq,
                             self._hkv)[:, 0]

    def _window_valid(self, pos, key_pos):
        """(.., Sq, S): keys a query at ``pos`` sees in a window layer."""
        diff = pos[..., :, None] - key_pos[..., None, :]
        return (key_pos[..., None, :] >= 0) & (diff >= 0) \
            & (diff < self._window)

    def _self_pair_rows(self, pm, pa, lam0, x, pos, n_valid, h0, tail,
                        keys):
        """One Mamba layer and one window layer over ``T`` rows of one
        sequence. ``keys(k, v)`` takes the rows' K and V (T, Hkv d),
        writes them where they are cached, and returns what the rows
        attend: ``(k_S, v_S, key_pos (S,))``. Returns the stream, and
        the Mamba layer's state and tail after row ``n_valid - 1``."""
        u = _ln(x, pm["ln1_g"], pm["ln1_b"], self._eps)
        _, out, h_end, new_tail = _mamba_rows(pm, u, h0, tail, n_valid)
        x = _mlp(pm, _add(x, out), self._eps)
        with _scope("swa_ring"):
            u = _ln(x, pa["ln1_g"], pa["ln1_b"], self._eps)
            q, k, v = self._qkv(pa, u)
            k_s, v_s, key_pos = keys(k, v)
            out = self._attend(pa, q[None], k_s[None], v_s[None],
                               self._window_valid(pos, key_pos)[None, None],
                               lam0)[0]
        return _mlp(pa, _add(x, out), self._eps), h_end, new_tail

    def _cross_pair(self, pg, pa, lam0, x, memory, attend):
        """One gated memory unit and one cross-attention layer over rows
        ``x`` (R, D) with their ``memory`` (R, C). ``attend(p, q, lam0)``
        attends flat queries (R, Hq d) over layer ``half + 1``'s keys
        and returns (R, D) float32."""
        u = _ln(x, pg["ln1_g"], pg["ln1_b"], self._eps)
        x = _mlp(pg, _add(x, _gmu(pg, u, memory)), self._eps)
        with _scope("cross_attend"):
            u = _ln(x, pa["ln1_g"], pa["ln1_b"], self._eps)
            q = (_dot(u, pa["w_q"]) + pa["b_q"].astype(_F32)).astype(
                u.dtype)
            out = attend(pa, q, lam0)
        return _mlp(pa, _add(x, out), self._eps)

    def _cross_decoder(self, params, x, memory, attend):
        """Layers ``half + 2 ..`` over rows ``x`` and the final norm's
        logits (R, V) float32."""
        def pair(x, xs):
            pg, pa, lam0 = xs
            return self._cross_pair(pg, pa, lam0, x, memory, attend), None

        x = lax.scan(pair, x, (params["cross_g"], params["cross_a"],
                               self._lam0("cross_a")))[0]
        with _scope("lm_head"):
            top = params["top"]
            z = _ln(x, top["final_g"], top["final_b"], self._eps)
            return lax.dot_general(z, top["embed"],
                                   (((1,), (1,)), ((), ())),
                                   preferred_element_type=_F32)

    # -- the cache -----------------------------------------------------------
    def init_paged_cache(self, batch_size, n_pages, page_size,
                         max_length=None, dtype=None):
        """Three kinds of state under one slot. ``"k"``, ``"v"``: the one
        paged pool each, layer ``half + 1``'s rows ``(n_pages, page_size,
        Hkv d)`` under ``"table"`` (B, P_max) and ``"len"`` (B,); page 0
        is the scrap page. ``"ring_k"``, ``"ring_v"``: the window layers'
        rings ``(L / 4, B, ring_size, Hkv d)``, a window and a chunk.
        ``"ssm"`` ``(L / 4 + 1, B, N, C)`` float32 and ``"conv"`` ``(L / 4
        + 1, B, K - 1, C)``: the Mamba layers' states and tails, layer
        ``half``'s last. Stacked on the layer, as the leaves are."""
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
        kv = self._hkv * self._dh
        n_ssm = self._n_self + 1
        return {
            "k": jnp.zeros((int(n_pages), ps, kv), dt),
            "v": jnp.zeros((int(n_pages), ps, kv), dt),
            "ring_k": jnp.zeros((self._n_self, b, self._ring, kv), dt),
            "ring_v": jnp.zeros((self._n_self, b, self._ring, kv), dt),
            "ssm": jnp.zeros((n_ssm, b, self._n, self._c), _F32),
            "conv": jnp.zeros((n_ssm, b, self._k - 1, self._c), dt),
            "table": jnp.zeros((b, s // ps), jnp.int32),
            "len": jnp.zeros((b,), jnp.int32),
        }

    # -- the programs ----------------------------------------------------------
    def _prefill_body(self, params, tokens, start, n_valid, slot, pages,
                      cache, fresh, last):
        """One chunk of ``slot``. ``fresh``: the prompt's only chunk, at
        position 0 (nothing cached is read). ``last``: the prompt's last
        chunk, which alone runs layer ``half + 1``'s attention, the
        cross-decoder and the head, on its last valid row."""
        w = tokens.shape[1]
        ps = _att.pool_page_size(cache["k"])
        pos = start + jnp.arange(w, dtype=jnp.int32)
        live = jnp.arange(w, dtype=jnp.int32) < n_valid
        x = jnp.take(params["top"]["embed"], tokens[0], axis=0)
        at_zero = start == 0

        def state_of(ssm, conv, i):
            """Layer ``i``'s state and tail of the slot; cleared where
            the chunk starts a sequence."""
            if fresh:
                return (jnp.zeros(ssm.shape[2:], ssm.dtype),
                        jnp.zeros(conv.shape[2:], conv.dtype))
            h0 = lax.dynamic_slice(
                ssm, (i, slot, 0, 0), (1, 1) + ssm.shape[2:])[0, 0]
            tail = lax.dynamic_slice(
                conv, (i, slot, 0, 0), (1, 1) + conv.shape[2:])[0, 0]
            return (jnp.where(at_zero, 0.0, h0),
                    jnp.where(at_zero, jnp.zeros_like(tail), tail))

        def keep(ssm, conv, i, h_end, tail):
            return (lax.dynamic_update_slice(ssm, h_end[None, None],
                                             (i, slot, 0, 0)),
                    lax.dynamic_update_slice(conv, tail[None, None],
                                             (i, slot, 0, 0)))

        at = jnp.mod(pos, self._ring)
        key_pos = _ring_positions(start + w - 1, self._ring)

        def pair(carry, xs):
            x, ssm, conv, ring_k, ring_v = carry
            pm, pa, lam0, i = xs
            h0, tail = state_of(ssm, conv, i)
            rings = [ring_k, ring_v]

            def keys(k, v):
                views = []
                for j, rows in enumerate((k, v)):
                    r = lax.dynamic_slice(
                        rings[j], (i, slot, 0, 0),
                        (1, 1) + rings[j].shape[2:])[0, 0]
                    # rows past n_valid leave the ring as it was
                    r = r.at[at].set(jnp.where(live[:, None], rows, r[at]))
                    rings[j] = lax.dynamic_update_slice(
                        rings[j], r[None, None], (i, slot, 0, 0))
                    views.append(r)
                if fresh:
                    return k, v, pos
                return views[0], views[1], key_pos

            x, h_end, tail = self._self_pair_rows(
                pm, pa, lam0, x, pos, n_valid, h0, tail, keys)
            ssm, conv = keep(ssm, conv, i, h_end, tail)
            return (x, ssm, conv, rings[0], rings[1]), None

        (x, ssm, conv, ring_k, ring_v), _ = lax.scan(
            pair, (x, cache["ssm"], cache["conv"], cache["ring_k"],
                   cache["ring_v"]),
            (params["self_m"], params["self_a"], self._lam0("self_a"),
             jnp.arange(self._n_self, dtype=jnp.int32)))

        # layer half: the Mamba layer whose memory the cross-decoder reads
        pm, pa = params["mid_m"], params["mid_a"]
        i_mid = jnp.int32(self._n_self)
        h0, tail = state_of(ssm, conv, i_mid)
        u = _ln(x, pm["ln1_g"], pm["ln1_b"], self._eps)
        memory, out, h_end, tail = _mamba_rows(pm, u, h0, tail, n_valid)
        ssm, conv = keep(ssm, conv, i_mid, h_end, tail)
        x = _mlp(pm, _add(x, out), self._eps)

        # layer half + 1: every row's K and V go to the pool
        with _scope("full_kv"):
            u = _ln(x, pa["ln1_g"], pa["ln1_b"], self._eps)
            k, v = self._kv_only(pa, u)
            page_ids = lax.dynamic_slice(pages, (start // ps,), (w // ps,))
            k_pool = _att.write_pages(cache["k"], page_ids,
                                      _heads(k[None], self._hkv))
            v_pool = _att.write_pages(cache["v"], page_ids,
                                      _heads(v[None], self._hkv))
        new = {"k": k_pool, "v": v_pool, "ring_k": ring_k, "ring_v": ring_v,
               "ssm": ssm, "conv": conv,
               "table": cache["table"].at[slot].set(pages),
               "len": cache["len"].at[slot].set(start + n_valid)}
        if not last:
            return jnp.zeros((1, self._vocab_size), _F32), new

        # the prompt's last position: layer half + 1's own attention,
        # the cross-decoder and the head, on one row
        r = jnp.clip(n_valid - 1, 0, w - 1)
        x, u, memory = (lax.dynamic_slice_in_dim(a, r, 1, 0)
                        for a in (x, u, memory))
        if fresh:
            k_rows, v_rows = k[None], v[None]
        else:
            k_rows = _att.gather_rows(k_pool, pages[None])
            v_rows = _att.gather_rows(v_pool, pages[None])
        seen = (jnp.arange(k_rows.shape[1], dtype=jnp.int32)
                < start + n_valid)[None]

        def attend(p, q, lam0):
            return self._attend_rows(p, q, k_rows, v_rows, seen, lam0)

        with _scope("full_kv"):
            q = (_dot(u, pa["w_qkv"][:, :self._d])
                 + pa["b_qkv"][:self._d].astype(_F32)).astype(u.dtype)
            out = attend(pa, q, self._lam0_mid)
        x = _mlp(pa, _add(x, out), self._eps)
        return self._cross_decoder(params, x, memory, attend), new

    def _attend_held(self, p, q, k_pool, v_pool, table, n_valid, lam0):
        """``_attend_rows`` over each slot's held pages of the pool, read
        in place: the first ``n_valid`` (B,) positions under ``table``
        (B, P_max) (``ops.attention.paged_rows_attention``, a kernel)."""
        o = _att.paged_rows_attention(
            _diff_queries(q[:, None], self._hq, self._hkv)[:, :, 0],
            k_pool, v_pool, table, n_valid, (self._hkv, self._hkv // 2),
            1.0 / math.sqrt(self._dh))
        return _diff_combine(p, o[:, :, None], lam0, self._hq,
                             self._hkv)[:, 0]

    def _decode_body(self, params, tokens, active, cache):
        b = tokens.shape[0]
        ps = _att.pool_page_size(cache["k"])
        t = cache["len"]
        live = active > 0
        rows_b = jnp.arange(b)
        x = jnp.take(params["top"]["embed"], tokens, axis=0)
        at = jnp.mod(t, self._ring)
        key_pos = _ring_positions(t, self._ring)              # (B, ring)
        in_window = self._window_valid(t[:, None], key_pos)[:, 0]

        def pair(carry, xs):
            x, ssm, conv, ring_k, ring_v = carry
            pm, pa, lam0, i = xs
            u = _ln(x, pm["ln1_g"], pm["ln1_b"], self._eps)
            _, out, h_new, tail = _mamba_tick(pm, u, ssm[i], conv[i], live)
            ssm = lax.dynamic_update_index_in_dim(ssm, h_new, i, 0)
            conv = lax.dynamic_update_index_in_dim(conv, tail, i, 0)
            x = _mlp(pm, _add(x, out), self._eps)
            with _scope("swa_ring"):
                u = _ln(x, pa["ln1_g"], pa["ln1_b"], self._eps)
                q, k, v = self._qkv(pa, u)
                views = []
                for ring, rows in ((ring_k, k), (ring_v, v)):
                    # an inactive row's ring stands still
                    old = ring[i, rows_b, at]
                    ring = ring.at[i, rows_b, at].set(
                        jnp.where(live[:, None], rows, old))
                    views.append(ring)
                ring_k, ring_v = views
                out = self._attend_rows(pa, q, ring_k[i], ring_v[i],
                                        in_window, lam0)
            x = _mlp(pa, _add(x, out), self._eps)
            return (x, ssm, conv, ring_k, ring_v), None

        (x, ssm, conv, ring_k, ring_v), _ = lax.scan(
            pair, (x, cache["ssm"], cache["conv"], cache["ring_k"],
                   cache["ring_v"]),
            (params["self_m"], params["self_a"], self._lam0("self_a"),
             jnp.arange(self._n_self, dtype=jnp.int32)))

        pm, pa = params["mid_m"], params["mid_a"]
        u = _ln(x, pm["ln1_g"], pm["ln1_b"], self._eps)
        memory, out, h_new, tail = _mamba_tick(
            pm, u, ssm[self._n_self], conv[self._n_self], live)
        ssm = ssm.at[self._n_self].set(h_new)
        conv = conv.at[self._n_self].set(tail)
        x = _mlp(pm, _add(x, out), self._eps)

        with _scope("full_kv"):
            u = _ln(x, pa["ln1_g"], pa["ln1_b"], self._eps)
            q, k, v = self._qkv(pa, u)
            # an inactive row's write lands in the scrap page
            page = jnp.where(
                live, cache["table"][rows_b, jnp.minimum(
                    t // ps, cache["table"].shape[1] - 1)], 0)
            k_pool = _att.write_rows(cache["k"], page, t % ps, k)
            v_pool = _att.write_rows(cache["v"], page, t % ps, v)

        if _att._use_pallas():
            # eight layers read each slot's held pages where they lie
            def attend(p, q, lam0):
                return self._attend_held(p, q, k_pool, v_pool,
                                         cache["table"], t + 1, lam0)
        else:
            # one gather a tick of every slot's whole table
            k_rows = _att.gather_rows(k_pool, cache["table"])
            v_rows = _att.gather_rows(v_pool, cache["table"])
            seen = jnp.arange(k_rows.shape[1],
                              dtype=jnp.int32)[None, :] <= t[:, None]

            def attend(p, q, lam0):
                return self._attend_rows(p, q, k_rows, v_rows, seen, lam0)

        with _scope("full_kv"):
            out = attend(pa, q, self._lam0_mid)
        x = _mlp(pa, _add(x, out), self._eps)
        logits = self._cross_decoder(params, x, memory, attend)
        new = {"k": k_pool, "v": v_pool, "ring_k": ring_k, "ring_v": ring_v,
               "ssm": ssm, "conv": conv, "table": cache["table"],
               "len": t + live.astype(jnp.int32)}
        return logits, new

    def _ensure_programs(self):
        if self._progs is not None:
            return self._progs

        def named(fn, name):
            def wrapper(*args):
                telemetry.counter(TRACE_COUNTER)
                tracing.flight.record("compile", what="model.phi4flash")
                return fn(*args)
            wrapper.__name__ = wrapper.__qualname__ = name
            return wrapper

        def fresh(params, tokens, n_valid, slot, pages, cache):
            return self._prefill_body(params, tokens, jnp.int32(0), n_valid,
                                      slot, pages, cache, True, True)

        def chunk(last):
            def run(params, tokens, start, n_valid, slot, pages, cache):
                return self._prefill_body(params, tokens, start, n_valid,
                                          slot, pages, cache, False, last)
            return run

        def advance(delta, cache):
            new = dict(cache)
            new["len"] = cache["len"] + delta
            return new

        self._progs = {
            "fresh": jax.jit(named(fresh, "phi4flash_paged_fresh"),
                             donate_argnums=(5,)),
            "chunk": jax.jit(named(chunk(False), "phi4flash_paged_chunk"),
                             donate_argnums=(6,)),
            "chunk_last": jax.jit(
                named(chunk(True), "phi4flash_paged_chunk_last"),
                donate_argnums=(6,)),
            "decode": jax.jit(named(self._decode_body,
                                    "phi4flash_paged_decode"),
                              donate_argnums=(3,)),
            "advance": jax.jit(named(advance, "phi4flash_paged_advance"),
                               donate_argnums=(1,)),
        }
        return self._progs

    # -- the calls the engine makes ----------------------------------------
    def _note_len(self, cache):
        b = cache["len"].shape[0]
        if self._host_len is None or self._host_len.shape[0] != b:
            self._host_len = onp.zeros((b,), "i8")
        return self._host_len

    def _count(self, rows, contexts, phase, cross_rows=None):
        """Counters of what a call computes, from lengths the host
        already holds: ``rows`` tokens through the self-decoder, whose
        positions see ``contexts`` keys (an array, their own counted)."""
        pre = "model.phi4flash."
        telemetry.counter(pre + "ssm_token_layers." + phase,
                          rows * (self._n_self + 1))
        telemetry.counter(pre + "window_keys", self._n_self * int(
            onp.minimum(contexts, self._window).sum()))
        if cross_rows is None:                  # a tick: every row is whole
            telemetry.counter(pre + "keys_attended",
                              (self._n_cross + 1) * int(contexts.sum()))
            return
        telemetry.counter(pre + "self_rows", rows)
        if cross_rows:
            telemetry.counter(pre + "cross_rows", cross_rows)
            telemetry.counter(pre + "keys_attended",
                              (self._n_cross + 1) * int(contexts[-1]))

    def prefill_paged(self, tokens, n_valid, slot, pages, cache, *,
                      start=0, fresh=False, last=True):
        """Prefill one chunk of ``slot`` (``tokens`` (1, W) int32, W a
        multiple of the page size and at most the ``prefill_chunk`` the
        model was built for; ``pages`` the slot's full page-table row;
        ``start`` the chunk's position, a multiple of the page size), or
        with ``fresh=True`` a whole prompt of at most one chunk. ``last``
        says whether the chunk is the prompt's last: one that is not runs
        the self-decoder only and returns logits that mean nothing.
        Returns ``(last valid logits (1, V), cache)``; the cache is
        donated."""
        tokens = jnp.asarray(tokens, jnp.int32)
        if tokens.ndim != 2 or tokens.shape[0] != 1:
            raise ValueError(f"paged prefill tokens must be (1, W), got "
                             f"shape {tokens.shape}")
        ps = _att.pool_page_size(cache["k"])
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
        if fresh and (int(start) != 0 or not last):
            raise ValueError("fresh prefill is a whole prompt: it starts "
                             "at 0 and is its last chunk")
        n = int(n_valid)
        self._note_len(cache)[int(slot)] = int(start) + n
        self._count(n, int(start) + 1 + onp.arange(n), "prefill",
                    cross_rows=int(bool(last)))
        pr = self._ensure_programs()
        params = self._datas()
        pages = jnp.asarray(pages, jnp.int32)
        if fresh:
            return pr["fresh"](params, tokens, jnp.int32(n), jnp.int32(slot),
                               pages, cache)
        return pr["chunk_last" if last else "chunk"](
            params, tokens, jnp.int32(start), jnp.int32(n), jnp.int32(slot),
            pages, cache)

    def decode_step_paged(self, tokens, active, cache):
        """One decode step for every slot: each active row's token is
        written at its ``len`` (the pool through the page table, rings at
        ``len mod ring_size``, states and tails in place), attends, and
        ``len`` is bumped. Inactive rows ride along: their pool writes
        land in the scrap page; their state, tail, ring and ``len`` stand
        still. Returns ``(logits (B, V) float32, cache)``; the cache is
        donated."""
        active_h = onp.asarray(active) > 0
        lens = self._note_len(cache)
        self._count(int(active_h.sum()), lens[active_h] + 1, "decode")
        lens[active_h] += 1
        return self._ensure_programs()["decode"](
            self._datas(), jnp.asarray(tokens, jnp.int32),
            jnp.asarray(active, jnp.int32), cache)

    def advance_len_paged(self, delta, cache):
        """Advance each row's valid length by ``delta`` (B,) int32.
        Cache donated."""
        self._note_len(cache)[:] += onp.asarray(delta, "i8")
        return self._ensure_programs()["advance"](
            jnp.asarray(delta, jnp.int32), cache)

    # -- the whole forward, for a user who wants logits --------------------
    def forward(self, tokens):
        """Logits (B, T, V) float32 of ``tokens`` (B, T), every position
        attending as the generation programs do (no cache, and the
        cross-decoder on every position)."""
        toks = tokens._data if isinstance(tokens, NDArray) \
            else jnp.asarray(tokens)
        if self._forward is None:
            def phi4flash_forward(params, toks):
                return jnp.stack([self._forward_row(params, r)
                                  for r in toks])
            self._forward = jax.jit(phi4flash_forward)
        return NDArray(self._forward(self._datas(),
                                     toks.astype(jnp.int32)))

    def _forward_row(self, params, row):
        t = row.shape[0]
        pos = jnp.arange(t, dtype=jnp.int32)
        n_valid = jnp.int32(t)
        dt = jnp.dtype(self._dtype)
        h0 = jnp.zeros((self._n, self._c), _F32)
        tail = jnp.zeros((self._k - 1, self._c), dt)
        x = jnp.take(params["top"]["embed"], row, axis=0)

        def pair(x, xs):
            pm, pa, lam0 = xs
            return self._self_pair_rows(
                pm, pa, lam0, x, pos, n_valid, h0, tail,
                lambda k, v: (k, v, pos))[0], None

        x = lax.scan(pair, x, (params["self_m"], params["self_a"],
                               self._lam0("self_a")))[0]
        pm, pa = params["mid_m"], params["mid_a"]
        u = _ln(x, pm["ln1_g"], pm["ln1_b"], self._eps)
        memory, out, _, _ = _mamba_rows(pm, u, h0, tail, n_valid)
        x = _mlp(pm, _add(x, out), self._eps)
        u = _ln(x, pa["ln1_g"], pa["ln1_b"], self._eps)
        q, k, v = self._qkv(pa, u)
        causal = (pos[None, :] <= pos[:, None])[None, None]

        def attend(p, q, lam0):
            return self._attend(p, q[None], k[None], v[None], causal,
                                lam0)[0]

        out = attend(pa, q, self._lam0_mid)
        x = _mlp(pa, _add(x, out), self._eps)
        return self._cross_decoder(params, x, memory, attend)
