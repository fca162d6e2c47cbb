"""The selective state-space recurrence (Mamba-1), for serving.

One token ``t`` of one sequence, ``C`` channels each with an ``N``-wide
state: ``h_t = exp(dt_t (x) A) * h_{t-1} + (dt_t * x_t) (x) B_t``,
``m_t = h_t C_t + D * x_t`` (``x`` the convolved, activated input, ``dt``
the step after its softplus, ``A`` negative, ``B_t``/``C_t`` the token's
input and output maps, ``D`` the learned skip). Everything here is
float32 whatever the model's dtype: a state is summed over hundreds of
positions.

The state lives ``(N, C)``, channels minor-most, so that on a TPU its
minor dimension is a multiple of 128 and nothing is padded (``(C, N)``
with ``N = 16`` would be held eight times its size).

* ``selective_scan``: a chunk of ``T`` positions that takes a state up
  and hands it on. Positions at or past ``n_valid`` (a chunk padded to
  its bucket) leave the state as it was: their step is set to 0, and
  ``exp(0) * h + 0 = h`` exactly. One device operation under a stable
  name on a TPU, the Pallas kernel ``ssm_chunk_scan``
  (docs/OBSERVABILITY.md): channels blocked into VMEM, 1024 to a block,
  each ``(8, 128)`` register holding 1024 channels of one state column;
  time is walked inside the kernel, ``B_t`` and ``C_t`` read as scalars;
  the state is carried from one time block to the next in the output's
  own block. Elsewhere a ``lax.scan`` over the positions, which is also
  the kernel's reference.
* ``selective_step``: one position of every slot (a decode tick), rows
  that are not ``active`` leaving their state untouched. ``jnp``.
* ``causal_conv_chunk`` / ``causal_conv_step``: the depthwise causal
  convolution before the recurrence, whose state is the last ``K - 1``
  inputs (the *tail*), under the same two rules.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from . import attention as _att

KERNEL_NAME = "ssm_chunk_scan"
_F32 = jnp.float32
_LANES = 128


# ---------------------------------------------------------------------------
# the depthwise causal convolution and its tail
# ---------------------------------------------------------------------------
def causal_conv_chunk(x, w, bias, tail, n_valid):
    """``y_t = sum_k w[k] * x_{t - (K-1) + k} + bias`` over a chunk:
    ``x`` (T, C), ``w`` (K, C), ``tail`` (K-1, C) the inputs just before
    the chunk (zeros at a sequence's start). Returns ``y`` (T, C)
    float32 and the new tail: the last ``K - 1`` inputs before position
    ``n_valid``, so a padded chunk hands on what a chunk of ``n_valid``
    positions would."""
    k, t = w.shape[0], x.shape[0]
    xp = jnp.concatenate([tail.astype(x.dtype), x], axis=0)
    y = bias.astype(_F32)[None, :]
    for j in range(k):
        y = y + w[j].astype(_F32)[None, :] * xp[j:j + t].astype(_F32)
    new_tail = lax.dynamic_slice_in_dim(xp, n_valid, k - 1, axis=0)
    return y, new_tail.astype(tail.dtype)


def causal_conv_step(x, w, bias, tail, active):
    """One position of every slot: ``x`` (B, C), ``tail`` (B, K-1, C).
    Rows that are not ``active`` (B,) keep their tail."""
    xp = jnp.concatenate([tail.astype(x.dtype), x[:, None, :]], axis=1)
    y = bias.astype(_F32)[None, :] + jnp.sum(
        w.astype(_F32)[None] * xp.astype(_F32), axis=1)
    new_tail = jnp.where(active[:, None, None], xp[:, 1:], tail)
    return y, new_tail.astype(tail.dtype)


# ---------------------------------------------------------------------------
# the recurrence over a chunk
# ---------------------------------------------------------------------------
def _scan_jnp(x, dt, a, b, c, d, h0):
    def step(h, args):
        x_t, dt_t, b_t, c_t = args
        h = jnp.exp(dt_t[None, :] * a) * h \
            + (dt_t * x_t)[None, :] * b_t[:, None]
        return h, jnp.sum(h * c_t[:, None], axis=0) + d * x_t

    h_end, m = lax.scan(step, h0, (x, dt, b, c))
    return m, h_end


def _scan_kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, d_ref, h0_ref,
                 m_ref, h_ref, *, n_state, t_block):
    """One (channel block, time block) grid step. A register is
    ``(8, 128)`` channels of one state column; ``h_ref`` (the output's
    block, which stays in VMEM over the time axis) carries the state."""
    tb = pl.program_id(1)

    @pl.when(tb == 0)
    def _take_up():
        h_ref[...] = h0_ref[...]

    a = [a_ref[n] for n in range(n_state)]
    d = d_ref[...]
    base = tb * (t_block * n_state)

    def step(t, h):
        x_t, dt_t = x_ref[t], dt_ref[t]
        dtx = dt_t * x_t
        at = base + t * n_state
        y = d * x_t
        out = []
        for n in range(n_state):
            h_n = jnp.exp(dt_t * a[n]) * h[n] + dtx * b_ref[at + n]
            y = y + h_n * c_ref[at + n]
            out.append(h_n)
        m_ref[t] = y
        return tuple(out)

    h = lax.fori_loop(0, t_block, step,
                      tuple(h_ref[n] for n in range(n_state)))
    for n in range(n_state):
        h_ref[n] = h[n]


def _time_block(t):
    for tb in (128, 64, 32, 16, 8):
        if t % tb == 0:
            return tb
    return t


def kernel_takes(channels):
    """The kernel blocks channels by whole 128-lane rows."""
    return channels % _LANES == 0


def selective_scan_pallas(x, dt, a, b, c, d, h0, interpret=False):
    """The kernel on ``(T, C)`` float32 ``x`` and ``dt`` (``dt`` already
    0 past the valid positions), ``a``/``h0`` (N, C), ``b``/``c``
    (T, N), ``d`` (C,); ``C`` a multiple of 128."""
    from jax.experimental.pallas import tpu as pltpu
    t, ch = x.shape
    n = a.shape[0]
    rows = ch // _LANES
    rb = 8 if rows % 8 == 0 else rows        # sublanes of a channel block
    tb = _time_block(t)
    x3, dt3 = (v.astype(_F32).reshape(t, rows, _LANES) for v in (x, dt))
    a3, h3 = (v.astype(_F32).reshape(n, rows, _LANES) for v in (a, h0))
    d2 = d.astype(_F32).reshape(rows, _LANES)
    seq = pl.BlockSpec((tb, rb, _LANES), lambda j, i: (i, j, 0))
    state = pl.BlockSpec((n, rb, _LANES), lambda j, i: (0, j, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    m, h_end = pl.pallas_call(
        functools.partial(_scan_kernel, n_state=n, t_block=tb),
        out_shape=(jax.ShapeDtypeStruct((t, rows, _LANES), _F32),
                   jax.ShapeDtypeStruct((n, rows, _LANES), _F32)),
        grid=(rows // rb, t // tb),
        in_specs=[smem, smem, seq, seq, state,
                  pl.BlockSpec((rb, _LANES), lambda j, i: (j, 0)), state],
        out_specs=(seq, state),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAME,
    )(b.astype(_F32).reshape(-1), c.astype(_F32).reshape(-1), x3, dt3, a3,
      d2, h3)
    return m.reshape(t, ch), h_end.reshape(n, ch)


def selective_scan(x, dt, a, b, c, d, h0, n_valid):
    """``T`` positions of one sequence from state ``h0``: ``x``, ``dt``
    (T, C), ``a`` (N, C), ``b``, ``c`` (T, N), ``d`` (C,), ``h0``
    (N, C), ``n_valid`` an int32 scalar. Returns ``m`` (T, C) and the
    state after position ``n_valid - 1``, float32. Rows of ``m`` at or
    past ``n_valid`` mean nothing."""
    t = x.shape[0]
    live = jnp.arange(t, dtype=jnp.int32) < n_valid
    dt = jnp.where(live[:, None], dt.astype(_F32), 0.0)
    args = (x.astype(_F32), dt, a.astype(_F32), b.astype(_F32),
            c.astype(_F32), d.astype(_F32), h0.astype(_F32))
    if _att._use_pallas() and kernel_takes(x.shape[1]):
        return selective_scan_pallas(*args)
    return _scan_jnp(*args)


def selective_step(x, dt, a, b, c, d, h, active):
    """One position of every slot: ``x``, ``dt`` (B, C), ``b``, ``c``
    (B, N), ``h`` (B, N, C), ``active`` (B,) bool. Returns ``m`` (B, C)
    and the states, those of inactive rows as they came."""
    x, dt, h = x.astype(_F32), dt.astype(_F32), h.astype(_F32)
    new = jnp.exp(dt[:, None, :] * a.astype(_F32)[None]) * h \
        + (dt * x)[:, None, :] * b.astype(_F32)[:, :, None]
    m = jnp.sum(new * c.astype(_F32)[:, :, None], axis=1) \
        + d.astype(_F32)[None, :] * x
    return m, jnp.where(active[:, None, None], new, h)
