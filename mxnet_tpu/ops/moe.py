"""Dropless top-k expert layer for serving, told which experts it holds.

A serving replica of an expert-parallel deployment holds a contiguous
range of the routed experts; a model that no chip shares a layer of holds
them all (``lo = 0``, ``n_held = E_all``: every pick is kept). Either
routes every token over ALL experts at the published router width
(``route_sigmoid_topk``), keeps the picks that fall on the experts it
holds, and computes their part of the result (``expert_layer``): sort the
kept picks by expert, three grouped products over the held experts (two
for ungated ones), unsort, combine with the gates. No capacity, no
dropped token; what absent experts would add is left out (their chips
add it), and no code stands in for them or their traffic.
``parallel/moe.py`` is the training-side top-1 operator over an ``ep``
mesh axis; nothing of it is used here.

Layout of the grouped product: the kept picks are laid into a row buffer
in which every held expert's rows start at a multiple of ``tm`` rows, so
that each ``tm``-row tile belongs to exactly one expert. ``tile_expert``
names it, and only the first ``n_active`` tiles hold rows. The buffer has
room for the worst case (every pick kept, every expert's last tile
ragged): ``rows_for(picks, n_held, tm)``.

``grouped_matmul`` is one device operation under a stable name: the
Pallas kernel ``moe_grouped_matmul`` (docs/OBSERVABILITY.md) on a TPU,
whose grid visits the active tiles only and streams each hit expert's
weights once per tile; elsewhere a ``jnp`` gather-and-contract with the
same tile semantics.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from . import attention as _att

KERNEL_NAME = "moe_grouped_matmul"


def route_sigmoid_topk(z, w_router, bias, top_k, scaling_factor=1.0):
    """Sigmoid ``noaux_tc`` routing with one group: scores
    ``s = sigmoid(z W_r)`` in float32, the ``top_k`` experts by ``s +
    bias``, gates ``scaling_factor * s_i / sum_chosen s_j``
    (``norm_topk_prob``; ``routed_scaling_factor``, 1 unless the model
    publishes another). ``z`` (T, D) float32 (the normed input before it
    is cast to the model's dtype); ``w_router`` (D, E_all) and ``bias``
    (E_all,) float32. Returns ``ids`` (T, k) int32 and ``gates`` (T, k)
    float32."""
    # both operands float32 arrays: a product with one bfloat16 operand
    # rounds the other on a TPU, whatever precision it is asked for
    logits = jnp.dot(z.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, ids = lax.top_k(s + bias.astype(jnp.float32), top_k)
    chosen = jnp.take_along_axis(s, ids, axis=-1)
    gates = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    if scaling_factor != 1:
        gates = gates * jnp.float32(scaling_factor)
    return ids.astype(jnp.int32), gates


def rows_for(picks, n_held, tm):
    """Rows of the buffer that holds ``picks`` kept picks of ``n_held``
    experts in ``tm``-aligned segments, whatever the routing."""
    return -(-(picks + n_held * (tm - 1)) // tm) * tm


def tile_rows(picks):
    """Rows a tile of the grouped product: 128 where a call's picks fill
    them (a prefill chunk), 32 for a decode tick's few rows an expert
    (one of 32 slots x 8 picks over 256 experts of which 32 are held; two
    of 32 x 4 picks over 64 experts all held: either way a tile is mostly
    padding and what a tick pays for is the weights of the experts hit);
    the ``jnp`` path needs no more than the sublane's 8."""
    if not _att._use_pallas():
        return 8
    return 128 if picks >= 1024 else 32


def dispatch(ids, lo, n_held, tm):
    """Where each pick goes. ``ids`` (T, k) are expert ids over all
    routed experts; the experts held are ``[lo, lo + n_held)``. Returns
    ``row_token`` (M,) the token each buffer row reads (padding rows
    read token 0), ``pick_row`` (T, k) each pick's buffer row (``M`` for
    a pick that is not held), ``tile_expert`` (M / tm,), ``n_active``
    (the number of tiles that hold rows) and ``n_hit`` (the number of
    held experts that hold rows: whose weights the products stream)."""
    t, k = ids.shape
    p = t * k
    m = rows_for(p, n_held, tm)
    local = ids.reshape(p) - lo
    held = (local >= 0) & (local < n_held)
    key = jnp.where(held, local, n_held)
    order = jnp.argsort(key, stable=True)
    skey = key[order]
    counts = jnp.sum(key[:, None] == jnp.arange(n_held)[None, :], axis=0,
                     dtype=jnp.int32)
    padded = -(-counts // tm) * tm
    seg_end = jnp.cumsum(padded)
    seg_start = seg_end - padded
    first = jnp.cumsum(counts) - counts      # first sorted pick of each
    e = jnp.minimum(skey, n_held - 1)
    dest = jnp.where(skey < n_held,
                     seg_start[e] + jnp.arange(p, dtype=jnp.int32)
                     - first[e], m)
    row_token = jnp.zeros((m,), jnp.int32).at[dest].set(
        (order // k).astype(jnp.int32), mode="drop")
    pick_row = jnp.zeros((p,), jnp.int32).at[order].set(dest)
    tiles = jnp.arange(m // tm, dtype=jnp.int32) * tm
    tile_expert = jnp.minimum(
        jnp.searchsorted(seg_end, tiles, side="right"),
        n_held - 1).astype(jnp.int32)
    n_active = (seg_end[-1] // tm).astype(jnp.int32)
    n_hit = jnp.sum(counts > 0, dtype=jnp.int32)
    return row_token, pick_row.reshape(t, k), tile_expert, n_active, n_hit


def _fit(total, want):
    """The largest multiple of 128 at most ``want`` that divides
    ``total`` (``total`` itself where it is no multiple of 128)."""
    if total % 128:
        return total
    best = 128
    for c in range(128, min(want, total) + 1, 128):
        if total % c == 0:
            best = c
    return best


def _gmm_kernel(te_ref, x_ref, w_ref, o_ref, acc_ref, *, nk, transposed):
    del te_ref
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if transposed:                  # both blocks contract their minor dim
        acc_ref[...] += lax.dot_general(
            x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


#: the kernel's weight block, at most (K, N) = (1024, 768) bfloat16
BLOCK_K, BLOCK_N = 1024, 768


def grouped_matmul_pallas(x, w, tile_expert, n_active, tm,
                          transposed=False):
    """``out[r] = x[r] @ w[tile_expert[r // tm]]`` for the rows of the
    first ``n_active`` tiles; the other rows are left as they come.
    ``x`` (M, K), ``w`` (E, K, N), or (E, N, K) held output-major with
    ``transposed``. The grid is ``(N tiles, active tiles, K tiles)``: a
    tile's expert is read from the scalar-prefetched ``tile_expert``, so
    each active tile streams one expert's weights once, and a tile that
    holds no rows costs no step."""
    from jax.experimental.pallas import tpu as pltpu
    m, kk = x.shape
    if transposed:
        n = w.shape[1]
        # a whole row of a block is ``tn`` wide: at float32 half as much
        # of ``K`` fits the scoped VMEM
        tk = _fit(kk, BLOCK_K * 2 // w.dtype.itemsize)
        w_spec = pl.BlockSpec((None, _fit(n, BLOCK_N), tk),
                              lambda j, i, k, te: (te[i], j, k))
    else:
        n = w.shape[2]
        tk = _fit(kk, BLOCK_K)
        w_spec = pl.BlockSpec((None, tk, _fit(n, BLOCK_N)),
                              lambda j, i, k, te: (te[i], k, j))
    tn = _fit(n, BLOCK_N)
    nk = kk // tk
    return pl.pallas_call(
        functools.partial(_gmm_kernel, nk=nk, transposed=transposed),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda j, i, k, te: (i, k)),
                w_spec,
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda j, i, k, te: (i, j)),
            grid=(n // tn, n_active, nk),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        name=KERNEL_NAME,
    )(tile_expert, x, w)


def grouped_matmul(x, w, tile_expert, n_active, tm, transposed=False):
    """The grouped product of ``dispatch``'s buffer: the Pallas kernel on
    a TPU, else every row against its tile's expert in ``jnp``. With
    ``transposed`` each expert's matrix is held output-major, ``w`` (E,
    N, K): a leaf ``(E, K, N)`` whose minor width ``N`` is no multiple of
    128 is laid out by the chip with ``K`` minor-most, and a kernel that
    asks for it row-major gets a copy of the whole leaf before every
    call."""
    if _att._use_pallas():
        return grouped_matmul_pallas(x, w, tile_expert, n_active, tm,
                                     transposed)
    row_expert = jnp.repeat(tile_expert, tm)
    return jnp.einsum("mk,mnk->mn" if transposed else "mk,mkn->mn",
                      x, w[row_expert],
                      preferred_element_type=jnp.float32).astype(x.dtype)


def routed_layer(z32, z, p, top_k, lo, scaling_factor=1.0, up_t=False):
    """A routed layer's held part on the normed input: ``z32`` (T, D)
    float32 for the router, ``z`` the same in the model's dtype for the
    experts; ``p`` the layer's arrays (``router``, ``router_bias``,
    ``e_up``, ``e_down``, and ``e_gate`` where the experts are gated);
    ``lo`` the first expert held; ``up_t`` as ``expert_layer``'s.
    Returns (T, D) float32 and the number of held experts hit."""
    ids, gates = route_sigmoid_topk(z32, p["router"], p["router_bias"],
                                    top_k, scaling_factor)
    return expert_layer(z, p.get("e_gate"), p["e_up"], p["e_down"], ids,
                        gates, lo, tile_rows(z.shape[0] * top_k), up_t)


def relu2(x):
    """``relu(x)^2``, the activation of ungated experts."""
    return jnp.square(jax.nn.relu(x))


def expert_layer(z, w_gate, w_up, w_down, ids, gates, lo, tm, up_t=False):
    """``sum_{i chosen, held} g_i E_i(z)``: the held experts' part of a
    routed layer, with ``E(z; W) = (silu(z W_gate) * (z W_up)) W_down``,
    or, where ``w_gate`` is None, the ungated ``E(z; W) = relu(z
    W_up)^2 W_down`` (two grouped products). ``z`` (T, D); ``w_gate`` /
    ``w_up`` (E_held, D, F), ``w_up`` (E_held, F, D) output-major with
    ``up_t`` (``grouped_matmul``'s ``transposed``); ``w_down`` (E_held,
    F, D); ``ids`` / ``gates`` (T, k) from the router over all experts;
    ``lo`` the first expert held. Returns (T, D) float32 and
    ``dispatch``'s ``n_hit``."""
    n_held = w_up.shape[0]
    row_token, pick_row, tile_expert, n_active, n_hit = dispatch(
        ids, lo, n_held, tm)
    m = row_token.shape[0]
    xb = z[row_token]
    if w_gate is None:
        u = grouped_matmul(xb, w_up, tile_expert, n_active, tm, up_t)
        h = relu2(u.astype(jnp.float32)).astype(z.dtype)
    else:
        g = grouped_matmul(xb, w_gate, tile_expert, n_active, tm)
        u = grouped_matmul(xb, w_up, tile_expert, n_active, tm, up_t)
        h = (jax.nn.silu(g.astype(jnp.float32))
             * u.astype(jnp.float32)).astype(z.dtype)
    yb = grouped_matmul(h, w_down, tile_expert, n_active, tm)
    held = pick_row < m
    rows = yb[jnp.minimum(pick_row, m - 1)]              # (T, k, D)
    # a pick that is not held reads a row no tile wrote: select, never
    # multiply (0 x NaN)
    rows = jnp.where(held[..., None], rows.astype(jnp.float32), 0.0)
    return jnp.sum(rows * gates[..., None], axis=1), n_hit


def route_softmax_topk(z, w_router, top_k, norm_topk_prob=True):
    """Softmax routing (Qwen3-MoE's, which ``sdar_moe`` continues):
    probabilities ``softmax(z W_r)`` over ALL experts in float32, the
    ``top_k`` most probable kept, their probabilities renormalised to sum
    to 1 with ``norm_topk_prob`` (else left as they are). ``z`` (T, D)
    float32, the normed input before it is cast to the model's dtype;
    ``w_router`` (D, E_all) float32. Returns ``ids`` (T, k) int32 and
    ``gates`` (T, k) float32, as ``route_sigmoid_topk``."""
    logits = jnp.dot(z.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = lax.top_k(probs, top_k)
    if norm_topk_prob:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return ids.astype(jnp.int32), gates
