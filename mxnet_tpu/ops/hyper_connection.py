"""Manifold-constrained hyper-connections (mHC): a residual of ``n``
streams, read, written and mixed by three per-token maps.

A token's residual is ``X`` (n, D). A sublayer ``F`` (attention, or a
feed-forward) with its own ``phi`` (n + n + n^2, n D), ``alpha`` (3,) and
``b`` (n + n + n^2,) computes, all in float32 whatever the streams'
dtype::

    x~     = vec(X) / sqrt(mean(vec(X)^2) + eps)        # over all n D values
    [p|q|r] = x~ phi^T          # phi is held maps-major: no lane padding
    H_pre  = sigmoid(alpha_pre p + b_pre)                           (n,)
    H_post = 2 sigmoid(alpha_post q + b_post)                       (n,)
    M_0    = exp(clip(alpha_res mat(r) + b_res, lo, hi))            (n, n)
    M_t    = rownorm(colnorm(M_{t-1})),  t = 1..iters   # each / (sum + eps)
    H_res  = M_iters                # doubly stochastic: Sinkhorn-Knopp
    u      = H_pre X                                                (D,)
    X'     = H_res X + H_post^T F(N(u))

``coefficients`` gives the three maps, ``mix_in`` the sublayer's input,
``mix_out`` the new streams (rounded once, when ``X'`` is written).

The Sinkhorn iterations run on ``n^2`` separate (T,) vectors, one per
matrix entry, and every step is elementwise on them: sums of ``n``
vectors, not reductions over an axis of size ``n``. The compiler then has
one fusion to make of a round's two normalisations, where a (T, n, n)
array would cost it a reduction kernel a normalisation: on a decode tick
that is 12 sublayers x 40 launches of pure latency. The rounds are a loop,
not unrolled: unrolled, 20 rounds of 56 operations take seconds to compile
a sublayer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_F32 = jnp.float32


def sinkhorn(m, iters, eps):
    """``m`` an ``n x n`` list of lists of equal-shaped float32 arrays
    (entry ``[i][j]``: row ``i``, column ``j``): ``iters`` rounds of
    column then row normalisation, each dividing by ``sum + eps``. One
    loop of ``iters`` trips whose body is elementwise."""
    n = len(m)

    def one_round(_, flat):
        m = [list(flat[i * n:(i + 1) * n]) for i in range(n)]
        col = [sum(m[i][j] for i in range(n)) + eps for j in range(n)]
        m = [[m[i][j] / col[j] for j in range(n)] for i in range(n)]
        row = [sum(m[i][j] for j in range(n)) + eps for i in range(n)]
        return tuple(m[i][j] / row[i] for i in range(n) for j in range(n))

    flat = lax.fori_loop(0, iters, one_round,
                         tuple(v for row in m for v in row))
    return [list(flat[i * n:(i + 1) * n]) for i in range(n)]


def coefficients(x, phi, alpha, b, *, iters, eps, clamp):
    """The three maps of one sublayer for every token of ``x`` (T, n, D):
    ``H_pre`` (T, n), ``H_post`` (T, n), ``H_res`` (T, n, n), float32.
    ``phi``, ``alpha``, ``b`` are float32 arrays (see ``dot32`` in
    ``ops/latent_attention.py``: the product's operands both have to be
    float32 arrays, and ``x~`` is one, computed here)."""
    t, n, d = x.shape
    assert phi.dtype == _F32 and phi.shape == (2 * n + n * n, n * d), \
        (phi.dtype, phi.shape)
    x32 = x.astype(_F32).reshape(t, n * d)
    xn = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    pqr = lax.dot_general(xn, phi, (((1,), (1,)), ((), ())),
                          precision=lax.Precision.HIGHEST)
    alpha, b = alpha.astype(_F32), b.astype(_F32)
    h_pre = jax.nn.sigmoid(alpha[0] * pqr[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * pqr[:, n:2 * n] + b[n:2 * n])
    r = alpha[2] * pqr[:, 2 * n:] + b[2 * n:]
    m = [[jnp.exp(jnp.clip(r[:, i * n + j], clamp[0], clamp[1]))
          for j in range(n)] for i in range(n)]
    m = sinkhorn(m, iters, eps)
    h_res = jnp.stack([jnp.stack(row, -1) for row in m], -2)   # (T, n, n)
    return h_pre, h_post, h_res


def mix_in(h_pre, x):
    """``u = H_pre X``: the sublayer's input (T, D), float32."""
    return jnp.sum(h_pre[..., None] * x.astype(_F32), axis=1)


def mix_out(h_res, h_post, x, f):
    """``X' = H_res X + H_post^T f`` in float32, rounded once to the
    streams' dtype. ``x`` (T, n, D), ``f`` (T, D) float32."""
    n = x.shape[1]
    x32 = x.astype(_F32)
    # elementwise over the streams: n is small, no product on the MXU
    mixed = sum(h_res[:, :, j, None] * x32[:, j, None, :] for j in range(n))
    return (mixed + h_post[..., None] * f.astype(_F32)[:, None, :]
            ).astype(x.dtype)
