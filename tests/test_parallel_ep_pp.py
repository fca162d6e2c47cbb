"""Expert-parallel MoE ('ep' all_to_all) and pipeline parallelism
('pp' ppermute) on the virtual 8-device mesh.

Beyond-reference capability (SURVEY §2.3 reserves both axes; the
reference is data-parallel only). Each mode is checked for exact
agreement with the equivalent sequential computation AND for gradient
flow through the collectives."""
import numpy as onp
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

from mxnet_tpu.parallel import moe_ffn, pipeline_apply


@pytest.fixture(scope="module")
def devs():
    d = jax.devices()
    if len(d) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    return onp.asarray(d[:8])


def test_moe_matches_dense_top1_and_differentiates(devs):
    mesh = Mesh(devs.reshape(2, 4), ("dp", "ep"))
    rs = onp.random.RandomState(0)
    B, T, D, H, E = 4, 8, 16, 32, 4
    x = jnp.asarray(rs.rand(B, T, D).astype("float32"))
    gw = jnp.asarray(rs.rand(D, E).astype("float32") * 0.1)
    wu = jnp.asarray(rs.rand(E, D, H).astype("float32") * 0.1)
    wd = jnp.asarray(rs.rand(E, H, D).astype("float32") * 0.1)
    with mesh:
        y = moe_ffn(x, gw, wu, wd, mesh, capacity_factor=4.0)

    tok = onp.asarray(x).reshape(-1, D)
    probs = onp.exp(tok @ onp.asarray(gw))
    probs /= probs.sum(-1, keepdims=True)
    e = probs.argmax(-1)
    g = probs[onp.arange(len(e)), e]
    ref = onp.zeros_like(tok)
    for i, (ei, gi) in enumerate(zip(e, g)):
        h = onp.maximum(tok[i] @ onp.asarray(wu)[ei], 0)
        ref[i] = gi * (h @ onp.asarray(wd)[ei])
    onp.testing.assert_allclose(onp.asarray(y).reshape(-1, D), ref,
                                rtol=1e-4, atol=1e-5)

    def loss_fn(xv, g_, u_, d_):
        with mesh:
            return moe_ffn(xv, g_, u_, d_, mesh,
                           capacity_factor=4.0).sum()

    grads = jax.grad(loss_fn, argnums=(0, 1, 2, 3))(x, gw, wu, wd)
    assert all(bool(jnp.isfinite(t).all()) for t in grads)
    assert float(jnp.abs(grads[2]).sum()) > 0  # experts got gradient


def test_moe_capacity_drops_overflow_tokens(devs):
    mesh = Mesh(devs.reshape(2, 4), ("dp", "ep"))
    # all tokens route to one expert; tiny capacity drops the overflow
    D, E = 8, 4
    x = jnp.ones((2, 8, D), jnp.float32)
    gw = jnp.zeros((D, E), jnp.float32).at[:, 1].set(1.0)
    wu = jnp.ones((E, D, 4), jnp.float32)
    wd = jnp.ones((E, 4, D), jnp.float32)
    with mesh:
        y = moe_ffn(x, gw, wu, wd, mesh, capacity_factor=0.25)
    out = onp.asarray(y).reshape(-1, D)
    served = (onp.abs(out).sum(-1) > 0).sum()
    # per dp shard: 8 tokens, capacity = 0.25*8/4 = 1 slot in the hot
    # expert -> exactly 1 token served per shard
    assert served == 2, served


def test_pipeline_matches_sequential_and_differentiates(devs):
    mesh = Mesh(devs.reshape(2, 4), ("dp", "pp"))
    rs = onp.random.RandomState(1)
    S, B, D = 4, 8, 6
    Ws = jnp.asarray(rs.rand(S, D, D).astype("float32") * 0.2)
    bs = jnp.asarray(rs.rand(S, D).astype("float32") * 0.1)
    x = jnp.asarray(rs.rand(B, D).astype("float32"))

    def stage(params, h):
        W, b = params
        return jnp.tanh(h @ W + b)

    with mesh:
        out = pipeline_apply(stage, (Ws, bs), x, mesh, n_microbatch=2,
                             pp_axis="pp", dp_axis="dp")
    ref = onp.asarray(x)
    for s in range(S):
        ref = onp.tanh(ref @ onp.asarray(Ws)[s] + onp.asarray(bs)[s])
    onp.testing.assert_allclose(onp.asarray(out), ref, rtol=1e-4,
                                atol=1e-5)

    def loss(ws, bsv, xv):
        with mesh:
            return pipeline_apply(stage, (ws, bsv), xv, mesh,
                                  n_microbatch=2, pp_axis="pp",
                                  dp_axis="dp").sum()

    gw_, gb_, gx_ = jax.grad(loss, argnums=(0, 1, 2))(Ws, bs, x)
    assert bool(jnp.isfinite(gw_).all())
    # every stage's weights receive gradient
    per_stage = onp.asarray(jnp.abs(gw_).sum(axis=(1, 2)))
    assert (per_stage > 0).all(), per_stage


def test_pipeline_trains_end_to_end(devs):
    """A few SGD steps through the pipelined composition reduce loss."""
    mesh = Mesh(devs.reshape(1, 8), ("dp", "pp"))
    rs = onp.random.RandomState(2)
    S, B, D = 8, 8, 4
    Ws = jnp.asarray(rs.rand(S, D, D).astype("float32") * 0.3)
    bs = jnp.zeros((S, D), jnp.float32)
    x = jnp.asarray(rs.rand(B, D).astype("float32"))
    target = jnp.asarray(rs.rand(B, D).astype("float32"))

    def stage(params, h):
        W, b = params
        return jnp.tanh(h @ W + b)

    def loss(ws, bsv):
        with mesh:
            out = pipeline_apply(stage, (ws, bsv), x, mesh,
                                 n_microbatch=4, pp_axis="pp",
                                 dp_axis="dp")
        return ((out - target) ** 2).mean()

    # traced and compiled once: un-jitted, every call re-traces the
    # shard_map pipeline over 8 devices
    loss_and_grad = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
    l0 = float(loss_and_grad(Ws, bs)[0])
    for _ in range(5):  # the loss is under 0.25 * l0 by the fifth
        _, (gw_, gb_) = loss_and_grad(Ws, bs)
        Ws = Ws - 0.5 * gw_
        bs = bs - 0.5 * gb_
    lf = float(loss_and_grad(Ws, bs)[0])
    assert lf < l0 * 0.5, (l0, lf)
