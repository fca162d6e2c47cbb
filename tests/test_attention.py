"""Attention kernel + sequence parallelism tests."""
import numpy as onp
import pytest
import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel, autograd
from mxnet_tpu.gluon import nn
from mxnet_tpu.ops import attention as at


def _qkv(b=2, h=4, s=128, d=32, seed=0):
    onp.random.seed(seed)
    mk = lambda: jnp.asarray(  # noqa: E731
        onp.random.randn(b, h, s, d).astype("float32") * 0.5)
    return mk(), mk(), mk()


@pytest.mark.requires_pallas
def test_pallas_kernel_matches_reference():
    q, k, v = _qkv()
    ref = at.mha_reference(q, k, v, causal=False)
    pal, _lse = at.flash_attention_pallas(q, k, v, causal=False,
                                          block_q=64, block_k=64,
                                          interpret=True)
    onp.testing.assert_allclose(onp.asarray(ref), onp.asarray(pal),
                                rtol=2e-4, atol=2e-5)


@pytest.mark.requires_pallas
def test_pallas_kernel_causal():
    q, k, v = _qkv(s=64)
    ref = at.mha_reference(q, k, v, causal=True)
    pal, _lse = at.flash_attention_pallas(q, k, v, causal=True,
                                          block_q=32, block_k=32,
                                          interpret=True)
    onp.testing.assert_allclose(onp.asarray(ref), onp.asarray(pal),
                                rtol=2e-4, atol=2e-5)


def test_flash_attention_grad_matches_reference():
    q, k, v = _qkv(s=64)
    g1 = jax.grad(lambda q, k, v: at.flash_attention(
        q, k, v, True).sum(), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: at.mha_reference(
        q, k, v, causal=True).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_jit(causal):
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = parallel.make_mesh((8,), ("sp",))
    q, k, v = _qkv(s=128)
    ref = at.mha_reference(q, k, v, causal=causal)
    with parallel.mesh_scope(mesh):
        out = jax.jit(lambda q, k, v: at.ring_attention(
            q, k, v, mesh=mesh, causal=causal))(q, k, v)
    onp.testing.assert_allclose(onp.asarray(ref), onp.asarray(out),
                                rtol=2e-4, atol=2e-5)


def test_ring_attention_grads():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = parallel.make_mesh((4,), ("sp",), devices=jax.devices()[:4])
    q, k, v = _qkv(s=64)
    with parallel.mesh_scope(mesh):
        g1 = jax.jit(jax.grad(lambda q, k, v: at.ring_attention(
            q, k, v, mesh=mesh, causal=True).sum(),
            argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.grad(lambda q, k, v: at.mha_reference(
        q, k, v, causal=True).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=2e-3, atol=2e-4)


def test_mha_layer_shapes_and_grad():
    net = nn.MultiHeadAttention(32, 4, causal=True)
    net.initialize()
    x = mx.np.random.uniform(size=(2, 16, 32))
    out = net(x)
    assert out.shape == (2, 16, 32)
    with autograd.record():
        loss = net(x).sum()
    loss.backward()
    assert net.q_proj.weight.grad() is not None


def test_hybridize_sequence_parallel_matches_eager():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = parallel.make_mesh((2, 4), ("dp", "sp"))
    with parallel.mesh_scope(mesh):
        net = nn.TransformerEncoderCell(32, 4, causal=True,
                                        sequence_parallel=True)
        net.initialize()
        x = mx.np.random.uniform(size=(2, 16, 32))
        eager = net(x).asnumpy()       # eager path: flash fallback
        net.hybridize()
        hyb = net(x).asnumpy()         # jitted: ring over sp
    onp.testing.assert_allclose(eager, hyb, rtol=2e-4, atol=2e-5)


@pytest.mark.requires_pallas
def test_flash_ragged_and_decode_shapes():
    # non-multiple-of-block lengths pad cleanly; sq != sk uses the
    # end-aligned causal offset (decode with KV cache)
    onp.random.seed(1)
    mk = lambda s: jnp.asarray(  # noqa: E731
        onp.random.randn(2, 2, s, 32).astype("float32") * 0.5)
    q, k, v = mk(200), mk(200), mk(200)
    ref = at.mha_reference(q, k, v, causal=True)
    pal, _ = at.flash_attention_pallas(q, k, v, causal=True, block_q=128,
                                       block_k=128, interpret=True)
    onp.testing.assert_allclose(onp.asarray(ref), onp.asarray(pal),
                                rtol=2e-4, atol=2e-5)
    q1 = mk(1)
    ref = at.mha_reference(q1, k, v, causal=True)
    pal, _ = at.flash_attention_pallas(q1, k, v, causal=True,
                                       block_q=128, block_k=64,
                                       interpret=True)
    onp.testing.assert_allclose(onp.asarray(ref), onp.asarray(pal),
                                rtol=2e-4, atol=2e-5)


@pytest.mark.requires_pallas
def test_flash_kv_len_matches_sliced_cache():
    """kv_len on a long cache buffer == flash over the sliced cache ==
    mha_reference — the cache-backed prefill convention (padded tail
    masked, causal diagonal end-aligned to the VALID prefix)."""
    onp.random.seed(2)
    mk = lambda s: jnp.asarray(  # noqa: E731
        onp.random.randn(2, 2, s, 32).astype("float32") * 0.5)
    kbuf, vbuf = mk(96), mk(96)
    for sq, kvl in [(16, 70), (70, 70), (16, 16), (1, 33)]:
        q = mk(sq)
        ref = at.mha_reference(q, kbuf[:, :, :kvl], vbuf[:, :, :kvl],
                               causal=True)
        out = at.flash_attention(q, kbuf, vbuf, True, None, kvl)
        onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                    rtol=2e-4, atol=2e-5, err_msg=(sq, kvl))
        pal, _ = at.flash_attention_pallas(q, kbuf, vbuf, causal=True,
                                           kv_len=kvl, block_q=32,
                                           block_k=32, interpret=True)
        onp.testing.assert_allclose(onp.asarray(pal), onp.asarray(ref),
                                    rtol=2e-4, atol=2e-5, err_msg=(sq, kvl))
    with pytest.raises(ValueError, match="out of range"):
        at.flash_attention_pallas(mk(4), kbuf, vbuf, kv_len=97)


def test_flash_kv_len_grads_match_and_tail_is_zero():
    """Backward under kv_len: grads match the sliced-cache reference
    and the masked cache tail gets EXACTLY zero dk/dv."""
    onp.random.seed(3)
    mk = lambda s: jnp.asarray(  # noqa: E731
        onp.random.randn(2, 2, s, 32).astype("float32") * 0.5)
    q, kbuf, vbuf = mk(16), mk(96), mk(96)
    kvl = 40
    g1 = jax.grad(lambda q, k, v: at.flash_attention(
        q, k, v, True, None, kvl).sum(), argnums=(0, 1, 2))(q, kbuf, vbuf)
    g2 = jax.grad(lambda q, k, v: at.mha_reference(
        q, k, v, causal=True).sum(), argnums=(0, 1, 2))(
        q, kbuf[:, :, :kvl], vbuf[:, :, :kvl])
    onp.testing.assert_allclose(onp.asarray(g1[0]), onp.asarray(g2[0]),
                                rtol=2e-3, atol=2e-4)
    onp.testing.assert_allclose(onp.asarray(g1[1][:, :, :kvl]),
                                onp.asarray(g2[1]), rtol=2e-3, atol=2e-4)
    onp.testing.assert_allclose(onp.asarray(g1[2][:, :, :kvl]),
                                onp.asarray(g2[2]), rtol=2e-3, atol=2e-4)
    assert onp.abs(onp.asarray(g1[1][:, :, kvl:])).max() == 0.0
    assert onp.abs(onp.asarray(g1[2][:, :, kvl:])).max() == 0.0


@pytest.mark.requires_pallas
def test_decode_attention_matches_sliced_reference():
    """Single-query decode attention with per-slot lengths: each row
    matches mha_reference over that row's valid cache prefix; jnp path
    and the Pallas kernel (interpret) agree; an empty slot (length 0)
    returns zeros."""
    onp.random.seed(4)
    B, H, S, D = 4, 2, 200, 32
    mk = lambda *s: jnp.asarray(  # noqa: E731
        onp.random.randn(*s).astype("float32") * 0.5)
    q = mk(B, H, 1, D)
    k, v = mk(B, H, S, D), mk(B, H, S, D)
    lengths = jnp.asarray([0, 1, 77, 200], jnp.int32)
    out = at.decode_attention(q, k, v, lengths)
    assert onp.abs(onp.asarray(out[0])).max() == 0.0  # empty slot
    for i in range(1, B):
        ln = int(lengths[i])
        ref = at.mha_reference(q[i:i + 1], k[i:i + 1, :, :ln],
                               v[i:i + 1, :, :ln])
        onp.testing.assert_allclose(onp.asarray(out[i:i + 1]),
                                    onp.asarray(ref),
                                    rtol=2e-4, atol=2e-5)
    pal = at.decode_attention_pallas(q, k, v, lengths, block_k=64,
                                     interpret=True)
    onp.testing.assert_allclose(onp.asarray(pal), onp.asarray(out),
                                rtol=2e-4, atol=2e-5)


def test_npx_decode_attention_wrapper():
    onp.random.seed(5)
    from mxnet_tpu import numpy_extension as npx
    q = mx.np.random.uniform(size=(2, 2, 1, 16))
    k = mx.np.random.uniform(size=(2, 2, 32, 16))
    v = mx.np.random.uniform(size=(2, 2, 32, 16))
    lengths = mx.np.array([5, 32], dtype="int32")
    out = npx.decode_attention(q, k, v, lengths)
    assert out.shape == (2, 2, 1, 16)
    ref = at.decode_attention(q._data, k._data, v._data, lengths._data)
    onp.testing.assert_allclose(out.asnumpy(), onp.asarray(ref),
                                rtol=1e-6, atol=1e-7)


def test_transformer_cell_trains_sequence_parallel():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = parallel.make_mesh((2, 4), ("dp", "sp"))
    with parallel.mesh_scope(mesh):
        class Net(nn.HybridSequential):
            def __init__(self):
                super().__init__()
                self.cell = nn.TransformerEncoderCell(
                    32, 4, causal=True, sequence_parallel=True)
                self.head = nn.Dense(8)

            def forward(self, x):
                return self.head(self.cell(x).mean(axis=1))

        net = Net()
        net.initialize()
        step = parallel.TrainStep(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
            optimizer_params={"learning_rate": 3e-3},
            mesh=mesh, batch_axis="dp")
        x = mx.np.random.uniform(size=(4, 16, 32))
        y = mx.np.array(onp.random.randint(0, 8, size=(4,)), dtype="int32")
        losses = [float(step(x, y).asnumpy()) for _ in range(30)]
    assert losses[-1] < losses[0] * 0.7, losses[:3] + losses[-3:]


def _ulps(out, ref, dtype):
    """Largest gap between two (B, ...) results, a slot at a time, in
    units of ``dtype``'s last place at the slot's largest magnitude (a
    weighted sum rounds at the scale of its summands, not of an element
    that cancelled)."""
    out, ref = (onp.asarray(x, "f8") for x in (out, ref))
    worst = 0.0
    for o, r in zip(out, ref):
        mag = onp.abs(r).max()
        if mag == 0:
            assert (o == 0).all()
            continue
        ulp = 2.0 ** (onp.floor(onp.log2(mag)) - jnp.finfo(dtype).nmant)
        worst = max(worst, float(onp.abs(o - r).max() / ulp))
    return worst


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_paged_decode_attention_matches_gathered_reference(dtype):
    """The paged tick over a (pool, table) cache agrees with the dense
    ``decode_attention`` over the gathered per-slot view to the
    rounding of the dtype (the paged engine's greedy identity with the
    dense engine rests on this), with empty slots returning exactly
    zeros. The tick reads the rows as they lie
    (``rows_decode_attention``: float32 products summed over a whole row
    in another order), so the two are no longer equal bit for bit."""
    onp.random.seed(6)
    B, H, D, PS, NP = 4, 2, 32, 16, 40
    P_MAX = 8                                      # capacity 128
    mk = lambda *s: jnp.asarray(  # noqa: E731
        onp.random.randn(*s).astype("float32") * 0.5).astype(dtype)
    kpool, vpool = mk(NP, PS, H * D), mk(NP, PS, H * D)
    rng = onp.random.RandomState(7)
    table = jnp.asarray(rng.permutation(onp.arange(1, NP))
                        [:B * P_MAX].reshape(B, P_MAX).astype("i4"))
    lengths = jnp.asarray([0, 1, 77, 128], jnp.int32)
    q = mk(B, H, 1, D)
    kg = at.gather_pages(kpool, table, H)
    vg = at.gather_pages(vpool, table, H)
    assert kg.shape == (B, H, P_MAX * PS, D)
    ref = at.decode_attention(q, kg, vg, lengths)
    out = at.paged_decode_attention(q, kpool, vpool, table, lengths)
    assert out.dtype == ref.dtype == q.dtype
    # float32: a few ulp; bfloat16: identical or one bf16 ulp
    assert _ulps(out, ref, dtype) <= (4 if dtype == "float32" else 1)
    assert onp.abs(onp.asarray(out[0], "f4")).max() == 0.0   # empty slot


# every length the masks treat apart, in one batch: an empty slot, one
# token, exactly a page, a page + 1, six pages, one more, ragged, and
# the full table
_PAGED_LENGTHS = [0, 1, 16, 17, 96, 97, 77, 128]


def _paged_case(h, sq, dtype, seed=8, ps=16, d=64, p_max=8):
    """A paged cache holding ``_PAGED_LENGTHS``: every slot's pages
    scattered over the pool, slot 6 sharing its first two pages with
    slot 4 (a cached prefix), free table entries on the scrap page 0.
    Wherever no valid position lies — the scrap page and every held
    page's rest past its slot's length — K is NaN and V large (V has to
    be finite there: its probability is 0, and 0 * NaN is NaN)."""
    rng = onp.random.RandomState(seed)
    lengths = onp.asarray(_PAGED_LENGTHS, "i4")
    b = len(lengths)
    n_pages = 1 + b * p_max
    mk = lambda *sh: (rng.randn(*sh) * 0.5).astype("f4")  # noqa: E731
    kpool, vpool = mk(n_pages, ps, h * d), mk(n_pages, ps, h * d)
    free = list(rng.permutation(onp.arange(1, n_pages)))
    table = onp.zeros((b, p_max), "i4")
    for i, n in enumerate(lengths):
        held = -(-int(n) // ps)
        table[i, :held] = [free.pop() for _ in range(held)]
    table[6, :2] = table[4, :2]                    # a shared prefix
    for pool, junk in ((kpool, onp.nan), (vpool, 1e4)):
        pool[0] = junk                             # the scrap page
        for i, n in enumerate(lengths):
            if n % ps and i != 4:   # slot 4's pages are slot 6's too
                pool[table[i, n // ps], n % ps:] = junk
    q = mk(b, h, sq, d)
    cast = lambda x: jnp.asarray(x).astype(dtype)  # noqa: E731
    return (cast(q), cast(kpool), cast(vpool), jnp.asarray(table),
            jnp.asarray(lengths))


def _paged_reference(q, kpool, vpool, table, lengths, k_scale=None,
                     v_scale=None):
    """Plain numpy, slot by slot: the slot's pages in table order, cut
    to its length, softmax in float64. A pool is (n_pages, page_size,
    H * D), a row one position's heads one after another; an int8 pool
    is dequantized page by page first."""
    q = onp.asarray(q, "f8")
    h, d = q.shape[1], q.shape[3]
    kpool, vpool = (onp.asarray(x, "f8").reshape(*x.shape[:2], h, d)
                    for x in (kpool, vpool))
    if k_scale is not None:
        kpool = kpool * onp.asarray(k_scale, "f8")[:, None, :, None]
        vpool = vpool * onp.asarray(v_scale, "f8")[:, None, :, None]
    table, lengths = onp.asarray(table), onp.asarray(lengths)
    out = onp.zeros(q.shape)
    for i, n in enumerate(lengths):
        if n == 0:
            continue
        k = onp.concatenate(list(kpool[table[i]]), axis=0)[:n]
        v = onp.concatenate(list(vpool[table[i]]), axis=0)[:n]
        s = onp.einsum("hqd,khd->hqk", q[i], k) / onp.sqrt(d)
        p = onp.exp(s - s.max(axis=-1, keepdims=True))
        out[i] = onp.einsum("hqk,khd->hqd",
                            p / p.sum(axis=-1, keepdims=True), v)
    return out


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("sq", [1, 5], ids=["decode", "verify"])
@pytest.mark.parametrize("heads", [2, 16, 20])
def test_paged_decode_attention_parity(heads, sq, dtype):
    """Paged decode matches a plain reference over each slot's own
    pages for every length in ``_PAGED_LENGTHS``, a page two slots
    share, and junk wherever no valid position lies."""
    q, kpool, vpool, table, lengths = _paged_case(heads, sq, dtype)
    out = at.paged_decode_attention(q, kpool, vpool, table, lengths)
    assert out.dtype == q.dtype
    out = onp.asarray(out.astype(jnp.float32))
    assert (out[0] == 0).all()                     # the empty slot
    tol = dict(rtol=2e-4, atol=2e-5) if dtype == "float32" \
        else dict(rtol=2e-2, atol=4e-3)
    onp.testing.assert_allclose(
        out, _paged_reference(q, kpool, vpool, table, lengths), **tol)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("heads", [2, 16, 20])
def test_paged_tick_rows_reader_matches_gathered_reader(heads, dtype):
    """The two readers of one paged cache say the same: the one-query
    tick (``rows_decode_attention`` over ``gather_rows``, the rows as
    they lie) against the view split into heads (what ``Sq > 1`` and
    tp-mesh programs attend), on ``_paged_case``: every length in
    ``_PAGED_LENGTHS``, the shared prefix, NaN K and large V past the
    lengths. The trace-time counters say which reader a call took."""
    from mxnet_tpu import telemetry
    q, kpool, vpool, table, lengths = _paged_case(heads, 1, dtype)
    telemetry.reset()
    rows = at.paged_decode_attention(q, kpool, vpool, table, lengths)
    assert telemetry.counter_value("ops.attention.paged_decode.rows") == 1
    assert telemetry.counter_value(
        "ops.attention.paged_decode.gathered") == 0
    with at.jnp_only():               # as an engine over a tp mesh traces
        gathered = at.paged_decode_attention(q, kpool, vpool, table,
                                             lengths)
    assert telemetry.counter_value(
        "ops.attention.paged_decode.gathered") == 1
    assert rows.dtype == gathered.dtype == q.dtype
    assert rows.shape == gathered.shape == q.shape
    assert (onp.asarray(rows[0], "f4") == 0).all()   # the empty slot
    assert onp.isfinite(onp.asarray(rows, "f4")).all()
    tol = dict(rtol=2e-5, atol=2e-6) if dtype == "float32" \
        else dict(rtol=2e-2, atol=4e-3)
    onp.testing.assert_allclose(onp.asarray(rows, "f4"),
                                onp.asarray(gathered, "f4"), **tol)


def test_paged_decode_attention_under_jit_follows_lengths():
    """One compiled program serves every set of lengths."""
    q, kpool, vpool, table, lengths = _paged_case(2, 1, "float32")
    fn = jax.jit(at.paged_decode_attention)
    for lens in (lengths, jnp.zeros_like(lengths),
                 jnp.minimum(lengths, 16)):
        onp.testing.assert_allclose(
            onp.asarray(fn(q, kpool, vpool, table, lens)),
            _paged_reference(q, kpool, vpool, table, lens),
            rtol=2e-4, atol=2e-5)


# the held-pages reader (``paged_rows_attention``): every length its
# blocks treat apart, page 64 — none, one, a page less one, a page, a page
# and one, a few hundred, the whole table (eight pages)
_HELD_LENGTHS = [0, 1, 63, 64, 65, 300, 512]
#: (Hq, (Hk, Hv), D): phi4flash's differential layers (40 query heads over
#: K 20 x 64 and V read as 10 heads x 128) and plain heads
_HELD_HEADS = {"differential": (40, (20, 10), 64),
               "plain": (20, (20, 20), 64)}


def _held_case(heads, dtype, stale, seed=9, ps=64, p_max=8):
    """A pool holding ``_HELD_LENGTHS``, every slot's pages scattered over
    it and its table's free entries on the scrap page 0. The scrap page
    and the rows past each slot's length in its last held page hold
    ``stale`` (a former tenant's values, or NaN). Returns the case and
    the pools with those rows zeroed, for the reference, whose V has to
    be finite everywhere."""
    hq, (hk, hv), d = _HELD_HEADS[heads]
    rng = onp.random.RandomState(seed)
    lengths = onp.asarray(_HELD_LENGTHS, "i4")
    b = len(lengths)
    n_pages = 1 + b * p_max
    mk = lambda *sh: (rng.randn(*sh) * 0.5).astype("f4")  # noqa: E731
    kpool, vpool = mk(n_pages, ps, hk * d), mk(n_pages, ps, hk * d)
    free = list(rng.permutation(onp.arange(1, n_pages)))
    table = onp.zeros((b, p_max), "i4")
    for i, n in enumerate(lengths):
        held = -(-int(n) // ps)
        table[i, :held] = [free.pop() for _ in range(held)]
    # the scrap page whole, and the rows past each length in its last page
    spots = [(0, 0)] + [(table[i, n // ps], n % ps)
                        for i, n in enumerate(lengths) if n % ps]
    clean = kpool.copy(), vpool.copy()
    for pool, ref in zip((kpool, vpool), clean):
        for page, row in spots:
            pool[page, row:] = onp.nan if stale == "nan" \
                else mk(ps - row, hk * d) * 50
            ref[page, row:] = 0.0
    cast = lambda x: jnp.asarray(x).astype(dtype)  # noqa: E731
    return ((cast(mk(b, hq, d)), cast(kpool), cast(vpool),
             jnp.asarray(table), jnp.asarray(lengths), (hk, hv)),
            (cast(clean[0]), cast(clean[1])))


@pytest.mark.parametrize("stale", ["stale", "nan"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("heads", sorted(_HELD_HEADS))
def test_held_pages_reader_matches_the_rows_reader(heads, dtype, stale):
    """``paged_rows_attention`` (interpret mode) says what
    ``rows_decode_attention`` over ``gather_rows`` says, for every length
    in ``_HELD_LENGTHS`` (several pages a step, so blocks of one, several
    and a part of one), with the scrap page and a former tenant's rows
    (or NaN) past each length in its last held page: the kernel reads
    none of them. An empty slot returns zeros."""
    from mxnet_tpu import telemetry
    (q, kpool, vpool, table, lengths, kv), (kref, vref) = _held_case(
        heads, dtype, stale)
    before = telemetry.counter_value("ops.attention.paged_decode.held")
    out = at.paged_rows_attention(q, kpool, vpool, table, lengths, kv,
                                  interpret=True)
    assert telemetry.counter_value("ops.attention.paged_decode.held") \
        == before + 1
    kr, vr = at.gather_rows(kref, table), at.gather_rows(vref, table)
    valid = jnp.arange(kr.shape[1])[None] < lengths[:, None]
    want = at.rows_decode_attention(q, kr, vr, valid, kv)
    assert out.dtype == want.dtype == jnp.float32
    assert out.shape == want.shape == (q.shape[0], q.shape[1],
                                       vpool.shape[2] // kv[1])
    out = onp.asarray(out)
    assert (out[0] == 0).all() and onp.isfinite(out).all()
    # bfloat16: P is rounded before P x V at another scale (online softmax)
    tol = dict(rtol=2e-5, atol=2e-6) if dtype == "float32" \
        else dict(rtol=2e-2, atol=4e-3)
    onp.testing.assert_allclose(out, onp.asarray(want), **tol)


def test_held_pages_reader_under_jit_follows_lengths():
    """One compiled program serves every set of lengths, the pages held
    read again from the table and lengths of each call."""
    (q, kpool, vpool, table, lengths, kv), (kref, vref) = _held_case(
        "differential", "float32", "nan")
    fn = jax.jit(lambda *a: at.paged_rows_attention(*a, kv, interpret=True))
    kr, vr = at.gather_rows(kref, table), at.gather_rows(vref, table)
    for lens in (lengths, jnp.minimum(lengths, 65)):
        valid = jnp.arange(kr.shape[1])[None] < lens[:, None]
        onp.testing.assert_allclose(
            onp.asarray(fn(q, kpool, vpool, table, lens)),
            onp.asarray(at.rows_decode_attention(q, kr, vr, valid, kv)),
            rtol=2e-5, atol=2e-6)


def test_chunked_prefill_attention_matches_reference():
    """A chunk's queries at global positions [start, start+C) against a
    cache buffer == the matching rows of full causal mha_reference over
    [0, start+C) — per-row global causal masking, any start."""
    onp.random.seed(10)
    H, D, S = 2, 32, 96
    mk = lambda *s: jnp.asarray(  # noqa: E731
        onp.random.randn(*s).astype("float32") * 0.5)
    kbuf, vbuf = mk(1, H, S, D), mk(1, H, S, D)
    for start, c in [(0, 8), (24, 8), (88, 8), (0, 32)]:
        q = mk(1, H, c, D)
        out = at.chunked_prefill_attention(q, kbuf, vbuf, start)
        fq = onp.zeros((1, H, start + c, D), "f4")
        fq[:, :, start:] = onp.asarray(q)
        ref = at.mha_reference(jnp.asarray(fq),
                               kbuf[:, :, :start + c],
                               vbuf[:, :, :start + c], causal=True)
        onp.testing.assert_allclose(
            onp.asarray(out), onp.asarray(ref)[:, :, start:],
            rtol=2e-4, atol=2e-5, err_msg=(start, c))
