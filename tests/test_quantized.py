"""Low-precision fast path: weight-only int8 decode + quantized KV.

Guarantees under test:
- per-output-channel symmetric quantization round-trips within half a
  scale step per channel (``ops.quantized.quantize_channelwise``);
- the fused dequant-matmul pair — blocked jnp reference and Pallas
  kernel — is BITWISE identical (one numerical path, two executors);
- int8-KV decode attention (dense: jnp and Pallas; paged: jnp) stays
  within a per-step error bound of the fp32 cache on the same values;
- an int8-weights GenerationEngine holds the bounded-divergence
  contract against its fp32 twin (greedy agreement + logit bound,
  teacher-forced), with ZERO steady-state compiles, and a weight
  rollover RE-QUANTIZES under the swap lock without retracing;
- an int8-KV cache round-trips through prefill/decode/chunked-prefill/
  prefix-reuse with zero steady-state compiles;
- InferenceEngine rollover on a quantize_net-produced block
  re-quantizes the twins bit-exactly and recompile-free;
- Router fleets must be precision-homogeneous;
- ``contrib.quantization._dynamic_scale`` survives the all-zero
  activation batch (no NaNs) and records its telemetry row.
"""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.gluon.model_zoo.gpt import gpt_small
from mxnet_tpu.serving import GenerationEngine, InferenceEngine, Router

VOCAB, SMAX = 64, 64


def _net(seed=0, units=64, layers=2, heads=4):
    mx.np.random.seed(seed)
    model = gpt_small(vocab_size=VOCAB, units=units, num_layers=layers,
                      num_heads=heads, max_length=SMAX)
    model.initialize(mx.init.Xavier())
    return model


def _prompts(n=6, seed=1):
    rng = onp.random.RandomState(seed)
    return [rng.randint(0, VOCAB, int(rng.randint(3, 21))).astype("i4")
            for _ in range(n)]


# -- ops/quantized.py ---------------------------------------------------

def test_channelwise_roundtrip_bound():
    """|w - dequant(quant(w))| <= scale/2 per output channel, and an
    all-zero channel dequantizes to exact zero (no div-by-zero)."""
    from mxnet_tpu.ops.quantized import quantize_channelwise
    rng = onp.random.RandomState(0)
    w = rng.randn(16, 48).astype("f4")
    w[3] = 0.0                                   # all-zero channel
    wq, s = quantize_channelwise(w)
    wq, s = onp.asarray(wq), onp.asarray(s)
    assert wq.dtype == onp.int8 and s.shape == (16,)
    deq = wq.astype("f4") * s[:, None]
    assert (deq[3] == 0.0).all()
    err = onp.abs(deq - w)
    assert (err <= s[:, None] / 2 + 1e-7).all()


def test_dequant_matmul_matches_dequantized_reference():
    from mxnet_tpu.ops.quantized import (dequant_matmul,
                                         quantize_channelwise)
    rng = onp.random.RandomState(1)
    w = rng.randn(96, 40).astype("f4")
    x = rng.randn(5, 40).astype("f4")
    wq, s = quantize_channelwise(w)
    ref = x @ (onp.asarray(wq, "f4") * onp.asarray(s)[:, None]).T
    out = onp.asarray(dequant_matmul(x, wq, s, block_n=32))
    assert onp.allclose(out, ref, atol=1e-4)
    # leading dims fold and unfold
    x3 = rng.randn(2, 3, 40).astype("f4")
    assert dequant_matmul(x3, wq, s).shape == (2, 3, 96)
    with pytest.raises(ValueError, match="features"):
        dequant_matmul(x[:, :8], wq, s)


@pytest.mark.requires_pallas
def test_dequant_matmul_jnp_pallas_bitwise():
    """The fused-kernel pair performs the identical per-block
    computation: bitwise equality, blocked and unblocked."""
    from mxnet_tpu.ops.quantized import (dequant_matmul,
                                         dequant_matmul_pallas,
                                         quantize_channelwise)
    rng = onp.random.RandomState(2)
    w = rng.randn(128, 64).astype("f4")
    x = rng.randn(8, 64).astype("f4")
    wq, s = quantize_channelwise(w)
    for bn in (32, 128):
        a = onp.asarray(dequant_matmul(x, wq, s, block_n=bn))
        b = onp.asarray(dequant_matmul_pallas(x, wq, s, block_n=bn,
                                              interpret=True))
        assert (a == b).all()


@pytest.mark.requires_pallas
@pytest.mark.parametrize("n", [96, 100, 257])
def test_dequant_matmul_tail_block(n):
    """N need not divide by the block (a vocabulary never does): the
    jnp path takes the leftover channels in one more dot, the kernel's
    last grid step overhangs and its out-of-range channels are dropped
    — both agree with the dequantized reference."""
    from mxnet_tpu.ops.quantized import (dequant_matmul,
                                         dequant_matmul_pallas,
                                         quantize_channelwise)
    rng = onp.random.RandomState(3)
    w = rng.randn(n, 40).astype("f4")
    x = rng.randn(5, 40).astype("f4")
    wq, s = quantize_channelwise(w)
    ref = x @ (onp.asarray(wq, "f4") * onp.asarray(s)[:, None]).T
    a = onp.asarray(dequant_matmul(x, wq, s, block_n=32))
    b = onp.asarray(dequant_matmul_pallas(x, wq, s, block_n=32,
                                          interpret=True))
    assert a.shape == b.shape == (5, n)
    assert onp.allclose(a, ref, atol=1e-4)
    assert onp.allclose(b, ref, atol=1e-4)


# -- int8-KV decode attention ------------------------------------------

def _quant_kv(kf, vf):
    ks = onp.maximum(onp.abs(kf).max(axis=(2, 3)), 1e-12) / 127.0
    vs = onp.maximum(onp.abs(vf).max(axis=(2, 3)), 1e-12) / 127.0
    kq = onp.clip(onp.round(kf / ks[:, :, None, None]),
                  -127, 127).astype("i1")
    vq = onp.clip(onp.round(vf / vs[:, :, None, None]),
                  -127, 127).astype("i1")
    return kq, vq, ks.astype("f4"), vs.astype("f4")


def test_int8_kv_decode_attention_error_bound():
    """Dense decode attention over an int8 cache stays within a tight
    bound of the fp32 cache holding the same values; an empty slot
    still returns zeros."""
    from mxnet_tpu.ops import attention as att
    rng = onp.random.RandomState(3)
    B, H, S, D = 4, 2, 32, 8
    q = rng.randn(B, H, 1, D).astype("f4")
    kf = rng.randn(B, H, S, D).astype("f4")
    vf = rng.randn(B, H, S, D).astype("f4")
    lengths = onp.asarray([5, 32, 17, 0], "i4")
    kq, vq, ks, vs = _quant_kv(kf, vf)
    ref = onp.asarray(att.decode_attention(q, kf, vf, lengths))
    out = onp.asarray(att.decode_attention(q, kq, vq, lengths,
                                           k_scale=ks, v_scale=vs))
    assert onp.abs(out - ref).max() < 0.05
    assert (out[3] == 0).all()


@pytest.mark.requires_pallas
def test_int8_kv_decode_attention_pallas_parity():
    """The Pallas int8 decode kernel (in-VMEM dequant) matches the jnp
    dequant path; the paged jnp path, with a scale a page, tracks the
    unquantized cache."""
    from mxnet_tpu.ops import attention as att
    rng = onp.random.RandomState(4)
    B, H, S, D = 3, 2, 32, 8
    q = rng.randn(B, H, 1, D).astype("f4")
    kf = rng.randn(B, H, S, D).astype("f4")
    vf = rng.randn(B, H, S, D).astype("f4")
    lengths = onp.asarray([7, 32, 12], "i4")
    kq, vq, ks, vs = _quant_kv(kf, vf)
    jnp_out = onp.asarray(att.decode_attention(q, kq, vq, lengths,
                                               k_scale=ks, v_scale=vs))
    pl_out = onp.asarray(att.decode_attention_pallas(
        q, kq, vq, lengths, k_scale=ks, v_scale=vs, interpret=True,
        block_k=16))
    assert onp.abs(jnp_out - pl_out).max() < 1e-5
    # paged: scatter the same rows into a pool with per-page scales
    ps, pm = 8, S // 8
    npages = 1 + B * pm
    # a page is ps rows of H * D: head h at columns [h * D, (h + 1) * D)
    pool_k = onp.zeros((npages, ps, H * D), "i1")
    pool_v = onp.zeros((npages, ps, H * D), "i1")
    rows = lambda x: x.transpose(1, 0, 2).reshape(ps, H * D)  # noqa: E731
    sc_k = onp.zeros((npages, H), "f4")
    sc_v = onp.zeros((npages, H), "f4")
    table = onp.zeros((B, pm), "i4")
    pid = 1
    for b in range(B):
        for p in range(pm):
            seg_k = kf[b, :, p * ps:(p + 1) * ps]
            seg_v = vf[b, :, p * ps:(p + 1) * ps]
            sk = onp.maximum(onp.abs(seg_k).max(axis=(1, 2)),
                             1e-12) / 127.0
            sv = onp.maximum(onp.abs(seg_v).max(axis=(1, 2)),
                             1e-12) / 127.0
            pool_k[pid] = rows(onp.clip(
                onp.round(seg_k / sk[:, None, None]), -127, 127))
            pool_v[pid] = rows(onp.clip(
                onp.round(seg_v / sv[:, None, None]), -127, 127))
            sc_k[pid], sc_v[pid] = sk, sv
            table[b, p] = pid
            pid += 1
    ref = onp.asarray(att.decode_attention(q, kf, vf, lengths))
    pg_jnp = onp.asarray(att.paged_decode_attention(
        q, pool_k, pool_v, table, lengths, k_scale=sc_k, v_scale=sc_v))
    assert onp.abs(pg_jnp - ref).max() < 0.05


def _int8_paged_case(heads, sq, dtype="float32"):
    """An int8 paged cache at a served head count: lengths 0, 1, a page,
    a page + 1, six pages, one more, ragged, the full table; a page two
    slots share; on the scrap page garbage values under a NaN scale for
    K and a large one for V (finite: 0 * NaN is NaN). Returns ``q``
    (``dtype``), the two int8 pools, their scale tables, the table, the
    lengths and the two pools dequantized by hand with the scrap page's
    scale still finite."""
    import jax.numpy as jnp
    rng = onp.random.RandomState(5)
    ps, d, p_max = 16, 64, 8
    lengths = onp.asarray([0, 1, 16, 17, 96, 97, 77, 128], "i4")
    b = len(lengths)
    n_pages = 1 + b * p_max
    kf = rng.randn(n_pages, heads, ps, d).astype("f4")
    vf = rng.randn(n_pages, heads, ps, d).astype("f4")
    kq, vq, ks, vs = _quant_kv(kf, vf)             # one scale a page, head
    free = list(rng.permutation(onp.arange(1, n_pages)))
    table = onp.zeros((b, p_max), "i4")
    for i, n in enumerate(lengths):
        held = -(-int(n) // ps)
        table[i, :held] = [free.pop() for _ in range(held)]
    table[6, :2] = table[4, :2]                    # a shared prefix
    q = jnp.asarray(rng.randn(b, heads, sq, d).astype("f4")).astype(dtype)
    # (n_pages, H, ps, D) -> the pool's (n_pages, ps, H * D)
    pool = lambda x: x.transpose(0, 2, 1, 3).reshape(  # noqa: E731
        n_pages, ps, heads * d)
    by_hand = (pool(kq * ks[:, :, None, None]),
               pool(vq * vs[:, :, None, None]))
    ks[0], vs[0] = onp.nan, 1e4
    return q, pool(kq), pool(vq), ks, vs, table, lengths, by_hand


@pytest.mark.parametrize("sq", [1, 5], ids=["decode", "verify"])
@pytest.mark.parametrize("heads", [16, 20])
def test_int8_paged_decode_attention_parity(heads, sq):
    """Paged decode over an int8 pool matches the same attention over
    the pool dequantized by hand, at the served head counts, on
    ``_int8_paged_case``. The one-query tick scales scores and
    probabilities and builds no dequantized view; ``sq > 1`` dequantizes
    the gathered view."""
    from mxnet_tpu.ops import attention as att
    q, kq, vq, ks, vs, table, lengths, (kd, vd) = _int8_paged_case(
        heads, sq)
    ref = onp.asarray(att.paged_decode_attention(q, kd, vd, table,
                                                 lengths))
    out = onp.asarray(att.paged_decode_attention(
        q, kq, vq, table, lengths, k_scale=ks, v_scale=vs))
    assert (out[0] == 0).all()                     # the empty slot
    onp.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("heads", [2, 16, 20])
def test_int8_paged_tick_rows_reader_matches_gathered_reader(heads, dtype):
    """The int8 twin of ``tests/test_attention.py``'s reader test: the
    one-query tick over int8 rows as they lie (the page's scale on the
    scores and on the probabilities) against the gathered reader's
    dequantized float32 view (what a tp-mesh program attends), on
    ``_int8_paged_case``. With bfloat16 queries the tick rounds scaled
    probabilities to bfloat16 before P x V, as over a bfloat16 pool."""
    from mxnet_tpu.ops import attention as att
    q, kq, vq, ks, vs, table, lengths, _ = _int8_paged_case(heads, 1,
                                                            dtype)
    telemetry.reset()
    rows = att.paged_decode_attention(q, kq, vq, table, lengths,
                                      k_scale=ks, v_scale=vs)
    assert telemetry.counter_value("ops.attention.paged_decode.rows") == 1
    with att.jnp_only():
        gathered = att.paged_decode_attention(q, kq, vq, table, lengths,
                                              k_scale=ks, v_scale=vs)
    assert telemetry.counter_value(
        "ops.attention.paged_decode.gathered") == 1
    assert rows.dtype == q.dtype and rows.shape == q.shape
    rows, gathered = (onp.asarray(x, "f4") for x in (rows, gathered))
    assert (rows[0] == 0).all()                    # the empty slot
    assert onp.isfinite(rows).all()
    tol = dict(rtol=2e-4, atol=2e-5) if dtype == "float32" \
        else dict(rtol=2e-2, atol=8e-3)
    onp.testing.assert_allclose(rows, gathered, **tol)


# -- model-level bounded divergence ------------------------------------

def test_int8_kv_dense_decode_vs_fp32_bound():
    """A full decode pass over an int8 dense cache tracks the fp32
    cache within a per-step logit bound (teacher-forced: same
    inputs)."""
    net = _net()
    prompts = _prompts(4)

    def run(kv_dtype, forced=None):
        cache = net.init_cache(4, SMAX, dtype=kv_dtype)
        firsts = []
        for b, p in enumerate(prompts):
            pad = onp.zeros((1, 32), "i4")
            pad[0, :p.size] = p
            lg, cache = net.prefill(pad, [p.size], cache, slots=[b])
            firsts.append(int(onp.asarray(lg)[0].argmax()))
        lasts = onp.asarray(firsts, "i4")
        logs = []
        for t in range(8):
            inp = lasts if forced is None or forced[t] is None \
                else forced[t]
            lg, cache = net.decode_step(inp, cache)
            arr = onp.asarray(lg)
            logs.append(arr.copy())
            lasts = arr.argmax(axis=1).astype("i4")
        return onp.stack(logs)

    ref = run(None)
    # teacher-forcing: the int8-KV run consumes the fp32 run's token
    # stream, so each step compares logits under identical inputs.
    # Step 0's input is the prefill argmax, which is identical across
    # runs by construction (KV quantization touches only the cache
    # write, not the prefill logits).
    forced = [None] + [ref[t].argmax(axis=1).astype("i4")
                       for t in range(7)]
    quant = run("int8", forced=forced)
    assert onp.abs(ref - quant).max() < 0.5


def test_quantize_params_refresh_keeps_closures():
    """First quantize_params invalidates the closures (structure
    change); a refresh after a weight update does NOT retrace."""
    net = _net()
    net.quantize_params()
    cache = net.init_cache(2, SMAX)
    lg, cache = net.prefill(onp.zeros((1, 8), "i4"), [4], cache,
                            slots=[0])
    lg, cache = net.decode_step(onp.zeros(2, "i4"), cache)
    telemetry.reset()
    net.quantize_params()      # refresh: same structure
    lg2, cache = net.decode_step(onp.zeros(2, "i4"), cache)
    snap = telemetry.snapshot()
    assert snap["counters"].get("model.gpt.trace", 0) == 0
    n, saved = net.quantized_param_stats()
    assert n > 0 and saved > 0


# -- engine-level contracts --------------------------------------------

def test_engine_int8_weights_bounded_divergence():
    """The int8-weights engine agrees with the fp32 engine on most
    greedy tokens over a mixed corpus; steady state compiles
    nothing."""
    prompts = _prompts(8, seed=7)
    ref_eng = GenerationEngine(_net(), max_slots=4, max_length=SMAX,
                               max_new_tokens=8).warmup()
    ref = [ref_eng.submit(p).result(60).tokens for p in prompts]
    ref_eng.close()
    eng = GenerationEngine(_net(), max_slots=4, max_length=SMAX,
                           max_new_tokens=8,
                           quantize="int8_weights").warmup()
    assert eng.precision == "int8_weights"
    telemetry.reset()
    out = [eng.submit(p).result(60).tokens for p in prompts]
    snap = telemetry.snapshot()
    eng.close()
    assert snap["counters"].get("model.gpt.trace", 0) == 0
    assert snap["counters"].get("gluon.cachedop.cache_miss", 0) == 0
    pairs = [(a, b) for ra, rb in zip(ref, out)
             for a, b in zip(ra, rb)]
    agree = sum(a == b for a, b in pairs) / len(pairs)
    assert agree >= 0.9      # tiny random model, all near-ties: a
    # loose engine-level floor


def test_engine_rollover_requantizes_without_retrace():
    """load_weights on a quantized engine re-quantizes under the swap
    lock: zero traces, and the post-swap output equals a FRESH
    quantized engine on the new weights."""
    prompts = _prompts(4, seed=9)
    eng = GenerationEngine(_net(seed=0), max_slots=2, max_length=SMAX,
                           max_new_tokens=6,
                           quantize="int8_weights").warmup()
    [eng.submit(p).result(60) for p in prompts[:2]]
    donor = _net(seed=5)
    donor._gen_params()
    new_params = {k: v.data().asnumpy()
                  for k, v in donor.collect_params().items()}
    telemetry.reset()
    eng.load_weights(new_params)
    post = [eng.submit(p).result(60).tokens for p in prompts]
    snap = telemetry.snapshot()
    eng.close()
    assert snap["counters"].get("model.gpt.trace", 0) == 0
    assert "serving.generate.quant.requantize" in snap["histograms"]
    fresh = GenerationEngine(_net(seed=5), max_slots=2,
                             max_length=SMAX, max_new_tokens=6,
                             quantize="int8_weights").warmup()
    expect = [fresh.submit(p).result(60).tokens for p in prompts]
    fresh.close()
    assert post == expect


def test_engine_int8_kv_paged_zero_steady_state_compiles():
    """Paged engine with int8 weights AND int8 KV: chunked prefill,
    prefix reuse (exact-duplicate peek path) and decode all run with
    zero steady-state traces; pool refcounts balance at close."""
    net = _net()
    eng = GenerationEngine(net, max_slots=4, max_length=SMAX,
                           max_new_tokens=6, paged=True, page_size=8,
                           prefill_chunk=16, quantize="int8_weights",
                           kv_dtype="int8").warmup()
    assert eng.precision == "int8_weights+int8_kv"
    prompts = _prompts(6, seed=11)
    long = onp.arange(40, dtype="i4") % VOCAB     # multi-chunk prompt
    [eng.submit(p).result(60) for p in prompts[:3]]
    telemetry.reset()
    r1 = eng.submit(long).result(60)
    rest = [eng.submit(p).result(60) for p in prompts[3:]]
    dup = eng.submit(long).result(60)             # exact repeat: peek
    snap = telemetry.snapshot()
    eng.close()
    assert snap["counters"].get("model.gpt.trace", 0) == 0
    assert snap["counters"].get("serving.generate.prefix_hits", 0) >= 1
    assert len(r1.tokens) == 6 and len(dup.tokens) == 6
    assert eng._pool.free_count == eng._pool.n_pages - 1


def test_engine_int8_kv_dense_zero_steady_state_compiles():
    """DENSE engine with an int8 KV cache (per-head-per-slot scales):
    warmup covers every bucket + the decode step, a mixed-length wave
    with slot churn then compiles nothing, and every request delivers
    its budget."""
    eng = GenerationEngine(_net(), max_slots=2, max_length=SMAX,
                           max_new_tokens=5,
                           kv_dtype="int8").warmup()
    assert eng.precision == "int8_kv"
    prompts = _prompts(6, seed=13)
    [eng.submit(p).result(60) for p in prompts[:2]]
    telemetry.reset()
    results = [eng.submit(p).result(60) for p in prompts]
    snap = telemetry.snapshot()
    eng.close()
    assert snap["counters"].get("model.gpt.trace", 0) == 0
    assert all(len(r.tokens) == 5 for r in results)


def test_engine_kv_dtype_validation():
    with pytest.raises(ValueError, match="quantize"):
        GenerationEngine(_net(), quantize="int4")
    with pytest.raises(ValueError, match="kv_dtype"):
        GenerationEngine(_net(), kv_dtype="int7")
    with pytest.raises(ValueError, match="conflicts"):
        GenerationEngine(_net(), kv_dtype="int8",
                         cache_dtype="float32")
    with pytest.raises(TypeError, match="quantize_params"):
        class NoQuant:
            max_length = SMAX

            def init_cache(self, *a, **k):
                return {}
            prefill = decode_step = init_cache
        GenerationEngine(NoQuant(), quantize="int8_weights")


# -- InferenceEngine + Router ------------------------------------------

def _mlp(seed):
    from mxnet_tpu import gluon
    mx.np.random.seed(seed)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(24, activation="relu"))
    net.add(gluon.nn.Dense(8))
    net.initialize(mx.init.Xavier())
    return net


def test_inference_engine_int8_rollover_requantizes():
    """A quantize_net-produced block rolls weights over bit-exactly
    (vs a freshly quantized twin of the new weights) with zero
    recompiles; precision reads int8."""
    from mxnet_tpu.contrib.quantization import quantize_net
    x = mx.np.array(onp.random.RandomState(0).randn(4, 16)
                    .astype("f4"))
    net = quantize_net(_mlp(0), quantized_dtype="int8",
                       calib_mode="none", data_shapes=[(4, 16)])
    net.hybridize()
    eng = InferenceEngine(net, max_batch_size=4).warmup(x)
    assert eng.precision == "int8"
    donor = _mlp(1)
    donor(x)
    new_params = {k: v.data().asnumpy()
                  for k, v in donor.collect_params().items()}
    telemetry.reset()
    eng.load_weights(new_params)
    y = eng.submit(x).result(60).asnumpy()
    snap = telemetry.snapshot()
    assert snap["counters"].get("gluon.cachedop.build", 0) == 0
    assert "serving.quant.requantize" in snap["histograms"]
    ref_net = quantize_net(_mlp(1), quantized_dtype="int8",
                           calib_mode="none", data_shapes=[(4, 16)])
    ref_net.hybridize()
    expect = ref_net(x).asnumpy()
    eng.close()
    assert (y == expect).all()


def test_inference_engine_int8_rollover_validates_first():
    """A checkpoint missing a quantized twin's weight (strict) or
    carrying the wrong shape must reject BEFORE any install."""
    from mxnet_tpu.contrib.quantization import quantize_net
    x = mx.np.array(onp.random.RandomState(0).randn(4, 16)
                    .astype("f4"))
    net = quantize_net(_mlp(0), quantized_dtype="int8",
                       calib_mode="none", data_shapes=[(4, 16)])
    net.hybridize()
    eng = InferenceEngine(net, max_batch_size=4).warmup(x)
    y0 = eng.submit(x).result(60).asnumpy()
    donor = _mlp(1)
    donor(x)
    good = {k: v.data().asnumpy()
            for k, v in donor.collect_params().items()}
    missing = {k: v for k, v in good.items() if k != "0.weight"}
    with pytest.raises(ValueError, match="missing"):
        eng.load_weights(missing)
    bad = dict(good)
    bad["0.weight"] = onp.zeros((3, 3), "f4")
    with pytest.raises(ValueError, match="shape"):
        eng.load_weights(bad)
    assert (eng.submit(x).result(60).asnumpy() == y0).all()
    eng.close()


def test_router_rejects_mixed_precision_fleet():
    e_fp = GenerationEngine(_net(seed=0), max_slots=2,
                            max_length=SMAX)
    e_q = GenerationEngine(_net(seed=0), max_slots=2, max_length=SMAX,
                           quantize="int8_weights")
    with pytest.raises(TypeError, match="precision-homogeneous"):
        Router([e_fp, e_q])
    e_q2 = GenerationEngine(_net(seed=0), max_slots=2,
                            max_length=SMAX, quantize="int8_weights")
    router = Router([e_q, e_q2])   # homogeneous int8: fine
    router.close()
    e_fp.close()


# -- contrib/quantization satellites -----------------------------------

def test_dynamic_scale_all_zero_activation():
    """All-zero activations quantize to zeros (no NaN), the duration
    row lands, and an empty activation is rejected."""
    import jax.numpy as jnp
    from mxnet_tpu.contrib.quantization import (_dynamic_scale,
                                                _quantize_act)
    telemetry.reset()
    x = jnp.zeros((4, 8), jnp.float32)
    s = _dynamic_scale(x)
    q = onp.asarray(_quantize_act(x, s))
    assert onp.isfinite(float(s)) and float(s) > 0
    assert (q == 0).all()
    snap = telemetry.snapshot()
    assert "quantization.dynamic_scale" in snap["histograms"]
    with pytest.raises(ValueError, match="empty"):
        _dynamic_scale(jnp.zeros((0,), jnp.float32))


def test_quantized_dense_eager_zero_batch_forward():
    """Regression for the guarded scale: a QuantizedDense forward on
    an all-zero batch returns finite (bias-only) outputs."""
    from mxnet_tpu.contrib.quantization import quantize_net
    net = quantize_net(_mlp(0), quantized_dtype="int8",
                       calib_mode="none", data_shapes=[(4, 16)])
    y = net(mx.np.zeros((2, 16))).asnumpy()
    assert onp.isfinite(y).all()


def test_int8_paged_pool_bytes_against_fp32():
    """At one pool geometry an int8 pool, its per-head-per-page scales
    counted against it, holds a little over a quarter of the fp32
    pool's bytes: the same memory holds nearly four times the pages."""
    net = _net()

    def pool_bytes(dtype):
        cache = net.init_paged_cache(4, 33, 8, SMAX, dtype=dtype)
        return sum(int(a.nbytes) for key, pools in cache.items()
                   if key not in ("table", "len") for a in pools)

    fp32, int8 = pool_bytes("float32"), pool_bytes("int8")
    assert fp32 / 4 < int8 <= 0.30 * fp32, (fp32, int8)
