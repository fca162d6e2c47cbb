"""Paged KV cache: page pool / prefix index units, the paged model
API, and the paged GenerationEngine (prefix reuse, COW, chunked
prefill).

Guarantees under test:
- the PAGED cache calls are numerically faithful to the dense ones —
  fresh prefill and decode are BITWISE identical (same arithmetic,
  page-shaped writes), chunk/peek agree within ulps;
- greedy engine output in paged mode is TOKEN-IDENTICAL to the dense
  engine under mixed prompt lengths (single-chunk, multi-chunk,
  shared-prefix, exact-duplicate) and evict/refill churn;
- refcount/COW correctness: shared-prefix requests can finish in any
  order, the divergence page is copied before the first write into a
  shared page, and the pool balances to fully free after close +
  index drop;
- chunked prefill runs AT MOST one chunk per engine iteration
  (decode-stall bound, asserted via the step telemetry gauge);
- the steady state compiles nothing (``model.gpt.trace`` flat).
"""
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.gluon.model_zoo.gpt import gpt_small
from mxnet_tpu.serving import EngineClosedError, GenerationEngine
from mxnet_tpu.serving.paging import PagePool, PrefixIndex

VOCAB, SLOTS, SMAX, PS, CHUNK = 97, 4, 64, 8, 16
N_PAGES = SLOTS * SMAX // PS + 1


@pytest.fixture(scope="module")
def net():
    onp.random.seed(1234)
    mx.np.random.seed(1234)
    model = gpt_small(vocab_size=VOCAB, units=32, num_layers=2,
                      num_heads=4, max_length=128)
    model.initialize(mx.init.Xavier())
    return model


def _prompt(rng, n):
    return rng.randint(0, VOCAB, size=n).astype("i4")


def _paged_engine(net, **kw):
    args = dict(max_slots=SLOTS, max_length=SMAX, max_new_tokens=8,
                queue_limit=64, paged=True, page_size=PS,
                prefill_chunk=CHUNK, n_pages=N_PAGES)
    args.update(kw)
    return GenerationEngine(net, **args)


def _dense_engine(net, **kw):
    args = dict(max_slots=SLOTS, max_length=SMAX, max_new_tokens=8,
                queue_limit=64)
    args.update(kw)
    return GenerationEngine(net, **args)


# -- page pool / prefix index units ------------------------------------

def test_page_pool_refcounts_and_accounting():
    pool = PagePool(8)           # pages 1..7 allocatable
    assert pool.free_count == 7
    a = pool.alloc(3)
    assert len(a) == 3 and pool.free_count == 4
    assert pool.alloc(5) is None          # insufficient: all-or-nothing
    assert pool.free_count == 4
    pool.retain(a[0])
    assert pool.refcount(a[0]) == 2
    assert not pool.release(a[0])         # still held
    assert pool.release(a[0])             # now freed
    assert pool.free_count == 5
    with pytest.raises(ValueError, match="unallocated"):
        pool.release(a[0])
    with pytest.raises(ValueError, match="scrap"):
        pool.retain(0)
    with pytest.raises(ValueError, match=">= 2"):
        PagePool(1)


def test_prefix_index_match_register_evict():
    pool = PagePool(32)
    idx = PrefixIndex(pool, page_size=4, max_records=8)
    rng = onp.random.RandomState(0)
    prompt = _prompt(rng, 10)             # 2 full blocks + partial
    pages = pool.alloc(3)
    row = onp.zeros(8, "i4")
    row[:3] = pages
    assert idx.match(prompt) == ([], 0)
    assert idx.register(prompt, row)
    assert not idx.register(prompt, row)  # idempotent per digest
    # every covering page retained by the index
    assert all(pool.refcount(p) == 2 for p in pages)
    # exact hit resolves the full prompt (partial tail included)
    assert idx.match(prompt) == (pages, 10)
    # a longer prompt with the same prefix chain-matches the FULL blocks
    longer = onp.concatenate([prompt[:8], _prompt(rng, 6)])
    assert idx.match(longer) == (pages[:2], 8)
    # a diverging prompt matches only the blocks before the divergence
    diverged = prompt.copy()
    diverged[5] = (diverged[5] + 1) % VOCAB
    assert idx.match(diverged) == (pages[:1], 4)
    # eviction releases the index references; slot refs still pin them
    assert idx.evict_lru()
    assert all(pool.refcount(p) == 1 for p in pages)
    assert idx.match(prompt) == ([], 0)
    assert not idx.evict_lru()


def test_prefix_index_registration_race_keeps_chain_consistent():
    """Two identical prompts prefilled PRIVATELY (both admitted before
    either registered) then registered... the second record must not
    keep the first record's chain entry alive with its own different
    page: evicting the creator record must retire the entry instead of
    letting match() hand out a freed page (regression — this used to
    resolve a stale page id and corrupt pool refcounts)."""
    pool = PagePool(32)
    idx = PrefixIndex(pool, page_size=4, max_records=8)
    rng = onp.random.RandomState(2)
    prompt = _prompt(rng, 8)
    other = onp.concatenate([prompt, _prompt(rng, 4)])  # same prefix,
    p1 = pool.alloc(2)                                  # distinct digest
    row1 = onp.zeros(8, "i4")
    row1[:2] = p1
    p2 = pool.alloc(3)
    row2 = onp.zeros(8, "i4")
    row2[:3] = p2
    assert idx.register(prompt, row1)
    assert idx.register(other, row2)   # its prefix pages differ from p1
    # evict the CREATOR of the shared chain entries
    assert idx.evict_lru()
    for pid in p1:
        assert pool.refcount(pid) == 1          # only the alloc ref
    pages, n = idx.match(onp.concatenate([prompt, _prompt(rng, 2)]))
    # the chain must not resolve the prefix to the evicted record's
    # freed pages; p2's copy was never published for those blocks
    for pid in pages:
        assert pool.refcount(pid) >= 1
        assert pid not in p1
    # the second record's own exact-match path still works
    assert idx.match(other) == (p2, 12)


def test_prefix_index_lru_bound():
    pool = PagePool(64)
    idx = PrefixIndex(pool, page_size=4, max_records=2)
    rng = onp.random.RandomState(1)
    rows = []
    for i in range(3):
        p = _prompt(rng, 8)
        pages = pool.alloc(2)
        row = onp.zeros(8, "i4")
        row[:2] = pages
        idx.register(p, row)
        rows.append((p, pages))
    assert len(idx) == 2                  # oldest evicted
    assert idx.match(rows[0][0]) == ([], 0)
    assert idx.match(rows[2][0])[1] == 8


# -- model-level parity ------------------------------------------------

def test_paged_fresh_prefill_bitwise_matches_dense(net):
    """The fresh (single-chunk, unshared) paged prefill runs the dense
    prefill's exact computation: logits and cached K/V values are
    bitwise identical — the foundation of engine token-identity. The
    decode ticks after it agree to float32 rounding and pick the same
    greedy token: the paged tick attends the pool's rows as they lie
    (``ops.attention.rows_decode_attention``), which sums the same
    products in another order than the dense cache's reader."""
    rng = onp.random.RandomState(2)
    prompt = _prompt(rng, 11)
    padded = onp.zeros((1, 16), "i4")
    padded[0, :11] = prompt
    dense = net.init_cache(SLOTS, SMAX)
    lg_d, dense = net.prefill(padded, [11], dense, slots=[2])
    paged = net.init_paged_cache(SLOTS, N_PAGES, PS, SMAX)
    row = onp.zeros(SMAX // PS, "i4")
    row[:4] = [5, 6, 7, 8]
    lg_p, paged = net.prefill_paged(padded, 11, 2, row, paged,
                                    fresh=True)
    assert (onp.asarray(lg_d) == onp.asarray(lg_p)).all()
    # decode stays identical to rounding, and in its token, step for step
    tok = int(onp.asarray(lg_d)[0].argmax())
    active = onp.zeros(SLOTS, "i4")
    active[2] = 1
    for _ in range(4):
        step = onp.zeros((SLOTS,), "i4")
        step[2] = tok
        lgd, dense = net.decode_step(step, dense)
        lgp, paged = net.decode_step_paged(step, active, paged)
        onp.testing.assert_allclose(onp.asarray(lgp)[2],
                                    onp.asarray(lgd)[2],
                                    rtol=2e-5, atol=2e-6)
        tok = int(onp.asarray(lgd)[2].argmax())
        assert int(onp.asarray(lgp)[2].argmax()) == tok


@pytest.mark.parametrize("write", ["fresh", "chunk"])
def test_paged_pool_holds_each_position_where_the_layout_says(net, write):
    """A pool is (n_pages, page_size, H * Dh): position ``t`` of slot
    ``b``, head ``h``, lives at ``pool[table[b, t // ps], t % ps,
    h * Dh:(h + 1) * Dh]`` — held against the dense cache's
    ``k[b, h, t]`` after a prefill (one fresh chunk: bitwise; two
    chunks over the gathered view: to rounding) and four decode
    steps (to float32 rounding past layer 0: the paged tick's reader
    sums in another order than the dense one), for K and V of every
    layer."""
    rng = onp.random.RandomState(7)
    n, slot, heads = 21, 2, 4
    prompt = _prompt(rng, n)
    width = 32 if write == "fresh" else CHUNK
    dense = net.init_cache(SLOTS, SMAX)
    padded = onp.zeros((1, 32), "i4")
    padded[0, :n] = prompt
    lg, dense = net.prefill(padded, [n], dense, slots=[slot])
    paged = net.init_paged_cache(SLOTS, N_PAGES, PS, SMAX)
    assert paged["k"][0].shape == (N_PAGES, PS, 32)
    row = onp.zeros(SMAX // PS, "i4")
    row[:5] = [9, 3, 17, 4, 12]
    for pos in range(0, n, width):
        nv = min(width, n - pos)
        chunk = onp.zeros((1, width), "i4")
        chunk[0, :nv] = prompt[pos:pos + nv]
        _lg, paged = net.prefill_paged(chunk, nv, slot, row, paged,
                                       start=pos, fresh=write == "fresh")
    tok = int(onp.asarray(lg)[0].argmax())
    active = onp.zeros(SLOTS, "i4")
    active[slot] = 1
    for _ in range(4):
        step = onp.zeros((SLOTS,), "i4")
        step[slot] = tok
        lgd, dense = net.decode_step(step, dense)
        _lgp, paged = net.decode_step_paged(step, active, paged)
        tok = int(onp.asarray(lgd)[slot].argmax())
    length = n + 4
    assert int(onp.asarray(paged["len"])[slot]) == length
    table = onp.asarray(paged["table"])
    for key in ("k", "v"):
        for pool, cache in zip(paged[key], dense[key]):
            pool, cache = onp.asarray(pool), onp.asarray(cache)
            dh = pool.shape[2] // heads
            for t in range(length):
                got = pool[table[slot, t // PS], t % PS] \
                    .reshape(heads, dh)
                if write == "fresh" and t < n:
                    assert (got == cache[slot, :, t]).all(), (key, t)
                elif write == "fresh":
                    onp.testing.assert_allclose(
                        got, cache[slot, :, t], rtol=2e-5, atol=2e-6)
                else:
                    onp.testing.assert_allclose(
                        got, cache[slot, :, t], rtol=2e-3, atol=2e-4)


def test_chunked_prefill_and_peek_match_full_forward(net):
    """Multi-chunk prefill reproduces the full causal forward's
    last-token logits, and peek (prefix-hit path) reproduces the last
    chunk's logits — no prefill, no cache write."""
    rng = onp.random.RandomState(3)
    prompt = _prompt(rng, 21)
    full = net(mx.np.array(prompt[None, :])).asnumpy()[0]
    cache = net.init_paged_cache(SLOTS, N_PAGES, PS, SMAX)
    row = onp.zeros(SMAX // PS, "i4")
    row[:4] = [10, 11, 12, 13]
    logits = None
    pos = 0
    while pos < 21:
        nv = min(CHUNK, 21 - pos)
        chunk = onp.zeros((1, CHUNK), "i4")
        chunk[0, :nv] = prompt[pos:pos + nv]
        logits, cache = net.prefill_paged(chunk, nv, 1, row, cache,
                                          start=pos)
        pos += nv
    onp.testing.assert_allclose(onp.asarray(logits)[0], full[-1],
                                rtol=2e-3, atol=2e-4)
    assert onp.asarray(cache["len"]).tolist() == [0, 21, 0, 0]
    peek = net.peek_logits_paged(int(prompt[-1]), 1, cache)
    assert int(onp.asarray(peek).argmax()) \
        == int(onp.asarray(logits)[0].argmax())
    # copy-page + rebind is invisible to attention (COW mechanics)
    cache = net.copy_page_paged(10, 20, cache)
    row2 = row.copy()
    row2[0] = 20
    cache = net.bind_slot_paged(1, row2, 21, cache)
    peek2 = net.peek_logits_paged(int(prompt[-1]), 1, cache)
    assert (onp.asarray(peek2) == onp.asarray(peek)).all()


def test_paged_cache_validation(net):
    with pytest.raises(ValueError, match="divide"):
        net.init_paged_cache(SLOTS, N_PAGES, 7, SMAX)
    with pytest.raises(ValueError, match="scrap"):
        net.init_paged_cache(SLOTS, 1, PS, SMAX)
    cache = net.init_paged_cache(SLOTS, N_PAGES, PS, SMAX)
    row = onp.zeros(SMAX // PS, "i4")
    with pytest.raises(ValueError, match="multiple of page_size"):
        net.prefill_paged(onp.zeros((1, 12), "i4"), 12, 0, row, cache)
    with pytest.raises(ValueError, match="multiple of page_size"):
        net.prefill_paged(onp.zeros((1, 16), "i4"), 16, 0, row, cache,
                          start=4)
    with pytest.raises(ValueError, match="fresh"):
        net.prefill_paged(onp.zeros((1, 16), "i4"), 16, 0, row, cache,
                          start=16, fresh=True)


# -- engine: token identity & churn ------------------------------------

def test_engine_paged_token_identity_mixed_lengths_and_churn(net):
    """The headline guarantee: paged mode (prefix reuse + chunked
    prefill + COW + page recycling under churn) changes NO request's
    tokens vs the dense engine — mixed single-chunk, multi-chunk,
    shared-prefix, and exact-duplicate prompts, three waves deep so
    slots and pages evict and refill mid-sequence."""
    rng = onp.random.RandomState(4)
    sys_prompt = _prompt(rng, 24)
    prompts = [_prompt(rng, n) for n in (3, 9, 17, 5, 30, 12, 7, 21,
                                         40, 2, 33, 14)]
    prompts += [onp.concatenate([sys_prompt, _prompt(rng, n)])
                for n in (4, 7, 3, 11)]
    prompts.append(prompts[-1].copy())     # exact duplicate
    prompts.append(prompts[4].copy())
    budgets = [3 + i % 7 for i in range(len(prompts))]

    dense = _dense_engine(net)
    d_res = [s.result(timeout=300) for s in
             [dense.submit(p, max_new_tokens=b)
              for p, b in zip(prompts, budgets)]]
    dense.close()

    paged = _paged_engine(net)
    p_res = [s.result(timeout=300) for s in
             [paged.submit(p, max_new_tokens=b)
              for p, b in zip(prompts, budgets)]]
    snap = telemetry.snapshot()
    for i, (d, p) in enumerate(zip(d_res, p_res)):
        assert p.tokens == d.tokens, f"request {i} diverged"
        assert p.finish_reason == d.finish_reason
    # sharing actually happened (the identity must not be vacuous)
    assert snap["counters"]["serving.generate.pages.shared"] > 0
    assert snap["counters"]["serving.generate.prefill_chunks"] > 0
    paged.close()


def test_engine_paged_zero_steady_state_compiles(net):
    """After warmup, a second traffic wave — shared prefixes, chunked
    long prompts, COW, evict/refill — triggers ZERO new traces."""
    eng = _paged_engine(net, queue_limit=128)
    eng.warmup()
    rng = onp.random.RandomState(5)
    sys_prompt = _prompt(rng, 16)
    first = [eng.submit(onp.concatenate([sys_prompt, _prompt(rng, 5)]),
                        max_new_tokens=4),
             eng.submit(_prompt(rng, 30), max_new_tokens=4)]
    for s in first:
        s.result(timeout=300)
    telemetry.reset()
    wave = [eng.submit(onp.concatenate([sys_prompt,
                                        _prompt(rng, 1 + i % 9)]),
                       max_new_tokens=2 + i % 5) for i in range(10)]
    wave += [eng.submit(_prompt(rng, 3 + (7 * i) % 40),
                        max_new_tokens=2 + i % 4) for i in range(6)]
    for s in wave:
        assert len(s.result(timeout=300).tokens) >= 1
    snap = telemetry.snapshot()
    assert telemetry.counter_value("model.gpt.trace") == 0, \
        "paged steady state retraced"
    assert "gluon.cachedop.cache_miss" not in snap["counters"]
    assert snap["counters"]["serving.generate.evictions"] == 16
    eng.close()


def test_warmup_without_prefix_index_covers_every_chunk_width(net):
    """With no prefix index chunks start at multiples of the chunk width:
    ``warmup()`` compiles the whole width and the one that reaches the
    cache's end, and prompts of every kind trace nothing after it."""
    eng = _paged_engine(net, prefix_cache=False, max_length=SMAX - PS)
    assert eng._chunk_widths() == [CHUNK - PS, CHUNK]
    eng.warmup()
    telemetry.reset()
    rng = onp.random.RandomState(9)
    for n in (3, CHUNK, CHUNK + 1, 3 * CHUNK, SMAX - PS - 2):
        eng.generate(_prompt(rng, n), max_new_tokens=1, timeout=300)
    assert telemetry.counter_value("model.gpt.trace") == 0
    eng.close()


def test_engine_paged_prefix_hit_skips_prefill(net):
    """An exact repeat of a cached prompt admits via the peek path:
    zero prefill chunks, first token identical."""
    eng = _paged_engine(net)
    rng = onp.random.RandomState(6)
    p = _prompt(rng, PS * 2)        # page-aligned: clean full-coverage
    r1 = eng.generate(p, max_new_tokens=5, timeout=300)
    telemetry.reset()
    r2 = eng.generate(p, max_new_tokens=5, timeout=300)
    snap = telemetry.snapshot()
    assert r2.tokens == r1.tokens
    assert snap["counters"].get("serving.generate.prefix_hits", 0) == 1
    assert "serving.generate.prefill_chunks" not in snap["counters"]
    eng.close()


def test_engine_paged_cow_and_arbitrary_finish_order(net):
    """N requests sharing one prompt finish in arbitrary order
    (different budgets force different completion times): every stream
    is correct, the divergence page is COW'd (counter observed), and
    after close + prefix-cache drop the pool balances to fully free —
    no leaked or double-freed page."""
    eng = _paged_engine(net, queue_limit=64)
    rng = onp.random.RandomState(7)
    p = _prompt(rng, 13)            # partial tail page -> COW territory
    dense = _dense_engine(net, max_new_tokens=16)
    refs = {b: dense.generate(p, max_new_tokens=b, timeout=300).tokens
            for b in (9, 2, 14, 5, 11, 3)}
    dense.close()
    telemetry.reset()
    streams = [eng.submit(p, max_new_tokens=b)
               for b in (9, 2, 14, 5, 11, 3)]
    outs = {}
    for b, s in zip((9, 2, 14, 5, 11, 3), streams):
        outs[b] = s.result(timeout=300).tokens
    snap = telemetry.snapshot()
    for b, toks in outs.items():
        assert toks == refs[b], f"budget {b} diverged"
    assert snap["counters"]["serving.generate.pages.cow_copies"] >= 1
    assert snap["counters"]["serving.generate.pages.shared"] > 0
    eng.close()
    # close() releases slot refs AND drains the prefix index itself:
    # the pool must read fully free with no manual drop
    assert eng._pool.free_count == eng._pool.n_pages - 1, \
        "page pool did not balance after close"


def test_engine_paged_one_chunk_per_iteration(net):
    """The decode-stall bound: while a long prompt chunk-prefills,
    each engine iteration runs AT MOST ONE chunk (telemetry gauge peak
    == 1) interleaved with decode — and the long prompt still comes
    out token-identical to the dense engine."""
    eng = _paged_engine(net, queue_limit=64)
    eng.warmup()
    rng = onp.random.RandomState(8)
    short = _prompt(rng, 4)
    long_p = _prompt(rng, 50)       # ceil(50/16) = 4 chunks
    dense = _dense_engine(net, max_new_tokens=24)
    ref_long = dense.generate(long_p, max_new_tokens=8,
                              timeout=300).tokens
    dense.close()
    telemetry.reset()
    busy = eng.submit(short, max_new_tokens=24)      # in-flight decode
    s = eng.submit(long_p, max_new_tokens=8)
    assert s.result(timeout=300).tokens == ref_long
    busy.result(timeout=300)
    snap = telemetry.snapshot()
    assert snap["counters"]["serving.generate.prefill_chunks"] >= 4
    assert snap["gauges"][
        "serving.generate.prefill_chunks_per_iter"]["peak"] <= 1
    eng.close()


def test_engine_paged_pool_exhaustion_defers_admission(net):
    """More concurrent demand than pages: admission BLOCKS (requests
    wait for freed pages) instead of corrupting shared state — and
    everything completes once slots/pages recycle. A request that can
    never fit is rejected at submit."""
    # 4 allocatable pages = ONE 20-token/12-budget request's worst case
    eng = GenerationEngine(net, max_slots=SLOTS, max_length=SMAX,
                           max_new_tokens=8, queue_limit=64, paged=True,
                           page_size=PS, prefill_chunk=CHUNK,
                           n_pages=SMAX // PS // 2 + 1)
    rng = onp.random.RandomState(9)
    with pytest.raises(ValueError, match="pages"):
        eng.submit(_prompt(rng, SMAX - 1), max_new_tokens=64)
    streams = [eng.submit(_prompt(rng, 20), max_new_tokens=12)
               for _ in range(6)]
    for s in streams:
        assert len(s.result(timeout=300).tokens) == 12
    eng.close()


def test_engine_paged_match_survives_eviction_during_alloc(net):
    """A matched prefix's pages must be retained BEFORE the private-
    page allocation may LRU-evict their backing record: with a tight
    pool, the evicted pages used to come straight back off the LIFO
    free list as the same request's private pages — the row aliased
    shared and private, chunk prefill overwrote the shared-prefix K/V,
    and greedy output silently diverged (regression, found by review
    with exactly this configuration)."""
    # 8 allocatable pages; prompt A fills 4 and is prefix-cached; the
    # follow-up shares 2 of them and needs 6 private -> must evict A's
    # record mid-admission
    eng = GenerationEngine(net, max_slots=SLOTS, max_length=SMAX,
                           max_new_tokens=8, queue_limit=64, paged=True,
                           page_size=PS, prefill_chunk=CHUNK, n_pages=9)
    rng = onp.random.RandomState(11)
    a = _prompt(rng, 4 * PS)                      # 32 tokens, 4 pages
    follow = onp.concatenate([a[:2 * PS], _prompt(rng, 2)])
    dense = _dense_engine(net, max_new_tokens=8)
    ref_a = dense.generate(a, max_new_tokens=4, timeout=300).tokens
    ref_f = dense.generate(follow, max_new_tokens=32,
                           timeout=300).tokens
    dense.close()
    assert eng.generate(a, max_new_tokens=4, timeout=300).tokens \
        == ref_a
    got = eng.generate(follow, max_new_tokens=32, timeout=300).tokens
    assert got == ref_f, "shared-prefix K/V corrupted by mid-" \
        "admission eviction"
    eng.close()
    assert eng._pool.free_count == eng._pool.n_pages - 1


def test_engine_paged_sync_escape_hatch(net, monkeypatch):
    """MXTPU_SERVING=0: inline synchronous paged generation matches
    the threaded paged engine."""
    monkeypatch.setenv("MXTPU_SERVING", "0")
    eng = _paged_engine(net, max_new_tokens=6)
    assert eng._worker is None
    rng = onp.random.RandomState(10)
    p = _prompt(rng, 25)            # multi-chunk in sync mode
    s = eng.submit(p)
    assert s.done()
    eng.close()
    eng2 = _paged_engine(net, max_new_tokens=6)
    assert eng2.generate(p, timeout=300).tokens == s.result().tokens
    eng2.close()


def test_engine_paged_rollover_flushes_prefix_cache():
    """load_weights on a paged engine drops the prefix cache: its K/V
    was computed with the OLD weights, and a post-swap prefix hit
    would silently serve stale attention context (regression, found by
    review). The repeat prompt re-prefills under the new weights and
    matches a fresh engine exactly."""
    def build(seed):
        onp.random.seed(seed)
        mx.np.random.seed(seed)
        m = gpt_small(vocab_size=VOCAB, units=32, num_layers=2,
                      num_heads=4, max_length=128)
        m.initialize(mx.init.Xavier())
        m(mx.np.array(onp.zeros((1, 4), "i4")))
        return m

    net_a = build(1)
    params_b = {k: onp.asarray(p.data()._data)
                for k, p in build(2).collect_params().items()}
    eng = _paged_engine(net_a)
    rng = onp.random.RandomState(12)
    p = _prompt(rng, 2 * PS)            # page-aligned: a clean peek hit
    eng.generate(p, max_new_tokens=4, timeout=300)
    assert len(eng._prefix) == 1
    eng.load_weights(params_b)
    assert len(eng._prefix) == 0, "stale prefix survived the rollover"
    telemetry.reset()
    got = eng.generate(p, max_new_tokens=4, timeout=300).tokens
    assert telemetry.counter_value(
        "serving.generate.prefix_hits") == 0
    ref = _dense_engine(build(3), max_new_tokens=4)
    ref.load_weights(params_b)
    assert got == ref.generate(p, max_new_tokens=4, timeout=300).tokens
    ref.close()
    eng.close()


def test_engine_paged_close_mid_prefill_rejects_not_empty(net):
    """A hard close while a long prompt is still chunk-prefilling must
    reject the stream (EngineClosedError) — never complete it
    'successfully' with zero tokens (regression: _close_active used to
    hand prefill-phase slots finish_reason='closed')."""
    outcomes = set()
    rng = onp.random.RandomState(13)
    for _ in range(4):
        eng = _paged_engine(net, max_new_tokens=4)
        s = eng.submit(_prompt(rng, SMAX - 2))   # many chunks pending
        eng.close(timeout=0.0)
        try:
            r = s.result(timeout=30)
            assert len(r.tokens) >= 1, \
                "empty stream delivered as a successful result"
            outcomes.add("tokens")
        except EngineClosedError:
            outcomes.add("rejected")
        # a mid-generation close must not leak page refcounts: the
        # terminal paths release slot refs and drain the index
        assert eng._pool.free_count == eng._pool.n_pages - 1, \
            "pages leaked by close mid-prefill"
    assert outcomes, "no outcome observed"


def test_engine_paged_prefix_hit_degrades_to_unshared_under_pressure(
        net, monkeypatch):
    """A prefix hit whose transient page demand (retained shared pages
    + full private reservation) exceeds the pool must degrade to a
    plain UNSHARED prefill, not fail the admission (regression: the
    slot's own retained refs pinned exactly the pages the eviction
    sweep tried to reclaim, and sync mode surfaced a spurious
    QueueFullError an immediate retry would have satisfied)."""
    monkeypatch.setenv("MXTPU_SERVING", "0")   # the single-attempt path
    eng = GenerationEngine(net, max_slots=2, max_length=SMAX,
                           max_new_tokens=8, queue_limit=16, paged=True,
                           page_size=16, prefill_chunk=16, n_pages=5)
    rng = onp.random.RandomState(14)
    p = _prompt(rng, 20)
    first = eng.generate(p, max_new_tokens=4, timeout=300)
    # needs all 4 allocatable pages while 2 are still prefix-retained:
    # must succeed by dropping the match, and stay token-identical
    second = eng.generate(p, max_new_tokens=44, timeout=300)
    assert second.tokens[:4] == first.tokens
    eng.close()


def test_engine_paged_constructor_validation(net):
    with pytest.raises(ValueError, match="power of two"):
        _paged_engine(net, page_size=12)
    with pytest.raises(ValueError, match="divide"):
        GenerationEngine(net, max_slots=2, max_length=40,
                         paged=True, page_size=16)
    with pytest.raises(ValueError, match="prefill_chunk"):
        _paged_engine(net, prefill_chunk=12)


# -- the tick in flight (docs/SERVING.md "The tick in flight") ----------

_KINDS = {
    "dense": _dense_engine,
    "paged": _paged_engine,
    "paged-no-prefix": lambda net, **kw: _paged_engine(
        net, prefix_cache=False, **kw),
}


def _ctr(name):
    return telemetry.counter_value("serving.generate." + name)


def _quiet(eng):
    """Between two iterations of the worker: is a tick in flight?"""
    with eng._gen_lock:
        return eng._ahead is None


def _sync_results(net, kind, monkeypatch, jobs, **kw):
    """What the synchronous engine (``MXTPU_SERVING=0``: no worker, no
    tick in flight) serves for ``jobs``, one request at a time."""
    monkeypatch.setenv("MXTPU_SERVING", "0")
    eng = _KINDS[kind](net, **kw)
    telemetry.reset()
    out = []
    for p, n, extra in jobs:
        s = eng.submit(p, max_new_tokens=n, **extra)
        assert s.done()            # whole results, inline
        out.append(s.result())
    assert _ctr("ticks_ahead") == 0 and eng._ahead is None
    eng.close()
    monkeypatch.delenv("MXTPU_SERVING")
    return out


def _wait_tokens(stream, n, timeout=60.0):
    t_end = time.monotonic() + timeout
    while len(stream.tokens) < n and not stream.done():
        assert time.monotonic() < t_end, "stream made no progress"
        time.sleep(0.0005)


@pytest.mark.parametrize("kind", list(_KINDS))
def test_tick_in_flight_serves_the_synchronous_engines_tokens(
        net, kind, monkeypatch):
    """Greedy streams under dispatch-ahead are the synchronous engine's,
    token for token: staggered admissions (rows enter decode while a tick
    is in flight and are merged in on the device), mixed lengths, long
    prompts that finish prefill while others decode, an end at the
    cache's capacity, an exact repeat. Every end here is the host's to
    predict, so no row-tick is wasted; the merge is no dispatch."""
    rng = onp.random.RandomState(21)
    lens = (3, 40, 7, 25, 12, 50, 5, 33, 18, 9, 45, 4, 50)
    news = (20, 6, 14, 9, 12, 5, 18, 11, 8, 13, 7, 16, 30)
    jobs = [(_prompt(rng, n), m, {}) for n, m in zip(lens, news)]
    jobs.append((jobs[0][0].copy(), 9, {}))      # an exact repeat
    want = _sync_results(net, kind, monkeypatch, jobs)
    assert want[12].finish_reason == "length" \
        and len(want[12].tokens) == SMAX - 50 + 1   # capacity, not budget

    eng = _KINDS[kind](net).warmup()
    telemetry.reset()
    streams = []
    for p, n, extra in jobs:
        streams.append(eng.submit(p, max_new_tokens=n, **extra))
        time.sleep(0.002)
    got = [s.result(timeout=300) for s in streams]
    for i, (g, w) in enumerate(zip(got, want)):
        assert g.tokens == w.tokens, f"request {i} diverged"
        assert g.finish_reason == w.finish_reason
    assert _quiet(eng)
    syncs = _ctr("host_syncs")
    assert _ctr("ticks_ahead") > 0.8 * syncs > 0
    assert _ctr("stale_row_ticks") == 0
    assert _ctr("dispatches") == syncs     # one program a tick, as before
    eng.close()


class _DecodeSpy:
    """Runs ``hook()`` in the worker, under the engine's lock, right
    before each paged decode tick is dispatched."""

    def __init__(self, net, hook):
        self.net, self.hook = net, hook
        self._decode = net.decode_step_paged
        net.decode_step_paged = self.decode

    def decode(self, tokens, active, cache, **kw):
        self.hook()
        return self._decode(tokens, active, cache, **kw)

    def undo(self):
        del self.net.decode_step_paged   # the class's method again


def _admitted_slot(stream):
    return next(s["attrs"]["slot"] for s in stream.trace()
                if s["name"] == "admission")


@pytest.mark.parametrize("tenant", ["fresh", "prefix-hit"])
@pytest.mark.parametrize("end", ["eos", "deadline"])
def test_unpredicted_end_costs_one_row_tick_and_nothing_else(
        net, end, tenant):
    """A request that ends where the host cannot predict it (its eos
    token, its deadline) while the next tick is in flight: nothing is
    emitted after its end, the row-tick computed for it is counted and
    dropped, and the slot's next tenant (a fresh prompt, and an exact
    prefix hit that binds shared pages) decodes what it decodes alone,
    as does the co-tenant that rode both ticks."""
    eng = _paged_engine(net, max_slots=2, max_new_tokens=40,
                        prefix_cache=tenant == "prefix-hit").warmup()
    rng = onp.random.RandomState(22)
    pa, pb, pc = _prompt(rng, 5), _prompt(rng, 2 * PS + 3), _prompt(rng, 6)
    alone = {k: eng.generate(p, max_new_tokens=n, timeout=300).tokens
             for k, p, n in (("a", pa, 40), ("b", pb, 10), ("c", pc, 50))}
    j = next(i for i in range(2, 30)
             if alone["a"][i] not in alone["a"][:i])
    telemetry.reset()
    sc = eng.submit(pc, max_new_tokens=50)           # rides every tick
    sa = eng.submit(pa, max_new_tokens=40, trace=True,
                    eos_id=alone["a"][j] if end == "eos" else None)

    def expire():
        # the commit that follows this dispatch finds A past its deadline
        if end == "deadline" and len(sa.tokens) >= 3:
            for s in eng._slots:
                if s is not None and s.stream is sa:
                    s.deadline = 0.0
    spy = _DecodeSpy(net, expire)
    try:
        sb = eng.submit(pb, max_new_tokens=10, trace=True)  # waits for a slot
        ra, rb, rc = (s.result(timeout=300) for s in (sa, sb, sc))
    finally:
        spy.undo()
    if end == "eos":
        assert ra.finish_reason == "eos" and ra.tokens == alone["a"][:j + 1]
    else:
        assert ra.finish_reason == "timeout" and 3 <= len(ra.tokens) < 40
        assert ra.tokens == alone["a"][:len(ra.tokens)]
    assert _ctr("stale_row_ticks") == 1
    assert _admitted_slot(sb) == _admitted_slot(sa)   # the next tenant
    if tenant == "prefix-hit":
        assert _ctr("prefix_hits") >= 1
    assert rb.tokens == alone["b"] and rc.tokens == alone["c"]
    assert _quiet(eng)
    assert sa.tokens == ra.tokens      # nothing after its end
    eng.close()
    assert eng._pool.free_count == eng._pool.n_pages - 1


@pytest.mark.parametrize("kind", ["dense", "paged"])
def test_sampled_request_drains_the_tick_in_flight_and_falls_back(
        net, kind, monkeypatch):
    """A sampled row's key lives on the host: admitting one drains the
    tick in flight and the engine ticks synchronously while it lives.
    Its stream is the synchronous engine's for the same seed, its greedy
    co-tenants' are theirs, and the engine dispatches ahead again once
    it has gone."""
    rng = onp.random.RandomState(23)
    sampled = dict(temperature=0.8, top_k=20, seed=7)
    jobs = [(_prompt(rng, 6), 40, {}), (_prompt(rng, 11), 30, {}),
            (_prompt(rng, 9), 12, sampled), (_prompt(rng, 4), 10, {})]
    want = _sync_results(net, kind, monkeypatch, jobs)
    eng = _KINDS[kind](net).warmup()
    telemetry.reset()
    streams = [eng.submit(p, max_new_tokens=n, **extra)
               for p, n, extra in jobs[:2]]
    _wait_tokens(streams[0], 3)
    ahead_before = _ctr("ticks_ahead")
    assert ahead_before > 0
    p, n, extra = jobs[2]
    streams.append(eng.submit(p, max_new_tokens=n, **extra))
    streams[2].result(timeout=300)
    assert _ctr("ahead_drains") >= 1
    ahead_mid = _ctr("ticks_ahead")
    p, n, extra = jobs[3]
    streams.append(eng.submit(p, max_new_tokens=n, **extra))
    got = [s.result(timeout=300) for s in streams]
    for i, (g, w) in enumerate(zip(got, want)):
        assert g.tokens == w.tokens, f"request {i} diverged"
    assert _ctr("ticks_ahead") > ahead_mid     # ahead again
    assert _ctr("stale_row_ticks") == 0
    assert _quiet(eng)
    eng.close()


@pytest.mark.parametrize("event", ["close", "hard-close", "load_weights",
                                   "warmup", "crash", "step-failure"])
def test_no_tick_in_flight_and_no_hung_stream_across(net, event,
                                                     monkeypatch):
    """``close()``, a rollover, a ``warmup()`` under load and a worker
    failure all meet a tick in flight: every stream still finishes
    exactly once, whoever holds a step boundary sees nothing in flight,
    and where the engine lives on its tokens are the undisturbed
    run's."""
    from mxnet_tpu import checkpoint
    from mxnet_tpu.serving import ReplicaFailedError
    from mxnet_tpu.serving.faults import FaultInjector
    eng = _paged_engine(net, max_new_tokens=40).warmup()
    rng = onp.random.RandomState(24)
    prompts = [_prompt(rng, n) for n in (5, 9, 20, 3, 7, 12)]
    want = [eng.generate(p, timeout=300).tokens for p in prompts]
    seen = []

    def boundary(fn):
        def at_boundary(*a, **kw):
            seen.append(eng._ahead)
            return fn(*a, **kw)
        return at_boundary
    telemetry.reset()
    streams = [eng.submit(p) for p in prompts]
    _wait_tokens(streams[0], 3)
    assert _ctr("ticks_ahead") > 0
    if event == "close":
        eng.close(timeout=300)
    elif event == "hard-close":
        eng.close(timeout=0.0)
    elif event == "load_weights":
        monkeypatch.setattr(checkpoint, "swap_param_buffers",
                            boundary(checkpoint.swap_param_buffers))
        eng.load_weights({k: onp.asarray(p.data()._data) for k, p in
                          net.collect_params().items()})
    elif event == "warmup":
        monkeypatch.setattr(eng, "_warmup_paged",
                            boundary(eng._warmup_paged))
        eng.warmup()
        assert telemetry.counter_value("model.gpt.trace") == 0
    elif event == "crash":
        monkeypatch.setattr(eng, "_fail_all", boundary(eng._fail_all))
        FaultInjector().crash(eng)
    else:
        boom = RuntimeError("tick exploded")

        def explode():
            raise boom
        spy = _DecodeSpy(net, explode)
    outcomes = []
    try:
        for s, w in zip(streams, want):
            try:
                r = s.result(timeout=300)
            except (EngineClosedError, ReplicaFailedError) as e:
                outcomes.append(type(e).__name__)
                assert s.tokens == w[:len(s.tokens)]
                continue
            outcomes.append(r.finish_reason)
            assert r.tokens == w[:len(r.tokens)]
            assert r.finish_reason == "closed" or r.tokens == w
            assert s.tokens == r.tokens
    finally:
        if event == "step-failure":
            spy.undo()
    if event in ("close", "load_weights", "warmup"):
        assert outcomes == ["length"] * len(prompts), outcomes
    if event in ("crash", "step-failure"):
        assert "ReplicaFailedError" in outcomes
    if event in ("load_weights", "warmup", "crash"):
        assert seen == [None]      # a step boundary has nothing in flight
    assert _quiet(eng)
    eng.close()
    assert eng._pool.free_count == eng._pool.n_pages - 1


@pytest.mark.parametrize("kind", list(_KINDS))
def test_tick_in_flight_compiles_nothing_after_warmup(net, kind):
    """Fifty and more ticks with admissions, prefix hits and evictions
    after ``warmup()``: no trace of a model or sampler program, and no
    compile the trace counters cannot see (the decode program takes a
    committed device array as its tokens on every path, the merge
    program was warmed on the pick as it is placed)."""
    import jax
    eng = _KINDS[kind](net, queue_limit=128).warmup()
    rng = onp.random.RandomState(25)
    shared = _prompt(rng, 2 * PS)
    eng.generate(shared, max_new_tokens=3, timeout=300)
    compiles = []

    def listener(name, _secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            compiles.append(name)
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        telemetry.reset()
        wave = []
        for i in range(24):
            p = shared if i % 5 == 4 else \
                onp.concatenate([shared, _prompt(rng, 1 + i % 7)]) \
                if i % 3 == 0 else _prompt(rng, 3 + (7 * i) % 40)
            wave.append(eng.submit(p, max_new_tokens=10 + i % 9))
            time.sleep(0.001)
        for s in wave:
            assert len(s.result(timeout=300).tokens) >= 1
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert _ctr("host_syncs") >= 50 and _ctr("ticks_ahead") >= 40
    assert telemetry.counter_value("model.gpt.trace") == 0
    assert telemetry.counter_value("ops.sampling.trace") == 0
    assert not compiles, "a program compiled after warm-up"
    eng.close()


def test_tick_in_flight_under_more_callers_than_cores(net):
    """Sixteen closed-loop callers on a shortened switch interval, every
    commit waking one of them while the worker builds the next tick:
    each request still gets the tokens it gets alone, every stream
    finishes, and nothing is left in flight."""
    import sys
    import threading
    eng = _paged_engine(net, queue_limit=256).warmup()
    rng = onp.random.RandomState(26)
    jobs = [(_prompt(rng, 3 + (5 * i) % 40), 4 + i % 9) for i in range(12)]
    want = [eng.generate(p, max_new_tokens=n, timeout=300).tokens
            for p, n in jobs]
    telemetry.reset()
    bad, t_end = [], time.monotonic() + 6.0

    def caller(seed):
        r = onp.random.RandomState(seed)
        while time.monotonic() < t_end and len(bad) < 3:
            i = int(r.randint(len(jobs)))
            got = eng.generate(jobs[i][0], max_new_tokens=jobs[i][1],
                               timeout=300).tokens
            if got != want[i]:
                bad.append((i, got))
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        threads = [threading.Thread(target=caller, args=(s,))
                   for s in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert not bad, bad[:3]
    assert _ctr("ticks_ahead") > 0.5 * _ctr("host_syncs") > 0
    assert _ctr("stale_row_ticks") == 0
    assert _quiet(eng)
    eng.close()
    assert eng._pool.free_count == eng._pool.n_pages - 1
