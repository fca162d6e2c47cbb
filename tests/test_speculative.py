"""Draft-model speculative decoding + per-request sampling in the
serving stack (gpt.py verify closures, GenerationEngine speculation).

Guarantees under test:
- the model-level verify program (``verify_step`` dense,
  ``verify_step_paged`` paged) reproduces the sequential decode
  logits for the same token chain (teacher-forced parity), and
  ``advance_len`` commits/rolls back so a continued decode agrees
  with the never-speculated reference;
- a GREEDY speculative engine is TOKEN-IDENTICAL to the
  non-speculative engine — dense, paged, and the full
  ``paged=True, kv_dtype="int8", quantize="int8_weights",
  speculative=True`` composition (the int8 bounded-divergence
  contract composes because spec-vs-nonspec is an identity within
  each precision config);
- the speculative steady state compiles NOTHING (``model.gpt.trace``
  and ``ops.sampling.trace`` stay flat across a second traffic wave,
  greedy and sampled);
- per-request sampling is reproducible: same ``seed=`` -> bitwise
  identical stream across engine RESTARTS, different seeds diverge,
  ``temperature=0`` == the greedy engine's output, and a greedy
  co-tenant is unperturbed by stochastic neighbors;
- speculation telemetry (``serving.generate.spec.*``) reports the
  proposed/accepted/rejected accounting.
"""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.gluon.model_zoo.gpt import gpt_small
from mxnet_tpu.serving import GenerationEngine

VOCAB, SLOTS, SMAX = 97, 4, 64


@pytest.fixture(scope="module")
def target():
    onp.random.seed(21)
    mx.np.random.seed(21)
    net = gpt_small(vocab_size=VOCAB, units=32, num_layers=2,
                    num_heads=4, max_length=128)
    net.initialize(mx.init.Xavier())
    return net


@pytest.fixture(scope="module")
def draft():
    onp.random.seed(22)
    mx.np.random.seed(22)
    net = gpt_small(vocab_size=VOCAB, units=16, num_layers=1,
                    num_heads=4, max_length=128)
    net.initialize(mx.init.Xavier())
    return net


def _prompt(rng, n):
    return rng.randint(0, VOCAB, size=n).astype("i4")


def _engine(target, max_new=8, **kw):
    return GenerationEngine(target, max_slots=SLOTS, max_length=SMAX,
                            max_new_tokens=max_new, queue_limit=64,
                            **kw).warmup()


# -- model level -------------------------------------------------------

def test_verify_step_matches_sequential_decode(target):
    """Teacher-forced parity: verify logits at position j equal the
    decode logits after feeding the same chain token by token, and a
    full commit continues bitwise-equal to the sequential cache."""
    rng = onp.random.RandomState(0)
    prompt, chain = _prompt(rng, 7), _prompt(rng, 3)
    pad = onp.zeros((1, 8), "i4")
    pad[0, :7] = prompt

    cache_a = target.init_cache(SLOTS, SMAX)
    lg, cache_a = target.prefill(pad, [7], cache_a, slots=[0])
    last = int(onp.asarray(lg)[0].argmax())
    seq = [last] + chain.tolist()
    ref = []
    for t in seq:
        step = onp.zeros((SLOTS,), "i4")
        step[0] = t
        lg, cache_a = target.decode_step(step, cache_a)
        ref.append(onp.asarray(lg)[0])
    ref = onp.stack(ref)

    cache_b = target.init_cache(SLOTS, SMAX)
    _, cache_b = target.prefill(pad, [7], cache_b, slots=[0])
    vt = onp.zeros((SLOTS, len(seq)), "i4")
    vt[0] = seq
    vlog, cache_b = target.verify_step(vt, cache_b)
    onp.testing.assert_allclose(onp.asarray(vlog)[0], ref, rtol=2e-3,
                                atol=2e-4)
    # argmax (what greedy acceptance compares) agrees exactly
    assert (onp.asarray(vlog)[0].argmax(-1) == ref.argmax(-1)).all()

    delta = onp.zeros((SLOTS,), "i4")
    delta[0] = len(seq)
    cache_b = target.advance_len(delta, cache_b)
    # row 0's committed length matches the sequential cache (free
    # rows differ: plain decode bumps every row, advance_len only
    # the committing ones — both are garbage rows either way)
    assert int(onp.asarray(cache_b["len"])[0]) \
        == int(onp.asarray(cache_a["len"])[0])
    nxt = onp.zeros((SLOTS,), "i4")
    nxt[0] = int(ref[-1].argmax())
    la, _ = target.decode_step(nxt, cache_a)
    lb, _ = target.decode_step(nxt, cache_b)
    onp.testing.assert_allclose(onp.asarray(la)[0], onp.asarray(lb)[0],
                                rtol=2e-3, atol=2e-4)


def test_verify_rollback_clips_rejected_tail(target):
    """A partial commit (the rejection case) leaves the cache exactly
    at the accept point: continuing with plain decode reproduces the
    sequential reference from that position."""
    rng = onp.random.RandomState(1)
    prompt, chain = _prompt(rng, 5), _prompt(rng, 3)
    pad = onp.zeros((1, 8), "i4")
    pad[0, :5] = prompt
    cache = target.init_cache(SLOTS, SMAX)
    lg, cache = target.prefill(pad, [5], cache, slots=[0])
    seq = [int(onp.asarray(lg)[0].argmax())] + chain.tolist()
    ref = []
    cache_r = target.init_cache(SLOTS, SMAX)
    _, cache_r = target.prefill(pad, [5], cache_r, slots=[0])
    for t in seq:
        step = onp.zeros((SLOTS,), "i4")
        step[0] = t
        lg, cache_r = target.decode_step(step, cache_r)
        ref.append(onp.asarray(lg)[0])

    vt = onp.zeros((SLOTS, len(seq)), "i4")
    vt[0] = seq
    _, cache = target.verify_step(vt, cache)
    delta = onp.zeros((SLOTS,), "i4")
    delta[0] = 2                    # accept only [last, chain[0]]
    cache = target.advance_len(delta, cache)
    step = onp.zeros((SLOTS,), "i4")
    step[0] = seq[2]                # teacher-force the next token
    lg, cache = target.decode_step(step, cache)
    onp.testing.assert_allclose(onp.asarray(lg)[0], ref[2], rtol=2e-3,
                                atol=2e-4)


def test_verify_step_paged_matches_sequential_decode(target):
    rng = onp.random.RandomState(2)
    ps = 8
    n_pages = SLOTS * (SMAX // ps) + 1
    prompt, chain = _prompt(rng, 7), _prompt(rng, 3)
    pad = onp.zeros((1, 8), "i4")
    pad[0, :7] = prompt
    row = onp.zeros((SMAX // ps,), "i4")
    row[:4] = [1, 2, 3, 4]
    active = onp.zeros((SLOTS,), "i4")
    active[0] = 1

    cache_a = target.init_paged_cache(SLOTS, n_pages, ps, SMAX)
    lg, cache_a = target.prefill_paged(pad, 7, 0, row, cache_a,
                                       fresh=True)
    seq = [int(onp.asarray(lg)[0].argmax())] + chain.tolist()
    ref = []
    for t in seq:
        step = onp.zeros((SLOTS,), "i4")
        step[0] = t
        lg, cache_a = target.decode_step_paged(step, active, cache_a)
        ref.append(onp.asarray(lg)[0])
    ref = onp.stack(ref)

    cache_b = target.init_paged_cache(SLOTS, n_pages, ps, SMAX)
    _, cache_b = target.prefill_paged(pad, 7, 0, row, cache_b,
                                      fresh=True)
    vt = onp.zeros((SLOTS, len(seq)), "i4")
    vt[0] = seq
    vlog, cache_b = target.verify_step_paged(vt, active, cache_b)
    onp.testing.assert_allclose(onp.asarray(vlog)[0], ref, rtol=2e-3,
                                atol=2e-4)
    delta = onp.zeros((SLOTS,), "i4")
    delta[0] = len(seq)
    cache_b = target.advance_len_paged(delta, cache_b)
    nxt = onp.zeros((SLOTS,), "i4")
    nxt[0] = int(ref[-1].argmax())
    la, _ = target.decode_step_paged(nxt, active, cache_a)
    lb, _ = target.decode_step_paged(nxt, active, cache_b)
    onp.testing.assert_allclose(onp.asarray(la)[0], onp.asarray(lb)[0],
                                rtol=2e-3, atol=2e-4)


def test_verify_inactive_rows_write_scrap_only(target):
    """An inactive row's verify write is redirected to scrap page 0 —
    the pool pages other slots own are untouched (the decode-write
    discipline, now for multi-position writes)."""
    ps = 8
    n_pages = SLOTS * (SMAX // ps) + 1
    cache = target.init_paged_cache(SLOTS, n_pages, ps, SMAX)
    pools_before = [onp.asarray(p).copy() for p in cache["k"]]
    vt = onp.ones((SLOTS, 4), "i4")
    vlog, cache = target.verify_step_paged(
        vt, onp.zeros((SLOTS,), "i4"), cache)
    for before, after in zip(pools_before, cache["k"]):
        after = onp.asarray(after)
        assert (after[1:] == before[1:]).all(), \
            "an inactive row's verify write escaped the scrap page"


# -- engine level ------------------------------------------------------

def test_engine_spec_greedy_token_identical_dense(target, draft):
    rng = onp.random.RandomState(3)
    prompts = [_prompt(rng, n) for n in (3, 9, 17, 5, 12, 7)]
    budgets = [4 + i % 5 for i in range(len(prompts))]
    plain = _engine(target)
    refs = [plain.submit(p, max_new_tokens=b).result(timeout=120).tokens
            for p, b in zip(prompts, budgets)]
    plain.close()
    spec = _engine(target, draft_model=draft, spec_k=3)
    outs = [s.result(timeout=120) for s in
            [spec.submit(p, max_new_tokens=b)
             for p, b in zip(prompts, budgets)]]
    snap = telemetry.snapshot()
    spec.close()
    for r, o in zip(refs, outs):
        assert o.tokens == r
        assert o.finish_reason == "length"
    c = snap["counters"]
    assert c.get("serving.generate.spec.proposed", 0) > 0
    assert c.get("serving.generate.spec.proposed", 0) == \
        c.get("serving.generate.spec.accepted", 0) \
        + c.get("serving.generate.spec.rejected", 0)
    assert "serving.generate.spec.accept_rate" in snap["gauges"]
    assert "serving.generate.spec.tokens_per_step" in snap["gauges"]


def test_engine_spec_agreeing_draft_multiplies_tokens_per_step(target):
    """Speculation multiplies tokens per iteration exactly when the
    draft agrees: with the target as its own draft an iteration
    commits ``spec_k + 1`` tokens a slot, so a lone request costs
    fewer host syncs than tokens, and the tokens are the plain
    engine's."""
    rng = onp.random.RandomState(5)
    p = _prompt(rng, 6)
    plain = _engine(target, max_new=9)
    ref = plain.submit(p).result(timeout=120).tokens
    plain.close()
    spec = _engine(target, max_new=9, draft_model=target, spec_k=3)
    telemetry.reset()
    out = spec.submit(p).result(timeout=120)
    snap = telemetry.snapshot()
    spec.close()
    assert out.tokens == ref and len(ref) == 9
    per_step = snap["gauges"]["serving.generate.spec.tokens_per_step"]
    assert per_step["peak"] == 4
    assert snap["counters"]["serving.generate.host_syncs"] < len(ref) - 1


def test_engine_spec_greedy_token_identical_paged(target, draft):
    """Paged + speculative: shared-prefix prompts (prefix reuse + COW
    under verify writes) and chunked prefill compose with speculation
    token-identically."""
    rng = onp.random.RandomState(4)
    sysp = _prompt(rng, 24)
    prompts = [onp.concatenate([sysp, _prompt(rng, 1 + i % 5)])
               for i in range(6)] + [_prompt(rng, 5)]
    kw = dict(paged=True, page_size=8, prefill_chunk=16)
    plain = _engine(target, **kw)
    refs = [s.result(timeout=240).tokens
            for s in [plain.submit(p, max_new_tokens=7)
                      for p in prompts]]
    plain.close()
    spec = _engine(target, draft_model=draft, spec_k=3, **kw)
    outs = [s.result(timeout=240).tokens
            for s in [spec.submit(p, max_new_tokens=7)
                      for p in prompts]]
    spec.close()
    assert outs == refs


def test_engine_spec_composes_with_paged_int8(target, draft):
    """The acceptance-criteria composition: a ``paged=True,
    kv_dtype='int8', quantize='int8_weights', speculative=True``
    engine matches the NON-speculative engine of the same precision
    config token for token (greedy identity within one numeric
    config is what makes the int8 bounded-divergence contract carry
    over unchanged)."""
    rng = onp.random.RandomState(5)
    sysp = _prompt(rng, 24)
    prompts = [onp.concatenate([sysp, _prompt(rng, 2 + i % 4)])
               for i in range(5)] + [_prompt(rng, 6)]
    kw = dict(paged=True, page_size=8, prefill_chunk=16,
              quantize="int8_weights", kv_dtype="int8")
    plain = _engine(target, **kw)
    refs = [s.result(timeout=240).tokens
            for s in [plain.submit(p, max_new_tokens=7)
                      for p in prompts]]
    plain.close()
    spec = _engine(target, draft_model=draft, spec_k=3, **kw)
    outs = [s.result(timeout=240).tokens
            for s in [spec.submit(p, max_new_tokens=7)
                      for p in prompts]]
    assert spec.precision == "int8_weights+int8_kv"
    assert spec.speculation.startswith("k=3:")
    spec.close()
    assert outs == refs


def test_engine_spec_zero_steady_state_compiles(target, draft):
    eng = _engine(target, draft_model=draft, spec_k=3)
    rng = onp.random.RandomState(6)
    first = [eng.submit(_prompt(rng, n)) for n in (3, 9, 17, 5)]
    for s in first:
        s.result(timeout=120)
    telemetry.reset()
    wave = [eng.submit(_prompt(rng, 3 + (5 * i) % 20),
                       max_new_tokens=2 + i % 5,
                       temperature=0.8 if i % 2 else None,
                       seed=i) for i in range(10)]
    for s in wave:
        assert len(s.result(timeout=120).tokens) >= 1
    snap = telemetry.snapshot()
    assert telemetry.counter_value("model.gpt.trace") == 0, \
        "speculative steady state retraced the model"
    assert telemetry.counter_value("ops.sampling.trace") == 0, \
        "speculative steady state retraced a sampler"
    assert "gluon.cachedop.cache_miss" not in snap["counters"]
    eng.close()


def test_engine_sampling_reproducible_across_restarts(target):
    rng = onp.random.RandomState(7)
    p = _prompt(rng, 6)
    eng = _engine(target, max_new=10)
    a = eng.submit(p, temperature=0.9, top_k=20, top_p=0.9,
                   seed=1234).result(timeout=120).tokens
    eng.close()
    eng2 = _engine(target, max_new=10)   # a fresh engine = a restart
    b = eng2.submit(p, temperature=0.9, top_k=20, top_p=0.9,
                    seed=1234).result(timeout=120).tokens
    c = eng2.submit(p, temperature=0.9, top_k=20, top_p=0.9,
                    seed=1235).result(timeout=120).tokens
    d = eng2.submit(p, temperature=0.0).result(timeout=120).tokens
    g = eng2.submit(p).result(timeout=120).tokens
    eng2.close()
    assert a == b, "same seed must survive an engine restart bitwise"
    assert a != c, "different seeds produced the same stream"
    assert d == g, "temperature=0 must equal the greedy path"
    assert "serving.generate.sampling.requests" in \
        telemetry.snapshot()["counters"]


def test_engine_greedy_cotenant_unperturbed_by_samplers(target):
    """A greedy request sharing the batch with stochastic co-tenants
    gets exactly the tokens of an all-greedy engine (greedy rows take
    the in-program argmax of the raw logits; rows are independent)."""
    rng = onp.random.RandomState(8)
    p = _prompt(rng, 9)
    eng = _engine(target, max_new=8)
    ref = eng.submit(p).result(timeout=120).tokens
    eng.close()
    eng2 = _engine(target, max_new=8)
    noisy = [eng2.submit(_prompt(rng, 4), temperature=1.2, seed=i)
             for i in range(SLOTS - 1)]
    got = eng2.submit(p).result(timeout=120).tokens
    for s in noisy:
        s.result(timeout=120)
    eng2.close()
    assert got == ref


def test_engine_spec_sampling_reproducible(target, draft):
    rng = onp.random.RandomState(9)
    p = _prompt(rng, 8)
    eng = _engine(target, draft_model=draft, spec_k=3, max_new=10)
    a = eng.submit(p, temperature=0.8, seed=7).result(timeout=120).tokens
    eng.close()
    eng2 = _engine(target, draft_model=draft, spec_k=3, max_new=10)
    b = eng2.submit(p, temperature=0.8, seed=7).result(timeout=120).tokens
    eng2.close()
    assert a == b


def test_spec_capacity_margin_and_eos(target, draft):
    """The spec_k scratch margin: usable capacity is max_length -
    spec_k, enforced at validation and at eviction; eos inside a
    multi-token commit truncates the emission at the stop token."""
    eng = GenerationEngine(target, draft_model=draft, spec_k=3,
                           max_slots=2, max_length=32,
                           max_new_tokens=100, queue_limit=16)
    rng = onp.random.RandomState(10)
    with pytest.raises(ValueError, match="no room"):
        eng.submit(_prompt(rng, 29))    # fits 32 but not 32 - spec_k
    r = eng.generate(_prompt(rng, 10), timeout=120)
    assert r.finish_reason == "length"
    assert len(r.tokens) == (32 - 3) - 10 + 1   # fills usable capacity
    p = _prompt(rng, 5)
    free = eng.generate(p, max_new_tokens=10, timeout=120)
    j = next(i for i in range(1, len(free.tokens))
             if free.tokens[i] not in free.tokens[:i])
    eos = free.tokens[j]
    r = eng.generate(p, max_new_tokens=10, eos_id=eos, timeout=120)
    assert r.finish_reason == "eos"
    assert r.tokens == free.tokens[:j + 1]
    eng.close()


def test_spec_validation(target, draft):
    with pytest.raises(ValueError, match="draft_model"):
        GenerationEngine(target, speculative=True, max_length=SMAX)
    with pytest.raises(ValueError, match="inert"):
        GenerationEngine(target, draft_model=draft, speculative=False,
                         max_length=SMAX)
    with pytest.raises(ValueError, match="spec_k"):
        GenerationEngine(target, draft_model=draft, spec_k=0,
                         max_length=SMAX)
    with pytest.raises(TypeError, match="explicit-cache"):
        GenerationEngine(target, draft_model=object(), max_length=SMAX)
    small_vocab = gpt_small(vocab_size=11, units=16, num_layers=1,
                            num_heads=4, max_length=128)
    with pytest.raises(TypeError, match="vocab"):
        GenerationEngine(target, draft_model=small_vocab,
                         max_length=SMAX)


def test_paged_sampled_stream_cotenant_independent(target):
    """Regression (review finding): a PAGED stochastic request's PRNG
    key used to be installed at ADMISSION, so every co-tenant decode
    tick during its chunked prefill split it — the pre-first-token
    split count (and hence the whole stream) depended on co-tenant
    activity, breaking seeded reproducibility and the Router's
    retry prefix-skip. The key now goes live at decode entry: the
    same seed yields the same stream whether the slot prefilled
    alone or next to busy decoders."""
    rng = onp.random.RandomState(12)
    prompt = _prompt(rng, 40)        # multi-chunk prefill
    kw = dict(paged=True, page_size=8, prefill_chunk=16)
    # high temperature, no truncation: a shifted key cannot hide
    # behind a peaky distribution
    eng = _engine(target, max_new=8, **kw)
    alone = eng.submit(prompt, temperature=1.8,
                       seed=99).result(timeout=240).tokens
    eng.close()
    eng2 = _engine(target, max_new=8, **kw)
    busy = [eng2.submit(_prompt(rng, 4), max_new_tokens=30,
                        temperature=1.1, seed=i) for i in range(2)]
    got = eng2.submit(prompt, temperature=1.8,
                      seed=99).result(timeout=240).tokens
    for s in busy:
        s.result(timeout=240)
    eng2.close()
    assert got == alone, \
        "a co-tenant's decode ticks perturbed a seeded stream"


def test_spec_sync_mode_parity(target, draft, monkeypatch):
    """MXTPU_SERVING=0 speculative generation matches the threaded
    engine's greedy output."""
    rng = onp.random.RandomState(11)
    p = _prompt(rng, 7)
    eng = _engine(target, draft_model=draft, spec_k=3, max_new=6)
    ref = eng.submit(p).result(timeout=120).tokens
    eng.close()
    monkeypatch.setenv("MXTPU_SERVING", "0")
    eng2 = GenerationEngine(target, draft_model=draft, spec_k=3,
                            max_slots=SLOTS, max_length=SMAX,
                            max_new_tokens=6, queue_limit=64)
    s = eng2.submit(p)
    assert s.done()
    assert s.result().tokens == ref
    eng2.close()
