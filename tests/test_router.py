"""Fault-tolerant serving fleet: Router + FaultInjector chaos tests.

Guarantees under test (all faults seeded/deterministic):
- join-shortest-queue balancing spreads traffic and never changes any
  request's tokens (greedy engine output is replica-independent when
  replicas share weights);
- a replica crash mid-decode is absorbed: in-flight requests retry on
  a DIFFERENT replica and the caller's stream is token-identical to
  the unfailed path (greedy decode is deterministic, so the retry
  regenerates the same prefix and the router skips what it already
  delivered);
- the circuit breaker opens after K consecutive failures, half-opens
  after the cooldown, and closes on a successful trial;
- per-tenant quotas and priority brownout shedding reject at the edge
  (``TenantQuotaError`` / ``LoadShedError``), with optional
  ``max_new_tokens`` capping under brownout;
- a rolling fleet-wide ``load_weights`` under live traffic drops zero
  requests and swaps every live replica;
- deadlines propagate end to end (queued-past-deadline requests are
  rejected, not served late);
- the same machinery fronts ``InferenceEngine`` fleets (Future-based).
"""
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.gluon.model_zoo.gpt import gpt_small
from mxnet_tpu.serving import (
    DOWN, HEALTHY, EngineClosedError, FaultInjector, FaultRule,
    GenerationEngine, InferenceEngine, InjectedFault, LoadShedError,
    ReplicaFailedError, RequestTimeoutError, Router, TenantQuotaError,
)

VOCAB, SLOTS, SMAX = 64, 2, 32


def _build_net(seed=7):
    mx.np.random.seed(seed)
    onp.random.seed(seed)
    net = gpt_small(vocab_size=VOCAB, units=16, num_layers=1,
                    num_heads=2, max_length=SMAX)
    net.initialize(mx.init.Xavier())
    net(mx.np.array(onp.zeros((1, 4), "i4")))  # materialize params
    return net


@pytest.fixture(scope="module")
def base():
    """Reference net + its parameter mapping (the fleet's weights)."""
    net = _build_net(seed=99)
    params = {k: onp.asarray(p.data()._data)
              for k, p in net.collect_params().items()}
    return net, params


def _mk_engine(params, slots=SLOTS, max_new=4, queue_limit=32):
    eng = GenerationEngine(_build_net(), max_slots=slots,
                           max_length=SMAX, max_new_tokens=max_new,
                           queue_limit=queue_limit)
    eng.load_weights(params)
    return eng


def _fleet(params, n=2, **eng_kw):
    return [_mk_engine(params, **eng_kw) for _ in range(n)]


def _prompt(rng, n=5):
    return rng.randint(0, VOCAB, size=n).astype("i4")


def _ref_generate(net, policy, prompt, max_new, width=SLOTS,
                  max_length=SMAX):
    """Single-request greedy loop at slot width ``width`` — what every
    fleet-served request must match token for token."""
    cache = net.init_cache(width, max_length)
    n = len(prompt)
    sb = policy.bucket(n)
    padded = onp.zeros((1, sb), "i4")
    padded[0, :n] = prompt
    logits, cache = net.prefill(padded, [n], cache, slots=[0])
    toks = [int(onp.asarray(logits)[0].argmax())]
    n_ctx = n
    while len(toks) < max_new and n_ctx < max_length:
        step = onp.zeros((width,), "i4")
        step[0] = toks[-1]
        lg, cache = net.decode_step(step, cache)
        toks.append(int(onp.asarray(lg)[0].argmax()))
        n_ctx += 1
    return toks


# -- balancing & parity ------------------------------------------------

def test_jsq_balancing_and_token_parity(base):
    net, params = base
    router = Router(_fleet(params, n=2), probe_interval_s=0.1)
    rng = onp.random.RandomState(0)
    prompts = [_prompt(rng, 3 + i % 9) for i in range(10)]
    streams = [router.submit(p, max_new_tokens=5) for p in prompts]
    results = [s.result(timeout=120) for s in streams]
    policy = router.replicas[0].policy
    for p, r in zip(prompts, results):
        assert r.finish_reason == "length"
        assert r.tokens == _ref_generate(net, policy, p, 5)
    h = router.health()
    assert all(v["state"] == HEALTHY for v in h.values())
    # JSQ spread the load: no replica served everything
    assert all(v["dispatches"] > 0 for v in h.values())
    assert sum(v["dispatches"] for v in h.values()) == len(prompts)
    router.close()
    with pytest.raises(EngineClosedError):
        router.submit(prompts[0])


# -- crash / retry -----------------------------------------------------

def test_replica_crash_mid_decode_retry_token_identical(base):
    """The tentpole guarantee: kill a replica while a request is
    mid-decode on it; the request is retried on the OTHER replica with
    the already-delivered token prefix skipped, and the caller's
    stream is token-identical to the unfailed path.

    Fully deterministic: the crash is a FaultRule keyed on replica 0's
    DISPATCH COUNT (its 2nd dispatch), not wall-clock — by then the
    1st request is provably mid-decode (its first token was observed
    before anything else was submitted)."""
    net, params = base
    engines = _fleet(params, n=2)
    injector = FaultInjector(
        rules=[FaultRule("crash", replica=0, after_n=2)], seed=0)
    router = Router(engines, max_retries=2, probe_interval_s=0.05,
                    fault_injector=injector)
    rng = onp.random.RandomState(1)
    prompts = [_prompt(rng) for _ in range(3)]
    # 1st request lands on replica 0 (idle JSQ tie-break); wait until
    # it is mid-decode (first token out, 19 to go)
    s1 = router.submit(prompts[0], max_new_tokens=20)
    deadline = time.monotonic() + 60
    while not s1.tokens and time.monotonic() < deadline:
        time.sleep(0.001)
    assert s1.tokens, "first request never started decoding"
    # 2nd goes to the idle replica 1; the 3rd ties back to replica 0 —
    # its dispatch is replica 0's 2nd, which fires the injected crash:
    # s1 dies mid-decode (retried, prefix skipped), s3's submit fails
    # over to replica 1
    s2 = router.submit(prompts[1], max_new_tokens=20)
    s3 = router.submit(prompts[2], max_new_tokens=20)
    streams = [s1, s2, s3]
    results = [s.result(timeout=120) for s in streams]
    policy = engines[1].policy
    for p, s, r in zip(prompts, streams, results):
        assert r.finish_reason == "length"
        assert r.tokens == _ref_generate(net, policy, p, 20), \
            f"retried stream diverged (retries={s.retries})"
    assert s1.retries == 1 and s1.replicas == [0, 1]
    assert s3.retries == 1, "the crashed dispatch must fail over"
    assert s2.retries == 0
    assert router.health()[0]["state"] == DOWN
    assert telemetry.counter_value("serving.router.retries") >= 2
    assert telemetry.counter_value("serving.faults.crashes") >= 1
    # post-crash traffic keeps flowing on the survivor
    r = router.generate(prompts[0], max_new_tokens=6, timeout=120)
    assert r.tokens == _ref_generate(net, policy, prompts[0], 6)
    router.close()


def test_retry_budget_exhausted_surfaces_fault(base):
    _net, params = base
    injector = FaultInjector(rules=[FaultRule("error", rate=1.0)],
                             seed=3)
    router = Router(_fleet(params, n=2), max_retries=1,
                    fault_injector=injector)
    with pytest.raises(InjectedFault):
        router.submit(_prompt(onp.random.RandomState(2)))
    assert telemetry.counter_value("serving.router.retries") >= 1
    router.close()


def test_no_replica_available(base):
    _net, params = base
    engines = _fleet(params, n=1)
    injector = FaultInjector()
    router = Router(engines, fault_injector=injector)
    injector.crash(engines[0])
    with pytest.raises(ReplicaFailedError):
        router.submit(_prompt(onp.random.RandomState(3)))
    assert router.health()[0]["state"] == DOWN
    router.close()


# -- circuit breaker ---------------------------------------------------

def test_circuit_breaker_opens_half_opens_closes(base):
    net, params = base
    injector = FaultInjector(
        rules=[FaultRule("error", replica=0, rate=1.0)], seed=0)
    router = Router(_fleet(params, n=2), max_retries=2,
                    breaker_threshold=3, breaker_cooldown_s=2.0,
                    probe_interval_s=0.05, fault_injector=injector)
    rng = onp.random.RandomState(4)
    base_opens = telemetry.counter_value("serving.router.breaker_opens")
    # idle JSQ prefers replica 0 (index tie-break) → each request
    # first hits the poisoned replica until its breaker opens
    for _ in range(6):
        r = router.generate(_prompt(rng), max_new_tokens=3, timeout=120)
        assert r.finish_reason == "length"
    assert router.health()[0]["breaker"] == "open"
    assert router.health()[0]["state"] == DOWN
    assert injector.dispatches(0) == 3, \
        "breaker kept routing to the open replica"
    assert telemetry.counter_value("serving.router.breaker_opens") \
        == base_opens + 1
    # cooldown: the probe flips the breaker to half-open; the next
    # request is the single trial — with the fault cleared it succeeds
    # and closes the breaker
    injector.clear()
    time.sleep(2.3)
    r = router.generate(_prompt(rng), max_new_tokens=3, timeout=120)
    assert r.finish_reason == "length"
    assert injector.dispatches(0) == 4  # the trial went to replica 0
    assert router.health()[0]["breaker"] == "closed"
    assert telemetry.counter_value(
        "serving.router.breaker_half_opens") >= 1
    assert telemetry.counter_value(
        "serving.router.breaker_closes") >= 1
    router.close()


def test_half_open_failure_reopens(base):
    _net, params = base
    injector = FaultInjector(
        rules=[FaultRule("error", replica=0, rate=1.0)], seed=0)
    router = Router(_fleet(params, n=2), max_retries=2,
                    breaker_threshold=2, breaker_cooldown_s=1.0,
                    probe_interval_s=0.05, fault_injector=injector)
    rng = onp.random.RandomState(5)
    for _ in range(3):
        router.generate(_prompt(rng), max_new_tokens=3, timeout=120)
    assert router.health()[0]["breaker"] == "open"
    time.sleep(1.3)  # half-opens; the fault is still active
    router.generate(_prompt(rng), max_new_tokens=3, timeout=120)
    assert router.health()[0]["breaker"] == "open", \
        "a failed half-open trial must re-open the circuit"
    router.close()


# -- admission: quotas, shedding, deadlines ----------------------------

def test_tenant_quota(base):
    _net, params = base
    router = Router(_fleet(params, n=1, slots=1), tenant_quota=2)
    rng = onp.random.RandomState(6)
    held = [router.submit(_prompt(rng), max_new_tokens=20, tenant="a")
            for _ in range(2)]
    with pytest.raises(TenantQuotaError):
        router.submit(_prompt(rng), tenant="a")
    # another tenant is unaffected
    other = router.submit(_prompt(rng), max_new_tokens=2, tenant="b")
    for s in held + [other]:
        assert s.result(timeout=120).finish_reason == "length"
    # quota released on completion
    s = router.submit(_prompt(rng), max_new_tokens=2, tenant="a")
    assert s.result(timeout=120).finish_reason == "length"
    assert telemetry.counter_value("serving.router.rejected_quota") >= 1
    router.close()


def test_brownout_sheds_low_priority_and_caps_budget(base):
    _net, params = base
    router = Router(_fleet(params, n=1, slots=1), queue_limit=10,
                    brownout_frac=0.5, brownout_max_new_tokens=2)
    rng = onp.random.RandomState(7)
    held = [router.submit(_prompt(rng), max_new_tokens=15)
            for _ in range(5)]           # outstanding = 5 = brownout_at
    with pytest.raises(LoadShedError):
        router.submit(_prompt(rng), priority=1)  # lowest priority first
    capped = router.submit(_prompt(rng), max_new_tokens=15, priority=0)
    held += [router.submit(_prompt(rng), max_new_tokens=15)
             for _ in range(4)]          # outstanding = 10 = queue_limit
    with pytest.raises(LoadShedError):
        router.submit(_prompt(rng), priority=0)  # hard limit: all shed
    assert capped.result(timeout=300).tokens \
        and len(capped.result().tokens) == 2, \
        "brownout must cap the admitted generation budget"
    for s in held:
        assert s.result(timeout=300).finish_reason == "length"
    assert telemetry.counter_value("serving.router.rejected_shed") >= 2
    assert telemetry.counter_value(
        "serving.router.brownout_capped") >= 1
    router.close()


def test_deadline_propagates_to_queued_rejection(base):
    _net, params = base
    router = Router(_fleet(params, n=1, slots=1))
    rng = onp.random.RandomState(8)
    busy = router.submit(_prompt(rng), max_new_tokens=25)
    doomed = router.submit(_prompt(rng), timeout_ms=5.0)
    with pytest.raises(RequestTimeoutError):
        doomed.result(timeout=120)
    assert busy.result(timeout=120).finish_reason == "length"
    assert telemetry.counter_value("serving.router.timeouts") >= 1
    router.close()


# -- rolling rollover --------------------------------------------------

def test_rolling_rollover_under_traffic_drops_nothing(base):
    net, params = base
    net_b = _build_net(seed=123)   # different weights, same shapes
    params_b = {k: onp.asarray(p.data()._data)
                for k, p in net_b.collect_params().items()}
    router = Router(_fleet(params, n=2), probe_interval_s=0.1)
    rng = onp.random.RandomState(9)
    swaps0 = telemetry.counter_value("serving.generate.weight_swaps")
    streams = [router.submit(_prompt(rng), max_new_tokens=8)
               for _ in range(10)]
    swapped = router.load_weights(params_b, drain_timeout_s=30.0)
    assert swapped == 2
    # zero dropped requests fleet-wide: everything completes normally
    for s in streams:
        assert s.result(timeout=120).finish_reason == "length"
    assert telemetry.counter_value("serving.generate.weight_swaps") \
        == swaps0 + 2
    assert telemetry.counter_value("serving.router.rollovers") >= 1
    # post-rollover traffic runs the NEW weights on every replica
    policy = router.replicas[0].policy
    p = _prompt(rng)
    for _ in range(4):   # JSQ alternates, covering both replicas
        r = router.generate(p, max_new_tokens=6, timeout=120)
        assert r.tokens == _ref_generate(net_b, policy, p, 6)
    router.close()


def test_rollover_skips_replica_that_dies_mid_sweep(base):
    """A replica that dies between the liveness check and its swap
    must be SKIPPED, not abort the sweep — aborting would strand the
    rest of the fleet on the old weights (mixed versions break retry
    token-identity fleet-wide)."""
    _net, params = base
    net_b = _build_net(seed=321)
    params_b = {k: onp.asarray(p.data()._data)
                for k, p in net_b.collect_params().items()}
    engines = _fleet(params, n=2)
    router = Router(engines, probe_interval_s=0.1)

    def dying_load_weights(source, strict=True):
        raise EngineClosedError("replica died mid-rollover")

    engines[0].load_weights, real = dying_load_weights, \
        engines[0].load_weights
    try:
        assert router.load_weights(params_b) == 1
    finally:
        engines[0].load_weights = real
    assert not router.health()[1]["cordoned"]
    router.close()


def test_probe_detects_silently_dead_worker(base):
    """The probe's 'DOWN on a silent death' contract: a worker thread
    that exits without recording a failure (no exception reached its
    handler) is detected by liveness, the replica is declared FAILED,
    and traffic keeps flowing on the survivor."""
    net, params = base
    engines = _fleet(params, n=2)
    router = Router(engines, probe_interval_s=0.05)
    rng = onp.random.RandomState(14)
    router.generate(_prompt(rng), max_new_tokens=2, timeout=120)
    # silent death: stop the worker loop without any failure record
    engines[0]._worker._stopped = True
    engines[0]._worker.join(timeout=30)
    assert not engines[0]._worker.is_alive()
    assert engines[0]._failure is None and not engines[0].closed
    deadline = time.monotonic() + 30
    while router.health()[0]["state"] != DOWN \
            and time.monotonic() < deadline:
        time.sleep(0.02)
    assert router.health()[0]["state"] == DOWN
    assert isinstance(engines[0]._failure, ReplicaFailedError)
    policy = engines[1].policy
    p = _prompt(rng)
    r = router.generate(p, max_new_tokens=4, timeout=120)
    assert r.tokens == _ref_generate(net, policy, p, 4)
    router.close()


# -- inference-engine fleets -------------------------------------------

def _mk_infer_engine(**kw):
    from mxnet_tpu.gluon import nn
    mx.np.random.seed(11)
    onp.random.seed(11)
    net = nn.HybridSequential()
    net.add(nn.Dense(8))
    net.initialize(mx.init.Xavier())
    net(mx.np.array(onp.zeros((1, 4), "f4")))
    return InferenceEngine(net, max_batch_size=4, **kw)


def test_infer_mode_routing_and_crash_retry(base):
    engines = [_mk_infer_engine(max_queue_ms=0.0),
               _mk_infer_engine(max_queue_ms=0.0)]
    injector = FaultInjector()
    router = Router(engines, max_retries=2, probe_interval_s=0.05,
                    fault_injector=injector)
    rng = onp.random.RandomState(12)
    xs = [mx.np.array(rng.randn(1, 4).astype("f4")) for _ in range(6)]
    # before any submit: this thread tracing the block while the
    # replica's dispatcher traces it too is an UnexpectedTracerError
    expected = [engines[1].block(x).asnumpy() for x in xs]
    futs = [router.submit(x) for x in xs]
    for f, want in zip(futs, expected):
        onp.testing.assert_allclose(f.result(timeout=120).asnumpy(),
                                    want, rtol=1e-5, atol=1e-6)
    # crash one replica; the fleet keeps answering
    injector.crash(engines[0])
    futs = [router.submit(x) for x in xs]
    for f, want in zip(futs, expected):
        onp.testing.assert_allclose(f.result(timeout=120).asnumpy(),
                                    want, rtol=1e-5, atol=1e-6)
    assert router.health()[0]["state"] == DOWN
    with pytest.raises(TypeError):
        router.submit(xs[0], max_new_tokens=3)  # generation-only knob
    router.close()


def test_infer_mode_queued_requests_survive_crash():
    # a generous coalescing window holds submissions in the doomed
    # replica's queue; the injected crash rejects them with
    # ReplicaFailedError and the router retries them elsewhere
    engines = [_mk_infer_engine(max_queue_ms=500.0, queue_limit=64),
               _mk_infer_engine(max_queue_ms=0.0, queue_limit=64)]
    injector = FaultInjector()
    router = Router(engines, max_retries=2, probe_interval_s=0.05,
                    fault_injector=injector)
    rng = onp.random.RandomState(13)
    xs = [mx.np.array(rng.randn(1, 4).astype("f4")) for _ in range(8)]
    futs = [router.submit(x) for x in xs]
    injector.crash(engines[0])
    expected = [engines[1].block(x).asnumpy() for x in xs]
    for f, want in zip(futs, expected):
        onp.testing.assert_allclose(f.result(timeout=120).asnumpy(),
                                    want, rtol=1e-5, atol=1e-6)
    assert sum(f.retries for f in futs) >= 1
    router.close()


def test_mixed_fleet_rejected(base):
    _net, params = base
    gen = _mk_engine(params)
    inf = _mk_infer_engine()
    with pytest.raises(TypeError):
        Router([gen, inf])
    gen.close()
    inf.close()


def _mk_draft():
    mx.np.random.seed(5)
    net = gpt_small(vocab_size=VOCAB, units=8, num_layers=1,
                    num_heads=2, max_length=SMAX)
    net.initialize(mx.init.Xavier())
    return net


def test_speculation_heterogeneous_fleet_rejected(base):
    """The PR-10 precision-homogeneity rule's sibling: a fleet mixing
    speculative and plain replicas (or two different draft/spec_k
    configs) is rejected at construction — a retried stochastic
    request's stream depends on the speculation config's key
    schedule, so it must not depend on which replica catches it."""
    _net, params = base
    plain = _mk_engine(params)
    spec = GenerationEngine(_build_net(), draft_model=_mk_draft(),
                            spec_k=2, max_slots=SLOTS,
                            max_length=SMAX, max_new_tokens=4,
                            queue_limit=32)
    spec.load_weights(params)
    with pytest.raises(TypeError, match="speculation-homogeneous"):
        Router([plain, spec])
    spec2 = GenerationEngine(_build_net(), draft_model=_mk_draft(),
                             spec_k=3, max_slots=SLOTS,
                             max_length=SMAX, max_new_tokens=4,
                             queue_limit=32)
    spec2.load_weights(params)
    with pytest.raises(TypeError, match="speculation-homogeneous"):
        Router([spec, spec2])
    # a homogeneous speculative fleet is fine (and still serves)
    router = Router([spec, spec2_ok := GenerationEngine(
        _build_net(), draft_model=_mk_draft(), spec_k=2,
        max_slots=SLOTS, max_length=SMAX, max_new_tokens=4,
        queue_limit=32)])
    spec2_ok.load_weights(params)
    router.close()
    plain.close()
    spec2.close()


def test_sampling_kwargs_propagate_and_pin_seed(base):
    """submit(temperature=, top_k=, top_p=, seed=) reaches the engine:
    a 1-replica fleet's stream equals the direct engine submit with
    the same seed, and an unseeded stochastic request gets a seed
    pinned at admission (req.sampling carries it) so retries replay
    the identical stream."""
    net, params = base
    rng = onp.random.RandomState(17)
    p = _prompt(rng)
    direct_eng = _mk_engine(params, max_new=6)
    direct = direct_eng.submit(
        p, temperature=0.9, top_k=12, seed=77).result(timeout=120).tokens
    direct_eng.close()
    eng = _mk_engine(params, max_new=6)
    router = Router([eng])
    via = router.submit(p, temperature=0.9, top_k=12,
                        seed=77).result(timeout=120).tokens
    assert via == direct
    # greedy requests stay greedy (and bit-identical) through the fleet
    g1 = router.submit(p).result(timeout=120).tokens
    g2 = router.submit(p, temperature=0.0).result(timeout=120).tokens
    assert g1 == g2
    router.close()


def test_infer_fleet_rejects_sampling_kwargs():
    inf = _mk_infer_engine()
    router = Router([inf])
    with pytest.raises(TypeError, match="generation fleets only"):
        router.submit(onp.zeros((1, 4), "f4"), temperature=0.5)
    router.close()


# -- randomized soak (excluded from tier-1 via the slow marker) --------

@pytest.mark.slow
def test_soak_randomized_fault_schedule(base):
    """Fixed-seed randomized chaos: transient dispatch errors, a slow
    replica, and a scheduled mid-window crash. Every request must
    resolve (success or an explicit error — never a hang) and
    successful streams stay token-identical to the reference."""
    net, params = base
    engines = _fleet(params, n=3, queue_limit=64)
    injector = FaultInjector(
        rules=[FaultRule("error", rate=0.05),
               FaultRule("slow", replica=2, rate=0.3, duration_ms=5.0),
               FaultRule("crash", replica=1, after_n=25)],
        seed=1234)
    router = Router(engines, max_retries=3, breaker_threshold=3,
                    breaker_cooldown_s=0.5, probe_interval_s=0.05,
                    fault_injector=injector)
    rng = onp.random.RandomState(42)
    prompts = [_prompt(rng, 3 + i % 10) for i in range(80)]
    budgets = [2 + i % 7 for i in range(80)]
    streams = [None] * 80
    errs = []

    def client(lo, hi):
        for i in range(lo, hi):
            try:
                streams[i] = router.submit(prompts[i],
                                           max_new_tokens=budgets[i])
            except Exception as e:  # noqa: BLE001 — shed/faulted is ok
                errs.append((i, e))
            time.sleep(0.002)

    threads = [threading.Thread(target=client, args=(0, 40)),
               threading.Thread(target=client, args=(40, 80))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    policy = engines[0].policy
    n_ok = 0
    for i, s in enumerate(streams):
        if s is None:
            continue
        try:
            r = s.result(timeout=300)
        except Exception:  # noqa: BLE001 — explicit failure, not a hang
            continue
        if r.finish_reason == "length":
            n_ok += 1
            assert r.tokens == _ref_generate(net, policy, prompts[i],
                                             budgets[i])
    assert n_ok >= 60, f"too few successes under chaos ({n_ok}/80)"
    assert telemetry.counter_value("serving.router.retries") >= 1
    router.close(timeout=60.0)
    assert not router._prober.is_alive()


def test_prefix_affinity_hint(base):
    """submit(prefix_key=...) softly biases dispatch toward the replica
    that last served that key: the biased replica wins over an idle one
    while its load is within the slack, the hit counter counts it, and
    a DOWN affinity replica is routed around (health always wins)."""
    net, params = base
    router = Router(_fleet(params, n=3, queue_limit=64),
                    probe_interval_s=10.0)
    rng = onp.random.RandomState(31)
    p = _prompt(rng, 5)
    try:
        s0 = router.submit(p, max_new_tokens=2, prefix_key="sys")
        s0.result(timeout=120)
        home = s0.replicas[0]
        telemetry.reset()
        # a long-running request keeps the home replica busier than
        # the idle others — JSQ alone would route away, the affinity
        # hint (within slack) keeps the prefix-warm replica
        busy = router.submit(p, max_new_tokens=24, prefix_key="sys")
        assert busy.replicas[0] == home
        warm = router.submit(p, max_new_tokens=2, prefix_key="sys")
        assert warm.replicas[0] == home
        # only dispatches the hint CHANGED are counted ("warm" beat a
        # shorter queue; "busy" may have been the JSQ pick anyway)
        assert telemetry.counter_value(
            "serving.router.prefix_affinity_hits") >= 1
        # no key -> pure JSQ, unaffected by the affinity map
        plain = router.submit(_prompt(rng, 4), max_new_tokens=2)
        assert plain.replicas[0] != home
        for s in (busy, warm, plain):
            s.result(timeout=120)
        # health wins: a dead home replica never gets hint traffic
        router.replicas[home].close()
        moved = router.submit(p, max_new_tokens=2, prefix_key="sys")
        assert moved.replicas[0] != home
        moved.result(timeout=120)
    finally:
        router.close()


# -- multi-tenant LoRA propagation (docs/SERVING.md "Multi-tenant
# LoRA"): adapter= rides every dispatch and retry -----------------------

LORA_RANK = 2


def _lora_adapter(seed, units=16, layers=1, scale=0.4):
    r = onp.random.RandomState(seed)
    return {f"layers.{li}.{p}.{h}":
            (r.randn(units, LORA_RANK) if h == "A"
             else r.randn(LORA_RANK, units)).astype("f4") * scale
            for li in range(layers)
            for p in ("q_proj", "k_proj", "v_proj", "out_proj")
            for h in ("A", "B")}


def _mk_lora_engine(params, max_new=4, queue_limit=32):
    eng = GenerationEngine(_build_net(), max_slots=SLOTS,
                           max_length=SMAX, max_new_tokens=max_new,
                           queue_limit=queue_limit,
                           lora_rank=LORA_RANK, max_adapters=2)
    eng.load_weights(params)
    return eng


def test_lora_config_heterogeneous_fleet_rejected(base):
    """One LoRA-armed replica + one plain replica cannot form a fleet:
    an adapter= retry could land where no bank exists. The error names
    each replica's capabilities (the shared helper)."""
    net, params = base
    engines = [_mk_lora_engine(params), _mk_engine(params)]
    with pytest.raises(TypeError, match="LoRA-config-homogeneous") as ei:
        Router(engines)
    assert "capabilities" in str(ei.value)
    for e in engines:
        e.close()


def test_unknown_adapter_and_heterogeneous_registry_rejected(base):
    """An adapter= submit resolves against the fleet AT DISPATCH: an
    unknown name is rejected at the router edge, and registries that
    diverged across replicas (a partial load) reject outright instead
    of letting a retry land on a replica that lacks the adapter."""
    net, params = base
    router = Router([_mk_lora_engine(params), _mk_lora_engine(params)])
    rng = onp.random.RandomState(41)
    p = _prompt(rng)
    try:
        with pytest.raises(ValueError, match="unknown adapter"):
            router.submit(p, adapter="ghost")
        assert router.load_adapter("t1", _lora_adapter(1)) == 2
        assert router.generate(p, adapter="t1", timeout=120).tokens
        # skew one replica's registry with an UNRELATED adapter: t1
        # resolves identically on every live replica, so its traffic
        # still flows (an in-progress rolling load of another tenant
        # must never shed valid traffic) — while a submit binding the
        # PARTIALLY-loaded name rejects, naming the fleet-wide fix
        router.replicas[0].load_adapter("skew", _lora_adapter(2))
        assert router.generate(p, adapter="t1", timeout=120).tokens
        with pytest.raises(TypeError, match="heterogeneous"):
            router.submit(p, adapter="skew")
        # adapter= on a plain fleet names the argument + capabilities
        plain = Router([_mk_engine(params)])
        with pytest.raises(TypeError, match="capabilities"):
            plain.submit(p, adapter="t1")
        plain.close()
        # and an infer fleet rejects it like the other gen-only knobs
        inf = Router([_mk_infer_engine()])
        with pytest.raises(TypeError, match="generation fleets only"):
            inf.submit(onp.zeros((1, 4), "f4"), adapter="t1")
        inf.close()
    finally:
        router.close()


def test_adapter_retry_on_crash_token_identical(base):
    """A replica crash mid-decode re-dispatches the request WITH its
    adapter binding: the retried stream (prefix skipped) is
    token-identical to a dedicated single-adapter engine's output."""
    net, params = base
    injector = FaultInjector(
        rules=[FaultRule("crash", replica=0, after_n=2)], seed=0)
    router = Router([_mk_lora_engine(params), _mk_lora_engine(params)],
                    max_retries=2, probe_interval_s=0.05,
                    fault_injector=injector)
    router.load_adapter("t1", _lora_adapter(3))
    ded = _mk_lora_engine(params)
    ded.load_adapter("t1", _lora_adapter(3))
    rng = onp.random.RandomState(42)
    prompts = [_prompt(rng) for _ in range(3)]
    refs = [ded.generate(p, adapter="t1", max_new_tokens=20,
                         timeout=120).tokens for p in prompts]
    ded.close()
    s1 = router.submit(prompts[0], adapter="t1", max_new_tokens=20)
    deadline = time.monotonic() + 60
    while not s1.tokens and time.monotonic() < deadline:
        time.sleep(0.001)
    assert s1.tokens, "first request never started decoding"
    s2 = router.submit(prompts[1], adapter="t1", max_new_tokens=20)
    s3 = router.submit(prompts[2], adapter="t1", max_new_tokens=20)
    streams = [s1, s2, s3]
    for p, s, ref in zip(prompts, streams, refs):
        assert s.result(timeout=120).tokens == ref, \
            f"adapter retry diverged (retries={s.retries})"
    assert s1.retries == 1 and s1.replicas == [0, 1], \
        "the crash must have re-dispatched s1 with its binding"
    router.close()


def test_fleet_unload_defers_while_request_in_flight(base):
    """REGRESSION: Router.unload_adapter of a name bound by an
    IN-FLIGHT request defers FLEET-WIDE (returns 0) — no replica
    frees its slot, so a crash-retry can still re-bind the adapter on
    the surviving replica (the module's stated invariant; the broken
    behavior freed unpinned replicas immediately and the retry died
    with 'not loaded'). The last bound request's release runs the
    rolling unload."""
    net, params = base
    injector = FaultInjector(
        rules=[FaultRule("crash", replica=0, after_n=2)], seed=0)
    router = Router([_mk_lora_engine(params), _mk_lora_engine(params)],
                    max_retries=2, probe_interval_s=0.05,
                    fault_injector=injector)
    router.load_adapter("t1", _lora_adapter(6))
    ded = _mk_lora_engine(params)
    ded.load_adapter("t1", _lora_adapter(6))
    rng = onp.random.RandomState(45)
    prompts = [_prompt(rng) for _ in range(3)]
    ref = ded.generate(prompts[0], adapter="t1", max_new_tokens=20,
                       timeout=120).tokens
    ded.close()
    s1 = router.submit(prompts[0], adapter="t1", max_new_tokens=20)
    deadline = time.monotonic() + 60
    while not s1.tokens and time.monotonic() < deadline:
        time.sleep(0.001)
    assert s1.tokens, "first request never started decoding"
    # unload mid-flight: defers fleet-wide; EVERY replica keeps the
    # adapter so the coming crash-retry can re-bind it anywhere
    assert router.unload_adapter("t1") == 0
    with pytest.raises(ValueError, match="unloading fleet-wide"):
        router.submit(prompts[1], adapter="t1")
    # a reload while the drain is pending would report success and
    # then be silently evicted when the last pin drops — rejected
    # like the engine-level rule
    with pytest.raises(ValueError, match="unloading fleet-wide"):
        router.load_adapter("t1", _lora_adapter(6))
    assert all("t1" in e.adapters for e in router.replicas), \
        "a replica freed its slot while the request was in flight"
    # base traffic drives replica 0 to its crashing dispatch; s1
    # retries on replica 1 — which must still hold the adapter
    s2 = router.submit(prompts[1], max_new_tokens=20)
    s3 = router.submit(prompts[2], max_new_tokens=20)
    assert s1.result(timeout=120).tokens == ref, \
        f"adapter retry diverged (retries={s1.retries})"
    assert s1.retries == 1 and s1.replicas == [0, 1]
    s2.result(timeout=120), s3.result(timeout=120)
    # s1 was the last bound request: its release rolls the deferred
    # unload across the surviving replica
    deadline = time.monotonic() + 10
    while "t1" in router.replicas[1].adapters \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    assert "t1" not in router.replicas[1].adapters, \
        "the deferred fleet unload never drained"
    with pytest.raises(ValueError, match="unknown adapter"):
        router.submit(prompts[1], adapter="t1")
    router.close()


def test_immediate_unload_blocks_validate_admit_window(base):
    """REGRESSION: an IMMEDIATE (nothing-in-flight) fleet unload
    marks the name draining for the duration of the roll, so a
    submit that already passed ``_validate_adapter`` cannot pin the
    name while replicas are freeing their slots (it would decode on
    a half-unloaded fleet and a retry could land where the slot is
    gone). After the roll the mark clears and the name is simply
    unknown."""
    net, params = base
    router = Router([_mk_lora_engine(params)])
    router.load_adapter("t1", _lora_adapter(7))
    eng = router.replicas[0]
    orig, seen = eng.unload_adapter, {}

    def mid_roll(name):
        # a submit that validated BEFORE the roll reaches admission
        # NOW — it must hit the draining rejection
        with pytest.raises(ValueError, match="unloading fleet-wide"):
            router._admit("default", 0, 4, adapter=name)
        seen["checked"] = True
        return orig(name)

    eng.unload_adapter = mid_roll
    try:
        assert router.unload_adapter("t1") == 1
    finally:
        eng.unload_adapter = orig
    assert seen.get("checked"), "the roll never consulted the engine"
    assert not router._adapter_draining, "the draining mark leaked"
    # post-roll: reloadable as usual
    assert router.load_adapter("t1", _lora_adapter(7)) == 1
    router.close()


def test_fleet_load_adapter_partial_rejection_keeps_rolling(base):
    """REGRESSION: a per-replica ValueError mid-roll (one engine
    still draining the name's previous unload) must not abort
    ``Router.load_adapter`` half-applied — the rest of the fleet
    installs and the error re-raises at the end, so a re-run
    converges instead of the fleet sticking heterogeneous."""
    net, params = base
    router = Router([_mk_lora_engine(params), _mk_lora_engine(params)])
    rng = onp.random.RandomState(46)
    p = _prompt(rng)
    try:
        router.load_adapter("X", _lora_adapter(8))
        before = router.replicas[1].generate(
            p, adapter="X", timeout=120).tokens
        # park replica 0's engine registry in its engine-level
        # draining state: the refresh will be rejected THERE FIRST
        e0 = router.replicas[0]
        e0._pin_adapter("X")
        assert e0.unload_adapter("X") is False
        with pytest.raises(ValueError, match="unloading"):
            router.load_adapter("X", _lora_adapter(9))
        after = router.replicas[1].generate(
            p, adapter="X", timeout=120).tokens
        assert after != before, \
            "replica 0's rejection aborted the roll before replica 1"
    finally:
        router.close()


def test_retried_unload_cancels_queued_drain(base):
    """REGRESSION: a deferred fleet unload queues its drain for the
    prober; when the caller retries unload_adapter after the pins
    drop (natural after the deferred 0 return) and the inline roll
    wins, the queued drain is STALE — it must not fire later and
    silently evict a freshly reloaded adapter."""
    net, params = base
    router = Router([_mk_lora_engine(params)],
                    probe_interval_s=30)      # prober parked
    try:
        router.load_adapter("t1", _lora_adapter(10))
        rng = onp.random.RandomState(47)
        p = _prompt(rng)
        s = router.submit(p, adapter="t1", max_new_tokens=8)
        assert router.unload_adapter("t1") == 0        # deferred
        s.result(timeout=120)
        dl = time.monotonic() + 10
        while "t1" not in router._adapter_drain_pending \
                and time.monotonic() < dl:
            time.sleep(0.01)
        assert "t1" in router._adapter_drain_pending
        # the retried unload rolls inline and must cancel the
        # queued drain with it
        assert router.unload_adapter("t1") == 1
        assert "t1" not in router._adapter_drain_pending
        router.load_adapter("t1", _lora_adapter(11))
        router._run_pending_drains()   # the prober path, by hand
        assert router.replicas[0].has_adapter("t1"), \
            "a stale queued drain evicted the reloaded adapter"
        assert router.generate(p, adapter="t1", timeout=120).tokens
    finally:
        router.close()


def test_adapter_sampled_stream_bitwise_reproducible(base):
    """The PR 11 seeded-stream contract extended to adapter=: the same
    seeds on a REPLAYED admission schedule (flood-submitted from one
    thread, single replica) produce bitwise-identical streams across a
    fleet rebuild — adapter bindings included."""
    net, params = base

    def run():
        router = Router([_mk_lora_engine(params, max_new=8,
                                         queue_limit=64)])
        router.load_adapter("t1", _lora_adapter(4))
        rng = onp.random.RandomState(43)
        prompts = [_prompt(rng, 4 + i % 3) for i in range(6)]
        streams = [router.submit(
            p, adapter="t1" if i % 2 else None, temperature=0.8,
            top_k=12, top_p=0.9, seed=500 + i, max_new_tokens=8)
            for i, p in enumerate(prompts)]
        out = [s.result(timeout=120).tokens for s in streams]
        router.close()
        return out

    first, second = run(), run()
    assert first == second, \
        "seeded adapter streams diverged across a fleet rebuild"


def test_fleet_load_unload_adapter_rollover(base):
    """Router.load_adapter installs an adapter on every live replica
    (the load_weights rolling pattern, zero retraces per engine);
    unload_adapter rolls the eviction; traffic keeps flowing
    throughout."""
    net, params = base
    router = Router([_mk_lora_engine(params), _mk_lora_engine(params)])
    rng = onp.random.RandomState(44)
    p = _prompt(rng)
    try:
        assert router.load_adapter("t1", _lora_adapter(5)) == 2
        assert all(e.adapters == ["t1"] for e in router.replicas)
        outs = {tuple(router.generate(p, adapter="t1",
                                      timeout=120).tokens)
                for _ in range(4)}
        assert len(outs) == 1, "replicas disagreed on the adapter"
        assert router.unload_adapter("t1") == 2
        assert all(e.adapters == [] for e in router.replicas)
        with pytest.raises(ValueError, match="unknown adapter"):
            router.submit(p, adapter="t1")
        # base traffic unaffected throughout
        assert router.generate(p, timeout=120).tokens
    finally:
        router.close()
