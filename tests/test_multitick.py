"""Multi-tick fused decode, bf16 compute, and gather/compute overlap.

Guarantees under test (ISSUE 17):
- ``decode_ticks=k`` is TOKEN-IDENTICAL to ``decode_ticks=1`` for
  greedy traffic in every engine composition (dense, paged, int8
  weights, LoRA adapters) — the in-program eos/budget masking never
  changes what a request receives, only how often the host syncs;
- eos and budget landing mid-scan truncate EXACTLY (a finished slot
  keeps scanning but its masked emissions are dropped on commit);
- seeded stochastic sampling is bitwise-reproducible ACROSS tick
  sizes: per-row keys advance once per scanned position, so the same
  admission schedule replays the same stream for k in {1, 4, 8};
- the host-sync amortization is real and gated from counters:
  ``serving.generate.host_syncs`` == ceil((new_tokens-1)/k) for a
  lone request (the first token rides the prefill sync), one dispatch
  per fused tick, ``ticks_per_sync`` == k;
- mixed-budget traffic through a multi-tick engine compiles NOTHING
  in steady state, and a multi-token tick records ONE ``decode`` span
  carrying ``tokens=<n>`` (not n spans, not zero);
- ``compute_dtype="bfloat16"`` holds the PR 10 teacher-forced
  bounded-divergence contract at model level (fp32-reported logits,
  bounded drift, corpus greedy agreement) while masters stay fp32;
- ``TrainStep(layout="tp_fsdp")`` chains ``optimization_barrier``
  across per-layer groups (``overlap_gather=True``, visible in the
  lowered HLO via ``compiled_hlo(optimized=False)``) without changing
  the all-gather count or the bitwise-equal-to-dp losses.
"""
import math

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.gluon.model_zoo.gpt import gpt_small
from mxnet_tpu.serving import GenerationEngine

VOCAB, SLOTS, SMAX = 97, 4, 64
UNITS, LAYERS, HEADS = 32, 2, 4


def _net(seed=1234):
    mx.np.random.seed(seed)
    onp.random.seed(seed)
    net = gpt_small(vocab_size=VOCAB, units=UNITS, num_layers=LAYERS,
                    num_heads=HEADS, max_length=128)
    net.initialize(mx.init.Xavier())
    return net


def _prompt(rng, n):
    return rng.randint(0, VOCAB, size=n).astype("i4")


def _corpus(seed=3, n=8):
    rng = onp.random.RandomState(seed)
    prompts = [_prompt(rng, 3 + (5 * i) % 17) for i in range(n)]
    budgets = [3 + (7 * i) % 11 for i in range(n)]
    return prompts, budgets


def _drain(eng, prompts, budgets, **submit_kw):
    streams = [eng.submit(p, max_new_tokens=b, **submit_kw)
               for p, b in zip(prompts, budgets)]
    return [s.result(timeout=120) for s in streams]


# -- greedy parity across compositions ---------------------------------

@pytest.mark.parametrize("k", [4, 8])
def test_multitick_greedy_parity_dense(k):
    """Dense engine: decode_ticks=k token-identical to k=1, mixed
    prompt lengths and budgets (budgets deliberately NOT multiples
    of k)."""
    prompts, budgets = _corpus()
    net = _net()
    ref_eng = GenerationEngine(net, max_slots=SLOTS, max_length=SMAX,
                               max_new_tokens=16).warmup()
    ref = _drain(ref_eng, prompts, budgets)
    ref_eng.close()
    eng = GenerationEngine(net, max_slots=SLOTS, max_length=SMAX,
                           max_new_tokens=16, decode_ticks=k).warmup()
    got = _drain(eng, prompts, budgets)
    eng.close()
    for r, g in zip(ref, got):
        assert g.tokens == r.tokens
        assert g.finish_reason == r.finish_reason


def test_multitick_greedy_parity_paged():
    """Paged pool: the scrap-page redirection for finished slots must
    not perturb any live row."""
    prompts, budgets = _corpus(seed=5)
    net = _net()
    ref_eng = GenerationEngine(net, max_slots=SLOTS, max_length=SMAX,
                               max_new_tokens=16, paged=True,
                               page_size=8).warmup()
    ref = _drain(ref_eng, prompts, budgets)
    ref_eng.close()
    eng = GenerationEngine(net, max_slots=SLOTS, max_length=SMAX,
                           max_new_tokens=16, paged=True, page_size=8,
                           decode_ticks=4).warmup()
    got = _drain(eng, prompts, budgets)
    eng.close()
    assert [g.tokens for g in got] == [r.tokens for r in ref]
    assert [g.finish_reason for g in got] \
        == [r.finish_reason for r in ref]


def test_multitick_greedy_parity_int8():
    """int8 weights + int8 KV: the fused scan reads the same quant
    tables as the single-step program."""
    prompts, budgets = _corpus(seed=9, n=6)
    ref_eng = GenerationEngine(_net(), max_slots=SLOTS,
                               max_length=SMAX, max_new_tokens=16,
                               quantize="int8_weights",
                               kv_dtype="int8").warmup()
    ref = _drain(ref_eng, prompts, budgets)
    ref_eng.close()
    eng = GenerationEngine(_net(), max_slots=SLOTS, max_length=SMAX,
                           max_new_tokens=16, quantize="int8_weights",
                           kv_dtype="int8", decode_ticks=4).warmup()
    got = _drain(eng, prompts, budgets)
    eng.close()
    assert [g.tokens for g in got] == [r.tokens for r in ref]


def test_multitick_greedy_parity_lora():
    """Batched LoRA: per-slot adapter indices ride the fused scan
    unchanged; base/adapter co-tenants stay row-independent."""
    rank = 2
    rng = onp.random.RandomState(11)
    adapter = {}
    for li in range(LAYERS):
        for proj in ("q_proj", "k_proj", "v_proj", "out_proj"):
            adapter[f"layers.{li}.{proj}.A"] = \
                (rng.randn(UNITS, rank) * 0.4).astype("f4")
            adapter[f"layers.{li}.{proj}.B"] = \
                (rng.randn(rank, UNITS) * 0.4).astype("f4")
    prompts, budgets = _corpus(seed=13, n=6)
    ads = [None, "t", None, "t", "t", None]

    def run(k):
        eng = GenerationEngine(_net(), max_slots=SLOTS,
                               max_length=SMAX, max_new_tokens=16,
                               lora_rank=rank, max_adapters=3,
                               decode_ticks=k)
        eng.load_adapter("t", adapter)
        eng.warmup()
        streams = [eng.submit(p, max_new_tokens=b, adapter=a)
                   for p, b, a in zip(prompts, budgets, ads)]
        out = [s.result(timeout=120).tokens for s in streams]
        eng.close()
        return out

    assert run(4) == run(1)


def test_multitick_sampled_bitwise_reproducible_across_k():
    """Seeded stochastic requests replayed through k in {1,4,8}
    engines produce bitwise-identical streams: keys advance once per
    scanned position regardless of tick size. Mixed greedy/stochastic
    batches share the one program."""
    prompts, budgets = _corpus(seed=17, n=6)
    kw = [dict(temperature=0.8, top_k=9, seed=100 + i) if i % 2
          else {} for i in range(len(prompts))]

    def run(k):
        net = _net()
        eng = GenerationEngine(net, max_slots=SLOTS, max_length=SMAX,
                               max_new_tokens=16,
                               decode_ticks=k).warmup()
        streams = [eng.submit(p, max_new_tokens=b, **s)
                   for p, b, s in zip(prompts, budgets, kw)]
        out = [s.result(timeout=120).tokens for s in streams]
        eng.close()
        return out

    r1, r4, r8 = run(1), run(4), run(8)
    assert r4 == r1
    assert r8 == r1


# -- in-program eos / budget semantics ---------------------------------

def test_multitick_eos_and_budget_truncate_mid_scan():
    """eos or budget landing in the middle of a fused scan truncates
    the committed block exactly where the k=1 engine stops, with the
    same finish_reason."""
    prompts, budgets = _corpus(seed=21, n=8)
    net = _net()
    # pick an eos that actually fires mid-stream for some requests:
    # run greedy once and use the most common emitted token
    probe = GenerationEngine(net, max_slots=SLOTS, max_length=SMAX,
                             max_new_tokens=16).warmup()
    ref0 = _drain(probe, prompts, budgets)
    probe.close()
    flat = [t for r in ref0 for t in r.tokens]
    eos = max(set(flat), key=flat.count)

    def run(k):
        eng = GenerationEngine(net, max_slots=SLOTS, max_length=SMAX,
                               max_new_tokens=16, eos_id=int(eos),
                               decode_ticks=k).warmup()
        out = _drain(eng, prompts, budgets)
        eng.close()
        return out

    ref, got = run(1), run(4)
    assert any(r.finish_reason == "eos" for r in ref), \
        "probe failed to arrange a mid-stream eos"
    for r, g in zip(ref, got):
        assert g.tokens == r.tokens
        assert g.finish_reason == r.finish_reason


# -- host-sync amortization, gated from counters ------------------------

@pytest.mark.parametrize("k", [1, 4, 8])
def test_multitick_host_sync_arithmetic(k):
    """A lone request emitting N tokens costs exactly
    ceil((N-1)/k) decode host syncs (token 1 rides the prefill sync),
    ONE dispatch per fused tick, and zero in-window compiles."""
    net = _net()
    eng = GenerationEngine(net, max_slots=SLOTS, max_length=SMAX,
                           max_new_tokens=32, decode_ticks=k).warmup()
    rng = onp.random.RandomState(2)
    n_new = 21
    eng.submit(_prompt(rng, 6), max_new_tokens=n_new).result(120)
    telemetry.reset()
    res = eng.submit(_prompt(rng, 6), max_new_tokens=n_new).result(120)
    snap = telemetry.snapshot()
    eng.close()
    assert len(res.tokens) == n_new
    want = math.ceil((n_new - 1) / k)
    assert snap["counters"]["serving.generate.host_syncs"] == want
    assert snap["counters"]["serving.generate.dispatches"] == want
    assert snap["gauges"]["serving.generate.ticks_per_sync"]["value"] \
        == k
    assert snap["counters"].get("model.gpt.trace", 0) == 0


def test_multitick_zero_steady_state_compiles_mixed_traffic():
    """Mixed prompt lengths, budgets, and greedy/sampled mixes
    through one decode_ticks=4 engine compile nothing after
    warmup + one settling wave."""
    net = _net()
    eng = GenerationEngine(net, max_slots=SLOTS, max_length=SMAX,
                           max_new_tokens=16, decode_ticks=4).warmup()
    prompts, budgets = _corpus(seed=23, n=8)
    _drain(eng, prompts[:4], budgets[:4])
    telemetry.reset()
    streams = [eng.submit(p, max_new_tokens=b,
                          **(dict(temperature=0.7, seed=i) if i % 3
                             else {}))
               for i, (p, b) in enumerate(zip(prompts, budgets))]
    for s in streams:
        s.result(timeout=120)
    snap = telemetry.snapshot()
    eng.close()
    assert snap["counters"].get("model.gpt.trace", 0) == 0


# -- tracing: one span per fused tick ----------------------------------

def test_multitick_records_one_decode_span_per_tick():
    """A fused tick records ONE ``decode`` span with a ``tokens``
    attribute covering the whole block — k spans would lie about
    dispatch count, zero spans would hide the tick."""
    net = _net()
    eng = GenerationEngine(net, max_slots=SLOTS, max_length=SMAX,
                           max_new_tokens=16, decode_ticks=4).warmup()
    rng = onp.random.RandomState(4)
    stream = eng.submit(_prompt(rng, 5), max_new_tokens=9, trace=True)
    res = stream.result(timeout=120)
    spans = stream.trace()
    eng.close()
    dec = [s for s in spans if s["name"] == "decode"]
    assert dec, "no decode span recorded"
    assert all("tokens" in s.get("attrs", {}) for s in dec)
    # 9 tokens: 1 from prefill + fused ticks covering the rest
    assert sum(s["attrs"]["tokens"] for s in dec) \
        == len(res.tokens) - 1
    assert len(dec) == math.ceil((len(res.tokens) - 1) / 4)


# -- knob validation ---------------------------------------------------

def test_decode_ticks_validation():
    net = _net()
    with pytest.raises(ValueError, match="decode_ticks"):
        GenerationEngine(net, max_slots=2, max_length=SMAX,
                         decode_ticks=0)
    draft = _net(seed=7)
    with pytest.raises(ValueError, match="amortization"):
        GenerationEngine(net, max_slots=2, max_length=SMAX,
                         draft_model=draft, decode_ticks=4)
    with pytest.raises(ValueError, match="compute_dtype"):
        GenerationEngine(net, max_slots=2, max_length=SMAX,
                         compute_dtype="float16")


# -- bf16 compute: bounded divergence, fp32 masters --------------------

def test_bf16_model_teacher_forced_bounded_divergence():
    """cast_compute_params("bfloat16") tracks the fp32 model within
    a per-step logit bound under teacher forcing (identical inputs
    each step) and agrees on (nearly) every greedy token; logits are
    REPORTED fp32 either way (the host sampler contract)."""
    rng = onp.random.RandomState(7)
    prompts = [_prompt(rng, n) for n in (5, 9, 13, 7)]

    def run(net, forced=None):
        cache = net.init_cache(4, SMAX)
        firsts = []
        for b, p in enumerate(prompts):
            pad = onp.zeros((1, 16), "i4")
            pad[0, :p.size] = p
            lg, cache = net.prefill(pad, [p.size], cache, slots=[b])
            firsts.append(int(onp.asarray(lg)[0].argmax()))
        lasts = onp.asarray(firsts, "i4")
        logs = []
        for t in range(10):
            inp = lasts if forced is None else forced[t]
            lg, cache = net.decode_step(inp, cache)
            arr = onp.asarray(lg)
            assert arr.dtype == onp.float32
            logs.append(arr.copy())
            lasts = arr.argmax(axis=1).astype("i4")
        return onp.stack(logs), onp.asarray(firsts, "i4")

    ref_net = _net()
    ref, f0 = run(ref_net)
    bf_net = _net()
    bf_net.cast_compute_params("bfloat16")
    assert bf_net.compute_dtype == "bfloat16"
    forced = [f0] + [ref[t].argmax(axis=1).astype("i4")
                     for t in range(9)]
    quant, _ = run(bf_net, forced=forced)
    assert onp.abs(ref - quant).max() < 0.25
    agree = (ref.argmax(-1) == quant.argmax(-1)).mean()
    assert agree >= 0.9
    # masters untouched: disarming restores bitwise fp32
    bf_net.cast_compute_params(None)
    assert bf_net.compute_dtype == "float32"
    back, _ = run(bf_net)
    onp.testing.assert_array_equal(ref, back)


def test_bf16_engine_composes_with_multitick_and_int8_kv():
    """The bf16 engine serves greedy traffic end to end with
    decode_ticks=4 and defaults its KV cache to bf16; the capability
    string advertises the precision."""
    prompts, budgets = _corpus(seed=29, n=4)
    eng = GenerationEngine(_net(), max_slots=SLOTS, max_length=SMAX,
                           max_new_tokens=16,
                           compute_dtype="bfloat16",
                           decode_ticks=4).warmup()
    assert "bf16" in eng.precision
    out = _drain(eng, prompts, budgets)
    eng.close()
    assert all(len(r.tokens) == b for r, b in zip(out, budgets))
    # bf16 ~tracks the fp32 greedy stream (bounded divergence, small
    # model: expect near-total agreement, not bitwise)
    ref_eng = GenerationEngine(_net(), max_slots=SLOTS,
                               max_length=SMAX,
                               max_new_tokens=16).warmup()
    ref = _drain(ref_eng, prompts, budgets)
    ref_eng.close()
    n = sum(len(r.tokens) for r in ref)
    same = sum(t == u for r, g in zip(ref, out)
               for t, u in zip(r.tokens, g.tokens))
    assert same / n >= 0.8


# -- TrainStep: bf16 + gather/compute overlap --------------------------

class _LmLoss:
    def __call__(self, out, label):
        from mxnet_tpu import gluon
        return gluon.loss.SoftmaxCrossEntropyLoss()(
            out.reshape(-1, out.shape[-1]), label.reshape(-1))


def _train_batch(seed=1):
    rng = onp.random.RandomState(seed)
    x = rng.randint(0, VOCAB, (16, 17)).astype("i4")
    return mx.np.array(x[:, :-1]), mx.np.array(x[:, 1:])


def test_trainstep_bf16_fp32_masters_and_bounded_loss():
    """TrainStep(compute_dtype="bfloat16") keeps fp32 master weights
    and optimizer state while the loss tracks the fp32 step; the
    default stays bitwise-deterministic."""
    from mxnet_tpu import parallel
    data, label = _train_batch()

    def run(**kw):
        net = _net()
        step = parallel.TrainStep(net, _LmLoss(), "adam",
                                  {"learning_rate": 0.01}, **kw)
        losses = [float(step(data, label)) for _ in range(3)]
        dtypes = {str(p.data()._data.dtype)
                  for p in net.collect_params().values()}
        return losses, dtypes

    l_fp, d_fp = run()
    l_fp2, _ = run()
    assert [float.hex(a) for a in l_fp] == [float.hex(a) for a in l_fp2]
    l_bf, d_bf = run(compute_dtype="bfloat16")
    assert d_bf == d_fp == {"float32"}
    assert all(abs(a - b) < 0.15 for a, b in zip(l_fp, l_bf))
    assert l_bf[-1] < l_bf[0]
    with pytest.raises(ValueError, match="compute_dtype"):
        run(compute_dtype="int8")


@pytest.mark.requires_mesh(4)
def test_overlap_gather_barrier_chain(mesh_devices):
    """tp_fsdp with overlap_gather=True (the default): the lowered
    program carries one optimization_barrier per adjacent layer-group
    pair, the optimized program keeps the SAME all-gather footprint,
    and losses stay bitwise equal to dp. overlap_gather=False removes
    the chain."""
    from mxnet_tpu import parallel
    from mxnet_tpu.parallel import partition
    mesh = parallel.make_mesh((2, 2), ("dp", "tp"),
                              devices=mesh_devices[:4])
    data, label = _train_batch()

    def run(layout, **kw):
        with parallel.mesh_scope(mesh):
            net = _net()
            step = parallel.TrainStep(net, _LmLoss(), "adam",
                                      {"learning_rate": 0.01},
                                      mesh=mesh, layout=layout, **kw)
            losses = [float.hex(float(step(data, label)))
                      for _ in range(3)]
            return losses, step

    l_dp, _ = run(None)
    l_on, s_on = run("tp_fsdp")
    l_off, s_off = run("tp_fsdp", overlap_gather=False)
    assert l_on == l_dp and l_off == l_dp
    with parallel.mesh_scope(mesh):
        low_on = s_on.compiled_hlo(data, label, optimized=False)
        low_off = s_off.compiled_hlo(data, label, optimized=False)
        hlo_on = s_on.compiled_hlo(data, label)
        hlo_off = s_off.compiled_hlo(data, label)
    # 2 layer groups + 1 leading non-layer group -> 2 chained barriers
    assert low_on.count("optimization_barrier") == LAYERS
    assert "optimization_barrier" not in low_off
    ag_on = partition.hlo_collectives(hlo_on).get("all-gather")
    ag_off = partition.hlo_collectives(hlo_off).get("all-gather")
    assert ag_on == ag_off


@pytest.mark.requires_mesh(4)
def test_mesh_trainstep_traces_attention_without_kernels(
        mesh_devices, monkeypatch):
    """The TPU compiler cannot partition a ``pallas_call`` over a mesh
    ("Mosaic kernels cannot be automatically partitioned"), so a
    TrainStep over several devices traces attention under
    ``jnp_only()``, like a mesh serving engine. Seen from the CPU by
    answering ``_use_pallas()`` as a TPU backend would: a kernel
    lowered here would be refused (no interpret mode), the jnp path
    is not — and a one-device step does take the kernel."""
    from mxnet_tpu import parallel
    from mxnet_tpu.ops import attention as att
    nets = [_net(), _net()]
    for net in nets:
        net._gen_params()   # the eager shape probe runs unpatched
    monkeypatch.setattr(
        att, "_use_pallas",
        lambda: not getattr(att._JNP_ONLY, "on", False))
    data, label = _train_batch()
    mesh = parallel.make_mesh((4,), ("dp",), devices=mesh_devices[:4])
    step = parallel.TrainStep(nets[0], _LmLoss(), "adam",
                              {"learning_rate": 0.01}, mesh=mesh,
                              layout="fsdp")
    assert onp.isfinite(float(step(data, label)))
    one = parallel.TrainStep(nets[1], _LmLoss(), "adam",
                             {"learning_rate": 0.01})
    with pytest.raises(ValueError, match="interpret mode"):
        one(data, label)
