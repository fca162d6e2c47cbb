"""Mesh-parallel serving composition + the 2-D tp_fsdp training layout.

Covers ISSUE 15: mesh_layout="tp" composed with the paged pool, int8
weights/KV, speculative decoding and the LoRA adapter bank (greedy
output token-identical to the single-device twin; int8 under the
PR 10 teacher-forced bounded-divergence contract), the combined
TrainStep(layout="tp_fsdp") (losses BITWISE equal to dp, per-device
param+opt bytes strictly below both 1-D layouts), the 2-D partitioner
edge cases, the paged-pool sharding round-trip, the Router's
mesh-homogeneity rule, and the new telemetry."""
import warnings

import numpy as onp
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel, telemetry
from mxnet_tpu import np as mnp
from mxnet_tpu.gluon.model_zoo.gpt import GPTModel
from mxnet_tpu.ops import attention as att
from mxnet_tpu.parallel import partition
from mxnet_tpu.serving import GenerationEngine, Router

pytestmark = pytest.mark.requires_mesh(8)

VOCAB, UNITS, LAYERS, HEADS, SMAX = 64, 32, 2, 4, 64


def _gpt(seed=0, layers=LAYERS, tied=True, heads=HEADS):
    mx.np.random.seed(seed)
    net = GPTModel(vocab_size=VOCAB, units=UNITS, num_layers=layers,
                   num_heads=heads, max_length=SMAX)
    net.initialize(mx.init.Xavier())
    if tied:
        # tied lm_head: peaky logits so the tp partial-sum noise
        # (~1e-5) cannot flip a greedy argmax — the PR 12 discipline
        net._gen_params()
        params = net.collect_params()
        params["lm_head.weight"].set_data(
            mx.np.array(params["word_embed.weight"].data().asnumpy()))
        net._clear_cached_op()
    return net


def _mesh24(devices=None):
    return parallel.make_mesh((2, 4), ("dp", "tp"), devices=devices)


def _mesh22(devices):
    # a 2x2 sub-mesh of the box (make_mesh needs the shape to cover
    # exactly the devices passed); tests take ``devices`` from the
    # conftest ``mesh_devices`` fixture — the documented accessor
    return parallel.make_mesh((2, 2), ("dp", "tp"),
                              devices=devices[:4])


def _prompts(n=8, seed=3, lo=4, hi=20):
    rng = onp.random.RandomState(seed)
    return [rng.randint(0, VOCAB, rng.randint(lo, hi)).astype("i4")
            for _ in range(n)]


def _lora_params(seed=7, rank=2):
    rng = onp.random.RandomState(seed)
    out = {}
    for li in range(LAYERS):
        for name in ("q_proj", "k_proj", "v_proj", "out_proj"):
            out[f"layers.{li}.{name}.A"] = \
                (rng.randn(UNITS, rank) * 0.02).astype("f4")
            out[f"layers.{li}.{name}.B"] = \
                (rng.randn(rank, UNITS) * 0.02).astype("f4")
    return out


def _engine(tp=False, paged=False, quant=False, spec=False,
            lora=False, heads=HEADS, **kw):
    mesh = _mesh24() if tp else None
    if paged:
        kw.setdefault("page_size", 8)
        kw.setdefault("prefill_chunk", 16)
        kw["paged"] = True
    if quant:
        kw.update(quantize="int8_weights", kv_dtype="int8")
    if spec:
        kw.update(draft_model=_gpt(layers=1), spec_k=3)
    if lora:
        kw.update(lora_rank=2, max_adapters=2)
    if tp:
        kw.update(mesh_layout="tp", mesh=mesh)
    return GenerationEngine(_gpt(heads=heads), max_slots=4,
                            max_length=SMAX,
                            max_new_tokens=10, **kw)


def _serve(eng, prompts, adapters=None):
    streams = []
    for i, p in enumerate(prompts):
        kw = {}
        if adapters and adapters[i]:
            kw["adapter"] = adapters[i]
        streams.append(eng.submit(p, **kw))
    return [s.result(timeout=300).tokens for s in streams]


# ---------------------------------------------------------------------------
# 2-D partitioner edge cases
# ---------------------------------------------------------------------------

def test_tp_fsdp_rules_resolution(mesh_devices):
    """The built-in tp_fsdp layout shards 2-D params over BOTH axes
    (tp on the heads/mlp/vocab dim, dp on the embed dim) and 1-D
    params over their one matching axis."""
    mesh = _mesh22(mesh_devices)
    part = partition.Partitioner("tp_fsdp", mesh=mesh)
    assert part.spec_for(("heads", "embed"), (32, 32)) == P("tp", "dp")
    assert part.spec_for(("embed", "heads"), (32, 32)) == P("dp", "tp")
    assert part.spec_for(("vocab", "embed"), (64, 32)) == P("tp", "dp")
    assert part.spec_for(("embed",), (32,)) == P("dp")
    assert part.spec_for(("heads",), (32,)) == P("tp")
    assert part.gather_compute
    assert not partition.Partitioner("fsdp", mesh=mesh).gather_compute


def test_2d_both_axes_claim_one_dim_ordered_first_match(mesh_devices):
    """When two rules (two different mesh axes) claim the SAME logical
    dim, the ordered first match wins — deterministically."""
    mesh = _mesh22(mesh_devices)
    part = partition.Partitioner(
        [("embed", "tp"), ("embed", "dp")], mesh=mesh)
    assert part.spec_for(("embed",), (32,)) == P("tp")
    part2 = partition.Partitioner(
        [("embed", "dp"), ("embed", "tp")], mesh=mesh)
    assert part2.spec_for(("embed",), (32,)) == P("dp")
    # 2-D param: the first rule takes the first matching dim; the
    # used-once rule forces the second dim onto the OTHER axis
    part3 = partition.Partitioner(
        [("embed", "tp"), ("embed", "dp")], mesh=mesh)
    assert part3.spec_for(("embed", "embed"), (32, 32)) == P("tp", "dp")


def test_divisibility_fallback_warns_once_not_per_param():
    """A non-dividing mesh axis warns ONCE per (logical, mesh) axis
    pair — not once per parameter."""
    mesh = parallel.make_mesh((2, 4), ("dp", "tp"))
    part = partition.Partitioner("tp_fsdp", mesh=mesh)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        # heads=6 does not divide tp=4: falls through (heads has no
        # second rule) to replication on that dim
        s1 = part.spec_for(("heads", "embed"), (6, 32), "a.weight")
        s2 = part.spec_for(("heads", "embed"), (6, 32), "b.weight")
        s3 = part.spec_for(("heads",), (6,), "c.bias")
        hits = [x for x in w if "not divisible" in str(x.message)]
    assert s1 == s2 == P(None, "dp")
    assert s3 == P()
    assert len(hits) == 1, [str(x.message) for x in hits]
    # a DIFFERENT axis pair still gets its own (single) warning
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        part.spec_for(("embed",), (7,), "d.bias")
        part.spec_for(("embed",), (7,), "e.bias")
        hits = [x for x in w if "not divisible" in str(x.message)]
    assert len(hits) == 1


def test_grad_sync_bytes_2d_shards_payload(mesh_devices):
    """A param sharded over BOTH tp and the batch (fsdp) axis
    reduce-scatters only its tp-shard's bytes over dp, then REGATHERS
    the full payload over the tp axis (the ZeRO gather-compute
    discipline) — so 2-D wire bytes per param come out EQUAL to 1-D
    fsdp's, never lower: the model must not invent a comm saving the
    executed HLO (more all-gathers, not fewer) does not show."""
    from mxnet_tpu import kvstore as kv
    mesh = _mesh22(mesh_devices)

    class _Param:
        grad_req = "grad"

        def __init__(self, arr):
            class _D:  # the nested NDArray._data duck
                pass
            self._data = _D()
            self._data._data = arr

    params = {"w": _Param(jnp.zeros((32, 32), "float32"))}
    got_2d = partition.grad_sync_bytes({"w": P("tp", "dp")}, params,
                                       mesh, "dp")
    got_1d = partition.grad_sync_bytes({"w": P(None, "dp")}, params,
                                       mesh, "dp")
    nbytes = 32 * 32 * 4
    want_2d = kv.collective_wire_bytes("reduce_scatter", nbytes // 2, 2) \
        + kv.collective_wire_bytes("all_gather", nbytes // 2, 2) \
        + kv.collective_wire_bytes("all_gather", nbytes, 2)
    want_1d = kv.collective_wire_bytes("reduce_scatter", nbytes, 2) \
        + kv.collective_wire_bytes("all_gather", nbytes, 2)
    assert got_2d == want_2d
    assert got_1d == want_1d
    assert got_2d == got_1d  # ZeRO comm ~independent of shard factor


# ---------------------------------------------------------------------------
# tp_fsdp TrainStep
# ---------------------------------------------------------------------------

class _LmLoss:
    def __call__(self, out, label):
        return gluon.loss.SoftmaxCrossEntropyLoss()(
            out.reshape(-1, out.shape[-1]), label.reshape(-1))


def _train_run(layout, devices, steps=6):
    mesh = _mesh22(devices)
    rng = onp.random.RandomState(1)
    x = rng.randint(0, VOCAB, (16, 17)).astype("i4")
    data, label = mnp.array(x[:, :-1]), mnp.array(x[:, 1:])
    with parallel.mesh_scope(mesh):
        net = _gpt(tied=False)
        step = parallel.TrainStep(net, _LmLoss(), "adam",
                                  {"learning_rate": 0.01}, mesh=mesh,
                                  layout=layout)
        losses = [float.hex(float(step(data, label)))
                  for _ in range(steps)]
        leaves = [p.data()._data
                  for p in net.collect_params().values()]
        opt = [s for st in step._opt_states
               for s in jax.tree.leaves(st) if hasattr(s, "nbytes")]
        perdev = partition.per_device_bytes(leaves + opt)
        params = {k: p.data().asnumpy().copy()
                  for k, p in net.collect_params().items()}
    return losses, perdev, params, net, step


def test_tp_fsdp_losses_bitwise_equal_dp(mesh_devices):
    """The 2-D tp_fsdp layout trains BITWISE equal to dp on the 2x2
    mesh — losses AND parameters (the gather-compute discipline: the
    step all-gathers weights and reduces grads fully before the
    sharded update slices them)."""
    l_dp, b_dp, p_dp, _, _ = _train_run(None, mesh_devices)
    l_2d, b_2d, p_2d, net, step = _train_run("tp_fsdp", mesh_devices)
    assert l_2d == l_dp
    for k in p_dp:
        onp.testing.assert_array_equal(p_dp[k], p_2d[k], err_msg=k)
    # params really sharded over BOTH axes
    w = net.collect_params()["layers.0.q_proj.weight"].data()._data
    assert w.sharding.spec == P("tp", "dp")
    # optimizer state follows the 2-D weight sharding
    sharded_2d = [
        s for st in step._opt_states for s in jax.tree.leaves(st)
        if hasattr(s, "sharding")
        and sum(e is not None for e in s.sharding.spec) >= 2]
    assert sharded_2d, "no optimizer-state leaf is 2-D sharded"


def test_tp_fsdp_per_device_bytes_below_both_1d_layouts(mesh_devices):
    _, b_dp, _, _, s_dp = _train_run(None, mesh_devices, steps=1)
    _, b_f, _, _, s_f = _train_run("fsdp", mesh_devices, steps=1)
    _, b_t, _, _, s_t = _train_run("tp", mesh_devices, steps=1)
    _, b_2d, _, _, s_2d = _train_run("tp_fsdp", mesh_devices, steps=1)
    assert b_2d < b_f < b_dp
    assert b_2d < b_t < b_dp
    # analytic comm: ZeRO wire bytes are ~independent of the sharding
    # factor — tp_fsdp must land in fsdp's neighborhood (never the
    # fictitious halving the unregathered model used to claim), and
    # both stay under dp's full allreduce
    assert 0 < s_2d.comm_bytes_per_step <= 1.05 * s_f.comm_bytes_per_step


# ---------------------------------------------------------------------------
# paged-pool sharding round-trip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("page_size", [8, HEADS],
                         ids=["pmax-eq-heads", "page-eq-heads"])
def test_paged_pool_sharding_round_trip(mesh_devices, page_size):
    """Shard a paged pool over the heads axis, gather it back to host:
    bitwise equal to the unsharded pool; the page table and lengths
    stay replicated — by pytree KEY, even when the table's P_max dim
    numerically equals num_heads; and the pool shards its last
    dimension even when its page size does."""
    mesh = _mesh24()
    net = _gpt()
    # P_max == num_heads == 4 on purpose: 32 / 8 = 4 logical pages;
    # or page_size == num_heads == 4
    cache = net.init_paged_cache(2, 12, page_size, 32, dtype="int8")
    rng = onp.random.RandomState(9)
    filled = {
        "k": tuple(rng.randint(-127, 127, c.shape).astype("i1")
                   for c in cache["k"]),
        "v": tuple(rng.randint(-127, 127, c.shape).astype("i1")
                   for c in cache["v"]),
        "k_scale": tuple(rng.rand(*c.shape).astype("f4")
                         for c in cache["k_scale"]),
        "v_scale": tuple(rng.rand(*c.shape).astype("f4")
                         for c in cache["v_scale"]),
        "table": rng.randint(0, 12, cache["table"].shape).astype("i4"),
        "len": rng.randint(0, 32, cache["len"].shape).astype("i4"),
    }
    # the coincidence traps
    assert HEADS in (filled["table"].shape[1], filled["k"][0].shape[1])
    part = partition.Partitioner("tp", mesh=mesh)
    placed = part.place_cache(filled, HEADS)
    # a pool's rows hold the heads one after another: the LAST
    # dimension shards, into contiguous blocks of H / tp whole heads
    assert placed["k"][0].sharding.spec == P(None, None, "tp")
    assert placed["k_scale"][0].sharding.spec == P(None, "tp")
    assert placed["table"].sharding.spec == P()
    assert placed["len"].sharding.spec == P()
    # sharded per-device K/V bytes = full / tp
    kv_full = sum(int(a.nbytes) for a in filled["k"] + filled["v"])
    kv_dev = partition.per_device_bytes(
        [{"k": placed["k"], "v": placed["v"]}])
    assert kv_dev == kv_full // 4
    # host gather round-trip: bitwise
    for key in filled:
        a = jax.tree.leaves(filled[key])
        b = jax.tree.leaves(placed[key])
        for x, y in zip(a, b):
            onp.testing.assert_array_equal(onp.asarray(x),
                                           onp.asarray(y))


# ---------------------------------------------------------------------------
# composed TP serving: token identity + zero steady-state compiles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("heads,page_size", [(HEADS, 8), (16, 16)],
                         ids=["h4-ps8", "h16-ps16"])
def test_tp_paged_engine_token_identity(heads, page_size):
    """mesh_layout="tp" + paged: greedy output token-identical to the
    single-device paged engine; the pool shards by heads, its last
    dimension (per-device KV-pool bytes = full / tp); steady state
    traces nothing. With ``page_size == num_heads`` (16 and 16, GPT-2
    medium's) a pool's dimension 1 LOOKS like a heads axis: the pool
    is told apart by the cache pytree, never by that coincidence, and
    a page's rows stay whole."""
    prompts = _prompts()
    ref = _engine(paged=True, heads=heads, page_size=page_size)
    want = _serve(ref, prompts)
    ref.close()
    eng = _engine(tp=True, paged=True, heads=heads,
                  page_size=page_size).warmup()
    try:
        assert eng._cache["k"][0].shape[1:] == (page_size, UNITS)
        assert eng._cache["k"][0].sharding.spec == P(None, None, "tp")
        assert eng._cache["table"].sharding.spec == P()
        assert eng._cache["len"].sharding.spec == P()
        got = _serve(eng, prompts[:4])
        telemetry.reset()
        got += _serve(eng, prompts[4:])
        snap = telemetry.snapshot()["counters"]
        assert got == want
        assert snap.get("model.gpt.trace", 0) == 0
        pool = {k: eng._cache[k] for k in ("k", "v")}
        full = sum(int(a.nbytes) for a in jax.tree.leaves(pool))
        dev = partition.per_device_bytes([pool])
        assert dev <= 0.30 * full
    finally:
        eng.close()


def test_tp_paged_spec_lora_token_identity():
    """The FULL composition — tp + paged + speculative + LoRA — is
    greedy token-identical to the single-device paged engine for base
    traffic AND to the single-device composed engine for adapter
    traffic, with zero steady-state traces."""
    prompts = _prompts(8, seed=13)
    adapters = [None if i % 2 == 0 else "t1"
                for i in range(len(prompts))]
    lp = _lora_params()

    def build(tp):
        eng = _engine(tp=tp, paged=True, spec=True, lora=True)
        eng.load_adapter("t1", lp, alpha=4.0)
        return eng.warmup()

    ref = build(False)
    want = _serve(ref, prompts, adapters)
    ref.close()
    # base traffic baseline: the plain single-device PAGED engine
    plain = _engine(paged=True)
    want_base = _serve(plain, [p for p, a in zip(prompts, adapters)
                               if a is None])
    plain.close()
    eng = build(True)
    try:
        got = _serve(eng, prompts[:4], adapters[:4])
        telemetry.reset()
        got += _serve(eng, prompts[4:], adapters[4:])
        snap = telemetry.snapshot()["counters"]
        assert got == want
        assert [t for t, a in zip(got, adapters) if a is None] \
            == want_base
        assert snap.get("model.gpt.trace", 0) == 0
        assert snap.get("ops.lora.trace", 0) == 0
    finally:
        eng.close()


def test_tp_int8_teacher_forced_bounded_divergence():
    """tp + int8 weights + int8 KV holds PR 10's teacher-forced
    contract against the fp32 single-device model: the int8-tp run
    replays the fp32 run's token stream and every step's logits stay
    inside the bound (int8 rounding + tp reduction order)."""
    mesh = _mesh24()
    prompts = _prompts(4, seed=17)

    def run(tp_int8, forced=None):
        net = _gpt()
        if tp_int8:
            part = partition.Partitioner("tp", mesh=mesh)
            net._gen_params()
            part.place(net.collect_params())
            net._force_jnp_attention = True
            net.quantize_params()
            net.shard_generation_state(part)
            cache = part.place_cache(
                net.init_cache(4, SMAX, dtype="int8"), HEADS)
            recommit = lambda c: part.place_cache(c, HEADS)  # noqa
        else:
            cache = net.init_cache(4, SMAX)
            recommit = lambda c: c  # noqa: E731
        firsts = []
        for b, p in enumerate(prompts):
            pad = onp.zeros((1, 32), "i4")
            pad[0, :p.size] = p
            lg, cache = net.prefill(pad, [p.size], cache, slots=[b])
            cache = recommit(cache)
            firsts.append(int(onp.asarray(lg)[0].argmax()))
        lasts = onp.asarray(firsts, "i4")
        logs = []
        for t in range(8):
            inp = lasts if forced is None or forced[t] is None \
                else forced[t]
            lg, cache = net.decode_step(inp, cache)
            cache = recommit(cache)
            arr = onp.asarray(lg)
            logs.append(arr.copy())
            lasts = arr.argmax(axis=1).astype("i4")
        return onp.stack(logs)

    ref = run(False)
    forced = [None] + [ref[t].argmax(axis=1).astype("i4")
                       for t in range(7)]
    quant = run(True, forced=forced)
    # the PR 10 int8-weights+int8-KV bound; the tp reduction-order
    # noise (~1e-5) vanishes inside it
    assert onp.abs(ref - quant).max() < 0.7
    # greedy corpus agreement at the engine level (the >= 0.9 floor
    # of test_quantized's random-head engine case)
    ref_eng = _engine(quant=True)
    want = _serve(ref_eng, prompts)
    ref_eng.close()
    eng = _engine(tp=True, quant=True).warmup()
    try:
        got = _serve(eng, prompts)
    finally:
        eng.close()
    pairs = [(a, b) for ra, rb in zip(want, got)
             for a, b in zip(ra, rb)]
    agree = sum(a == b for a, b in pairs) / len(pairs)
    assert agree >= 0.9


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------

def test_tp_engine_telemetry_gauges_and_collective_counters():
    telemetry.reset()
    eng = _engine(tp=True, paged=True).warmup()
    try:
        snap = telemetry.snapshot()
        g = {k: v["value"] for k, v in snap["gauges"].items()}
        assert g.get("parallel.mesh.axis_sizes.tp") == 4
        assert g.get("parallel.mesh.axis_sizes.dp") == 2
        perdev = g.get("serving.generate.per_device_bytes", 0)
        assert 0 < perdev
        # sharded share < the full footprint
        full = sum(
            int(p.data()._data.nbytes)
            for p in eng.model.collect_params().values()) + sum(
            int(a.nbytes) for a in jax.tree.leaves(eng._cache))
        assert perdev < full
        telemetry.reset()
        _serve(eng, _prompts(3, seed=19))
        snap = telemetry.snapshot()["counters"]
        colls = {k: v for k, v in snap.items()
                 if k.startswith("parallel.collectives.")}
        # the CPU backend lowers the tp partial-sum reductions as
        # all-reduce; whatever the lowering, the decode program's
        # collectives must be counted on the serving path
        assert sum(colls.values()) > 0, snap
    finally:
        eng.close()


def test_single_device_engine_emits_no_collective_counters():
    telemetry.reset()
    eng = _engine(paged=True).warmup()
    try:
        _serve(eng, _prompts(2, seed=21))
        snap = telemetry.snapshot()["counters"]
        assert not any(k.startswith("parallel.collectives.")
                       for k in snap)
        # the per-device gauge reports the FULL footprint unsharded
        assert telemetry.snapshot()["gauges"][
            "serving.generate.per_device_bytes"]["value"] > 0
    finally:
        eng.close()


def test_jnp_only_context_disables_pallas():
    """ops.attention.jnp_only() forces the jnp kernel paths while
    tracing (the SPMD-serving rule: no pallas_call inside a GSPMD
    program without its own shard_map)."""
    try:
        orig = att.jax.default_backend
        att.jax.default_backend = lambda: "tpu"
        assert att._use_pallas()
        with att.jnp_only():
            assert not att._use_pallas()
        assert att._use_pallas()
    finally:
        att.jax.default_backend = orig


# ---------------------------------------------------------------------------
# Router: mesh-homogeneous fleets only
# ---------------------------------------------------------------------------

def test_router_rejects_mesh_heterogeneous_fleet():
    """Mixed mesh_layout (or mesh shape) fleets reject at
    construction — a cross-replica retry must replay the identical
    numeric config (the precision/speculation rule's sibling)."""
    e_plain = _engine()
    e_tp = _engine(tp=True)
    try:
        with pytest.raises(TypeError, match="mesh-homogeneous"):
            Router([e_plain, e_tp])
    finally:
        e_plain.close()
        e_tp.close()


def test_router_accepts_mesh_homogeneous_tp_fleet():
    """Two identically-sharded TP replicas form a working fleet (and
    expose the mesh config in their capabilities)."""
    e1 = _engine(tp=True)
    e2 = _engine(tp=True)
    assert e1.mesh_config == e2.mesh_config == "tp:dp=2xtp=4"
    r = Router([e1, e2])
    try:
        prompts = _prompts(4, seed=23)
        out = [r.submit(p).result(timeout=300).tokens
               for p in prompts]
        ref = _engine()
        want = _serve(ref, prompts)
        ref.close()
        assert out == want
    finally:
        r.close()


def test_engine_mesh_config_off_single_device():
    eng = _engine()
    try:
        assert eng.mesh_config == "off"
        assert "mesh=off" in eng.capabilities()
    finally:
        eng.close()


def test_single_device_engine_resets_jnp_only_flag():
    """A tp engine marks its model for jnp-only attention tracing; a
    LATER single-device engine over the same model must clear the
    mark and invalidate the closures — otherwise it would silently
    trace the slow jnp paths instead of Pallas on a TPU box. (Fully
    SERVING a previously-mesh-placed model single-device would also
    need the params moved back to one device — unsupported before
    and after this change; the flag/closure hygiene is what this
    pins.)"""
    net = _gpt()
    eng_tp = _engine_on(net, tp=True)
    assert net._force_jnp_attention is True
    # build a tp closure so the reset has something to invalidate
    eng_tp.warmup()
    assert net._gen is not None or net._paged is not None
    eng_tp.close()
    eng = _engine_on(net)
    try:
        assert net._force_jnp_attention is False
        assert net._gen is None and net._paged is None \
            and net._spec_jits is None
    finally:
        eng.close()


def _engine_on(net, tp=False):
    kw = {"mesh_layout": "tp", "mesh": _mesh24()} if tp else {}
    return GenerationEngine(net, max_slots=4, max_length=SMAX,
                            max_new_tokens=6, **kw)
