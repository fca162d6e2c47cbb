"""SPMD sharding layer (parallel/partition.py): logical-axis rule
resolution, the reduce-scatter/all-gather collective pair, TP/FSDP
TrainStep layouts, tensor-parallel serving, and reshard-on-restore."""
import warnings

import numpy as onp
import pytest

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import checkpoint as ckpt
from mxnet_tpu import gluon, kvstore as kv, np as mnp, parallel, telemetry
from mxnet_tpu.gluon.model_zoo.gpt import GPTModel
from mxnet_tpu.parallel import partition


pytestmark = pytest.mark.requires_mesh(8)

VOCAB, UNITS, LAYERS, HEADS, SMAX = 64, 32, 2, 4, 32


def _gpt(seed=0, tied=False, vocab=VOCAB, units=UNITS):
    mx.np.random.seed(seed)
    net = GPTModel(vocab_size=vocab, units=units, num_layers=LAYERS,
                   num_heads=HEADS, max_length=SMAX)
    net.initialize(mx.init.Xavier())
    if tied:
        # tied lm_head: peaky logits, a real greedy gap for the TP
        # reduction-order noise (~1e-5) to clear
        net._gen_params()
        params = net.collect_params()
        params["lm_head.weight"].set_data(
            mx.np.array(params["word_embed.weight"].data().asnumpy()))
        net._clear_cached_op()
    return net


def _lm_batch(n=16, s=16, seed=1):
    rng = onp.random.RandomState(seed)
    x = rng.randint(0, VOCAB, (n, s)).astype("i4")
    return mnp.array(x[:, :-1]), mnp.array(x[:, 1:])


class _LmLoss:
    def __call__(self, out, label):
        return gluon.loss.SoftmaxCrossEntropyLoss()(
            out.reshape(-1, out.shape[-1]), label.reshape(-1))


# ---------------------------------------------------------------------------
# rule resolution
# ---------------------------------------------------------------------------

def test_rule_first_match_ordering():
    mesh = parallel.make_mesh((2, 4), ("dp", "tp"))
    # two rules for the same logical axis: the FIRST matching one wins
    part = partition.Partitioner(
        [("heads", "dp"), ("heads", "tp")], mesh=mesh)
    assert part.spec_for(("heads", "embed"), (32, 32)) == P("dp")
    part2 = partition.Partitioner(
        [("heads", "tp"), ("heads", "dp")], mesh=mesh)
    assert part2.spec_for(("heads", "embed"), (32, 32)) == P("tp")


def test_unmatched_replicated():
    mesh = parallel.make_mesh((8,), ("dp",))
    part = partition.Partitioner("tp", mesh=mesh)  # no 'tp' axis on mesh
    # logical axis whose mesh axis is absent (size 1) -> replicated
    assert part.spec_for(("heads", "embed"), (32, 32)) == P()
    # no logical metadata at all -> replicated
    assert part.spec_for(None, (32, 32)) == P()
    # logical name with no rule -> replicated
    fsdp = partition.Partitioner("fsdp", mesh=mesh)
    assert fsdp.spec_for(("nosuch",), (32,)) == P()


def test_divisibility_fallback_warns():
    mesh = parallel.make_mesh((8,), ("dp",))
    part = partition.Partitioner("fsdp", mesh=mesh)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        # heads dim 6 does not divide 8: dim0 falls back (warned),
        # dim1 (embed) still shards
        spec = part.spec_for(("heads", "embed"), (6, 64), "odd.weight")
        assert spec == P(None, "dp")
        assert any("not divisible" in str(x.message) for x in w)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        # nothing divides: fully replicated
        assert part.spec_for(("heads", "embed"), (6, 7), "odd2") == P()


def test_mesh_axis_used_once_per_param():
    mesh = parallel.make_mesh((8,), ("dp",))
    part = partition.Partitioner("fsdp", mesh=mesh)
    # both dims' logical axes map to 'dp'; only the first gets it
    assert part.spec_for(("heads", "embed"), (32, 32)) == P("dp")


def test_annotate_uses_metadata_and_override_rules():
    mesh = parallel.make_mesh((2, 4), ("dp", "tp"))
    net = _gpt()
    net._gen_params()
    part = partition.Partitioner("tp", mesh=mesh)
    import re
    specs = part.annotate(
        net.collect_params(),
        override_rules=[(re.compile(r"layers\.0\.ffn1\.weight$"), P())])
    assert specs["layers.0.q_proj.weight"] == P("tp")
    assert specs["layers.0.out_proj.weight"] == P(None, "tp")
    assert specs["layers.1.ffn2.weight"] == P(None, "tp")
    assert specs["lm_head.weight"] == P("tp")
    # escape hatch: the regex rule wins over the logical axes
    assert specs["layers.0.ffn1.weight"] == P()
    assert specs["layers.1.ffn1.weight"] == P("tp")
    # LayerNorms replicated under tp
    assert specs["layers.0.ln1.gamma"] == P()


# ---------------------------------------------------------------------------
# collectives
# ---------------------------------------------------------------------------

def test_reduce_scatter_plus_all_gather_equals_allreduce():
    """RS + AG must be BITWISE equal to the allreduce on the 8-device
    mesh — the layouts choose between them purely on bytes."""
    mesh = parallel.make_mesh((8,), ("dp",))
    with parallel.mesh_scope(mesh):
        host = onp.random.RandomState(0).randn(64, 8).astype("f4")
        # dp-sharded contributions (the gradient case)
        a = mnp.array(host)
        a._install(jax.device_put(a._data, NamedSharding(mesh, P("dp"))))
        b = mnp.array(host)
        b._install(jax.device_put(b._data, NamedSharding(mesh, P("dp"))))
        parallel.allreduce(a, axis_name="dp")
        kv.reduce_scatter(b, axis_name="dp")
        # compare specs padded with None to the array's rank, as
        # kvstore.reduce_scatter does: P('dp', None) != P('dp') as
        # objects, though they place the array identically
        spec = b._data.sharding.spec
        assert tuple(spec) + (None,) * (b._data.ndim - len(spec)) \
            == ("dp", None)
        kv.all_gather(b, axis_name="dp")
        onp.testing.assert_array_equal(a.asnumpy(), b.asnumpy())
        # replicated input (each copy counts once: sum = n * x)
        c, d = mnp.ones((8, 4)), mnp.ones((8, 4))
        parallel.allreduce(c, axis_name="dp")
        kv.reduce_scatter(d, axis_name="dp")
        kv.all_gather(d, axis_name="dp")
        onp.testing.assert_array_equal(c.asnumpy(), d.asnumpy())
        assert float(d.asnumpy()[0, 0]) == 8.0


def test_collective_telemetry_and_validation():
    mesh = parallel.make_mesh((8,), ("dp",))
    with parallel.mesh_scope(mesh):
        telemetry.reset()
        x = mnp.ones((16, 2))
        kv.reduce_scatter(x, axis_name="dp")
        kv.all_gather(x, axis_name="dp")
        snap = telemetry.snapshot()["counters"]
        # ring byte model: (n-1)/n of the payload per direction
        want = 16 * 2 * 4 * 7 // 8
        assert snap["kvstore.reduce_scatter.bytes"] == want
        assert snap["kvstore.all_gather.bytes"] == want
        # non-divisible scatter dim rejected
        with pytest.raises(ValueError, match="divisible"):
            kv.reduce_scatter(mnp.ones((13,)), axis_name="dp")
        # all_gather needs an axis-sharded input
        with pytest.raises(ValueError, match="not sharded"):
            kv.all_gather(mnp.ones((16,)), axis_name="dp")


def test_collective_wire_bytes_model():
    assert kv.collective_wire_bytes("allreduce", 1000, 8) == 2000
    assert kv.collective_wire_bytes("reduce_scatter", 1000, 8) == 875
    assert kv.collective_wire_bytes("all_gather", 1000, 8) == 875
    assert kv.collective_wire_bytes("allreduce", 1000, 1) == 0
    with pytest.raises(ValueError):
        kv.collective_wire_bytes("bogus", 1, 8)


def test_fused_bucket_reduce_scatter_path_bitwise():
    """Under an active fsdp layout, grad_fusion buckets sync via the
    kvstore reduce-scatter/all-gather pair — gradients bitwise equal
    to the allreduce path, RS/AG byte counters recorded."""
    mesh = parallel.make_mesh((8,), ("dp",))
    x, y = _lm_batch(n=8, s=8)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def run(layout_active):
        mx.np.random.seed(0)
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(16, activation="relu"),
                gluon.nn.Dense(4))
        net.initialize(mx.init.Xavier())
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.0})
        data = mnp.array(onp.random.RandomState(5).randn(8, 8)
                         .astype("f4"))
        lab = mnp.array(onp.random.RandomState(6).randint(0, 4, 8)
                        .astype("i4"))
        with mx.autograd.record():
            loss = loss_fn(net(data), lab).mean()
        loss.backward()
        part = partition.Partitioner("fsdp", mesh=mesh) \
            if layout_active else None
        with parallel.mesh_scope(mesh), partition.layout_scope(part):
            tr.allreduce_grads()
        return {k: p.grad().asnumpy().copy()
                for k, p in net.collect_params().items()
                if p.grad_req != "null"}

    telemetry.reset()
    g_ar = run(False)
    pre = telemetry.snapshot()["counters"]
    assert pre.get("kvstore.reduce_scatter.bytes", 0) == 0
    g_rs = run(True)
    snap = telemetry.snapshot()["counters"]
    assert snap.get("trainer.fused.rs_buckets", 0) > 0
    assert snap.get("kvstore.reduce_scatter.bytes", 0) > 0
    assert snap.get("kvstore.all_gather.bytes", 0) > 0
    for k in g_ar:
        onp.testing.assert_array_equal(g_ar[k], g_rs[k], err_msg=k)


def test_dist_kvstore_does_not_advertise_reduce_scatter():
    """The dist backend's inherited fused_reduce_scatter would run the
    FULL DCN allreduce plus extra reshards while the counters claimed
    (n-1)/n savings — it must not advertise the capability until it
    has a real cross-host psum_scatter (regression: review round 1)."""
    from mxnet_tpu.kvstore import KVStoreDistSync, KVStoreLocal
    assert KVStoreLocal().is_capable("reduce_scatter")
    dist = KVStoreDistSync.__new__(KVStoreDistSync)  # no jax.distributed
    assert not dist.is_capable("reduce_scatter")
    assert dist.is_capable("fused_pushpull")


# ---------------------------------------------------------------------------
# TrainStep layouts
# ---------------------------------------------------------------------------

def _layout_run(layout, mesh_shape, axes, n_steps=4):
    mesh = parallel.make_mesh(mesh_shape, axes)
    x, y = _lm_batch()
    with parallel.mesh_scope(mesh):
        net = _gpt()
        step = parallel.TrainStep(net, _LmLoss(), "adam",
                                  {"learning_rate": 0.01}, mesh=mesh,
                                  layout=layout)
        losses = [float(step(x, y)) for _ in range(n_steps)]
    return net, step, losses


def test_trainstep_layout_loss_parity():
    """TP and FSDP TrainStep losses match the DP baseline on the same
    batch (within reduction-order tolerance), with the params actually
    sharded the way the layout says."""
    _, _, l_dp = _layout_run(None, (8,), ("dp",))
    net_f, step_f, l_fsdp = _layout_run("fsdp", (8,), ("dp",))
    net_t, step_t, l_tp = _layout_run("tp", (2, 4), ("dp", "tp"))
    onp.testing.assert_allclose(l_dp, l_fsdp, rtol=1e-4, atol=1e-5)
    onp.testing.assert_allclose(l_dp, l_tp, rtol=1e-3, atol=1e-4)
    assert l_dp[-1] < l_dp[0]  # actually training
    wf = net_f.collect_params()["layers.0.q_proj.weight"].data()._data
    assert wf.sharding.spec == P("dp")
    wt = net_t.collect_params()["layers.0.q_proj.weight"].data()._data
    assert wt.sharding.spec == P("tp")
    # fsdp: optimizer state sharded like the weight (ZeRO)
    state_leaves = [s for st in step_f._opt_states
                    for s in jax.tree.leaves(st)
                    if hasattr(s, "sharding")]
    sharded = [s for s in state_leaves
               if any(e is not None for e in s.sharding.spec)]
    assert sharded, "no fsdp optimizer-state leaf is sharded"


def test_trainstep_fsdp_per_device_footprint_shrinks():
    """The fsdp layout's MEASURED per-device param+optimizer bytes are
    a fraction of dp's (the 'model bigger than one device' enabler)."""
    net_d, step_d, _ = _layout_run(None, (8,), ("dp",), n_steps=1)
    net_f, step_f, _ = _layout_run("fsdp", (8,), ("dp",), n_steps=1)

    def footprint(net, step):
        leaves = [p.data()._data
                  for p in net.collect_params().values()]
        leaves += list(step._opt_states)
        return partition.per_device_bytes(leaves)

    full, shard = footprint(net_d, step_d), footprint(net_f, step_f)
    assert shard < full / 3  # ~1/8 sharded + replicated LN/biases


def test_trainstep_comm_bytes_fsdp_below_dp():
    _, step_d, _ = _layout_run(None, (8,), ("dp",), n_steps=1)
    _, step_f, _ = _layout_run("fsdp", (8,), ("dp",), n_steps=1)
    assert 0 < step_f.comm_bytes_per_step < step_d.comm_bytes_per_step


def test_trainstep_fsdp_program_holds_the_all_gathers():
    """The layout is in the program, not only in the placements: the
    compiled fsdp step gathers its sharded weights before use, and
    the dp step, whose weights are whole on every device, gathers
    nothing."""
    x, y = _lm_batch()
    counts = {}
    for layout in (None, "fsdp"):
        _, step, _ = _layout_run(layout, (8,), ("dp",), n_steps=1)
        colls = partition.hlo_collectives(step.compiled_hlo(x, y))
        counts[layout] = colls.get("all-gather", {"count": 0})["count"]
    assert counts["fsdp"] > 0 and counts[None] == 0, counts


@pytest.mark.parametrize("layout,mesh_shape,axes", [
    ("fsdp", (8,), ("dp",)),
    ("tp", (2, 4), ("dp", "tp")),
])
def test_trainstep_layout_zero_steady_state_builds(layout, mesh_shape,
                                                   axes):
    mesh = parallel.make_mesh(mesh_shape, axes)
    x, y = _lm_batch()
    with parallel.mesh_scope(mesh):
        net = _gpt()
        step = parallel.TrainStep(net, _LmLoss(), "adam",
                                  {"learning_rate": 0.01}, mesh=mesh,
                                  layout=layout)
        float(step(x, y))
        telemetry.reset()
        for _ in range(3):
            float(step(x, y))
        snap = telemetry.snapshot()["counters"]
        assert snap.get("parallel.train_step.build", 0) == 0
        assert snap.get("parallel.train_step.comm_bytes", 0) \
            == 3 * step.comm_bytes_per_step


def test_trainstep_param_rules_override_layout():
    mesh = parallel.make_mesh((8,), ("dp",))
    x, y = _lm_batch()
    with parallel.mesh_scope(mesh):
        net = _gpt()
        step = parallel.TrainStep(
            net, _LmLoss(), "adam", {"learning_rate": 0.01},
            mesh=mesh, layout="fsdp",
            param_rules=[(r"q_proj\.weight$", P())])
        float(step(x, y))
        params = net.collect_params()
        q = params["layers.0.q_proj.weight"].data()._data
        k = params["layers.0.k_proj.weight"].data()._data
        assert q.sharding.spec == P()       # the escape hatch won
        assert k.sharding.spec == P("dp")   # layout still applies


def test_trainstep_layout_requires_mesh():
    net = _gpt()
    x, y = _lm_batch()
    old = parallel.get_mesh()
    parallel.set_mesh(None)
    try:
        step = parallel.TrainStep(net, _LmLoss(), "adam",
                                  {"learning_rate": 0.01},
                                  layout="fsdp")
        with pytest.raises(RuntimeError, match="mesh"):
            step(x, y)
    finally:
        parallel.set_mesh(old)
    with pytest.raises(ValueError, match="unknown layout"):
        partition.Partitioner("zp")


# ---------------------------------------------------------------------------
# tensor-parallel serving
# ---------------------------------------------------------------------------

def _tp_engines():
    from mxnet_tpu.serving import GenerationEngine
    mesh = parallel.make_mesh((2, 4), ("dp", "tp"))
    eng = GenerationEngine(_gpt(tied=True), max_slots=4,
                           max_length=SMAX, max_new_tokens=10)
    eng_tp = GenerationEngine(_gpt(tied=True), max_slots=4,
                              max_length=SMAX, max_new_tokens=10,
                              mesh_layout="tp", mesh=mesh)
    return eng, eng_tp


def test_tp_engine_token_identity():
    """A mesh_layout="tp" engine's greedy output is token-identical to
    the unsharded engine's, with the params AND KV cache measurably
    sharded across the mesh."""
    eng, eng_tp = _tp_engines()
    try:
        rng = onp.random.RandomState(3)
        prompts = [rng.randint(0, VOCAB, rng.randint(4, 20))
                   .astype("i4") for _ in range(8)]
        out_a = [eng.submit(p).result(timeout=120).tokens
                 for p in prompts]
        out_b = [eng_tp.submit(p).result(timeout=120).tokens
                 for p in prompts]
        assert out_a == out_b
        w = eng_tp.model.collect_params()["layers.0.q_proj.weight"] \
            .data()._data
        assert w.sharding.spec == P("tp")
        assert eng_tp._cache["k"][0].sharding.spec \
            == P(None, "tp", None, None)
        dense = partition.per_device_bytes(
            [p.data()._data
             for p in eng.model.collect_params().values()]
            + [eng._cache])
        tp = partition.per_device_bytes(
            [p.data()._data
             for p in eng_tp.model.collect_params().values()]
            + [eng_tp._cache])
        assert tp < dense / 2
    finally:
        eng.close()
        eng_tp.close()


def test_tp_engine_zero_steady_state_compiles():
    _, eng_tp = _tp_engines()
    try:
        eng_tp.warmup()
        rng = onp.random.RandomState(5)
        prompts = [rng.randint(0, VOCAB, rng.randint(4, 20))
                   .astype("i4") for _ in range(6)]
        for p in prompts[:3]:
            eng_tp.submit(p).result(timeout=120)
        telemetry.reset()
        for p in prompts[3:]:
            eng_tp.submit(p).result(timeout=120)
        snap = telemetry.snapshot()["counters"]
        assert snap.get("model.gpt.trace", 0) == 0
    finally:
        eng_tp.close()


def test_tp_engine_validation():
    from mxnet_tpu.serving import GenerationEngine
    mesh = parallel.make_mesh((2, 4), ("dp", "tp"))
    dp_mesh = parallel.make_mesh((8,), ("dp",))
    with pytest.raises(ValueError, match="mesh_layout"):
        GenerationEngine(_gpt(), mesh_layout="fsdp", mesh=mesh)
    with pytest.raises(ValueError, match="tp' axis"):
        GenerationEngine(_gpt(), mesh_layout="tp", mesh=dp_mesh)
    # a model without _num_heads must fail LOUDLY at construction —
    # the cache shards by heads (regression: review round 1)
    class _Headless:
        # passes the generation-API duck check but carries no head
        # count for the cache sharding
        def init_cache(self, *a, **k): ...
        def prefill(self, *a, **k): ...
        def decode_step(self, *a, **k): ...
    with pytest.raises(TypeError, match="_num_heads"):
        GenerationEngine(_Headless(), mesh_layout="tp", mesh=mesh)
    old = parallel.get_mesh()
    parallel.set_mesh(None)
    try:
        with pytest.raises(RuntimeError, match="mesh"):
            GenerationEngine(_gpt(), mesh_layout="tp")
    finally:
        parallel.set_mesh(old)


# ---------------------------------------------------------------------------
# checkpoint: same-layout bitwise resume + reshard-on-restore
# ---------------------------------------------------------------------------

def _ckpt_run(layout, mesh, steps, x, y, net=None, step=None,
              restore_from=None):
    with parallel.mesh_scope(mesh):
        if net is None:
            net = _gpt()
            step = parallel.TrainStep(net, _LmLoss(), "adam",
                                      {"learning_rate": 0.01},
                                      mesh=mesh, layout=layout)
        if restore_from is not None:
            float(step(x, y))  # build entries/opt states first
            ckpt.restore_training_state(restore_from, net=net,
                                        train_step=step)
        losses = [float.hex(float(step(x, y))) for _ in range(steps)]
    return net, step, losses


@pytest.mark.parametrize("layout,mesh_shape,axes", [
    ("fsdp", (8,), ("dp",)),
    ("tp", (2, 4), ("dp", "tp")),
])
def test_checkpoint_same_layout_bitwise(layout, mesh_shape, axes,
                                        tmp_path):
    """A TP-/FSDP-sharded TrainStep checkpoint restores bit-identically
    onto the SAME layout: post-resume losses and final params equal
    the uninterrupted run's."""
    mesh = parallel.make_mesh(mesh_shape, axes)
    x, y = _lm_batch()
    net_a, step_a, head = _ckpt_run(layout, mesh, 3, x, y)
    d = str(tmp_path / layout)
    with parallel.mesh_scope(mesh):
        ckpt.save_training_state(d, 3, net=net_a, train_step=step_a)
    _, _, tail_direct = _ckpt_run(layout, mesh, 2, x, y,
                                  net=net_a, step=step_a)
    w_direct = {k: p.data().asnumpy().copy()
                for k, p in net_a.collect_params().items()}

    net_b, step_b, tail_resumed = _ckpt_run(layout, mesh, 2, x, y,
                                            restore_from=d)
    assert tail_resumed == tail_direct
    for k, p in net_b.collect_params().items():
        onp.testing.assert_array_equal(p.data().asnumpy(),
                                       w_direct[k], err_msg=k)
    assert step_b.optimizer.num_update == step_a.optimizer.num_update


def test_checkpoint_restores_onto_different_mesh(tmp_path):
    """Reshard-on-restore: a checkpoint written under the fsdp layout
    on an (8,) mesh restores cleanly into a TP TrainStep on a (2, 4)
    mesh — full arrays from the manifest land on the NEW layout's
    shardings."""
    mesh_a = parallel.make_mesh((8,), ("dp",))
    x, y = _lm_batch()
    net_a, step_a, _ = _ckpt_run("fsdp", mesh_a, 3, x, y)
    d = str(tmp_path / "reshard")
    with parallel.mesh_scope(mesh_a):
        ckpt.save_training_state(d, 3, net=net_a, train_step=step_a)
    saved = {k: p.data().asnumpy().copy()
             for k, p in net_a.collect_params().items()}
    _, _, tail_a = _ckpt_run("fsdp", mesh_a, 1, x, y,
                             net=net_a, step=step_a)

    mesh_b = parallel.make_mesh((2, 4), ("dp", "tp"))
    net_b, step_b, _ = _ckpt_run("tp", mesh_b, 0, x, y,
                                 restore_from=d)
    for k, p in net_b.collect_params().items():
        onp.testing.assert_array_equal(p.data().asnumpy(), saved[k],
                                       err_msg=k)
    w = net_b.collect_params()["layers.0.q_proj.weight"].data()._data
    assert w.sharding.spec == P("tp")
    assert step_b.optimizer.num_update == 3
    # cross-layout continuation agrees within reduction-order noise
    with parallel.mesh_scope(mesh_b):
        lb = float(step_b(x, y))
    la = float.fromhex(tail_a[0])
    assert abs(la - lb) < 1e-3 * max(1.0, abs(la))
