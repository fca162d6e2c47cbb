"""A model family is found by name, and nothing outside it knows a block.

(a) the ``gpt2`` family has the interface ``chipbench/README.md`` documents;
(b) no file of ``chipbench/`` outside ``families/gpt2/`` (the configurations'
own data aside) says a GPT-2 name; (c) ``mfu`` and ``kernel_roofline`` take
their counts from the family the facts name, so a second family's count
cannot be GPT-2's; (d) ``op_share`` on a made-up trace that can be checked
in the head. CPU, seconds; (a) alone imports the program.
"""
import os
import re
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import harness, trace_reduce  # noqa: E402
from chipbench.reducers import kernel_roofline, mfu, op_share  # noqa: E402

BENCH = os.path.join(ROOT, "chipbench")
#: what every family gives, as the README's "A model family" states it
INTERFACE = {
    "weights": ("sizes", "make"),
    "reference": ("served_gaps", "loss_and_grads", "loss_only",
                  "leaf_norms", "leaf_index"),
    "costs": ("prompt_forward_flops", "token_forward_flops",
              "train_step_flops"),
    "program": ("build_model", "TRACE_COUNTERS"),
}
GPT2_NAMES = ("n_embd", "n_head", "n_positions", "layer_norm_epsilon",
              "GPTModel", "model.gpt.trace", "families.gpt2")
PEAKS = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


# -- (a) ---------------------------------------------------------------------
@pytest.mark.parametrize("module", sorted(INTERFACE))
def test_gpt2_family_has_the_documented_interface(module):
    got = harness.family({"family": "gpt2"}, module)
    with open(os.path.join(BENCH, "README.md")) as f:
        readme = f.read()
    section = readme[readme.index("**A model family**"):]
    for name in INTERFACE[module]:
        assert hasattr(got, name), (module, name)
        assert f"`{name}" in section, f"README does not document {name}"


def test_a_family_is_named_or_the_run_ends():
    with pytest.raises(SystemExit, match="family"):
        harness.family({"name": "a configuration without one"}, "costs")
    with pytest.raises(SystemExit, match="families/nosuch/costs.py"):
        harness.family({"family": "nosuch"}, "costs")
    with pytest.raises(SystemExit, match="families/gpt2/kernels.py"):
        harness.family({"family": "gpt2"}, "kernels")


def test_every_configuration_names_a_family_that_is_there():
    bench = harness.load_benchmark()
    names = [c["name"] for c in bench["configs"]] + ["rehearsal"]
    for name in names:
        config = harness.load_json("configs", name + ".json")
        assert os.path.isdir(os.path.join(BENCH, "families",
                                          config["family"]))
        weights = harness.family(config, "weights")
        assert weights.sizes(config["model"])["V"] > 0


# -- (b) ---------------------------------------------------------------------
def _common_files():
    for d, dirs, files in os.walk(BENCH):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        rel = os.path.relpath(d, BENCH)
        if rel.startswith(os.path.join("families", "gpt2")):
            continue
        # a configuration's file is that model's own published group
        if rel == "configs":
            continue
        for f in files:
            path = os.path.join(d, f)
            if f.endswith((".py", ".md", ".json")) and \
                    os.path.abspath(path) != os.path.abspath(__file__):
                yield path


def test_no_gpt2_name_outside_its_family():
    found = []
    for path in _common_files():
        with open(path) as f:
            text = f.read()
        found += [(os.path.relpath(path, ROOT), n) for n in GPT2_NAMES
                  if n in text]
    assert not found, found


def test_only_the_two_program_modules_import_the_program():
    importing = []
    for d, dirs, files in os.walk(BENCH):
        dirs[:] = [x for x in dirs if x not in ("__pycache__", "tests")]
        for f in files:
            if not f.endswith(".py"):
                continue
            with open(os.path.join(d, f)) as fh:
                if re.search(r"^\s*(import|from) mxnet_tpu", fh.read(),
                             re.M):
                    importing.append(os.path.relpath(
                        os.path.join(d, f), BENCH))
    assert sorted(importing) == ["families/gpt2/program.py", "program.py"]


# -- (c) ---------------------------------------------------------------------
@pytest.fixture
def stub_family(monkeypatch):
    """A family whose costs are constants, put where ``by_name`` looks."""
    costs = types.ModuleType("chipbench.families.stub.costs")
    costs.prompt_forward_flops = lambda s, n: 1000.0
    costs.token_forward_flops = lambda s, keys, with_head: 10.0
    costs.train_step_flops = lambda s, batch, seq: 5000.0
    costs.a_kernel = lambda f: (200.0 * f["steps"], 40.0 * f["steps"])
    monkeypatch.setitem(sys.modules, "chipbench.families.stub",
                        types.ModuleType("chipbench.families.stub"))
    monkeypatch.setitem(sys.modules, costs.__name__, costs)
    return "stub"


def _facts(family, **more):
    return dict({"family": family, "sizes": {}, "peaks": PEAKS,
                 "chips": 1, "seconds": 2.0}, **more)


def test_mfu_counts_by_the_family_the_facts_name(stub_family):
    serve = _facts(stub_family, prompts_done=[7, 300],
                   decode_contexts=[8, 9, 10])
    # (2 x 1000 + 3 x 10) operations over 2 s x 1 chip x 100 a second
    assert mfu.reduce({"kind": "serve"}, serve, None) == \
        pytest.approx(100.0 * 2030.0 / 200.0)
    train = _facts(stub_family, steps=4, batch=4, sequence=1024)
    assert mfu.reduce({"kind": "train"}, train, None) == \
        pytest.approx(100.0 * 20000.0 / 200.0)
    # the same window under GPT-2's count reads something else
    gpt2 = dict(serve, family="gpt2", sizes={"D": 8, "F": 32, "L": 2,
                                             "V": 64, "H": 2})
    assert mfu.reduce({"kind": "serve"}, gpt2, None) != \
        pytest.approx(100.0 * 2030.0 / 200.0)
    assert mfu.reduce({"kind": "serve"}, dict(serve, peaks=None),
                      None) is None


def test_kernel_roofline_counts_by_the_family_the_facts_name(stub_family):
    dev = "/device:TPU:0"
    trace = trace_reduce.Trace(
        {dev: [("custom-call k f32[8]", "a_kernel_call", 0.0, 10.0),
               ("custom-call k f32[8]", "a_kernel_call", 20.0, 10.0),
               ("fusion f f32[8]", "", 10.0, 5.0)]}, {dev: []}, [], 40.0)
    facts = _facts(stub_family, traced=_facts(stub_family, steps=3))
    args = {"pattern": "a_kernel_call", "cost": "a_kernel"}
    # 600 operations need 6 s, 120 bytes need 12 s: 12 s of the 20 s run
    assert kernel_roofline.reduce(args, facts, trace) == pytest.approx(60.0)
    assert kernel_roofline.reduce(dict(args, pattern="no_such"), facts,
                                  trace) is None
    with pytest.raises(SystemExit, match="no cost function"):
        kernel_roofline.reduce(dict(args, cost="flash_fwd_call"), facts,
                               trace)


# -- (d) ---------------------------------------------------------------------
def _op(instruction, start, dur):
    return trace_reduce.short(instruction) + (start, dur)


def test_op_share_on_a_made_up_trace():
    """On a 1 s window: two pool copies of 100 ms each, a fusion of 150 ms
    that has ``copy`` in its name, and a 50 ms kernel inside the first
    copy's interval: busy 350 ms, copies 200 ms, 4/7 of it."""
    pool = "bf16[513,20,16,64]"
    copy = " = " + pool + "{3,1,2,0} copy(" + pool + "{0,3,2,1} %param.3)"
    dev = "/device:TPU:0"
    ops = [_op("%copy.12" + copy, 0.0, 0.100),
           _op("%copy.13" + copy, 0.2, 0.100),
           _op("%copy_fusion.4 = " + pool + "{3,2,1,0} fusion(" + pool +
               "{3,2,1,0} %param.1), kind=kLoop", 0.4, 0.150),
           _op("%wrapper.2 = f32[8,64]{1,0} custom-call(f32[8,64]{1,0} "
               '%p), custom_call_target="tpu_custom_call"', 0.02, 0.050)]
    assert ops[0][0] == f"copy copy {pool}"
    trace = trace_reduce.Trace({dev: ops}, {dev: []}, [], 1.0)
    assert trace.busy_s() == pytest.approx(0.350)
    assert op_share.reduce({"pattern": "^copy "}, {}, trace) == \
        pytest.approx(100.0 * 0.200 / 0.350)
    assert op_share.reduce({"pattern": "^all-reduce "}, {}, trace) is None
    assert op_share.reduce({"pattern": "^copy "}, {}, None) is None
    assert op_share.reduce({"pattern": "^copy "}, {},
                           trace_reduce.Trace({}, {}, [], 1.0)) is None


def test_every_per_layer_metric_has_its_reader_and_no_reader_is_left_over():
    bench = harness.load_benchmark()
    names = {m["name"] for m in bench["per_layer"]}
    on_disk = {f[:-len(".json")] for f in
               os.listdir(os.path.join(BENCH, "layer_metrics"))}
    assert names == on_disk
    for name in names:
        spec = harness.load_json("layer_metrics", name + ".json")
        assert callable(harness.by_name("reducers", spec["reducer"]).reduce)
    cells = {c["name"] for c in bench["workloads"]}
    pool = next(m for m in bench["per_layer"]
                if m["name"] == "model.pool_copy_share")
    assert set(pool["workloads"]) < cells
