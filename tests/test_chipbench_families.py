"""The benchmark's model families, in tier 1.

``chipbench/tests/test_families.py`` (PR 26) holds the family interface,
the reducers on a stub family's constant costs and the scan for a model's
names outside its family; tier 1 runs ``tests/`` only, so its cases are
run from here. **Two of them cannot pass once a second family exists**
(they pin the list of modules that import the program to
``families/gpt2/program.py``, and look for GPT-2's ``n_head`` as a
substring, which ``index_n_heads`` contains), and a ``model_config`` PR
edits no file the benchmark has. They fail in ``python -m pytest
chipbench/tests`` on this tree; here they are left out of the cases taken
over and superseded by the two cases marked ``SUPERSEDES`` below, which
say the same for every family by whole words. The ``benchmark`` PR that
edits the two in place deletes the two marked here (``PERF.md`` section 7).
The ``dots3``, ``phi4flash`` and ``xing4`` families are held to the same
interface, and their configurations to the catalog's published keys.
"""
import importlib.util
import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402

BENCH = os.path.join(ROOT, "chipbench")
_spec = importlib.util.spec_from_file_location(
    "chipbench_tests_test_families",
    os.path.join(BENCH, "tests", "test_families.py"))
_cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cases)

# -- the cases of chipbench/tests/test_families.py that hold for any number
# -- of families, run as they stand
stub_family = _cases.stub_family
test_gpt2_family_has_the_documented_interface = \
    _cases.test_gpt2_family_has_the_documented_interface
test_a_family_is_named_or_the_run_ends = \
    _cases.test_a_family_is_named_or_the_run_ends
test_every_configuration_names_a_family_that_is_there = \
    _cases.test_every_configuration_names_a_family_that_is_there
test_mfu_counts_by_the_family_the_facts_name = \
    _cases.test_mfu_counts_by_the_family_the_facts_name
test_kernel_roofline_counts_by_the_family_the_facts_name = \
    _cases.test_kernel_roofline_counts_by_the_family_the_facts_name
test_op_share_on_a_made_up_trace = _cases.test_op_share_on_a_made_up_trace
test_every_per_layer_metric_has_its_reader_and_no_reader_is_left_over = \
    _cases.test_every_per_layer_metric_has_its_reader_and_no_reader_is_left_over

FAMILIES = sorted(
    d for d in os.listdir(os.path.join(BENCH, "families"))
    if os.path.isfile(os.path.join(BENCH, "families", d, "__init__.py")))
#: names that belong to one family's block and program, as whole words
NAMES = {
    "gpt2": ("n_embd", "n_head", "n_positions", "layer_norm_epsilon",
             "GPTModel", "model.gpt.trace", "families.gpt2"),
    # ``kv_lora_rank`` and ``n_routed_experts`` are no one family's since
    # a second one has latent attention and routed experts
    "dots3": ("Dots3Model", "model.dots3.trace", "families.dots3",
              "index_topk", "swa_kv_lora_rank", "router_experts",
              "expert_rank"),
    "phi4flash": ("Phi4FlashModel", "model.phi4flash.trace",
                  "families.phi4flash", "mb_per_layer", "sliding_window",
                  "d_state", "dt_rank", "ssm_scan_call"),
    "xing4": ("Xing4Model", "model.xing4.trace", "families.xing4",
              "hc_mult", "hc_sinkhorn_iters", "mhc_h_res_clamp_min",
              "num_nextn_predict_layers", "rope_scaling"),
}


def test_the_families_are_the_ones_named_here():
    assert FAMILIES == sorted(NAMES)


@pytest.mark.parametrize("family", sorted(NAMES))
@pytest.mark.parametrize("module", sorted(_cases.INTERFACE))
def test_family_has_the_documented_interface(family, module):
    got = harness.family({"family": family}, module)
    for name in _cases.INTERFACE[module]:
        assert hasattr(got, name), (family, module, name)


def _files_outside(family):
    """Code, text and data of ``chipbench/`` that belong to no one family:
    not ``families/<family>/``, not the configurations (each is its
    model's own published group), not a cell's, a mix's or a metric's
    data file that names the family's cell, program or kernel."""
    for d, dirs, files in os.walk(BENCH):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        rel = os.path.relpath(d, BENCH)
        if rel.startswith(os.path.join("families", family)) \
                or rel == "configs":
            continue
        for f in files:
            if f.endswith((".py", ".md")) or (
                    f.endswith(".json")
                    and rel not in ("limits", "traffic", "layer_metrics")):
                yield os.path.join(d, f)


# SUPERSEDES chipbench/tests/test_families.py::
# test_no_gpt2_name_outside_its_family
@pytest.mark.parametrize("family", sorted(NAMES))
def test_no_family_name_outside_its_family(family):
    found = []
    for path in _files_outside(family):
        with open(path) as f:
            text = f.read()
        found += [(os.path.relpath(path, ROOT), n) for n in NAMES[family]
                  if re.search(rf"(?<![\w.]){re.escape(n)}(?![\w])", text)]
    # the cases taken over from PR 26 name GPT-2's keys to look for them
    found = [x for x in found
             if x[0] != os.path.join("chipbench", "tests",
                                     "test_families.py")]
    assert not found, found


# SUPERSEDES chipbench/tests/test_families.py::
# test_only_the_two_program_modules_import_the_program
def test_only_the_program_modules_import_the_program():
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
    assert sorted(importing) == sorted(
        ["program.py"] + [f"families/{f}/program.py" for f in FAMILIES])


# -- the dots3 configuration against the catalog's published keys -----------
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WIDTHS = {
    "hidden_size": 5120, "num_attention_heads": 128,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "swa_num_attention_heads": 64, "swa_qk_nope_head_dim": 192,
    "swa_qk_rope_head_dim": 64, "swa_v_head_dim": 128,
    "q_lora_rank": 1024, "kv_lora_rank": 512, "swa_q_lora_rank": 1024,
    "swa_kv_lora_rank": 1024, "index_n_heads": 64, "index_head_dim": 128,
    "index_topk": 2048, "sliding_window_size": 513,
    "num_experts_per_tok": 8, "moe_intermediate_size": 1536,
    "intermediate_size": 13824,
}


@pytest.fixture(scope="module")
def config():
    return harness.load_json("configs", "dots3-note-prev.json")


def test_dots3_configuration_keeps_every_published_width(config):
    model = config["model"]
    for key, value in WIDTHS.items():
        assert model[key] == value, key
    assert model["router_experts"] == 256
    assert sorted(config["reduced"]) == [
        "layer_types", "n_routed_experts", "num_hidden_layers",
        "vocab_size"]
    assert not set(config["reduced"]) & set(WIDTHS)
    assert config["published"]["n_routed_experts"] == 256
    assert config["published"]["vocab_size"] == 8 * model["vocab_size"]
    assert model["layer_types"] == config["published"]["layer_types"][:5]
    assert "8 chips share each layer" in config["deployment"]


def test_dots3_configuration_states_its_keys_twice_alike(config):
    """The published keys stand twice, for two readers: at the top of
    the file, where the benchmark's contract compares them with the
    catalog's entry (a key left out there is refused before any run),
    and in the ``model`` group, which is all ``generators/closed_loop.py``
    hands the family. One truth."""
    model = config["model"]
    extra = {"router_experts", "expert_rank", "initializer_range"}
    for key, value in model.items():
        if key not in extra:
            assert config[key] == value, key


def test_dots3_configuration_is_the_catalog_entry_but_for_reduced(config):
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    entry = next(r for r in rows if r["name"] == "dots3-note-prev")
    assert config["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value and config["model"][key] == value, key


def test_dots3_parameter_count_is_the_share_the_configuration_states(config):
    weights = harness.family(config, "weights")
    s = weights.sizes(config["model"])
    n = weights.parameter_count(s)
    assert 4.08e9 < n < 4.10e9
    # bytes held by the program: two a parameter, and two more for the
    # leaves it keeps in float32 (the router, the indexer's branch)
    wide = sum(
        int(np.prod(shape)) for i in range(s["L"])
        for name, shape, _ in weights.layer_leaves(s, i)
        if weights.float32_in_program(s, i, name))
    assert (2 * n + 2 * wide) / (2 * n) < 1.01


# -- the phi4flash configuration: the catalog's row, nothing cut -------------
PHI = "phi4-mini-flash-reasoning"


@pytest.fixture(scope="module")
def phi_config():
    return harness.load_json("configs", PHI + ".json")


def test_phi4flash_configuration_is_the_catalog_entry_key_for_key(
        phi_config):
    assert phi_config["reduced"] == [] and phi_config["published"] == {}
    assert "one chip, one replica" in phi_config["deployment"]
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    entry = next(r for r in rows if r["name"] == "Phi-4-mini-flash-reasoning")
    assert phi_config["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        assert phi_config[key] == value, key
        assert phi_config["model"][key] == value, key
    bench = harness.load_benchmark()
    listed = next(c for c in bench["configs"] if c["name"] == PHI)
    assert listed["reduced"] == [] and listed["source"] == entry["source_url"]


def test_phi4flash_configuration_states_its_keys_twice_alike(phi_config):
    """As for ``dots3``: the published keys at the top for the contract,
    and in the ``model`` group for the family; beside them in the group
    only what ``assumed`` accounts for."""
    model = phi_config["model"]
    extra = {"d_state", "d_conv", "expand", "dt_rank", "initializer_range",
             "prefill_chunk"}
    for key, value in model.items():
        if key not in extra:
            assert phi_config[key] == value, key
    assert extra <= set(model)
    assert model["dt_rank"] == -(-model["hidden_size"] // 16)
    # the ring is built for the chunk the engine is given
    assert model["prefill_chunk"] == phi_config["serve"]["prefill_chunk"]
    assert phi_config["serve"]["prefix_cache"] is False
    for entry in phi_config["assumed"].values():
        assert len(entry) > 20


def test_phi4flash_parameter_count_is_the_whole_model(phi_config):
    weights = harness.family(phi_config, "weights")
    s = weights.sizes(phi_config["model"])
    n = weights.parameter_count(s)
    assert abs(n - 3.85e9) < 0.01 * 3.85e9
    kinds = [weights.kind(s, i) for i in range(s["L"])]
    assert [kinds.count(k) for k in ("ssm", "swa", "full", "gmu",
                                     "cross")] == [9, 8, 1, 7, 7]
    assert kinds[16] == "ssm" and kinds[17] == "full"
    # bytes held by the program: two a parameter, and two more for the
    # leaves it keeps in float32 (A_log, D, b_dt, the lam vectors)
    wide = sum(
        int(np.prod(shape)) for i in range(s["L"])
        for name, shape, _ in weights.layer_leaves(s, i)
        if name in weights.FLOAT32_IN_PROGRAM)
    assert (2 * n + 2 * wide) / (2 * n) < 1.001


def test_phi4flash_cell_reads_its_own_programs_and_kernel():
    bench = harness.load_benchmark()
    cell = PHI + ".serve.longgen"
    mine = {m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [cell]}
    assert mine == {"model.phi4flash_decode_device_ms", "model.ssm_share"}
    # the chunk programs' device time and the scan kernel's roofline are
    # not metrics of the cell: its traced 5 s hold ticks alone (PERF.md
    # section 7 has the two files a benchmark PR would add); the
    # kernel's cost function is there for them, under the kernel's name
    costs = harness.family({"family": "phi4flash"}, "costs")
    assert callable(costs.ssm_scan_call)
    from mxnet_tpu.ops import ssm
    share = harness.load_json("layer_metrics", "model.ssm_share.json")
    assert ssm.KERNEL_NAME in share["args"]["pattern"]
    tick = harness.load_json(
        "layer_metrics", "model.phi4flash_decode_device_ms.json")
    rx = re.compile(tick["args"]["module"])
    assert rx.search("jit_phi4flash_paged_decode(6)") \
        and not rx.search("jit_phi4flash_paged_chunk(123)") \
        and not rx.search("jit_phi4flash_paged_chunk_last(45)")


# -- the xing4 configuration: the catalog's row, one pipeline stage -----------
XING = "xing4-29b-a4b"
XING_CELL = XING + ".serve.longctx-decode"


@pytest.fixture(scope="module")
def xing_config():
    return harness.load_json("configs", XING + ".json")


def test_xing4_configuration_is_the_catalog_entry_but_for_reduced(
        xing_config):
    """Every width, all 64 experts, both dense layers and the whole
    vocabulary as published: only the depth and the next-token module
    are cut, and both stand in ``reduced`` with the published values."""
    assert sorted(xing_config["reduced"]) == [
        "num_hidden_layers", "num_nextn_predict_layers"]
    assert xing_config["published"] == {"num_hidden_layers": 40,
                                        "num_nextn_predict_layers": 1}
    assert xing_config["num_hidden_layers"] == 6
    assert xing_config["num_nextn_predict_layers"] == 0
    assert "one pipeline stage on one chip" in xing_config["deployment"]
    assert xing_config["ep_size"] == 1
    bench = harness.load_benchmark()
    listed = next(c for c in bench["configs"] if c["name"] == XING)
    assert sorted(listed["reduced"]) == sorted(xing_config["reduced"])
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    entry = next(r for r in rows if r["name"] == "Xing4.0-29B-A4B")
    assert xing_config["source"] == entry["source_url"] == listed["source"]
    for key, value in entry["config"].items():
        if key in xing_config["reduced"]:
            assert xing_config["published"][key] == value, key
        else:
            assert xing_config[key] == value, key
            assert xing_config["model"][key] == value, key


def test_xing4_configuration_states_its_keys_twice_alike(xing_config):
    model = xing_config["model"]
    for key, value in model.items():
        if key != "initializer_range":
            assert xing_config[key] == value, key
    assert model["initializer_range"] == 0.006
    serve = xing_config["serve"]
    assert serve["prefix_cache"] is False and serve["max_slots"] == 32
    # the cache holds the mix's longest prompt and its longest answer
    mix = harness.load_json("traffic", "closed32-longctx-reasoning.json")
    assert serve["max_length"] == mix["prompt_tokens"]["max"] \
        + mix["new_tokens"]["max"] == 9216
    for entry in xing_config["assumed"].values():
        assert len(entry) > 20
    assert len(xing_config["precision"]) > 20


def test_xing4_parameter_count_is_the_stage_the_configuration_states(
        xing_config):
    """ISSUE 33's count: attention 28.41 M a layer, a dense layer 127.5 M
    and 0.69 M of hyper-connections, a routed layer 745.0 M, embedding
    and head 939.5 M: 4.176 B, 8.35 GB in bfloat16."""
    weights = harness.family(xing_config, "weights")
    s = weights.sizes(xing_config["model"])
    by_layer = [sum(int(np.prod(sh)) for _, sh, _ in
                    weights.layer_leaves(s, i)) for i in range(s["L"])]
    hc = 2 * (24 * 4 * 3584 + 3 + 24)
    assert by_layer[0] == by_layer[1] and len(set(by_layer[2:])) == 1
    assert abs(by_layer[0] - hc - 127.5e6) < 0.05e6
    assert abs(by_layer[2] - 745.0e6) < 0.05e6
    n = weights.parameter_count(s)
    assert n == sum(by_layer) + 2 * 131072 * 3584 + 3584
    assert abs(n - 4.176e9) < 0.001e9
    # bytes held by the program: two a parameter, and two more for the
    # leaves it keeps in float32 (the maps' phi, alpha, b; the router)
    wide = sum(
        int(np.prod(shape)) for i in range(s["L"])
        for name, shape, _ in weights.layer_leaves(s, i)
        if name in weights.FLOAT32_IN_PROGRAM)
    assert (2 * n + 2 * wide) / (2 * n) < 1.002


def test_the_new_cells_are_the_traffic_the_issue_gave():
    bench = harness.load_benchmark()
    cells = {c["name"]: c for c in bench["workloads"]}
    assert cells[XING_CELL]["traffic"] == "closed32-longctx-reasoning"
    mix = harness.load_json("traffic", "closed32-longctx-reasoning.json")
    assert (mix["kind"], mix["callers"], mix["distinct_sizes"],
            mix["check_requests"], mix["trace_seconds"]) == (
        "closed_loop", 32, 48, 6, 5)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 3072,
                                    "sigma": 0.6, "min": 1024, "max": 8192}
    assert mix["new_tokens"] == {"dist": "lognormal", "median": 640,
                                 "sigma": 0.35, "min": 256, "max": 1024}
    others = [harness.load_json("traffic", f)["pairing_seed"]
              for f in os.listdir(os.path.join(BENCH, "traffic"))
              if f != "closed32-longctx-reasoning.json"
              and "pairing_seed" in harness.load_json("traffic", f)]
    assert mix["pairing_seed"] not in others
    queued = cells["gpt2-medium.serve.decode-heavy"]
    assert (queued["config"], queued["traffic"], queued["chips"]) == (
        "gpt2-medium", "closed8-chat", 1)
    for cell in (XING_CELL, "gpt2-medium.serve.decode-heavy"):
        judged = {m["name"] for m in bench["end_to_end"]
                  if harness.applies(m, cell)}
        assert judged == {"serve_tokens_per_s", "ttft_mean_ms", "setup_s"}
        limits = harness.load_json("limits", cell + ".json")
        assert 0 < limits["limits"]["served_logit_gap"] < 1


def test_xing4_cell_reads_its_own_programs_and_the_shared_kernel():
    bench = harness.load_benchmark()
    mine = {m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [XING_CELL]}
    assert {"model.xing4_decode_device_ms", "model.mhc_share"} <= mine \
        <= {"model.xing4_decode_device_ms", "model.xing4_chunk_device_ms",
            "model.mhc_share"}
    shared = {m["name"] for m in bench["per_layer"]
              if XING_CELL in m.get("workloads", ()) and m["name"]
              not in mine}
    assert {"model.moe_share", "model.pool_copy_share",
            "sched.itl_p50_ms.prefill-heavy",
            "sched.itl_p95_ms.prefill-heavy"} <= shared
    from mxnet_tpu.ops import moe
    share = harness.load_json("layer_metrics", "model.moe_share.json")
    assert moe.KERNEL_NAME in share["args"]["pattern"]
    tick = harness.load_json("layer_metrics",
                             "model.xing4_decode_device_ms.json")
    rx = re.compile(tick["args"]["module"])
    assert rx.search("jit_xing4_paged_decode(6)") \
        and not rx.search("jit_xing4_paged_chunk(123)") \
        and not rx.search("jit_dots3_paged_decode(45)")
