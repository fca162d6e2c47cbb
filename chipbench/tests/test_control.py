"""The control of "how correct is decided", at a size a test run can hold.

The control is the plain reference put in the program's place and computed
in fp8 (e4m3's four significant bits), the precision below the bf16 that
the configurations state. On the same prompts and tokens (serving), or from
the same seed through the same steps (training), it has to read over the
limit that a sound run of the program stays under. The chip's own readings,
at the cells' sizes, are in ``PERF.md`` section 2; these are the toy size
of ``configs/rehearsal.json`` on the CPU, with the ``rehearsal`` limits.
"""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import run as bench_run  # noqa: E402


def drive(capsys, cell, seed, seconds):
    bench_run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                    str(seconds), "--trace", "0", "--rehearse", "--control"])
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("seed", [32, 33, 34])
def test_serving_control_is_not_correct(capsys, seed):
    r = drive(capsys, "gpt2-large.serve.decode-heavy", seed, 3)
    limit = r["checks"]["served_logit_gap"]["limit"]
    assert r["correct"], r["checks"]
    assert r["notes"]["control_fp8"]["served_logit_gap"] > limit


@pytest.mark.parametrize("seed", [31, 32, 33])
def test_training_control_is_not_correct(capsys, seed):
    r = drive(capsys, "gpt2-large.train.dense-1k", seed, 1)
    limit = r["checks"]["grad_norm_gap"]["limit"]
    assert r["correct"], r["checks"]
    assert r["notes"]["control_fp8"]["grad_norm_gap"] > limit
    fault = r["notes"]["fault_half_batch"]
    assert fault["grad_norm_gap"] > limit
    assert fault["change_norm_gap"] > r["checks"]["change_norm_gap"]["limit"]


def test_trace_reduction_self_check():
    from chipbench import self_check
    assert self_check.main() == 0
