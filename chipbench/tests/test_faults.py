"""The rest of a run, driven with the timed path broken underneath:
``correct`` has to come out false, once for each fault a cell can have.

These skip the harness's look for a chip (``--rehearse``: the toy size of
``configs/rehearsal.json`` on the CPU) and break the *program* under the
harness, which runs unchanged: its limits are the ``rehearsal`` ones of
``chipbench/limits/<cell>.json``, set from sound toy runs the same way the
chip's were. A sound run beside each shows that the limits do not fail
everything.

    python -m pytest chipbench/tests -q        (about two minutes on a CPU)
"""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import run as bench_run  # noqa: E402

SERVE = "gpt2-large.serve.decode-heavy"
TRAIN = "gpt2-large.train.dense-1k"


def drive(capsys, cell, seed, seconds):
    bench_run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                    str(seconds), "--trace", "0", "--rehearse"])
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def failed_checks(result):
    return sorted(k for k, c in result["checks"].items() if not c["ok"])


def test_sound_serve_run_is_correct(capsys):
    r = drive(capsys, SERVE, 41, 3)
    assert r["correct"], r["checks"]
    assert r["device"]["platform"] == "cpu"


def test_altered_token_is_not_correct(capsys, monkeypatch):
    """A token altered where it is produced: every fifth token the engine
    emits is replaced by its neighbour in the vocabulary."""
    from mxnet_tpu.serving import generate
    real = generate.GenerationStream._emit_many
    seen = [0]

    def altered(self, tokens):
        out = []
        for t in tokens:
            seen[0] += 1
            out.append((int(t) + 1) % 503 if seen[0] % 5 == 0 else int(t))
        return real(self, out)

    monkeypatch.setattr(generate.GenerationStream, "_emit_many", altered)
    r = drive(capsys, SERVE, 42, 3)
    assert not r["correct"]
    assert "served_logit_gap" in failed_checks(r)


def test_sound_train_run_is_correct(capsys):
    r = drive(capsys, TRAIN, 43, 1)
    assert r["correct"], r["checks"]


def test_unchanged_state_is_not_correct(capsys, monkeypatch):
    """A step that returns its state unchanged: the loss comes back, the
    parameters and the optimizer's state are put back as they were."""
    from chipbench import program
    real = program.build_train_step

    def build(net, train_args):
        step = real(net, train_args)
        call = type(step).__call__

        class Frozen(type(step)):
            def __call__(self, data, label, pad=None):
                before = {n: p.data()._data.copy() for n, p in
                          self.net.collect_params().items()}
                states = None if self._opt_states is None else [
                    tuple(x.copy() for x in s) for s in self._opt_states]
                loss = call(self, data, label, pad)
                for n, p in self.net.collect_params().items():
                    p.data()._data = before[n]
                if states is not None:
                    self._opt_states = states
                else:
                    self._opt_states = [tuple(x * 0 for x in s)
                                        for s in self._opt_states]
                return loss

        step.__class__ = Frozen
        return step

    monkeypatch.setattr(program, "build_train_step", build)
    r = drive(capsys, TRAIN, 44, 1)
    assert not r["correct"]
    bad = failed_checks(r)
    assert "change_norm_gap" in bad and "grad_norm_gap" in bad
    assert r["checks"]["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_is_not_correct(capsys, monkeypatch):
    """Half of the batch left out, the mean taken over the rest."""
    from chipbench import program
    real = program.feed

    def half(tokens):
        return real(tokens[:tokens.shape[0] // 2])

    monkeypatch.setattr(program, "feed", half)
    r = drive(capsys, TRAIN, 45, 1)
    assert not r["correct"]
    assert "grad_norm_gap" in failed_checks(r)
