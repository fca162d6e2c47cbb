"""chip_smoke.py, as far as a machine without a TPU can hold it: the
``--tiny`` CPU rehearsal runs every phase of the one-chip path and
ends with the contract's last line; without ``--tiny`` and without a
TPU the script fails and prints no result line."""
import json
import os
import subprocess
import sys

import tpu_platform

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _run(*args, cache_dir):
    env = tpu_platform.cpu_child_env(n_devices=1)
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    return subprocess.run([sys.executable, SCRIPT, *args], env=env,
                          cwd=ROOT, capture_output=True, text=True,
                          timeout=600)


def test_tiny_rehearsal_runs_every_phase(tmp_path):
    proc = _run("--tiny", cache_dir=tmp_path)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.splitlines()
             if l.startswith("{")]
    assert [l["phase"] for l in lines[:-1]] == [
        "device", "serve", "released", "train", "calibrate"]
    # the last line names the device it REALLY ran on
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    assert proc.stdout.rstrip().splitlines()[-1] == json.dumps(lines[-1])
    device, serve, released, train, _ = lines[:-1]
    assert released["live_array_bytes"] < 1024
    assert device["compile_cache_dir"] == str(tmp_path)
    assert serve["steady_state"]["traces"] == 0
    assert serve["tokens_produced"] == 45
    assert serve["contract"]["greedy_agreement"] >= 0.9
    assert train["losses"][2] < train["losses"][0]
    # the cache went where the environment said, and nowhere else
    assert any(tmp_path.iterdir())


def test_without_tiny_and_without_a_tpu_it_fails(tmp_path):
    proc = _run(cache_dir=tmp_path)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout
