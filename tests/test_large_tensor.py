"""Large-tensor / int64-index smoke (round-4 VERDICT task #5; model:
/root/reference/tests/nightly/test_large_array.py).

The reference's nightly large-array suite proves ops stay correct when
element counts and flat indices exceed int32 range. Here a >2^31
-element array is exercised end to end in a subprocess running with
MXTPU_ENABLE_X64=1 (int64 arithmetic preserved). Skipped when the host
has <24 GB available — the reference gates these to nightly hosts the
same way.
"""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

INT32_MAX = 2 ** 31 - 1


def _avail_gb():
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable"):
                    return int(line.split()[1]) / 1024 / 1024
    except OSError:
        pass
    return 0.0


SCRIPT = r"""
import numpy as onp
import mxnet_tpu as mx

N = 2 ** 31 + 16                      # element count > int32 range

# sum overflows int32: 2^31+16 ones must count exactly in int64
a = mx.np.ones((N,), dtype="int8")
total = int(a.sum(dtype="int64").item())
assert total == N, total

# argmax at a flat position beyond int32 range
spike = mx.np.concatenate(
    [mx.np.zeros((N - 3,), dtype="int8"),
     mx.np.array([0, 7, 0], dtype="int8")])
pos = int(spike.argmax().item())
assert pos == N - 2, pos

# slicing at a >int32 offset reads the right elements
tail = spike[N - 4:].asnumpy()
assert tail.tolist() == [0, 0, 7, 0], tail.tolist()

# take with an int64 index beyond int32 range
idx = mx.np.array([N - 2, 0], dtype="int64")
vals = mx.np.take(spike, idx).asnumpy()
assert vals.tolist() == [7, 0], vals.tolist()

# 2-d shape whose SIZE exceeds int32 (dims individually small)
big2d = mx.np.zeros((2 ** 16, 2 ** 15 + 1), dtype="int8")
assert big2d.size == 2 ** 31 + 2 ** 16
assert int(big2d.shape[0]) * int(big2d.shape[1]) == big2d.size

print("large-tensor OK")
"""


@pytest.mark.skipif(_avail_gb() < 24,
                    reason="needs >=24 GB available host memory")
def test_large_tensor_int64_smoke():
    env = dict(os.environ)
    env["MXTPU_ENABLE_X64"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # 1 device; no virtual-mesh splitting
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout + proc.stderr[-3000:]
    assert "large-tensor OK" in proc.stdout
