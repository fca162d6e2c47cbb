"""mxnet_tpu — a TPU-native deep learning framework with the
capabilities of Apache MXNet (reference: szha/mxnet).

Compute substrate: JAX/XLA (PJRT) — imperative NDArray ops dispatch
asynchronously through JAX eager; hybridized Gluon blocks compile to
single whole-graph XLA programs; data parallelism rides ICI/DCN via
jax.sharding meshes and XLA collectives. See SURVEY.md at the repo root
for the capability map against the reference.

Typical usage mirrors the reference:

    import mxnet_tpu as mx
    from mxnet_tpu import np, npx, autograd, gluon
"""
from __future__ import annotations

import os as _os

import jax as _jax

# float64/int64 arrays are first-class in the reference, but a
# process-global x64 flag inflates every trace/compile and risks silent
# f64 on TPU hot paths (f64 is emulated there).  x64 is therefore
# opt-in via MXTPU_ENABLE_X64=1; the default keeps JAX's f32 world,
# which matches the reference's creation-op defaults (float32).

if _os.environ.get("MXTPU_ENABLE_X64", "") not in ("", "0"):
    _jax.config.update("jax_enable_x64", True)

from .base import MXNetError, __version__  # noqa: E402,F401
from .context import (  # noqa: E402,F401
    Context, cpu, cpu_pinned, gpu, tpu, num_gpus, num_tpus,
    current_context, default_context, gpu_memory_info,
)
from . import engine  # noqa: E402,F401
from .ndarray.ndarray import NDArray, waitall  # noqa: E402,F401
from . import ndarray  # noqa: E402,F401
from . import ndarray as nd  # noqa: E402,F401
from . import numpy  # noqa: E402,F401
from . import numpy as np  # noqa: E402,F401
from . import numpy_extension  # noqa: E402,F401
from . import numpy_extension as npx  # noqa: E402,F401
from . import autograd  # noqa: E402,F401
from .utils_io import save, load  # noqa: E402,F401
from .base import (  # noqa: E402,F401
    set_np, reset_np, is_np_array, is_np_shape, is_np_default_dtype)

# Subsystem modules land incrementally during the build; import what exists.
import importlib as _importlib

for _mod in ("initializer", "init", "optimizer", "lr_scheduler", "gluon",
             "kvstore", "parallel", "profiler", "runtime", "test_utils",
             "util", "recordio", "image", "io", "amp", "random", "symbol",
             "rtc", "contrib", "library", "visualization", "operator",
             "model", "callback", "name", "attribute", "registry",
             "error", "log", "misc", "dlpack", "executor", "telemetry",
             "tracing", "monitor", "bucketing", "compile_cache",
             "serving", "checkpoint", "resilience"):
    try:
        globals()[_mod] = _importlib.import_module(f".{_mod}", __name__)
    except ModuleNotFoundError as _e:
        if f"mxnet_tpu.{_mod}" not in str(_e):
            raise
del _importlib, _mod

# Persistent XLA compilation cache: adopt JAX_COMPILATION_CACHE_DIR
# (when set) before the first compile so cold starts replay
# yesterday's executables from disk (docs/PERFORMANCE.md).
if "compile_cache" in globals():
    globals()["compile_cache"].configure()

if "attribute" in globals():
    AttrScope = globals()["attribute"].AttrScope

# reference short aliases (python/mxnet/__init__.py:55-95)
if "visualization" in globals():
    viz = globals()["visualization"]
if "random" in globals():
    rnd = globals()["random"]
if "kvstore" in globals():
    kv = globals()["kvstore"]

if "symbol" in globals():
    sym = globals()["symbol"]
