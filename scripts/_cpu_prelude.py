"""Import-first prelude for local (non-TPU) smoke scripts.

Usage: `import _cpu_prelude` BEFORE importing mxnet_tpu. Forces the
host CPU platform with 8 virtual devices, matching tests/conftest.py.
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
