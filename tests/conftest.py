"""Test configuration.

Tests run on a virtual 8-device CPU mesh: ``JAX_PLATFORMS=cpu`` plus
``--xla_force_host_platform_device_count=8``, both set here before any
backend starts. The chip is exercised by ``chip_smoke.py``, not by
this suite.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import tpu_platform  # noqa: E402

tpu_platform.force_cpu(n_devices=8)

import pytest  # noqa: E402


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'` (ROADMAP.md): long soak/perf tests
    # opt out of the 870s window with this marker
    config.addinivalue_line(
        "markers", "slow: long soak/perf test, excluded from tier-1")
    config.addinivalue_line(
        "markers", "requires_pallas: exercises a Pallas kernel in "
        "interpret mode; auto-skipped on boxes whose jax build cannot "
        "run pallas_call (keeps tier-1 green on minimal CI boxes)")
    config.addinivalue_line(
        "markers", "requires_mesh(n): needs at least n host devices "
        "(the virtual CPU mesh this conftest forces via "
        "tpu_platform.force_cpu / --xla_force_host_platform_device_"
        "count). Auto-skipped when the process sees fewer — e.g. a "
        "box whose XLA_FLAGS were pinned elsewhere.")


_PALLAS_OK = None


def _pallas_supported():
    """Probe interpret-mode pallas_call once per session: some CPU-only
    jax builds ship without a working Pallas lowering, and a marked
    kernel test must skip there instead of failing tier-1."""
    global _PALLAS_OK
    if _PALLAS_OK is None:
        try:
            import jax
            import jax.numpy as jnp
            import jax.experimental.pallas as pl

            def _probe(x_ref, o_ref):
                o_ref[...] = x_ref[...] + 1.0

            out = pl.pallas_call(
                _probe,
                out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
                interpret=True)(jnp.zeros((8, 128), jnp.float32))
            _PALLAS_OK = bool((out == 1.0).all())
        except Exception:  # noqa: BLE001 — any failure means "skip"
            _PALLAS_OK = False
    return _PALLAS_OK


def _device_count():
    import jax
    try:
        return jax.device_count()
    except Exception:
        return 1


def pytest_collection_modifyitems(config, items):
    marked = [it for it in items if "requires_pallas" in it.keywords]
    if marked and not _pallas_supported():
        skip = pytest.mark.skip(
            reason="Pallas interpret mode unavailable on this box")
        for item in marked:
            item.add_marker(skip)
    # requires_mesh(n): mesh tests declare their device floor instead
    # of probing jax.devices() ad hoc (the requires_pallas pattern)
    mesh_marked = [(it, it.get_closest_marker("requires_mesh"))
                   for it in items
                   if it.get_closest_marker("requires_mesh")]
    if mesh_marked:
        have = _device_count()
        for item, mark in mesh_marked:
            need = int(mark.args[0]) if mark.args else 2
            if have < need:
                item.add_marker(pytest.mark.skip(
                    reason=f"needs a {need}-device mesh; this "
                           f"process sees {have} "
                           f"(--xla_force_host_platform_device_count "
                           f"is set before backend init by "
                           f"tests/conftest.py via tpu_platform."
                           f"force_cpu — it cannot change mid-run)"))


@pytest.fixture(scope="session")
def mesh_devices():
    """THE documented way for mesh tests to get their host devices.

    The virtual device count is fixed per process by
    ``--xla_force_host_platform_device_count`` (XLA reads it once at
    backend init), so this conftest sets it up front through
    ``tpu_platform.force_cpu(n_devices=8)`` — a fixture cannot raise
    it later, and tests must NEVER mangle ``XLA_FLAGS`` themselves
    (a late mutation silently does nothing, or worse, leaks into a
    subprocess with a different count). Mesh tests declare their
    floor with ``@pytest.mark.requires_mesh(n)`` (auto-skip below n)
    and take this fixture for the device list."""
    import jax
    return jax.devices()


@pytest.fixture(autouse=True)
def _seed_rng():
    """Deterministic per-test seeding (parity: the reference's seed
    fixture in tests/python/unittest/common.py)."""
    import mxnet_tpu as mx
    mx.np.random.seed(0)
    import numpy as onp
    onp.random.seed(0)
    yield
