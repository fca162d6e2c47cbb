"""Shared force-CPU helper for driver scripts and tests.

Pins JAX to the host CPU with a chosen number of virtual devices.
``JAX_PLATFORMS`` is read when jax is imported, so a process that may
already have imported it also needs ``jax.config.update`` — before any
backend starts. This is the single home for that;
__graft_entry__.py and tests/conftest.py use it.
"""
from __future__ import annotations

import os
import re


def _with_device_count(flags: str, n_devices: int) -> str:
    """Set (replace, never duplicate) the virtual host-device-count
    flag inside an XLA_FLAGS string."""
    opt = f"--xla_force_host_platform_device_count={n_devices}"
    pat = r"--xla_force_host_platform_device_count=\d+"
    if re.search(pat, flags):
        return re.sub(pat, opt, flags)
    return (flags + " " + opt).strip()


def force_cpu(n_devices: int | None = None) -> None:
    """Pin JAX to host CPU, optionally with n virtual devices.

    Must run before any JAX backend init.  If XLA_FLAGS already forces
    a different virtual device count, it is replaced (not silently
    kept) so callers actually get the count they asked for.
    """
    if n_devices is not None:
        os.environ["XLA_FLAGS"] = _with_device_count(
            os.environ.get("XLA_FLAGS", ""), n_devices)
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")


def cpu_child_env(env=None, n_devices: int | None = None) -> dict:
    """CPU-pinned environment for a SUBPROCESS — the child-process
    counterpart of :func:`force_cpu`, and the one sanctioned way for
    tests/benches to set the virtual device count for a child (an
    ad-hoc ``env["XLA_FLAGS"] += ...`` append silently duplicates the
    flag when the parent already forced a count). Returns a copy."""
    env = dict(os.environ if env is None else env)
    if n_devices is not None:
        env["XLA_FLAGS"] = _with_device_count(
            env.get("XLA_FLAGS", ""), n_devices)
    env["JAX_PLATFORMS"] = "cpu"
    return env
