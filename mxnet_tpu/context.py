"""Device contexts.

Parity with the reference's Context (include/mxnet/base.h:92 and
python/mxnet/context.py:24-249) mapped onto JAX's device model:

- ``cpu()``   -> a JAX CPU device (host).
- ``tpu(i)``  -> the i-th JAX accelerator device.
- ``gpu(i)``  -> alias of ``tpu(i)``; kept so reference-style scripts
  (`ctx=mx.gpu(0)`) run unchanged on TPU. `num_gpus()` reports the
  accelerator count for the same reason.
- ``cpu_pinned`` / ``cpu_shared`` -> the CPU device. On TPU, host staging
  is managed by PJRT itself (dma-mapped transfer buffers), so pinned
  memory is not a distinct user-visible pool; the spellings are kept for
  API parity.

There is no global device-id namespace like CUDA's: devices are JAX
device objects. A Context is a thin named handle around one.
"""
from __future__ import annotations

import threading
from typing import Optional

import jax


def _accelerator_platform():
    """The accelerator platform name JAX runs on, or None (cpu only).
    Asks JAX once per call (JAX caches its backends); a backend that
    fails to start raises here — it is never reported as a CPU."""
    backend = jax.default_backend()
    return None if backend == "cpu" else backend


class Context:
    """A device context. devtype: 'cpu', 'tpu' ('gpu' is accepted as an
    alias for 'tpu'), 'cpu_pinned', 'cpu_shared'."""

    _default_ctx = threading.local()

    devtype2str = {1: "cpu", 2: "tpu", 3: "cpu_pinned", 5: "cpu_shared"}
    devstr2type = {"cpu": 1, "tpu": 2, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5}

    def __init__(self, device_type: str, device_id: int = 0):
        if device_type not in self.devstr2type:
            raise ValueError(f"unknown device type {device_type!r}")
        if device_type == "gpu":
            device_type = "tpu"
        self.device_typeid = self.devstr2type[device_type]
        self.device_id = device_id
        self._old_ctx: Optional["Context"] = None

    # -- identity ---------------------------------------------------------
    @property
    def device_type(self) -> str:
        return self.devtype2str[self.device_typeid]

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    __str__ = __repr__

    # -- jax mapping ------------------------------------------------------
    @property
    def jax_device(self) -> jax.Device:
        """The concrete jax.Device this context names.

        Contexts name PROCESS-LOCAL devices (the reference's device ids
        are per-worker too) — under multi-process jax, jax.devices()
        lists the whole job's devices, most of them non-addressable."""
        def _local(platform):
            try:
                return jax.local_devices(backend=platform)
            except RuntimeError:
                # backend not initialized/present: fall back to
                # process-local devices of that platform
                return [d for d in jax.local_devices()
                        if d.platform == platform]

        if self.device_typeid == 2:
            plat = _accelerator_platform()
            if plat is None:
                # No accelerator attached (e.g. CPU test meshes): tpu(i)
                # degrades to the i-th host device so code is portable.
                devs = _local("cpu")
            else:
                devs = _local(plat)
        else:
            devs = _local("cpu")
        if self.device_id >= len(devs):
            raise ValueError(
                f"context {self} out of range: only {len(devs)} "
                f"device(s) of that type are visible"
            )
        return devs[self.device_id]

    # -- default-context management (thread-local, parity with reference) -
    @classmethod
    def _current(cls) -> "Context":
        ctx = getattr(cls._default_ctx, "value", None)
        if ctx is None:
            ctx = default_context()
            cls._default_ctx.value = ctx
        return ctx

    def __enter__(self):
        self._old_ctx = getattr(Context._default_ctx, "value", None)
        Context._default_ctx.value = self
        return self

    def __exit__(self, *exc):
        Context._default_ctx.value = self._old_ctx
        return False

    def empty_cache(self):
        """Release cached device memory back to the allocator.

        The reference's GPU pooled storage manager exposes ReleaseAll
        (src/storage/storage.cc); on PJRT the backing allocator (BFC) is
        internal, so this clears JAX's live-executable caches instead.
        """
        jax.clear_caches()

    def memory_info(self):
        """(free_bytes, total_bytes) for this context's device (parity:
        mx.context.gpu_memory_info, python/mxnet/context.py:24-249;
        backed by PJRT memory stats).

        On backends without allocator stats (CPU PJRT), total falls
        back to host memory and free = total - live jax allocations.
        """
        dev = self.jax_device
        stats = None
        try:
            stats = dev.memory_stats()
        except Exception:  # noqa: BLE001 — backend without stats
            stats = None
        if stats:
            total = int(stats.get("bytes_limit",
                                  stats.get("bytes_reservable_limit", 0)))
            in_use = int(stats.get("bytes_in_use", 0))
            if total:
                return (total - in_use, total)
        # host fallback: total from /proc, in-use from live arrays
        try:
            with open("/proc/meminfo") as f:
                total = next(int(l.split()[1]) * 1024 for l in f
                             if l.startswith("MemTotal"))
        except (OSError, StopIteration):
            total = 0
        in_use = sum(b.nbytes for b in jax.live_arrays()
                     if b.device == dev)
        return (max(total - in_use, 0), total)


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Alias of tpu() for source compatibility with reference scripts."""
    return Context("tpu", device_id)


def num_gpus() -> int:
    """Number of accelerator devices visible (parity: mx.context.num_gpus)."""
    plat = _accelerator_platform()
    if plat is None:
        return 0
    return len(jax.devices(plat))


def num_tpus() -> int:
    return num_gpus()


def default_context() -> Context:
    """tpu(0) when an accelerator is attached, else cpu(0)."""
    return tpu(0) if _accelerator_platform() is not None else cpu(0)


def current_context() -> Context:
    return Context._current()


def gpu_memory_info(device_id: int = 0):
    """(free, total) bytes on accelerator `device_id` (parity:
    mx.context.gpu_memory_info — 'gpu' means 'the accelerator')."""
    return tpu(device_id).memory_info()


def tpu_memory_info(device_id: int = 0):
    return tpu(device_id).memory_info()
