"""mxnet_tpu.checkpoint — the resilience subsystem.

Replaces the synchronous Orbax wrapper that used to live in
``parallel/checkpoint.py`` (kept there as a deprecation shim) with a
real checkpoint stack:

- :class:`CheckpointManager` — async per-shard save off the training
  thread (donation-safe snapshot + ``BoundedQueueWorker`` writer),
  atomic commit-via-marker, retention GC, retry-with-backoff, and
  corrupt/partial-checkpoint fallback on restore (manager.py).
- :func:`capture_training_state` / :func:`apply_training_state` —
  full resumable state for Trainer/Estimator/TrainStep: params,
  optimizer tensors AND counters, lr-scheduler position, AMP loss
  scale, data-iterator cursor, explicit RNG keys — a resumed run
  continues bit-identically (state.py).
- :func:`save_training_state` / :func:`restore_training_state` — the
  two-liner most callers want.
- :func:`read_params` — the fast parallel-restore entry point serving
  uses for zero-downtime weight rollover
  (``GenerationEngine.load_weights`` / ``InferenceEngine
  .load_weights``).

See docs/CHECKPOINT.md for the on-disk layout, atomicity and
retention rules, resume semantics, and the serving rollover story.
The stall a save costs a training step is not measured on the chip
(no cell saves: PERF.md section 7).
"""
from __future__ import annotations

from .manager import (  # noqa: F401
    CheckpointCorruptError, CheckpointError, CheckpointManager,
    CheckpointWriteError, MANIFEST_FILE, MARKER_FILE, STEP_PREFIX,
    is_committed, read_checkpoint, read_params, snapshot_tree,
    write_checkpoint,
)
from .state import (  # noqa: F401
    apply_training_state, capture_training_state, swap_param_buffers,
)
from ._fs import LocalFS  # noqa: F401

__all__ = [
    "CheckpointManager", "CheckpointError", "CheckpointCorruptError",
    "CheckpointWriteError", "capture_training_state",
    "apply_training_state", "save_training_state",
    "restore_training_state", "swap_param_buffers", "read_params",
    "read_checkpoint",
    "write_checkpoint", "snapshot_tree", "is_committed", "LocalFS",
]


def save_training_state(target, step, net=None, trainer=None,
                        train_step=None, data_iter=None,
                        include_rng: bool = True, metadata=None,
                        block: bool = False, **manager_kwargs):
    """Capture + save in one call.

    ``target`` is a :class:`CheckpointManager` (reused across steps —
    the async fast path) or a directory string (a throwaway
    synchronous manager is created, committed, and closed). Returns
    the manager so periodic callers can keep it."""
    if isinstance(target, CheckpointManager):
        mgr, own = target, False
    else:
        manager_kwargs.setdefault("async_save", False)
        mgr, own = CheckpointManager(target, **manager_kwargs), True
    tree, meta = capture_training_state(
        net=net, trainer=trainer, train_step=train_step,
        data_iter=data_iter, include_rng=include_rng)
    if metadata:
        meta.update(metadata)
    mgr.save(step, tree, metadata=meta, block=block)
    if own:
        mgr.close()
    return mgr


def restore_training_state(target, net=None, trainer=None,
                           train_step=None, data_iter=None, step=None,
                           strict: bool = True, **manager_kwargs):
    """Restore the latest (or an explicit) committed step into live
    objects -> ``(step, metadata)``. ``target`` as in
    :func:`save_training_state`."""
    if isinstance(target, CheckpointManager):
        mgr = target
    else:
        manager_kwargs.setdefault("async_save", False)
        mgr = CheckpointManager(target, **manager_kwargs)
    step, tree, metadata = mgr.restore(step=step)
    apply_training_state(tree, metadata, net=net, trainer=trainer,
                         train_step=train_step, data_iter=data_iter,
                         strict=strict)
    return step, metadata
