"""mxnet_tpu.resilience — self-healing training.

The training-side completion of ROADMAP item 4: PR 6 made training
state capturable and bit-identically resumable
(``mxnet_tpu.checkpoint``); this package makes a long run actually
*finish* through the three real killers — preemption, divergence, and
hangs:

- :class:`TrainSupervisor` — wraps a Trainer/TrainStep step loop with
  SIGTERM/SIGINT flush-on-signal checkpointing, automatic restore +
  bounded restart budget with exponential backoff, divergence rewind
  with poisoned-batch skipping, and per-step hang deadlines
  (supervisor.py).
- :class:`DivergenceWatchdog` / :class:`HangWatchdog` — the detection
  halves: a cheap loss-stream health check (non-finite / spike-vs-EMA,
  AMP overflow-skips excluded) and an async per-step deadline
  (watchdog.py).
- :class:`TrainFaultInjector` — the seeded deterministic chaos seam
  (the ``serving/faults.py`` discipline applied to training):
  crash-at-step-N, SIGKILL, SIGTERM, NaN-batch/NaN-gradient
  injection, slow-step, kill-mid-checkpoint (faults.py).

Telemetry lands under ``resilience.*`` (docs/OBSERVABILITY.md);
tests/test_resilience.py chaos-proves the whole stack;
docs/RESILIENCE.md is the narrative.
"""
from __future__ import annotations

from .faults import (  # noqa: F401
    InjectedTrainingFault, TrainFaultInjector, TrainFaultRule,
)
from .supervisor import TrainingAborted, TrainSupervisor  # noqa: F401
from .watchdog import (  # noqa: F401
    DivergenceError, DivergenceWatchdog, HangWatchdog, StepHangError,
)

__all__ = [
    "TrainSupervisor", "TrainingAborted", "DivergenceWatchdog",
    "HangWatchdog", "DivergenceError", "StepHangError",
    "TrainFaultInjector", "TrainFaultRule", "InjectedTrainingFault",
]
