"""mxnet_tpu.serving — the inference fast path.

`InferenceEngine` coalesces concurrent single-sample (or small-batch)
requests onto one AOT-warmed CachedOp forward per dispatch — dynamic
micro-batching with bounded queueing delay, admission control, and
graceful shutdown. `GenerationEngine` is its autoregressive sibling:
slot-based continuous batching over one fixed-shape KV-cache decode
step (generate.py); with ``paged=True`` the cache is a PAGED pool
with prefix reuse (shared prompts prefilled once, refcounted,
copy-on-write) and chunked prefill (paging.py owns the host-side
page/prefix bookkeeping); with ``draft_model=`` it decodes
SPECULATIVELY (a small draft proposes k tokens, the target verifies
k+1 positions in one program — greedy output token-identical,
stochastic distribution-preserving), and ``submit(temperature=,
top_k=, top_p=, seed=)`` gives every request its own sampling knobs
and explicit PRNG key. `Router` fronts N engine replicas as ONE
fault-tolerant fleet: join-shortest-queue balancing, per-replica
health/circuit-breaker state, cross-replica retry, per-tenant quotas,
priority load shedding, and rolling zero-downtime weight rollover
(router.py); `FaultInjector` (faults.py) is the deterministic
chaos-injection seam that proves all of it. See docs/SERVING.md for
knobs and operational guidance; what is measured on the chip, and in
which cell, is the table at the top of docs/PERFORMANCE.md.
"""
from .engine import (  # noqa: F401
    InferenceEngine, ServingError, EngineClosedError, QueueFullError,
    RequestTimeoutError, ReplicaFailedError,
)
from .generate import (  # noqa: F401
    GenerationEngine, GenerationStream, GenerationResult,
)
from .faults import FaultInjector, FaultRule, InjectedFault  # noqa: F401
from .router import (  # noqa: F401
    Router, RouterStream, LoadShedError, TenantQuotaError,
    HEALTHY, DEGRADED, DOWN,
)

__all__ = ["InferenceEngine", "ServingError", "EngineClosedError",
           "QueueFullError", "RequestTimeoutError", "ReplicaFailedError",
           "GenerationEngine", "GenerationStream", "GenerationResult",
           "Router", "RouterStream", "LoadShedError", "TenantQuotaError",
           "FaultInjector", "FaultRule", "InjectedFault",
           "HEALTHY", "DEGRADED", "DOWN"]
