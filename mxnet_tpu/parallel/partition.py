"""SPMD sharding layer — logical-axis partitioning over the device mesh.

The T5X-style ``Partitioner``: parameters carry
NAMED LOGICAL AXES (``"embed"``, ``"mlp"``, ``"heads"``, ``"kv"``,
``"vocab"``, ``"batch"``), an ORDERED rule list maps each logical axis
to a mesh axis (or to ``None`` = replicated), and every parameter
resolves to a per-leaf ``PartitionSpec`` / ``NamedSharding`` over the
process mesh. Everything upstream (``TrainStep``, the serving
engines, the checkpoint restore path) consumes the resolved specs —
the rules are the ONE place a layout is described.

Resolution semantics (per parameter, dims in order):

- the FIRST rule whose logical axis matches the dim wins;
- a mesh axis may be used at most ONCE per parameter (you cannot
  shard two dims of one array over the same devices);
- a mesh axis that does not DIVIDE the dim size falls through to the
  next matching rule, and ultimately to replication — with a one-shot
  warning, because a silently-replicated "sharded" layout is how a
  model quietly stops fitting;
- a dim with no logical name, or no matching rule, stays replicated.

Built-in layouts:

- ``"dp"`` — pure data parallel (every param replicated; batch over
  ``dp``). The pre-partitioner behavior, kept as the explicit
  baseline.
- ``"tp"`` — tensor parallel: attention q/k/v/out sharded over ``tp``
  by heads, ffn1/ffn2 over ``tp`` by the mlp dim, embeddings and
  lm_head over the vocab dim; activations replicated within a TP
  group. One model spread across the mesh — the multi-device serving
  layout.
- ``"fsdp"`` — fully-sharded data parallel (ZeRO-3 style): every
  parameter AND its optimizer state sharded over ``dp`` along its
  first shardable dim; inside the compiled step XLA all-gathers each
  layer's weights right before use (the gathers overlap compute under
  the latency-hiding scheduler) and reduces gradients straight into
  the owning shard — reduce-scatter semantics, ``(N-1)/N`` of the
  bytes per direction of the full allreduce the ``"dp"`` layout pays
  (see ``kvstore.collective_wire_bytes`` for the byte model).
- ``"tp_fsdp"`` — the 2-D composition over a ``(dp, tp)`` mesh:
  every parameter (and its optimizer state) shards over BOTH axes —
  the tp-sharded dim (heads/mlp/vocab) over ``tp`` and the embed dim
  over ``dp`` — so per-device param+optimizer bytes shrink by the
  whole mesh size, strictly below either 1-D layout. Compute keeps
  the fsdp (ZeRO) discipline: the step all-gathers each weight
  before use and the gradient reduce-scatters back into the owning
  shard over the fsdp axis / all-reduces over the tp axis
  (``gather_compute`` — ``TrainStep`` pins the in-step weight AND
  gradient placements so the math is the dense program's, which is
  what makes tp_fsdp losses BITWISE equal to dp on a deterministic
  backend).

Per-device footprint is MEASURED, not modeled: ``per_device_bytes``
walks real ``jax.Array`` shards, so "this model's param+optimizer
footprint exceeds one device's share" is checked against what the
runtime actually placed (tests/test_partition.py).
"""
from __future__ import annotations

import contextlib
import re
import warnings
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .. import telemetry

P = PartitionSpec

__all__ = [
    "LOGICAL_AXES", "LAYOUTS", "Partitioner", "current_layout",
    "set_layout", "layout_scope", "grad_sync_bytes",
    "per_device_bytes", "hlo_collectives",
]

#: the logical-axis vocabulary (gpt.py annotates its parameters with
#: these; "kv" is the per-head feature dim — replicated in both
#: built-in layouts, named so a future head-dim layout is one rule)
LOGICAL_AXES = ("embed", "mlp", "heads", "kv", "vocab", "batch")

#: tensor parallel: weights split across 'tp' by heads / mlp / vocab,
#: activations (the "embed" residual stream) replicated within the TP
#: group, batch over 'dp'
TP_RULES = (
    ("heads", "tp"),
    ("mlp", "tp"),
    ("vocab", "tp"),
    ("kv", None),
    ("embed", None),
    ("batch", "dp"),
)

#: fully-sharded data parallel: every parameter sharded over 'dp'
#: along its first shardable dim (ordering puts the big dims first so
#: q/k/v shard by heads, ffn1 by mlp, embeddings by vocab; out_proj/
#: ffn2 fall through to their "embed" dim). Optimizer state follows
#: the weight sharding (TrainStep maps same-shape state leaves to the
#: weight's spec).
FSDP_RULES = (
    ("vocab", "dp"),
    ("heads", "dp"),
    ("mlp", "dp"),
    ("embed", "dp"),
    ("kv", None),
    ("batch", "dp"),
)

#: pure data parallel — the explicit baseline: no parameter sharding
DP_RULES = (
    ("batch", "dp"),
)

#: 2-D tp×fsdp: the big projection dim over 'tp', the embed dim over
#: 'dp' — a 2-D param shards over the WHOLE mesh (ordered first-match
#: per dim, each mesh axis used once per param). Storage-only layout:
#: TrainStep's gather_compute path all-gathers weights in-step and
#: reduce-scatters grads back, so the math stays the dense program's.
TP_FSDP_RULES = (
    ("heads", "tp"),
    ("mlp", "tp"),
    ("vocab", "tp"),
    ("embed", "dp"),
    ("kv", None),
    ("batch", "dp"),
)

LAYOUTS = {"dp": DP_RULES, "tp": TP_RULES, "fsdp": FSDP_RULES,
           "tp_fsdp": TP_FSDP_RULES}

#: layouts whose in-step COMPUTE must run on the gathered (replicated)
#: weights and gradients — the ZeRO discipline made explicit. 1-D fsdp
#: gets there through GSPMD's own propagation (PR 12's committed
#: bitwise result); the 2-D layout must pin it, because the 2-D output
#: shardings otherwise back-propagate tp splits into the backward
#: contractions and the partial-sum order drifts a ulp per step.
_GATHER_COMPUTE_LAYOUTS = ("tp_fsdp",)


def _axis_size(mesh: Mesh, axis) -> int:
    return int(mesh.shape.get(axis, 1)) if axis is not None else 1


class Partitioner:
    """Resolve named logical axes to mesh placements.

    Parameters
    ----------
    layout : str or sequence
        ``"dp"`` / ``"tp"`` / ``"fsdp"``, or an explicit ordered rule
        list ``[(logical_axis, mesh_axis_or_None), ...]``.
    mesh : jax.sharding.Mesh, optional
        Defaults to the process-global ``parallel.get_mesh()`` at
        resolution time.
    batch_axis : str
        Mesh axis the data batch is sharded over (default: whatever
        the ``"batch"`` rule names, falling back to ``"dp"``).
    """

    def __init__(self, layout="dp", mesh: Optional[Mesh] = None,
                 batch_axis=None):
        if isinstance(layout, str):
            if layout not in LAYOUTS:
                raise ValueError(
                    f"unknown layout {layout!r} (choose from "
                    f"{sorted(LAYOUTS)} or pass an explicit rule list)")
            self.layout = layout
            rules = LAYOUTS[layout]
        else:
            self.layout = "custom"
            rules = tuple(layout)
        for r in rules:
            if (not isinstance(r, (tuple, list)) or len(r) != 2
                    or not isinstance(r[0], str)):
                raise ValueError(
                    f"malformed rule {r!r}: want (logical_axis, "
                    f"mesh_axis_or_None)")
        self.rules = tuple((str(l), a) for l, a in rules)
        self._explicit_mesh = mesh
        if batch_axis is None:
            batch_axis = next((a for l, a in self.rules
                               if l == "batch" and a is not None), "dp")
        self.batch_axis = batch_axis
        self._warned = set()

    # -- mesh ----------------------------------------------------------
    @property
    def mesh(self) -> Mesh:
        from . import get_mesh
        mesh = self._explicit_mesh or get_mesh()
        if mesh is None:
            raise RuntimeError(
                "Partitioner needs a mesh: pass mesh= or call "
                "parallel.set_mesh() first")
        return mesh

    # -- resolution ----------------------------------------------------
    def spec_for(self, logical_axes, shape, name="<param>") -> PartitionSpec:
        """Resolve one array's logical axes to a ``PartitionSpec``.

        ``logical_axes`` is a tuple of logical names (or ``None``) per
        dim; ``None``/unmatched dims stay replicated. First matching
        rule wins per dim; each mesh axis is used at most once per
        array; a non-dividing mesh axis falls through to the next
        matching rule and finally to replication (one-shot warning)."""
        if logical_axes is None:
            return P()
        mesh = self.mesh
        logical_axes = tuple(logical_axes)
        if len(logical_axes) != len(shape):
            raise ValueError(
                f"{name}: logical axes {logical_axes} do not match "
                f"shape {tuple(shape)}")
        used = set()
        entries = []
        for d, (lax_name, dim) in enumerate(zip(logical_axes, shape)):
            pick = None
            if lax_name is not None:
                for rule_axis, mesh_axis in self.rules:
                    if rule_axis != lax_name or mesh_axis is None:
                        continue
                    if mesh_axis in used:
                        continue
                    n = _axis_size(mesh, mesh_axis)
                    if n <= 1:
                        continue
                    if int(dim) % n != 0:
                        # warn ONCE per (logical axis, mesh axis) pair
                        # — a model with 50 odd-sized heads params
                        # must not emit 50 copies of the same fact
                        # (the first offender is named in the message)
                        key = (lax_name, mesh_axis)
                        if key not in self._warned:
                            self._warned.add(key)
                            warnings.warn(
                                f"partition: {name} dim {d} "
                                f"({lax_name}={dim}) is not divisible "
                                f"by mesh axis {mesh_axis!r} "
                                f"(size {n}); falling back to "
                                f"replication for this dim (warned "
                                f"once per ({lax_name!r}, "
                                f"{mesh_axis!r}) pair)")
                        continue
                    pick = mesh_axis
                    break
            if pick is not None:
                used.add(pick)
            entries.append(pick)
        while entries and entries[-1] is None:
            entries.pop()
        return P(*entries)

    def sharding(self, spec: PartitionSpec) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def batch_spec(self, ndim: int, axis: int = 0) -> PartitionSpec:
        entries = [None] * ndim
        entries[axis] = self.batch_axis
        return P(*entries)

    # -- parameter annotation ------------------------------------------
    def annotate(self, params, override_rules=None):
        """Resolve and record each parameter's spec (``p.sharding``).

        ``params`` is a ``{name: Parameter}`` dict (``collect_params``
        output). A parameter's logical axes come from its
        ``logical_axes`` attribute (gpt.py sets them); parameters
        without metadata stay replicated. ``override_rules`` is the
        ``TrainStep(param_rules=)`` escape hatch — a list of
        ``(compiled_regex_or_pattern, PartitionSpec)`` whose first
        match wins over the logical-axis resolution for that
        parameter. Returns ``{name: PartitionSpec}``."""
        compiled = []
        for pat, spec in (override_rules or []):
            if isinstance(pat, str):
                pat = re.compile(pat)
            compiled.append((pat, spec))
        out = {}
        n_sharded = 0
        for name, p in params.items():
            spec = None
            for pat, s in compiled:
                if pat.search(name):
                    spec = s
                    break
            if spec is None:
                # prefer the MATERIALIZED shape: a deferred Parameter's
                # declared shape may carry unknown (-1/0) dims, which
                # must not pretend to divide a mesh axis
                if p._data is not None:
                    shape = tuple(p._data.shape)
                else:
                    shape = getattr(p, "shape", None)
                if shape is None or any(int(d) <= 0 for d in shape):
                    spec = P()
                else:
                    spec = self.spec_for(
                        getattr(p, "logical_axes", None), shape, name)
            p.sharding = spec
            out[name] = spec
            if any(e is not None for e in spec):
                n_sharded += 1
        telemetry.gauge("parallel.partition.params_sharded", n_sharded)
        return out

    def place(self, params, override_rules=None):
        """Annotate AND move each materialized parameter onto its
        resolved ``NamedSharding`` (replicated params land replicated
        over the mesh). Records the measured per-device parameter
        bytes. Returns the spec dict."""
        specs = self.annotate(params, override_rules=override_rules)
        mesh = self.mesh
        for name, p in params.items():
            if p._data is None:
                continue
            sh = NamedSharding(mesh, specs[name])
            d = p._data._data
            if not (isinstance(d, jax.Array)
                    and getattr(d, "sharding", None) == sh):
                p._data._install(jax.device_put(d, sh))
        telemetry.gauge(
            "parallel.partition.bytes_per_device",
            per_device_bytes([p._data._data for p in params.values()
                              if p._data is not None]))
        return specs

    # -- KV-cache placement (serving TP) -------------------------------
    def cache_spec(self, shape, num_heads, heads_dim=1) -> PartitionSpec:
        """Spec for one KV-cache leaf: shard dimension ``heads_dim``,
        the one the heads lie along, over the axis the ``"heads"``
        rule names. That is dimension 1 of a dense cache ``(B, H, S,
        Dh)`` and of a scale table ``(B|n_pages, H)``, and the LAST of
        a paged pool ``(n_pages, ps, H * Dh)``, whose rows hold the
        heads one after another, so an even split gives each device
        a contiguous block of ``H / tp`` whole heads. The caller says
        which (``cache_shardings`` knows a paged cache by its pytree):
        a pool's dimension 1 is its page size, which may well equal
        ``num_heads``."""
        tp_axis = next((a for l, a in self.rules
                        if l == "heads" and a is not None), None)
        if tp_axis is None or _axis_size(self.mesh, tp_axis) <= 1:
            return P()
        if len(shape) >= 2 and int(shape[heads_dim]) % int(num_heads) == 0 \
                and int(num_heads) % _axis_size(self.mesh, tp_axis) == 0:
            entries = [None] * len(shape)
            entries[heads_dim] = tp_axis
            return P(*entries)
        return P()

    #: cache-pytree keys whose leaves shard by heads (dense caches,
    #: paged pools, and their int8 scale tables). The page TABLE and
    #: the ``len`` vector are host-logic state and stay replicated
    #: even when their shapes coincide with a heads dim (a (B, P_max)
    #: table with P_max == num_heads must never shard).
    _CACHE_SHARDED_KEYS = frozenset(("k", "v", "k_scale", "v_scale"))

    def cache_shardings(self, cache, num_heads):
        """Pytree of ``NamedSharding``s matching a generation-cache
        pytree (``init_cache``/``init_paged_cache`` layout): K/V
        buffers (and their int8 scale tables) shard over the heads
        axis; the page table and lengths replicate — keyed by the
        pytree path, not by shape coincidence. A ``table`` key marks
        a PAGED cache, whose ``k``/``v`` pools carry their heads in
        the last dimension."""
        mesh = self.mesh
        rep = NamedSharding(mesh, P())
        paged = "table" in cache

        def leaf_sh(path, leaf):
            keys = {getattr(p, "key", None) for p in path}
            if keys & self._CACHE_SHARDED_KEYS:
                pool = paged and bool(keys & {"k", "v"})
                return NamedSharding(mesh, self.cache_spec(
                    tuple(leaf.shape), num_heads,
                    heads_dim=-1 if pool else 1))
            return rep

        return jax.tree_util.tree_map_with_path(leaf_sh, cache)

    def place_cache(self, cache, num_heads):
        """Commit a cache pytree onto the mesh with the heads axis
        sharded (the serving-TP analog of ``GenerationEngine._commit``
        — the explicit target keeps the arrays COMMITTED, which the
        pjit executable cache keys on)."""
        return jax.device_put(cache,
                              self.cache_shardings(cache, num_heads))

    # -- in-step compute discipline ------------------------------------
    @property
    def gather_compute(self) -> bool:
        """True when the layout's in-step compute must run on the
        GATHERED weights and gradients (``TrainStep`` pins replicated
        in-step placements): the 2-D ``tp_fsdp`` layout, whose 2-D
        output shardings would otherwise back-propagate tp splits
        into the backward contractions and drift the losses a ulp
        per step away from dp."""
        return self.layout in _GATHER_COMPUTE_LAYOUTS

    # -- grad-sync selection -------------------------------------------
    @property
    def grad_collective(self) -> str:
        """``"reduce_scatter"`` when this layout shards parameters (and
        therefore optimizer state) over the batch/dp axis — the
        gradient can be reduced straight into the owning shard and the
        updated shard all-gathered, ``(N-1)/N`` of the bytes per
        direction of a full allreduce. ``"allreduce"`` otherwise."""
        for rule_axis, mesh_axis in self.rules:
            if rule_axis == "batch":
                continue
            if mesh_axis is not None and mesh_axis == self.batch_axis:
                return "reduce_scatter"
        return "allreduce"

    # -- comm accounting -----------------------------------------------
    def comm_bytes_per_step(self, specs, params) -> int:
        """Analytic per-step gradient-sync wire bytes for this layout
        (see :func:`grad_sync_bytes`)."""
        return grad_sync_bytes(specs, params, self.mesh,
                               self.batch_axis)


def grad_sync_bytes(specs, params, mesh: Mesh, batch_axis="dp") -> int:
    """Per-step gradient-sync wire bytes for a resolved layout, under
    the byte model ``kvstore.collective_wire_bytes`` documents (full
    bytes per direction for allreduce; ``(N-1)/N`` per direction for
    reduce-scatter + all-gather — the fsdp path). ``specs`` maps
    param name -> resolved ``PartitionSpec``; ``params`` maps name ->
    Parameter (only ``grad_req != "null"`` params sync). A param
    sharded over the batch axis syncs by reduce-scatter + all-gather
    (its optimizer state lives sharded); everything else (replicated
    or tp-sharded) syncs its grad by allreduce over the batch axis."""
    from .. import kvstore as _kv
    n_dp = _axis_size(mesh, batch_axis)
    total = 0
    for name, p in params.items():
        if p.grad_req == "null" or p._data is None:
            continue
        nbytes = int(p._data._data.nbytes)
        spec = specs.get(name) or P()
        flat = [a for e in spec if e is not None
                for a in (e if isinstance(e, (tuple, list)) else (e,))]
        if batch_axis in flat:
            # 2-D layouts: a param ALSO sharded over a non-batch axis
            # (tp) reduce-scatters only its tp-shard's bytes over the
            # fsdp axis — each tp group syncs 1/tp of the payload —
            # but the in-step REGATHER (the ZeRO gather-compute
            # discipline: the weight must be replicated before use)
            # then also all-gathers the full payload over each
            # non-batch axis. Net effect at 2x2: tp_fsdp wire bytes
            # per param equal fsdp's — ZeRO comm is ~independent of
            # the sharding factor; the 2-D win is MEMORY, and the
            # model must not invent a comm saving that the executed
            # HLO (more all-gathers, not fewer) does not show.
            shard = nbytes
            for e in flat:
                if e != batch_axis:
                    shard //= max(_axis_size(mesh, e), 1)
            total += _kv.collective_wire_bytes(
                "reduce_scatter", shard, n_dp)
            total += _kv.collective_wire_bytes(
                "all_gather", shard, n_dp)
            for e in flat:
                if e != batch_axis:
                    total += _kv.collective_wire_bytes(
                        "all_gather", nbytes, _axis_size(mesh, e))
        elif n_dp > 1:
            shard = nbytes
            for e in flat:
                shard //= max(_axis_size(mesh, e), 1)
            total += _kv.collective_wire_bytes("allreduce", shard, n_dp)
    return total


# ---------------------------------------------------------------------------
# process-global active layout (grad_fusion consults it per bucket)
# ---------------------------------------------------------------------------
_current: Optional[Partitioner] = None


def current_layout() -> Optional[Partitioner]:
    """The process-global active layout, or None (pure DP)."""
    return _current


def set_layout(part: Optional[Partitioner]):
    global _current
    _current = part
    return part


@contextlib.contextmanager
def layout_scope(part: Optional[Partitioner]):
    global _current
    prev = _current
    _current = part
    try:
        yield part
    finally:
        _current = prev


# ---------------------------------------------------------------------------
# measurement helpers
# ---------------------------------------------------------------------------

def per_device_bytes(leaves, device=None) -> int:
    """MEASURED bytes one device holds for ``leaves`` (arrays or
    pytrees of arrays): walks each ``jax.Array``'s addressable shards
    and sums the ones on ``device`` (default: the first device of the
    first sharded leaf; single-device arrays count in full). This is
    what the "fits one device's share of HBM" bench gate reads."""
    flat = []
    for leaf in leaves:
        flat.extend(x for x in jax.tree.leaves(leaf)
                    if hasattr(x, "nbytes"))
    if device is None:
        for x in flat:
            if isinstance(x, jax.Array):
                try:
                    device = x.sharding._device_assignment[0]
                except Exception:
                    device = next(iter(x.devices()))
                break
    total = 0
    for x in flat:
        if isinstance(x, jax.Array):
            try:
                shards = x.addressable_shards
            except Exception:
                total += int(x.nbytes)
                continue
            total += sum(int(s.data.nbytes) for s in shards
                         if s.device == device)
        else:
            total += int(getattr(x, "nbytes", 0))
    return int(total)


_HLO_COLL = re.compile(
    r"=\s*(?:\(([^)]*)\)|([a-z0-9]+)\[([0-9,]*)\][^ ]*)\s*"
    r"(all-reduce|reduce-scatter|all-gather)(?:-start)?\(")
_HLO_TUPLE_ELT = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8": 1,
                "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2,
                "u16": 2, "s8": 1, "u8": 1, "pred": 1}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def hlo_collectives(compiled_text: str) -> dict:
    """Count the cross-device collectives in a compiled HLO module:
    ``{"all-reduce": {"count": n, "bytes": output_bytes}, ...}``.
    Structural evidence for the layout A/B — the DP program's grad
    sync is all-reduce; the FSDP program must show the per-layer
    all-gathers (XLA lowers the reduce-scatter half as
    reduce-scatter on TPU/GPU and as all-reduce + dynamic-slice on
    the CPU backend — either way the all-gathers only exist under the
    sharded layout)."""
    out = {}
    for m in _HLO_COLL.finditer(compiled_text):
        tuple_body, dtype, dims, kind = m.groups()
        if tuple_body is not None:
            nbytes = sum(_shape_bytes(dt, dm) for dt, dm
                         in _HLO_TUPLE_ELT.findall(tuple_body))
        else:
            nbytes = _shape_bytes(dtype, dims)
        slot = out.setdefault(kind, {"count": 0, "bytes": 0})
        slot["count"] += 1
        slot["bytes"] += nbytes
    return out
