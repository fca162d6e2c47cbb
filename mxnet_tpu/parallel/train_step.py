"""Fused training step — forward + backward + optimizer in ONE XLA program.

The reference overlaps backward with gradient pushes through engine
dependencies (SURVEY.md §3.4: priority = -key so push(layer N) overlaps
backward(layer N-1)). On TPU the equivalent — and stronger — guarantee
comes from compiling the whole training step into a single XLA program:
XLA's latency-hiding scheduler overlaps the gradient all-reduce over the
'dp' mesh axis with remaining backward compute, and buffer donation
makes the parameter/optimizer-state update fully in-place.

This is the throughput path (the one the benchmark's training cell
and the multi-chip dryrun run); the imperative Trainer path (gluon/trainer.py) remains for
step-by-step parity with the reference's
`autograd.record → backward → trainer.step` flow.
"""
from __future__ import annotations

import contextlib as _contextlib
import re

_nullcontext = _contextlib.nullcontext

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import numpy as onp

from .. import autograd
from .. import bucketing as _bucketing
from .. import compile_cache
from .. import engine
from .. import telemetry
from .. import tracing
from ..ndarray.ndarray import NDArray
from ..random_state import next_key, trace_rng
from ..gluon import _deferred
from ..gluon.block import _flatten_arrays, _rebuild, CachedOp
from ..ops import attention as _attention
from . import get_mesh, AXIS_DP


def _as_tuple(x):
    if isinstance(x, (list, tuple)):
        return tuple(x)
    return (x,)


_LAYER_RE = re.compile(r"layers\.(\d+)\.")


def _layer_groups(diff_names, frozen_names):
    """Group parameter positions by transformer-layer index for the
    gather/compute overlap chain.

    Returns an ordered list of groups, each a list of ``('d'|'f',
    position)`` entries indexing into the step's diff/frozen data
    tuples. Params whose name carries a ``layers.<N>.`` prefix land in
    group ``N``; everything else (embeddings, final LN, output head)
    lands in a leading group — their gathers are small and issuing
    them up front keeps the per-layer chain clean. Returns None when
    fewer than two groups exist (nothing to stagger)."""
    groups = {}
    for tag, names in (("d", diff_names), ("f", frozen_names)):
        for pos, name in enumerate(names):
            m = _LAYER_RE.search(name)
            key = int(m.group(1)) if m else -1
            groups.setdefault(key, []).append((tag, pos))
    ordered = [groups[k] for k in sorted(groups)]
    return ordered if len(ordered) >= 2 else None


def _stacked_hypers(opt, n_diff, check=False):
    """The hyperparameters of parameters ``0..n_diff-1`` as the step
    program takes them: ONE dict of stacked fields
    (`Optimizer._stack_hypers`), not a dict of scalars a parameter —
    4,067 host scalars a call kept a v5e idle two thirds of every GPT-2
    large step. Read anew on every call: nothing here outlives the
    step, so a scheduler or a changed multiplier reaches the next one."""
    return opt._stack_hypers([opt._hyper(k) for k in range(n_diff)],
                             check=check)


class TrainStep:
    """Compile `loss_fn(net(data), label)` + grad + optimizer update into
    one jitted, donation-friendly XLA program, optionally sharded over a
    `jax.sharding.Mesh`.

    Parameters
    ----------
    net : HybridBlock (or any Block whose forward is trace-safe)
    loss_fn : callable(out, label) -> NDArray loss (gluon.loss.* works)
    optimizer : mxnet_tpu.optimizer.Optimizer instance or name string
    mesh : optional Mesh; defaults to parallel.get_mesh()
    batch_axis : mesh axis name the leading batch dim is sharded over
    param_rules : list of (regex, PartitionSpec) giving tensor-parallel
        placements by parameter name; unmatched params are replicated.
        With ``layout=`` set this is the ESCAPE HATCH: a matching rule
        overrides the layout's logical-axis resolution for that
        parameter.
    layout : str or parallel.partition.Partitioner, optional
        Named SPMD layout over the mesh — ``"dp"`` (pure data
        parallel, the default behavior), ``"tp"`` (tensor parallel by
        logical axes), ``"fsdp"`` (params + optimizer state sharded
        over the batch axis; XLA all-gathers each layer's weights
        inside the step — overlapped with compute by the
        latency-hiding scheduler — and reduces gradients straight
        into the owning shard: reduce-scatter semantics, ``(N-1)/N``
        of the allreduce bytes per direction). Parameters resolve
        through their ``logical_axes`` metadata (gpt.py annotates the
        GPT family; un-annotated params stay replicated — use
        ``param_rules`` for those). Requires a mesh.
    bucketing : BucketingPolicy, optional
        Pad odd batches (the last partial batch of every epoch) up to
        a bucket so they reuse an existing compiled entry instead of
        forcing a rebuild; padded rows are masked out of the loss.
        None (default) inherits the process-global
        `mxnet_tpu.bucketing` policy; ``False`` opts this step out of
        even the global policy (exact unpadded behavior).
    compute_dtype : str, optional
        ``"bfloat16"`` runs forward/backward math in bf16 while the
        MASTER weights, gradients, and optimizer state stay fp32:
        params and floating inputs are cast to bf16 INSIDE the
        differentiated loss (so the cast's transpose returns fp32
        cotangents to the masters), the loss is reported in fp32, and
        LN/softmax accumulate fp32 via the ``ops.nn.accum_dtype``
        policy. None / ``"float32"`` (default) is bitwise-identical
        to today's fp32 path. Composes with every layout: the casts
        sit downstream of the gather pins.
    overlap_gather : bool
        On gather-compute layouts (``tp_fsdp``), chain
        ``lax.optimization_barrier`` across per-layer parameter groups
        so layer ``k``'s compute cannot be scheduled before layer
        ``k+1``'s all-gather has issued — double-buffering the ZeRO
        weight gathers against the matmuls instead of trusting the
        latency-hiding scheduler to find the overlap. Numerically the
        barrier is identity (losses stay bitwise equal to dp);
        structurally it is visible as ``opt-barrier`` ops in
        ``compiled_hlo``. Default True; ignored on layouts that do
        not gather in-step.
    """

    def __init__(self, net, loss_fn, optimizer, optimizer_params=None,
                 mesh=None, batch_axis=AXIS_DP, param_rules=None,
                 layout=None, donate=True, bucketing=None,
                 compute_dtype=None, overlap_gather=True):
        from .. import optimizer as opt_mod
        self.net = net
        self.loss_fn = loss_fn
        if isinstance(optimizer, str):
            optimizer = opt_mod.create(optimizer, **(optimizer_params or {}))
        self.optimizer = optimizer
        self._explicit_mesh = mesh
        self.batch_axis = batch_axis
        self.param_rules = [(re.compile(pat), spec)
                            for pat, spec in (param_rules or [])]
        self._layout = layout
        self._partitioner = None
        #: analytic gradient-sync wire bytes per step for the resolved
        #: layout (kvstore.collective_wire_bytes model); set at build
        self.comm_bytes_per_step = 0
        self.donate = donate
        if compute_dtype is None or str(compute_dtype) == "float32":
            self.compute_dtype = "float32"
            self._cast_dt = None
        elif str(compute_dtype) == "bfloat16":
            self.compute_dtype = "bfloat16"
            self._cast_dt = jnp.bfloat16
        else:
            raise ValueError(
                f"TrainStep compute_dtype must be None, 'float32' or "
                f"'bfloat16', got {compute_dtype!r}")
        self.overlap_gather = bool(overlap_gather)
        # False is a distinct value: "no bucketing, not even the
        # global policy" (as_policy would collapse it to None = inherit)
        self.bucketing = False if bucketing is False \
            else _bucketing.as_policy(bucketing)
        self._entries = {}
        self._opt_states = None  # shared across signatures: a shape
        self._mp_flags = None    # change (last odd batch) must NOT
        #                          reset Adam/momentum accumulators

    # -- helpers -------------------------------------------------------
    @property
    def mesh(self):
        return self._explicit_mesh or get_mesh()

    @property
    def partitioner(self):
        """The resolved layout Partitioner (built lazily: the mesh may
        be the process-global one set after construction). None when
        no ``layout=`` was requested."""
        if self._layout is None:
            return None
        if self._partitioner is None:
            from . import partition as _partition
            if isinstance(self._layout, _partition.Partitioner):
                self._partitioner = self._layout
            else:
                if self.mesh is None:
                    raise RuntimeError(
                        f"TrainStep(layout={self._layout!r}) needs a "
                        f"mesh: pass mesh= or parallel.set_mesh first")
                self._partitioner = _partition.Partitioner(
                    self._layout, mesh=self.mesh,
                    batch_axis=self.batch_axis)
        return self._partitioner

    def _spec_for(self, name):
        for pat, spec in self.param_rules:
            if pat.search(name):
                return spec
        return P()

    # -- build ---------------------------------------------------------
    def _build(self, data_leaves, data_spec, label_leaves, label_spec):
        net, loss_fn = self.net, self.loss_fn
        params_dict = net.collect_params()
        if any(p._data is None for p in params_dict.values()):
            CachedOp(net)._abstract_init(list(data_leaves),
                                         data_spec)
            params_dict = net.collect_params()

        part = self.partitioner
        if part is not None:
            # resolve every parameter's logical axes to a spec over
            # the mesh (p.sharding), param_rules overriding per name —
            # the pjit wiring below consumes p.sharding as before
            part.annotate(params_dict, override_rules=self.param_rules)

        names = list(params_dict.keys())
        params = [params_dict[n] for n in names]
        diff_idx = [i for i, p in enumerate(params)
                    if p.grad_req != "null"]
        frozen_idx = [i for i, p in enumerate(params)
                      if p.grad_req == "null"]
        diff_nds = [params[i].data() for i in diff_idx]
        frozen_nds = [params[i].data() for i in frozen_idx]
        all_nds = diff_nds + frozen_nds
        # the step differentiates inside its own program and never
        # reads or writes the imperative .grad buffers initialize()
        # allocated — release them: a dead copy of every parameter
        # (3.4 GB at GPT-2 large, with which the step does not fit a
        # 16 GB chip beside Adam's state)
        for nd in diff_nds:
            nd.release_grad()

        opt = self.optimizer
        if self._opt_states is None:
            self._opt_states = [
                opt.create_state_multi_precision(k, diff_nds[k])
                for k in range(len(diff_idx))]
            self._mp_flags = [opt._use_mp(w) for w in diff_nds]
        states = self._opt_states
        mp_flags = self._mp_flags

        out_box = {}
        # capture only the contexts — closing over the leaf NDArrays
        # would pin the build-time batch buffers in HBM for the
        # lifetime of this cached entry
        data_ctxs = [l.ctx for l in data_leaves]
        label_ctxs = [l.ctx for l in label_leaves]

        def forward_loss(key, diff_datas, frozen_datas,
                         input_datas, label_datas, n_valid):
            saved = [nd._data for nd in all_nds]
            scope = _deferred.trace_scope()
            rec = autograd._RecordingScope(False, True)
            # a step partitioned over several devices traces attention
            # on its jnp paths, as mesh serving engines do: the TPU
            # compiler cannot partition a pallas_call ("Mosaic kernels
            # cannot be automatically partitioned") and the kernels
            # have no shard_map wrapper yet (ROADMAP D6)
            att = _attention.jnp_only() \
                if self.mesh is not None and self.mesh.size > 1 \
                else _nullcontext()
            with scope, rec, trace_rng(key), att:
                for nd, d in zip(diff_nds, diff_datas):
                    nd._data = d
                for nd, d in zip(frozen_nds, frozen_datas):
                    nd._data = d
                try:
                    in_nds = [NDArray(d, ctx=c)
                              for d, c in zip(input_datas, data_ctxs)]
                    lab_nds = [NDArray(d, ctx=c)
                               for d, c in zip(label_datas, label_ctxs)]
                    args = _rebuild(data_spec, in_nds)
                    out = net.forward(*args)
                    labels = _rebuild(label_spec, lab_nds)
                    if loss_fn is not None:
                        loss = loss_fn(out, *labels)
                    else:
                        loss = out
                    if loss.ndim > 0:
                        # mean over the VALID rows only: bucketing pads
                        # a partial batch up to a stable signature and
                        # passes n_valid < batch; the where (not a
                        # multiply) keeps a non-finite padded-row loss
                        # from poisoning the sum via 0*inf. With
                        # n_valid == batch this is exactly loss.mean().
                        ld = loss._data
                        mask = jnp.arange(ld.shape[0]) < n_valid
                        mask = mask.reshape((ld.shape[0],)
                                            + (1,) * (ld.ndim - 1))
                        per_row = ld.size // ld.shape[0]
                        denom = jnp.maximum(n_valid, 1) * per_row
                        loss = NDArray(
                            jnp.where(mask, ld, 0).sum() / denom,
                            ctx=loss.ctx)
                    else:
                        # loss_fn reduced to a scalar itself: there is
                        # no per-row axis left to mask — dispatch warns
                        # if this entry ever receives a padded batch
                        out_box["scalar_loss"] = True
                finally:
                    for nd, s in zip(all_nds, saved):
                        nd._data = s
            out_box["aux_targets"] = [nd for nd, _ in scope.state_updates]
            # pin aux (BN running stats) to the target's STORED dtype:
            # a bf16 compute_dtype forward must not narrow the fp32
            # stat buffers (that would change the entry's avals and
            # drift the accumulators)
            aux = tuple(jnp.asarray(t, nd._data.dtype)
                        for nd, t in scope.state_updates)
            return loss._data, aux

        opt_cls = type(opt)
        n_diff = len(diff_nds)
        # once a program: a hyper field that is not stacked is one
        # value for every parameter
        _stacked_hypers(opt, n_diff, check=True)

        # gather-compute layouts (tp_fsdp): weights AND gradients are
        # pinned replicated INSIDE the step — the forward all-gathers
        # each weight before use (ZeRO-3) and the backward reduces the
        # gradient fully before the sharded optimizer update slices
        # it. Without the gradient pin, the 2-D output shardings
        # back-propagate tp splits into the backward contractions and
        # the partial-sum order drifts the updates a ulp per step away
        # from dp (losses stop being bitwise-comparable). The sharded
        # placements remain the STORAGE layout via in/out_shardings.
        gather_rep = None
        if part is not None and part.gather_compute \
                and self.mesh is not None:
            gather_rep = NamedSharding(self.mesh, P())

        # gather/compute overlap: per-layer barrier chain staggering
        # layer k+1's weight all-gather against layer k's compute
        overlap_groups = None
        if gather_rep is not None and self.overlap_gather:
            overlap_groups = _layer_groups(
                [names[i] for i in diff_idx],
                [names[i] for i in frozen_idx])

        cast_dt = self._cast_dt

        def _cast_leaves(datas):
            return tuple(d.astype(cast_dt)
                         if jnp.issubdtype(d.dtype, jnp.floating)
                         else d for d in datas)

        def step_fn(key, diff_datas, frozen_datas, opt_states, hypers,
                    input_datas, label_datas, n_valid):
            if gather_rep is not None:
                diff_datas = tuple(
                    jax.lax.with_sharding_constraint(d, gather_rep)
                    for d in diff_datas)
                frozen_datas = tuple(
                    jax.lax.with_sharding_constraint(d, gather_rep)
                    for d in frozen_datas)
            if overlap_groups is not None:
                # chain pairwise: bundling layer k's (post-gather)
                # weights with layer k+1's inside one barrier makes
                # every consumer of layer k's weights depend on layer
                # k+1's gather — XLA must issue gather k+1 no later
                # than compute k (the prefetch). Identity on values.
                dd, fz = list(diff_datas), list(frozen_datas)
                for prev, nxt in zip(overlap_groups,
                                     overlap_groups[1:]):
                    pick = prev + nxt
                    vals = tuple(dd[p] if t == "d" else fz[p]
                                 for t, p in pick)
                    vals = jax.lax.optimization_barrier(vals)
                    for (t, p), v in zip(pick, vals):
                        if t == "d":
                            dd[p] = v
                        else:
                            fz[p] = v
                # re-pin: the SPMD partitioner propagates shardings
                # THROUGH the barrier and would otherwise re-shard its
                # outputs back to the storage layout, silently undoing
                # the gather-compute pin (and its bitwise-vs-dp
                # guarantee)
                diff_datas = tuple(
                    jax.lax.with_sharding_constraint(d, gather_rep)
                    for d in dd)
                frozen_datas = tuple(
                    jax.lax.with_sharding_constraint(d, gather_rep)
                    for d in fz)

            def loss_f(dd):
                fz, ins = frozen_datas, input_datas
                if cast_dt is not None:
                    # cast INSIDE the differentiated function: the
                    # astype's transpose casts cotangents back, so
                    # grads land fp32 on the fp32 masters
                    dd = _cast_leaves(dd)
                    fz = _cast_leaves(fz)
                    ins = _cast_leaves(ins)
                loss, aux = forward_loss(key, dd, fz, ins,
                                         label_datas, n_valid)
                if cast_dt is not None:
                    loss = loss.astype(jnp.float32)
                return loss, aux

            (loss, aux), grads = jax.value_and_grad(
                loss_f, has_aux=True)(diff_datas)
            if gather_rep is not None:
                grads = tuple(
                    jax.lax.with_sharding_constraint(g, gather_rep)
                    for g in grads)
            new_ws, new_ss = [], []
            for k in range(n_diff):
                w, g, s, h = (diff_datas[k], grads[k], opt_states[k],
                              opt_cls._hyper_at(hypers, k))
                if mp_flags[k]:
                    nw, ns = opt_cls._step_mp(w, g, s, h)
                else:
                    nw, ns = opt_cls._step(
                        w, jnp.asarray(g, w.dtype), s, h)
                new_ws.append(nw)
                new_ss.append(ns)
            return tuple(new_ws), tuple(new_ss), loss, aux

        mesh = self.mesh
        jit_kwargs = {}
        if self.donate:
            jit_kwargs["donate_argnums"] = (1, 3)
        if mesh is not None:
            rep = NamedSharding(mesh, P())
            diff_sh = []
            for k, i in enumerate(diff_idx):
                spec = getattr(params[i], "sharding", None)
                if spec is None:
                    spec = self._spec_for(names[i])
                diff_sh.append(NamedSharding(mesh, spec))
            frozen_sh = []
            for i in frozen_idx:
                spec = getattr(params[i], "sharding", None)
                if spec is None:
                    spec = self._spec_for(names[i])
                frozen_sh.append(NamedSharding(mesh, spec))
            state_sh = []
            for k in range(n_diff):
                w = diff_nds[k]
                wsh = diff_sh[k]
                wshape = tuple(w.shape)

                def leaf_sh(s, _wsh=wsh, _wshape=wshape):
                    shp = getattr(s, "shape", None)
                    return _wsh if shp is not None and tuple(shp) == _wshape \
                        else rep
                state_sh.append(jax.tree.map(leaf_sh, states[k]))

            # the PRIMARY input's leading dim defines the batch; other
            # leaves (e.g. RNN states shaped (layers, batch, hidden))
            # may carry it elsewhere — shard the axis that matches, or
            # replicate when none/ambiguous (dim0 wins ties: the
            # conventional batch-major layout)
            bsz = next((l.shape[0] for l in data_leaves if l.ndim),
                       None)

            def batch_sh(leaf):
                spec = [None] * leaf.ndim
                if leaf.ndim > 0 and bsz is not None:
                    if leaf.shape[0] == bsz:
                        spec[0] = self.batch_axis
                    else:
                        hits = [i for i, d in enumerate(leaf.shape)
                                if d == bsz]
                        if len(hits) == 1:
                            spec[hits[0]] = self.batch_axis
                return NamedSharding(mesh, P(*spec))

            data_sh = tuple(batch_sh(l) for l in data_leaves)
            label_sh = tuple(batch_sh(l) for l in label_leaves)
            hyper_sh = jax.tree.map(lambda _: rep,
                                    _stacked_hypers(opt, n_diff))
            jit_kwargs["in_shardings"] = (
                rep, tuple(diff_sh), tuple(frozen_sh),
                tuple(state_sh), hyper_sh, data_sh, label_sh, rep)
            # aux (BN stats) shardings: let XLA decide (None subtree)
            jit_kwargs["out_shardings"] = (tuple(diff_sh),
                                           tuple(state_sh), rep, None)
            # place current param values onto the mesh
            for k in range(n_diff):
                d = diff_nds[k]._data
                if not _placed_as(d, diff_sh[k]):
                    diff_nds[k]._data = jax.device_put(d, diff_sh[k])
                states[k] = jax.tree.map(
                    lambda s, sh: jax.device_put(s, sh)
                    if hasattr(s, "shape") else s,
                    states[k], state_sh[k])
            for j in range(len(frozen_nds)):
                d = frozen_nds[j]._data
                if not _placed_as(d, frozen_sh[j]):
                    frozen_nds[j]._data = jax.device_put(d, frozen_sh[j])
            # layout accounting: the analytic grad-sync wire bytes of
            # the resolved layout and the MEASURED per-device
            # param+optimizer footprint (walks real shards;
            # tests/test_partition.py holds fsdp under dp in both)
            from . import partition as _partition
            spec_map = {names[i]: diff_sh[k].spec
                        for k, i in enumerate(diff_idx)}
            self.comm_bytes_per_step = _partition.grad_sync_bytes(
                spec_map, {names[i]: params[i] for i in diff_idx},
                mesh, self.batch_axis)
            telemetry.gauge("parallel.train_step.comm_bytes_per_step",
                            self.comm_bytes_per_step)
            telemetry.gauge(
                "parallel.partition.bytes_per_device",
                _partition.per_device_bytes(
                    [nd._data for nd in diff_nds]
                    + [nd._data for nd in frozen_nds] + list(states)))
        else:
            data_sh = label_sh = None
            # COMMIT params and optimizer state to the device they sit
            # on: a jitted step returns committed arrays, and jit keeps
            # one executable per input-commitment signature — fresh
            # (uncommitted) first inputs cost a second whole compile on
            # step 2, which no build or trace counter sees
            def commit(a):
                if not isinstance(a, jax.Array) or a.committed:
                    return a
                return jax.device_put(a, next(iter(a.devices())))

            for nd in all_nds:
                nd._data = commit(nd._data)
            for k in range(n_diff):
                states[k] = jax.tree.map(commit, states[k])

        entry = {
            "data_sh": data_sh,
            "label_sh": label_sh,
            "jit": jax.jit(step_fn, **jit_kwargs),
            "step_fn": step_fn,
            "jit_kwargs": jit_kwargs,
            "params": params,
            "diff_idx": diff_idx,
            "diff_nds": diff_nds,
            "frozen_nds": frozen_nds,
            "out_box": out_box,
            "data_spec": data_spec,
            "label_spec": label_spec,
        }
        return entry

    # -- bulk (scan) path ----------------------------------------------
    def _build_chain(self, entry):
        """jit a lax.scan of step_fn over a leading steps axis.

        TPU-native equivalent of the reference engine's bulk mode
        (`MXNET_EXEC_BULK_EXEC_*`, BulkAppend/BulkFlush in
        src/engine/threaded_engine.h:507): instead of fusing engine
        pushes, N whole training steps compile into ONE XLA program —
        zero per-step host dispatch. BN running stats thread through
        the scan carry; Adam-style bias-correction counters advance
        per scanned step. LR schedules are evaluated at launch and
        held constant across the chain (document-level divergence:
        schedules step at chain granularity).
        """
        step_fn = entry["step_fn"]
        frozen_nds = entry["frozen_nds"]
        out_box = entry["out_box"]
        # aux target positions are resolved AT TRACE TIME inside the
        # scan body: out_box["aux_targets"] is only populated when
        # step_fn is first traced, which for a fresh entry happens
        # during this very chain trace
        aux_pos_box = {}

        def _aux_positions():
            if "pos" not in aux_pos_box:
                frozen_ids = [id(nd) for nd in frozen_nds]
                aux_pos_box["pos"] = [
                    frozen_ids.index(id(nd))
                    if id(nd) in frozen_ids else -1
                    for nd in out_box.get("aux_targets", [])]
            return aux_pos_box["pos"]

        def chain_fn(key, diff, frozen, states, hypers, datas, labels,
                     n_valids):
            n = datas[0].shape[0]

            def body(carry, xs):
                key, diff, frozen, states, t_off = carry
                ks = jax.random.split(key)
                key, sub = ks[0], ks[1]
                d, l, nv = xs
                hy = {**hypers, "t": hypers["t"] + t_off}
                new_ws, new_ss, loss, aux = step_fn(
                    sub, diff, frozen, states, hy, d, l, nv)
                frozen2 = list(frozen)
                for pos, a in zip(_aux_positions(), aux):
                    if pos >= 0:
                        frozen2[pos] = a
                return ((key, tuple(new_ws), tuple(frozen2),
                         tuple(new_ss), t_off + 1), (loss, aux))

            (key, diff, frozen, states, _), (losses, auxs) = \
                jax.lax.scan(body, (key, diff, frozen, states,
                                    jnp.int32(0)),
                             (datas, labels, n_valids))
            last_aux = jax.tree.map(lambda a: a[n - 1], auxs)
            return diff, frozen, states, losses, last_aux

        kw = {}
        chain_data_sh = chain_label_sh = None
        base = entry["jit_kwargs"]
        if self.donate:
            kw["donate_argnums"] = (1, 2, 3)
        if "in_shardings" in base:
            (rep, diff_sh, frozen_sh, state_sh, hyper_sh,
             data_sh, label_sh, _nv_sh) = base["in_shardings"]
            mesh = self.mesh

            def lift(sh):
                # same placement with a replicated leading steps axis
                return NamedSharding(mesh, P(None, *sh.spec))

            chain_data_sh = tuple(lift(s) for s in data_sh)
            chain_label_sh = tuple(lift(s) for s in label_sh)
            kw["in_shardings"] = (
                rep, diff_sh, frozen_sh, state_sh, hyper_sh,
                chain_data_sh, chain_label_sh, rep)
            kw["out_shardings"] = (diff_sh, frozen_sh, state_sh,
                                   rep, None)
        return {"jit": jax.jit(chain_fn, **kw),
                "aux_positions": _aux_positions,
                "data_sh": chain_data_sh,
                "label_sh": chain_label_sh,
                "dispatched": False}

    # -- bucketing / signatures ----------------------------------------
    def _effective_policy(self):
        if self.bucketing is False:
            return None
        return self.bucketing if self.bucketing is not None \
            else _bucketing.get_policy()

    @staticmethod
    def _sig(data_leaves, label_leaves, data_spec, label_spec):
        return (tuple((l.shape, str(l.dtype)) for l in data_leaves),
                tuple((l.shape, str(l.dtype)) for l in label_leaves),
                repr(data_spec), repr(label_spec))

    def _apply_bucketing(self, data_leaves, label_leaves, pad):
        """Resolve the pad count for one batch: an explicit ``pad``
        argument wins, then pad marks left by the data pipeline, then
        the active bucketing policy (which pads the leaves here).
        Returns (data_leaves, label_leaves, pad)."""
        if pad is not None:
            return list(data_leaves), list(label_leaves), int(pad)
        pad = max([_bucketing.get_pad(l)
                   for l in list(data_leaves) + list(label_leaves)]
                  or [0])
        if pad:
            return list(data_leaves), list(label_leaves), pad
        policy = self._effective_policy()
        bsz = next((l.shape[0] for l in data_leaves if l.ndim), None)
        if policy is not None and bsz is not None:
            target = policy.bucket(bsz)
            if target > bsz:
                telemetry.counter("parallel.train_step.bucket_pad")
                data_leaves, pad = _bucketing.pad_leaves(
                    data_leaves, target, bsz)
                label_leaves, _ = _bucketing.pad_leaves(
                    label_leaves, target, bsz)
                return data_leaves, label_leaves, pad
        return list(data_leaves), list(label_leaves), 0

    def _get_entry(self, data_leaves, data_spec, label_leaves,
                   label_spec):
        sig = self._sig(data_leaves, label_leaves, data_spec, label_spec)
        entry = self._entries.get(sig)
        if entry is None:
            telemetry.counter("parallel.train_step.build")
            t0 = telemetry.clock()
            entry = self._build(data_leaves, data_spec, label_leaves,
                                label_spec)
            telemetry.duration_since("parallel.train_step.build", t0)
            self._entries[sig] = entry
        return sig, entry

    def _check_maskable(self, entry, pad):
        """A padded batch whose loss_fn already reduced to a scalar
        cannot be masked — the padded rows WILL contribute. Surface
        that loudly instead of silently breaking the bit-identical
        guarantee."""
        if pad and entry["out_box"].get("scalar_loss") \
                and not getattr(self, "_warned_scalar_loss", False):
            import warnings
            self._warned_scalar_loss = True
            warnings.warn(
                "TrainStep received a padded batch but loss_fn returns "
                "a scalar (already reduced over the batch): padded rows "
                "cannot be masked out of the loss and WILL affect "
                "training. Return a per-sample loss (gluon.loss.* "
                "default) to make padding exact, or disable bucketing "
                "for this step (bucketing=False).")

    def run_chain(self, data, label, pad=None):
        """Run `data.shape[0]` chained training steps in one compiled
        XLA program (bulk mode). `data`/`label` carry a leading steps
        axis: ``(n_steps, batch, ...)``. ``pad`` (int or length-
        ``n_steps`` sequence) marks trailing padded rows per step;
        their loss contribution is masked out. Returns the per-step
        losses as an NDArray of shape ``(n_steps,)``."""
        with tracing.phase("train.chain"):
            return self._run_chain(data, label, pad)

    def _run_chain(self, data, label, pad):
        data_t, label_t = _as_tuple(data), _as_tuple(label)
        data_leaves, data_spec = _flatten_arrays(data_t)
        label_leaves, label_spec = _flatten_arrays(label_t)
        n_steps = data_leaves[0].shape[0]

        # per-batch entry (strip the steps axis for the signature)
        one_data = [l[0] for l in data_leaves]
        one_label = [l[0] for l in label_leaves]
        sig, entry = self._get_entry(one_data, data_spec, one_label,
                                     label_spec)
        chain_key = ("chain", sig, n_steps)
        chain = self._entries.get(chain_key)
        if chain is None:
            # chain_build times the (cheap) trace-graph construction;
            # the first dispatch below carries the XLA compile and is
            # recorded separately as chain_compile — same split as
            # __call__'s build vs compile (a warm chain re-keyed by
            # n_steps must not book its whole run as compile time)
            telemetry.counter("parallel.train_step.chain_build")
            t0 = telemetry.clock()
            chain = self._build_chain(entry)
            telemetry.duration_since("parallel.train_step.chain_build",
                                     t0)
            self._entries[chain_key] = chain

        opt = self.optimizer
        n_diff = len(entry["diff_nds"])
        # count the first chained step BEFORE reading hypers (Adam's
        # bias correction needs t>=1), then the remaining n-1; the
        # scan body advances t by its step offset
        opt._update_count(list(range(n_diff)))
        hypers = _stacked_hypers(opt, n_diff)
        for _ in range(n_steps - 1):
            opt._update_count(list(range(n_diff)))

        bsz = next((l.shape[1] for l in data_leaves if l.ndim > 1),
                   None) or 1
        if pad is None:
            pads = onp.zeros((n_steps,), onp.int32)
        else:
            pads = onp.broadcast_to(
                onp.asarray(pad, onp.int32), (n_steps,))
        n_valids = (bsz - pads).astype(onp.int32)

        data_datas = [l._data for l in data_leaves]
        label_datas = [l._data for l in label_leaves]
        if chain["data_sh"] is not None:
            data_datas = [d if _placed_as(d, sh)
                          else jax.device_put(d, sh) for d, sh in
                          zip(data_datas, chain["data_sh"])]
            label_datas = [d if _placed_as(d, sh)
                           else jax.device_put(d, sh) for d, sh in
                           zip(label_datas, chain["label_sh"])]

        first_dispatch = not chain["dispatched"]
        t0 = telemetry.clock()
        new_ws, new_fr, new_ss, losses, last_aux = chain["jit"](
            next_key(),
            tuple(nd._data for nd in entry["diff_nds"]),
            tuple(nd._data for nd in entry["frozen_nds"]),
            tuple(self._opt_states), hypers,
            tuple(data_datas), tuple(label_datas), n_valids)
        chain["dispatched"] = True
        telemetry.duration_since(
            "parallel.train_step.chain_compile" if first_dispatch else
            "parallel.train_step.run_chain", t0)
        telemetry.counter("parallel.train_step.chained_steps", n_steps)
        if self.comm_bytes_per_step and telemetry.enabled():
            telemetry.counter("parallel.train_step.comm_bytes",
                              self.comm_bytes_per_step * n_steps)
        self._check_maskable(entry, int(pads.max()) if len(pads) else 0)

        for nd, nw in zip(entry["diff_nds"], new_ws):
            nd._data = nw
        for nd, nf in zip(entry["frozen_nds"], new_fr):
            nd._data = nf
        self._opt_states = list(new_ss)
        targets = entry["out_box"].get("aux_targets", [])
        aux_positions = chain["aux_positions"]
        with autograd.pause():
            for nd, pos, new in zip(targets, aux_positions(), last_aux):
                if pos < 0:  # not threaded through frozen: install last
                    nd._install(new)
        engine.sample_memory()
        return NDArray(engine.track(losses))

    # -- call ----------------------------------------------------------
    def __call__(self, data, label, pad=None):
        """Run one training step; returns the (scalar NDArray) loss.

        ``pad`` marks the trailing rows of the batch as padding (their
        loss contribution is masked out — see bucketing.py). When None,
        pad marks left on the arrays by the data pipeline apply, and
        an active bucketing policy pads odd batches here so they reuse
        an existing compiled entry."""
        # the phases of one call, in the profiler's trace while a
        # session is live (docs/OBSERVABILITY.md); step_num is the
        # update this call makes
        with tracing.phase("train.step",
                           step_num=self.optimizer.num_update + 1):
            with tracing.phase("train.prepare"):
                entry, args, pad = self._prepare(data, label, pad)
            # dispatch is async and entry["jit"] is lazily compiled: its
            # FIRST dispatch (even when the entry was built by an earlier
            # run_chain) pays trace + XLA compile — unless warmup() AOT-
            # compiled the entry, in which case dispatch goes through the
            # precompiled executable; steady-state 'run' measures enqueue
            # latency (the host-side cost the reference's engine-push
            # timing captured)
            first_dispatch = not entry.get("jit_dispatched")
            t0 = telemetry.clock()
            with tracing.phase("train.enqueue"):
                out, first_dispatch = self._enqueue(entry, args,
                                                    first_dispatch)
            with tracing.phase("train.writeback"):
                entry["jit_dispatched"] = True
                telemetry.duration_since(
                    "parallel.train_step.compile" if first_dispatch else
                    "parallel.train_step.run", t0)
                return self._writeback(entry, out, pad)

    def _prepare(self, data, label, pad):
        """Everything of a call before the program is handed its
        arguments: flatten and bucket the batch, find the entry, count
        the update, stack the hypers, place the batch. Returns
        ``(entry, args, pad)``."""
        data_leaves, data_spec = _flatten_arrays(_as_tuple(data))
        label_leaves, label_spec = _flatten_arrays(_as_tuple(label))
        data_leaves, label_leaves, pad = self._apply_bucketing(
            data_leaves, label_leaves, pad)
        _, entry = self._get_entry(data_leaves, data_spec,
                                   label_leaves, label_spec)
        opt = self.optimizer
        n_diff = len(entry["diff_nds"])
        opt._update_count(list(range(n_diff)))
        hypers = _stacked_hypers(opt, n_diff)

        data_datas = [l._data for l in data_leaves]
        label_datas = [l._data for l in label_leaves]
        if entry["data_sh"] is not None:
            # skip leaves a DeviceFeed already placed on the entry's
            # shardings — the H2D happened off the dispatch path
            data_datas = [d if _placed_as(d, sh)
                          else jax.device_put(d, sh) for d, sh in
                          zip(data_datas, entry["data_sh"])]
            label_datas = [d if _placed_as(d, sh)
                           else jax.device_put(d, sh) for d, sh in
                           zip(label_datas, entry["label_sh"])]

        bsz = next((l.shape[0] for l in data_leaves if l.ndim), 1)
        n_valid = onp.int32(bsz - pad)
        diff_datas = tuple(nd._data for nd in entry["diff_nds"])
        args = (next_key(), diff_datas,
                tuple(nd._data for nd in entry["frozen_nds"]),
                tuple(self._opt_states), hypers,
                tuple(data_datas), tuple(label_datas), n_valid)
        if telemetry.enabled():
            # what the call hands over from the host: each such leaf is
            # a transfer of its own inside the program's call, so this
            # must not grow with the number of parameters (counted once
            # an entry: its calls all pass the same tree)
            if "host_arg_leaves" not in entry:
                entry["host_arg_leaves"] = sum(
                    not isinstance(a, jax.Array)
                    for a in jax.tree.leaves(args))
            telemetry.gauge("parallel.train_step.host_arg_leaves",
                            entry["host_arg_leaves"])
        return entry, args, pad

    @staticmethod
    def _enqueue(entry, args, first_dispatch):
        """The program call and nothing else. Returns its outputs and
        whether the call paid a trace and compile."""
        out = None
        if entry.get("aot") is not None:
            try:
                out = entry["aot"](*args)
            except (TypeError, ValueError):
                # aval mismatch vs. the warmed signature (e.g. weak
                # types): fall back to the lazy jit path for good.
                # That jit has never dispatched (warmup marked the
                # entry dispatched for the AOT path), so the fallback
                # pays a real trace+compile — label it as one
                telemetry.counter("parallel.train_step.aot_fallback")
                entry["aot"] = None
                first_dispatch = True
        if out is None:
            with compile_cache.measure() if first_dispatch \
                    else _nullcontext():
                out = entry["jit"](*args)
        return out, first_dispatch

    def _writeback(self, entry, out, pad):
        """Rebind weights and optimizer state to the program's outputs,
        install the aux updates; returns the loss."""
        new_ws, new_ss, loss, aux = out
        if self.comm_bytes_per_step and telemetry.enabled():
            telemetry.counter("parallel.train_step.comm_bytes",
                              self.comm_bytes_per_step)
        self._check_maskable(entry, pad)

        for nd, nw in zip(entry["diff_nds"], new_ws):
            nd._data = nw
        self._opt_states = list(new_ss)
        targets = entry["out_box"].get("aux_targets", [])
        with autograd.pause():
            for nd, new in zip(targets, aux):
                nd._install(new)
        engine.sample_memory()
        return NDArray(engine.track(loss))

    # -- introspection -------------------------------------------------
    def compiled_hlo(self, data, label, optimized=True):
        """Compiled HLO text of the entry serving this batch signature
        — the structural-evidence hook: fed to
        ``partition.hlo_collectives`` it shows that the fsdp program
        really contains the per-layer all-gathers and the dp program
        none (tests/test_partition.py; ``chip_smoke.py --chips 4``).
        Build the entry (run one step) first; this lowers/compiles a
        fresh executable for inspection, so call it OUTSIDE any timed
        window.

        ``optimized=False`` returns the LOWERED (pre-optimization)
        StableHLO instead — the hook for asserting program STRUCTURE
        the backend is allowed to fold, e.g. the ``overlap_gather``
        chain's ``optimization_barrier`` ops (the CPU backend erases
        ``opt-barrier`` late in its pipeline; TPU keeps it)."""
        data_leaves, data_spec = _flatten_arrays(_as_tuple(data))
        label_leaves, label_spec = _flatten_arrays(_as_tuple(label))
        data_leaves, label_leaves, _pad = self._apply_bucketing(
            data_leaves, label_leaves, None)
        _, entry = self._get_entry(data_leaves, data_spec,
                                   label_leaves, label_spec)
        opt = self.optimizer
        n_diff = len(entry["diff_nds"])
        hypers = _stacked_hypers(opt, n_diff)
        abstract = [jax.ShapeDtypeStruct(l.shape, l.dtype)
                    for l in data_leaves]
        labstract = [jax.ShapeDtypeStruct(l.shape, l.dtype)
                     for l in label_leaves]
        bsz = next((l.shape[0] for l in data_leaves if l.ndim), 1)
        lowered = entry["jit"].lower(
            next_key(),
            tuple(nd._data for nd in entry["diff_nds"]),
            tuple(nd._data for nd in entry["frozen_nds"]),
            tuple(self._opt_states), hypers,
            tuple(abstract), tuple(labstract), onp.int32(bsz))
        if not optimized:
            return lowered.as_text()
        return lowered.compile().as_text()

    # -- AOT warmup ----------------------------------------------------
    def warmup(self, shapes, dtype="float32", label_dtype="int32"):
        """AOT-compile training-step entries ahead of the first step.

        ``shapes`` is a list of ``(data_shapes, label_shapes)``
        signatures; each side is one shape tuple or a tuple/list of
        them, and a ``(shape, dtype)`` pair overrides the default
        dtype per leaf::

            step.warmup([((64, 16), (64,))])               # one entry
            step.warmup([((b, 16), (b,)) for b in (32, 64)])

        Each signature builds its entry (if missing) and compiles it
        via ``jit.lower(...).compile()`` — moving trace + XLA compile
        off the first training step. With ``JAX_COMPILATION_CACHE_DIR``
        set the compile replays from the persistent cache, so a
        restarted process warms up at disk-read speed. Telemetry:
        ``parallel.train_step.warmup`` (count),
        ``parallel.train_step.aot_compile`` (ms), plus the
        ``compile_cache.*`` hit/miss counters."""
        import jax.numpy as _jnp

        def _leafspecs(side, default_dtype):
            if isinstance(side, (list, tuple)) and side and \
                    isinstance(side[0], (list, tuple)):
                items = list(side)
                # distinguish the (shape, dtype) pair form from a list
                # of shapes: a pair has a str dtype second element
                if len(side) == 2 and isinstance(side[1], str):
                    items = [side]
            else:
                items = [side]
            out = []
            for it in items:
                if (isinstance(it, (list, tuple)) and len(it) == 2
                        and isinstance(it[1], str)):
                    out.append((tuple(it[0]), it[1]))
                else:
                    out.append((tuple(it), default_dtype))
            return out

        compiled = []
        for data_side, label_side in shapes:
            data_leaves = [NDArray(_jnp.zeros(s, dt)) for s, dt in
                           _leafspecs(data_side, dtype)]
            label_leaves = [NDArray(_jnp.zeros(s, dt)) for s, dt in
                            _leafspecs(label_side, label_dtype)]
            # bucket the template exactly like dispatch will, so
            # warming the real odd-tail shape warms the entry dispatch
            # actually uses (not a never-hit unpadded signature)
            data_leaves, label_leaves, _ = self._apply_bucketing(
                data_leaves, label_leaves, None)
            _, dspec = _flatten_arrays(tuple(data_leaves))
            _, lspec = _flatten_arrays(tuple(label_leaves))
            sig, entry = self._get_entry(data_leaves, dspec,
                                         label_leaves, lspec)
            telemetry.counter("parallel.train_step.warmup")
            if entry.get("aot") is not None:
                compiled.append(sig)
                continue
            opt = self.optimizer
            n_diff = len(entry["diff_nds"])
            # hypers carry the CURRENT counters; their avals (one
            # strong (n_diff,) numpy array a field, as every call
            # passes) are what matters for the compiled signature, not
            # the values
            hypers = _stacked_hypers(opt, n_diff)
            abstract = [jax.ShapeDtypeStruct(l.shape, l.dtype)
                        for l in data_leaves]
            labstract = [jax.ShapeDtypeStruct(l.shape, l.dtype)
                         for l in label_leaves]
            bsz = next((l.shape[0] for l in data_leaves if l.ndim), 1)
            t0 = telemetry.clock()
            lowered = entry["jit"].lower(
                next_key(),
                tuple(nd._data for nd in entry["diff_nds"]),
                tuple(nd._data for nd in entry["frozen_nds"]),
                tuple(self._opt_states), hypers,
                tuple(abstract), tuple(labstract), onp.int32(bsz))
            with compile_cache.measure():
                entry["aot"] = lowered.compile()
            telemetry.duration_since("parallel.train_step.aot_compile",
                                     t0)
            # first *training* dispatch is now a plain enqueue
            entry["jit_dispatched"] = True
            compiled.append(sig)
        return compiled

    # -- async feed support --------------------------------------------
    def prepare_batch(self, data, label, pad=None):
        """Pad (bucketing) + device-place one batch ahead of dispatch.

        Called by `io.DeviceFeed` from its worker thread: applies the
        same bucketing/pad resolution as ``__call__``, then
        ``device_put``s each leaf onto the matching compiled entry's
        ``data_sh``/``label_sh`` shardings so the dispatch path skips
        the H2D transfer. Batches whose entry is not built yet come
        back host-resident (the first step's build handles them).
        Returns ``(data, label)`` with the input nesting preserved."""
        data_t, label_t = _as_tuple(data), _as_tuple(label)
        data_leaves, data_spec = _flatten_arrays(data_t)
        label_leaves, label_spec = _flatten_arrays(label_t)
        data_leaves, label_leaves, pad = self._apply_bucketing(
            data_leaves, label_leaves, pad)
        sig = self._sig(data_leaves, label_leaves, data_spec,
                        label_spec)
        entry = self._entries.get(sig)
        if entry is not None and entry["data_sh"] is not None:
            def place(leaves, shs):
                out = []
                for l, sh in zip(leaves, shs):
                    if _placed_as(l._data, sh):
                        out.append(l)
                    else:
                        nd = NDArray(jax.device_put(l._data, sh),
                                     ctx=l.ctx)
                        out.append(nd)
                return out

            data_leaves = place(data_leaves, entry["data_sh"])
            label_leaves = place(label_leaves, entry["label_sh"])
        else:
            # no mesh shardings (single device) — still move any
            # host-resident leaf onto the default device off the
            # dispatch path; leaves already backed by a jax.Array were
            # placed when they were created
            def to_device(leaves):
                return [l if isinstance(l._data, jax.Array)
                        else NDArray(jax.device_put(l._data), ctx=l.ctx)
                        for l in leaves]

            data_leaves = to_device(data_leaves)
            label_leaves = to_device(label_leaves)
        if pad:
            for l in data_leaves + label_leaves:
                _bucketing.mark_pad(l, pad)
        new_data = _rebuild(data_spec, data_leaves)
        new_label = _rebuild(label_spec, label_leaves)
        if not isinstance(data, (list, tuple)):
            new_data = new_data[0]
        if not isinstance(label, (list, tuple)):
            new_label = new_label[0]
        return new_data, new_label


def _placed_as(data, sh):
    try:
        return isinstance(data, jax.Array) and data.sharding == sh
    except Exception:
        return False
