"""Gluon Block / HybridBlock (parity: python/mxnet/gluon/block.py).

- ``Block``: child/parameter registration through ``__setattr__``
  (block.py:202 in the reference), collect_params, initialize,
  save/load_parameters, cast, apply.
- ``HybridBlock``: adds ``hybridize()``. The reference traces forward
  via deferred compute into an nnvm Symbol and executes it with
  CachedOp (block.py:997-1221 → src/imperative/cached_op.cc:776).
  TPU-native equivalent: the trace is jax tracing and the executable is
  ONE whole-graph XLA program per (input-signature, train-flag):

    * forward-only: jit(raw_fn) — the entire network is a single fused
      XLA executable; memory planning = XLA buffer assignment (the
      reference's static_alloc/static_shape for free).
    * under autograd.record(): jit(vjp(raw_fn)) captures forward +
      residuals; backward is a second cached XLA program. The CachedOp
      registers ONE tape node (the reference registers "_CachedOp").

  Stateful bits are made explicit: a PRNG key feeds dropout-style ops
  (random_state.trace_rng) and BatchNorm running-stat updates are
  returned as aux outputs and written back after each call
  (_deferred.trace_scope), matching the reference's aux-state mutation
  semantics without breaking XLA purity.
"""
from __future__ import annotations

import os
from collections import OrderedDict
from contextlib import nullcontext as _nullcontext

import numpy as onp
import jax

from .. import autograd
from .. import bucketing as _bucketing
from .. import compile_cache
from .. import engine
from .. import telemetry
from ..context import current_context
from ..ndarray.ndarray import NDArray
from ..random_state import next_key, trace_rng
from . import _deferred
from .parameter import Parameter, ParameterDict, DeferredInitializationError

# bumped whenever a registered Parameter attribute is rebound to a
# different Parameter object (share_parameters / tied weights); lets
# CachedOp caches re-validate lazily instead of walking collect_params
# on every call
_PARAM_REBIND_EPOCH = 0


def _maybe_transpose_conv_kernel(name, p, val):
    """Auto-transpose a reference-written NCHW conv kernel (O,I,H,W)
    into a channels-last model expecting (O,H,W,I).

    Fires ONLY on parameters a Conv2D layer tagged with
    ``_kernel_layout == "OHWI"`` (conv_layers.py) — never on arbitrary
    4-d parameters, so genuinely incompatible checkpoints still raise
    the usual shape error. Layout is detected by locating the known
    kernel (H, W) dims in the loaded array; a square-kernel array where
    both interpretations fit (e.g. 3x3 kernel over 3 channels with
    in_channels still deferred) is ambiguous and raises with guidance
    instead of silently guessing (MIGRATION.md porting recipe).
    """
    if getattr(p, "_kernel_layout", None) != "OHWI" \
            or getattr(val, "ndim", 0) != 4:
        return val
    kh, kw = p._kernel_hw
    shape = tuple(val.shape)
    if p._shape_known():
        expected = tuple(p.shape)
        if shape == expected:
            return val
        if (shape[0], shape[2], shape[3], shape[1]) == expected:
            import warnings
            warnings.warn(
                f"Parameter '{name}': loaded kernel {shape} treated as "
                f"reference NCHW (O,I,H,W) and transposed to {expected}"
                f" (O,H,W,I). If this checkpoint was NOT written by an "
                f"NCHW model, the weights are mis-permuted.",
                UserWarning, stacklevel=4)
            return val.transpose((0, 2, 3, 1))
        return val  # let set_data raise its usual shape error
    # deferred in_channels: expected is (O, kh, kw, 0) — decide by
    # where the known kernel dims sit in the loaded array
    looks_ohwi = shape[1:3] == (kh, kw)
    looks_oihw = shape[2:4] == (kh, kw)
    if looks_ohwi and looks_oihw:
        raise ValueError(
            f"Parameter '{name}': cannot tell whether the checkpoint "
            f"kernel {shape} is NCHW (O,I,H,W) or NHWC (O,H,W,I) — "
            f"kernel {kh}x{kw} with matching channel count is "
            f"ambiguous while in_channels is deferred. Run one forward "
            f"pass (or construct the layer with in_channels=...) "
            f"before load_parameters.")
    if looks_oihw:
        return val.transpose((0, 2, 3, 1))
    return val


class _ArgSpec:
    """Rebuild spec for a flattened arg nest, with its ``repr`` string
    cached on the object. The string is the hashable half of every
    dispatch signature (`CachedOp._signature`, `TrainStep._sig`), and
    re-stringifying the nest used to be a per-dispatch host cost —
    `gluon.cachedop.signature` telemetry proves the cut. Equality and
    hash go through the string so specs keep working as dict keys."""

    __slots__ = ("tree", "_str")

    def __init__(self, tree):
        self.tree = tree
        self._str = None

    @property
    def string(self) -> str:
        s = self._str
        if s is None:
            s = self._str = repr(self.tree)
        return s

    def __repr__(self):
        return self.string

    def __eq__(self, other):
        if isinstance(other, _ArgSpec):
            return self.string == other.string
        return NotImplemented

    def __hash__(self):
        return hash(self.string)


# interned specs for the dominant call shape — every positional arg an
# NDArray, no nesting — keyed by arg count: the SAME spec object (repr
# already computed) comes back on every dispatch, so the signature
# never walks or stringifies the nest again
_FLAT_SPECS: dict = {}


def _flatten_arrays(args):
    """Flatten nested (list/tuple/dict) args into NDArray leaves +
    a rebuild `_ArgSpec`. Non-array leaves become static."""
    flat = all(type(a) is NDArray or isinstance(a, NDArray)
               for a in args)
    if flat:
        spec = _FLAT_SPECS.get(len(args))
        if spec is None:
            spec = _FLAT_SPECS[len(args)] = _ArgSpec(
                ("list", [("arr", i) for i in range(len(args))]))
            spec.string  # pre-compute: shared objects must stay frozen
        return list(args), spec
    leaves = []

    def walk(x):
        if isinstance(x, NDArray):
            leaves.append(x)
            return ("arr", len(leaves) - 1)
        if isinstance(x, (list, tuple)):
            return (type(x).__name__, [walk(v) for v in x])
        if isinstance(x, dict):
            return ("dict", [(k, walk(v)) for k, v in sorted(x.items())])
        return ("static", x)

    return leaves, _ArgSpec(walk(list(args)))


def _rebuild(spec, leaves):
    if isinstance(spec, _ArgSpec):
        spec = spec.tree
    kind, payload = spec
    if kind == "arr":
        return leaves[payload]
    if kind == "static":
        return payload
    if kind == "dict":
        return {k: _rebuild(v, leaves) for k, v in payload}
    seq = [_rebuild(v, leaves) for v in payload]
    return tuple(seq) if kind == "tuple" else seq


class Block:
    """Base class for all neural network layers and models."""

    def __init__(self):
        self._children: "OrderedDict[str, Block]" = OrderedDict()
        self._reg_params: "OrderedDict[str, Parameter]" = OrderedDict()
        self._forward_hooks = OrderedDict()
        self._forward_pre_hooks = OrderedDict()

    # -- registration --------------------------------------------------
    def __setattr__(self, name, value):
        children = self.__dict__.get("_children")
        reg = self.__dict__.get("_reg_params")
        global _PARAM_REBIND_EPOCH
        if isinstance(value, Block):
            if children is not None:
                if children.get(name) is not value:
                    # replacing a child swaps its whole parameter
                    # subtree out from under any compiled ancestor
                    _PARAM_REBIND_EPOCH += 1
                children[name] = value
            if reg is not None and reg.pop(name, None) is not None:
                _PARAM_REBIND_EPOCH += 1
        elif isinstance(value, Parameter):
            if reg is not None:
                if reg.get(name) is not value:
                    # a Parameter was rebound (share_parameters, tied
                    # weights): any CachedOp built against the old
                    # object is stale — bump the global epoch so every
                    # cache re-validates (cheap: rebinds are rare)
                    _PARAM_REBIND_EPOCH += 1
                reg[name] = value
            if children is not None and children.pop(name, None) \
                    is not None:
                _PARAM_REBIND_EPOCH += 1
        else:
            # overwriting a registered child/param with something else
            # de-registers it (otherwise collect_params keeps ghosts)
            if children is not None and children.pop(name, None) \
                    is not None:
                _PARAM_REBIND_EPOCH += 1
            if reg is not None and reg.pop(name, None) is not None:
                _PARAM_REBIND_EPOCH += 1
        super().__setattr__(name, value)

    def register_child(self, block, name=None):
        if name is None:
            name = str(len(self._children))
        self._children[name] = block
        return block

    def register_forward_hook(self, hook):
        key = len(self._forward_hooks)
        self._forward_hooks[key] = hook
        return _HookHandle(self._forward_hooks, key)

    def register_forward_pre_hook(self, hook):
        key = len(self._forward_pre_hooks)
        self._forward_pre_hooks[key] = hook
        return _HookHandle(self._forward_pre_hooks, key)

    # -- parameters ----------------------------------------------------
    def collect_params(self, select=None) -> ParameterDict:
        """All Parameters of this block and children, keyed by dotted
        attribute path (the reference's structured naming)."""
        import re
        out = ParameterDict()

        def walk(block, prefix):
            for name, p in block._reg_params.items():
                key = f"{prefix}{name}"
                p._structured_name = key
                out[key] = p
            for cname, child in block._children.items():
                walk(child, f"{prefix}{cname}.")

        walk(self, "")
        if select is not None:
            pat = re.compile(select)
            out = ParameterDict({k: v for k, v in out.items()
                                 if pat.match(k)})
        return out

    @property
    def params(self):
        return ParameterDict(self._reg_params)

    def share_parameters(self, shared):
        """Tie this block's Parameters to `shared` (a dict as returned
        by collect_params), matched by dotted attribute path relative
        to this block — the Parameter OBJECTS are shared, so later
        load_parameters on either model updates both (parity:
        reference gluon/block.py:791 share_parameters). Returns self.
        """
        import warnings
        if shared is None:
            return self
        if not isinstance(shared, dict):
            raise ValueError(
                f"'shared' should be in type of Dict. Get type "
                f"{type(shared)}!")
        shared_set = set(shared.keys())
        self._shared_parameters(shared, shared_set)
        for name in shared_set:
            warnings.warn(f"Parameter name {name} is not in the "
                          "current model!")
        return self

    def _shared_parameters(self, shared, shared_set, prefix=""):
        if prefix:
            prefix += "."
        for name in list(self._reg_params):
            key = prefix + name
            if shared.get(key) is not None:
                setattr(self, name, shared[key])
                shared_set.discard(key)
        for name, child in self._children.items():
            child._shared_parameters(shared, shared_set, prefix + name)
        # compiled graphs captured the pre-share Parameter objects; a
        # stale cache would keep training the orphaned originals
        if hasattr(self, "_clear_cached_op"):
            self._clear_cached_op()

    def initialize(self, init=None, device=None, ctx=None, verbose=False,
                   force_reinit=False):
        from .. import initializer as _init_mod
        default = _init_mod.Uniform()
        self.collect_params().initialize(
            init=None, device=device, ctx=ctx,
            default_init=init if init is not None else default,
            force_reinit=force_reinit)

    def zero_grad(self):
        self.collect_params().zero_grad()

    def reset_ctx(self, ctx):
        self.collect_params().reset_ctx(ctx)

    reset_device = reset_ctx

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for p in self._reg_params.values():
            p.cast(dtype)
        self._on_cast(dtype)

    def _on_cast(self, dtype):
        pass

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    # -- save/load -----------------------------------------------------
    def save_parameters(self, filename, deduplicate=False):
        from .. import utils_io
        params = self.collect_params()
        utils_io.save(filename, {k: v.data() for k, v in params.items()
                                 if v._data is not None})

    def load_parameters(self, filename, device=None, ctx=None,
                        allow_missing=False, ignore_extra=False,
                        cast_dtype=False, dtype_source="current"):
        from .. import utils_io
        loaded = utils_io.load(filename)
        params = self.collect_params()
        if not allow_missing:
            for name, p in params.items():
                if name not in loaded:
                    raise AssertionError(
                        f"Parameter '{name}' is missing in '{filename}'")
        for name, val in loaded.items():
            if name not in params:
                if not ignore_extra:
                    raise AssertionError(
                        f"Parameter '{name}' loaded from '{filename}' is "
                        "not present in the Block")
                continue
            if cast_dtype:
                params[name].cast(val.dtype if dtype_source == "saved"
                                  else params[name].dtype)
            p = params[name]
            val = _maybe_transpose_conv_kernel(name, p, val)
            p.set_data(val)

    def save(self, prefix):
        self.save_parameters(f"{prefix}-model.params")

    def load(self, prefix):
        self.load_parameters(f"{prefix}-model.params")

    # -- execution -----------------------------------------------------
    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks.values():
            hook(self, args)
        out = self.forward(*args, **kwargs)
        for hook in self._forward_hooks.values():
            hook(self, args, out)
        return out

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def summary(self, *inputs):
        """Print a per-layer summary (parity: Block.summary)."""
        summary = []

        def hook(block, ins, out):
            shapes = [o.shape for o in (out if isinstance(out, (list, tuple))
                                        else [out]) if isinstance(o, NDArray)]
            n_params = sum(
                int(onp.prod(p.shape)) for p in block._reg_params.values()
                if p._shape_known())
            summary.append((type(block).__name__, shapes, n_params))

        handles = []
        for blk in self._iter_blocks():
            handles.append(blk.register_forward_hook(hook))
        try:
            self(*inputs)
        finally:
            for h in handles:
                h.remove()
        print(f"{'Layer':<30}{'Output Shape':<30}{'Params':<15}")
        print("=" * 75)
        total = 0
        for name, shapes, n in summary:
            print(f"{name:<30}{str(shapes):<30}{n:<15}")
            total += n
        print("=" * 75)
        print(f"Total params: {total}")

    def _iter_blocks(self):
        yield self
        for child in self._children.values():
            yield from child._iter_blocks()

    def __repr__(self):
        s = f"{type(self).__name__}(\n"
        for name, child in self._children.items():
            child_repr = repr(child).replace("\n", "\n  ")
            s += f"  ({name}): {child_repr}\n"
        return s + ")"


class _HookHandle:
    def __init__(self, hooks, key):
        self._hooks = hooks
        self._key = key

    def remove(self):
        self._hooks.pop(self._key, None)

    # the reference's HookHandle spells it detach() (gluon/utils.py)
    detach = remove


class _CachedEntry:
    __slots__ = ("fwd", "fwd_vjp", "bwd", "out_spec", "aux_targets",
                 "param_nds", "params", "in_spec", "epoch", "compiled",
                 "fwd_aot")


class CachedOp:
    """Whole-graph compiled executor for a HybridBlock (parity:
    src/imperative/cached_op.cc — here the 'graph passes + memory plan +
    bulked exec' pipeline is XLA compilation)."""

    def __init__(self, block: "HybridBlock"):
        self.block = block
        self._entries = {}

    def _signature(self, leaves, spec, training):
        # spec.string is cached on the spec object (interned for flat
        # all-NDArray calls), so steady-state dispatch never re-reprs
        # the nest — timed as gluon.cachedop.signature by callers
        return (tuple((l.shape, str(l.dtype)) for l in leaves),
                spec.string, training)

    def _build(self, leaves, spec, training):
        block = self.block
        params = [p for p in block.collect_params().values()]
        # Deferred params: infer shapes with an abstract trace (no FLOPs).
        if any(p._data is None for p in params):
            self._abstract_init(leaves, spec)
            params = [p for p in block.collect_params().values()]
        param_nds = [p.data() for p in params]

        out_box = {}
        aux_box = {}

        def raw_fn(key, param_datas, input_datas):
            saved = [nd._data for nd in param_nds]
            in_nds = [NDArray(d, ctx=l.ctx) for d, l in
                      zip(input_datas, leaves)]
            scope = _deferred.trace_scope()
            rec = autograd._RecordingScope(False, training)
            with scope, rec, trace_rng(key):
                for nd, d in zip(param_nds, param_datas):
                    nd._data = d
                try:
                    out = block.forward(*_rebuild(spec, in_nds))
                finally:
                    for nd, s in zip(param_nds, saved):
                        nd._data = s
            out_leaves, out_spec = _flatten_arrays(
                out if isinstance(out, tuple) else (out,))
            out_box["spec"] = out_spec
            out_box["single"] = not isinstance(out, tuple)
            aux_box["targets"] = [nd for nd, _ in scope.state_updates]
            aux = tuple(t for _, t in scope.state_updates)
            return tuple(l._data for l in out_leaves), aux

        entry = _CachedEntry()
        entry.in_spec = spec
        entry.params = params
        entry.param_nds = param_nds
        entry.epoch = _PARAM_REBIND_EPOCH
        entry.fwd = jax.jit(raw_fn)
        entry.fwd_vjp = jax.jit(
            lambda key, p, i: jax.vjp(
                lambda pp, ii: raw_fn(key, pp, ii), p, i, has_aux=True))
        entry.bwd = jax.jit(lambda vjp, ct: vjp(ct))
        # which of the lazily-jitted callables has been dispatched:
        # fwd and fwd_vjp compile independently on first use
        entry.compiled = set()
        entry.fwd_aot = None
        entry.out_spec = out_box
        entry.aux_targets = aux_box
        return entry

    def _abstract_init(self, leaves, spec):
        """Finish deferred parameter init by running one eager forward on
        a batch-of-1 slice (parity: the reference also runs the first
        forward imperatively inside _build_cache, block.py:1095).

        Deferred init cannot run inside a jax trace (initializer RNG
        would be staged out as tracers), so this is deliberately eager;
        the batch-1 slice keeps the wasted compute negligible.
        """
        block = self.block
        probes = []
        for l in leaves:
            if l.ndim > 0 and l.shape[0] > 1:
                probes.append(l[0:1])
            else:
                probes.append(l)
        # trace_scope also keeps child HybridBlocks on their plain
        # forward path (no nested CachedOp builds during the probe)
        with autograd._RecordingScope(False, False), _deferred.trace_scope():
            try:
                block.forward(*_rebuild(spec, probes))
            except Exception:
                # the batch-1 slice assumes every leaf carries batch on
                # axis 0 — false for e.g. RNN states ((layers, batch,
                # hidden), batch on axis 1), whose consumers then see
                # inconsistent shapes. Re-probe with the full-size
                # arrays: one wasted eager forward, always consistent.
                block.forward(*_rebuild(spec, leaves))

    def warmup(self, *args, training=False):
        """AOT-compile the forward for these template inputs via
        ``jit.lower(...).compile()``, moving trace + XLA compile off
        the first real call (and, with ``JAX_COMPILATION_CACHE_DIR``
        set, replaying the compile from the persistent cache across
        process restarts). Only the inference program (``fwd``) is
        AOT-compiled; a recording-path first dispatch still benefits
        from the persistent cache. Telemetry:
        ``gluon.cachedop.aot_compile`` (ms)."""
        leaves, spec = _flatten_arrays(args)
        key_sig = self._signature(leaves, spec, training)
        entry = self._entries.get(key_sig)
        if entry is self._DYNAMIC:
            return self
        if entry is None:
            telemetry.counter("gluon.cachedop.cache_miss")
            t0 = telemetry.clock()
            try:
                entry = self._build(leaves, spec, training)
            except self._dynamic_errors():
                self._entries[key_sig] = self._DYNAMIC
                return self
            telemetry.duration_since("gluon.cachedop.build", t0)
            self._entries[key_sig] = entry
        if entry.fwd_aot is None:
            param_datas = [nd._data for nd in entry.param_nds]
            abstract = [jax.ShapeDtypeStruct(l.shape, l.dtype)
                        for l in leaves]
            t0 = telemetry.clock()
            try:
                lowered = entry.fwd.lower(next_key(), param_datas,
                                          abstract)
                with compile_cache.measure():
                    entry.fwd_aot = lowered.compile()
            except self._dynamic_errors():
                self._entries[key_sig] = self._DYNAMIC
                return self
            telemetry.duration_since("gluon.cachedop.aot_compile", t0)
            entry.compiled.add("fwd")
        return self

    # sentinel: this signature contains a data-dependent-shape op and
    # must execute imperatively (reference: CachedOp's dynamic-shape
    # graphs skip static planning and run op-by-op, cached_op.cc:707)
    _DYNAMIC = "dynamic"

    @staticmethod
    def _dynamic_errors():
        import jax.errors as jerr
        return (jerr.TracerArrayConversionError,
                jerr.ConcretizationTypeError,
                jerr.TracerBoolConversionError,
                jerr.TracerIntegerConversionError,
                jerr.NonConcreteBooleanIndexError)

    def _dynamic_fallback(self, key_sig, args, err):
        """A data-dependent-shape op (boolean_mask, nonzero, dynamic
        indexing) cannot live inside one static XLA program; remember
        the signature and run the forward imperatively from now on —
        each primitive still jit-compiles, autograd records normally.
        """
        import warnings
        if not getattr(self, "_warned_dynamic", False):
            self._warned_dynamic = True
            warnings.warn(
                f"{type(self.block).__name__}: forward contains a "
                "data-dependent-shape op; hybridize falls back to "
                "imperative execution for this block "
                f"({type(err).__name__})")
        telemetry.counter("gluon.cachedop.dynamic_fallback")
        self._entries[key_sig] = self._DYNAMIC
        return self.block.forward(*args)

    def __call__(self, *args):
        leaves, spec = _flatten_arrays(args)
        training = autograd.is_training()
        # bucketing: pad an off-bucket batch up to its bucket and slice
        # the outputs back, so variable batch sizes (the odd last batch
        # of an epoch, ragged inference requests) reuse ONE compiled
        # entry instead of rebuilding. Inference path only — under
        # recording, input gradients would come back padded — and only
        # for batch-decoupled outputs (leaves carrying the batch dim).
        pad_n, orig_bsz = 0, None
        policy = _bucketing.get_policy()
        if policy is not None and not autograd.is_recording():
            orig_bsz = next((l.shape[0] for l in leaves if l.ndim), None)
            if orig_bsz is not None and all(
                    l.shape[0] == orig_bsz for l in leaves if l.ndim):
                target = policy.bucket(orig_bsz)
                if target > orig_bsz:
                    telemetry.counter("gluon.cachedop.bucket_pad")
                    leaves, pad_n = _bucketing.pad_leaves(
                        leaves, target, orig_bsz)
        t_sig = telemetry.clock()
        key_sig = self._signature(leaves, spec, training)
        telemetry.duration_since("gluon.cachedop.signature", t_sig)
        entry = self._entries.get(key_sig)
        if entry is self._DYNAMIC:
            return self.block.forward(*args)
        if entry is not None and entry.epoch != _PARAM_REBIND_EPOCH:
            # Some Parameter somewhere was rebound since this entry
            # compiled (share_parameters on ANY block, incl. a child
            # whose ancestor holds this cache). Re-validate against the
            # live parameter set and rebuild on mismatch.
            current = list(self.block.collect_params().values())
            if [id(p) for p in current] != [id(p) for p in entry.params]:
                self._entries.clear()
                entry = None
            else:
                entry.epoch = _PARAM_REBIND_EPOCH
        if entry is not None and any(
                p._data is not nd for p, nd in
                zip(entry.params, entry.param_nds)):
            # A Parameter was rebound (cast/reset_ctx) after the graph
            # was compiled; the entry holds stale buffers — rebuild.
            self._entries.clear()
            entry = None
        if entry is None:
            # cache miss: build a fresh whole-graph program (jit is
            # lazy — the XLA compile itself lands on this call's
            # execute below and is timed as gluon.cachedop.compile)
            telemetry.counter("gluon.cachedop.cache_miss")
            t0 = telemetry.clock()
            try:
                entry = self._build(leaves, spec, training)
            except self._dynamic_errors() as e:
                return self._dynamic_fallback(key_sig, args, e)
            telemetry.duration_since("gluon.cachedop.build", t0)
            self._entries[key_sig] = entry
        else:
            telemetry.counter("gluon.cachedop.cache_hit")

        key = next_key()
        param_datas = [nd._data for nd in entry.param_nds]
        input_datas = [l._data for l in leaves]

        # mesh-aware hybridize: if a global mesh is active (e.g. an sp
        # layer shard_maps inside the graph), operands must live on the
        # mesh — replicate any that don't (no-op once installed)
        from .. import parallel as _parallel
        mesh = _parallel.get_mesh()
        if mesh is not None and mesh.devices.size > 1:
            import jax.numpy as _jnp  # noqa: F401
            from jax.sharding import NamedSharding, PartitionSpec as _P
            rep = NamedSharding(mesh, _P())

            def place(d):
                sh = getattr(d, "sharding", None)
                if sh is not None and getattr(sh, "mesh", None) == mesh:
                    return d
                return jax.device_put(d, rep)

            key = place(key)
            param_datas = [place(d) for d in param_datas]
            input_datas = [place(d) for d in input_datas]
            for nd, d in zip(entry.param_nds, param_datas):
                nd._data = d
        recording = autograd.is_recording() and (
            any(nd._grad_req != "null" for nd in entry.param_nds)
            or any(autograd._on_tape(l) for l in leaves))

        # fwd and fwd_vjp are distinct lazily-jitted programs: either
        # one's FIRST dispatch pays trace + XLA compile (recorded as
        # 'compile') — unless warmup() AOT-compiled fwd, which makes
        # dispatch a plain enqueue; later dispatches measure async
        # enqueue cost only
        jit_kind = "fwd_vjp" if recording else "fwd"
        first_dispatch = jit_kind not in entry.compiled
        t0 = telemetry.clock()
        try:
            if recording:
                with compile_cache.measure() if first_dispatch \
                        else _nullcontext():
                    outs_raw, vjp, aux = entry.fwd_vjp(
                        key, param_datas, input_datas)
            elif entry.fwd_aot is not None:
                try:
                    outs_raw, aux = entry.fwd_aot(key, param_datas,
                                                  input_datas)
                except (TypeError, ValueError):
                    # aval mismatch vs. the warmed signature: drop the
                    # AOT executable and take the lazy jit path — its
                    # first dispatch here pays a real trace+compile
                    # (warmup marked 'fwd' compiled for the AOT path),
                    # so label and classify it as one
                    telemetry.counter("gluon.cachedop.aot_fallback")
                    entry.fwd_aot = None
                    first_dispatch = True
                    with compile_cache.measure():
                        outs_raw, aux = entry.fwd(key, param_datas,
                                                  input_datas)
            else:
                with compile_cache.measure() if first_dispatch \
                        else _nullcontext():
                    outs_raw, aux = entry.fwd(key, param_datas,
                                              input_datas)
        except self._dynamic_errors() as e:
            return self._dynamic_fallback(key_sig, args, e)
        entry.compiled.add(jit_kind)
        telemetry.duration_since(
            "gluon.cachedop.compile" if first_dispatch else
            "gluon.cachedop.run", t0)

        # write back aux state (BN running stats etc.)
        targets = entry.aux_targets.get("targets", [])
        with autograd.pause():
            for nd, new in zip(targets, aux):
                nd._install(new)

        ctx = leaves[0].ctx if leaves else current_context()
        out_nds = [NDArray(engine.track(o), ctx=ctx) for o in outs_raw]
        if pad_n:
            # slice the padded rows back off every output that carries
            # the (padded) batch on axis 0
            padded = orig_bsz + pad_n
            out_nds = [nd[0:orig_bsz]
                       if nd.ndim and nd.shape[0] == padded else nd
                       for nd in out_nds]

        if recording:
            tape_inputs = entry.param_nds + leaves
            n_out = len(out_nds)

            def vjp_fn(cotangent, _entry=entry, _n=n_out):
                cts = cotangent if isinstance(cotangent, tuple) else \
                    (cotangent,)
                pgrads, igrads = _entry.bwd(vjp, tuple(cts))
                return tuple(list(pgrads) + list(igrads))

            # Replayable forward for create_graph: re-runs the compiled
            # graph (same RNG key → deterministic replay) over raw
            # buffers in tape-input order, so autograd._replay_vjp can
            # jax.vjp through it for grad-of-grad on hybridized blocks
            # (parity: python/mxnet/autograd.py:245 create_graph support
            # through CachedOp).
            n_params = len(entry.param_nds)

            def replay_fn(*raws, _entry=entry, _key=key, _np=n_params):
                outs, _aux = _entry.fwd(_key, list(raws[:_np]),
                                        list(raws[_np:]))
                return tuple(outs)

            autograd._record(f"CachedOp_{type(self.block).__name__}",
                             replay_fn, vjp_fn, tape_inputs, out_nds)

        result = _rebuild(entry.out_spec["spec"], out_nds)
        if entry.out_spec["single"]:
            return result[0]
        return result

    def infer(self, *args):
        """Slim inference-only dispatch (the serving fast path).

        Skips everything ``__call__`` does for the training/recording
        world — recording checks, tape setup, mesh placement — and
        goes straight from signature to the AOT-compiled forward
        (``fwd_aot``, see ``warmup``). Any condition the fast path
        can't honor exactly (cache miss, rebound params, recording
        active, a live mesh, a global bucketing policy, an AOT aval
        mismatch) falls back to ``__call__``, which handles it; for
        any given call the two paths run the SAME compiled program,
        so results are bit-identical. Callers wanting zero
        steady-state compiles must ``warmup()`` their signatures
        first.
        """
        if _bucketing.get_policy() is not None:
            # a global policy pads __call__ to a bucket width; the
            # fast path must not dispatch a DIFFERENT width for the
            # same inputs (bit-identity is per compiled width) — take
            # the full path, which applies the policy exactly. The
            # serving engine pads batches itself and never installs a
            # global policy, so its dispatches stay on the fast path.
            return self(*args)
        leaves, spec = _flatten_arrays(args)
        t_sig = telemetry.clock()
        key_sig = self._signature(leaves, spec, False)
        telemetry.duration_since("gluon.cachedop.signature", t_sig)
        entry = self._entries.get(key_sig)
        if (entry is None or entry is self._DYNAMIC
                or entry.fwd_aot is None
                or autograd.is_recording() or autograd.is_training()):
            return self(*args)
        if entry.epoch != _PARAM_REBIND_EPOCH or any(
                p._data is not nd for p, nd in
                zip(entry.params, entry.param_nds)):
            return self(*args)  # stale entry: full path re-validates
        from .. import parallel as _parallel
        if _parallel.get_mesh() is not None:
            return self(*args)  # mesh placement lives on the full path
        telemetry.counter("gluon.cachedop.infer")
        t0 = telemetry.clock()
        try:
            outs_raw, aux = entry.fwd_aot(
                next_key(), [nd._data for nd in entry.param_nds],
                [l._data for l in leaves])
        except (TypeError, ValueError):
            # aval mismatch vs. the warmed signature — let the full
            # path run its lazy-jit fallback and telemetry
            return self(*args)
        telemetry.duration_since("gluon.cachedop.run", t0)
        targets = entry.aux_targets.get("targets", [])
        if targets:
            with autograd.pause():
                for nd, new in zip(targets, aux):
                    nd._install(new)
        ctx = leaves[0].ctx if leaves else current_context()
        out_nds = [NDArray(engine.track(o), ctx=ctx) for o in outs_raw]
        result = _rebuild(entry.out_spec["spec"], out_nds)
        if entry.out_spec["single"]:
            return result[0]
        return result


class HybridBlock(Block):
    """A Block that can be hybridized into a compiled graph."""

    def __init__(self):
        super().__init__()
        self._active = False
        self._cached_op: CachedOp | None = None

    def hybridize(self, active=True, **kwargs):
        self._active = active
        self._cached_op = None
        super().hybridize(active, **kwargs)

    def _clear_cached_op(self):
        self._cached_op = None

    def _on_cast(self, dtype):
        # compiled graphs captured the old-dtype buffers
        self._clear_cached_op()

    def optimize_for(self, x, *args, backend=None, **kwargs):
        """Parity shim: backend partitioning is XLA itself."""
        self.hybridize(True)
        return self(x, *args)

    def infer_shape(self, *args):
        """Run deferred shape inference without compute."""
        leaves, spec = _flatten_arrays(args)
        CachedOp(self)._abstract_init(leaves, spec)

    def warmup(self, *args, training=False):
        """Hybridize + AOT-compile the graph for these template inputs
        ahead of the first real call (see CachedOp.warmup). Pair with
        ``JAX_COMPILATION_CACHE_DIR`` to make the compile survive
        process restarts."""
        if not self._active:
            self.hybridize(True)
        if self._cached_op is None:
            self._cached_op = CachedOp(self)
        self._cached_op.warmup(*args, training=training)
        return self

    def infer(self, *args):
        """Inference fast path: dispatch the AOT-compiled forward with
        none of the recording-path setup (see ``CachedOp.infer``).
        Forward hooks are NOT run — this is the entry the serving
        engine (`mxnet_tpu.serving`) uses under its batcher thread.
        Falls back to the full ``__call__`` path whenever the fast
        path can't honor the call exactly."""
        if not self._active:
            self.hybridize(True)
        if self._cached_op is None:
            self._cached_op = CachedOp(self)
        return self._cached_op.infer(*args)

    def __call__(self, *args, **kwargs):
        # Only the OUTERMOST active block owns a CachedOp; children
        # invoked inside a parent's trace (or its deferred-init probe)
        # run their plain forward so the whole model lowers into ONE
        # XLA program (parity: nested blocks inline into the parent's
        # deferred-compute graph in the reference).
        if self._active and not kwargs and not _deferred.is_tracing():
            for hook in self._forward_pre_hooks.values():
                hook(self, args)
            if self._cached_op is None:
                self._cached_op = CachedOp(self)
            out = self._cached_op(*args)
            for hook in self._forward_hooks.values():
                hook(self, args, out)
            return out
        return super().__call__(*args, **kwargs)

    def export(self, path, epoch=0, remove_amp_cast=True):
        """Serialize for deployment: params + compiled-graph artifact.

        The reference writes `-symbol.json` + `-NNNN.params`
        (block.py:1471), reloaded by SymbolBlock.imports (:1670). Here
        the graph IR is StableHLO via jax.export: `-symbol.mxir` holds
        the serialized program, `-symbol.json` a manifest, and
        SymbolBlock.imports reloads the pair. A human-readable
        `-symbol.stablehlo` dump is written alongside.

        Requires one prior hybridized forward (the reference likewise
        exports the first cached graph).
        """
        import json as _json
        from jax import export as jax_export

        params_file = f"{path}-{epoch:04d}.params"
        self.save_parameters(params_file)
        if self._cached_op is None or not self._cached_op._entries:
            raise RuntimeError(
                "export requires a hybridized forward call first "
                "(net.hybridize(); net(x))")
        # export the INFERENCE graph: a training-mode entry would bake
        # dropout masks / batch statistics into the artifact. Dynamic-
        # fallback sentinels are not compiled graphs and cannot export.
        static_entries = {s: e for s, e in
                          self._cached_op._entries.items()
                          if e is not CachedOp._DYNAMIC}
        if not static_entries:
            raise RuntimeError(
                "export: this block's forward contains a data-"
                "dependent-shape op (boolean_mask / dynamic indexing) "
                "and runs imperatively; there is no static graph to "
                "export. Rewrite the dynamic op (e.g. mask + where) "
                "to make the block exportable.")
        sig = entry = None
        for s, e in static_entries.items():
            if not s[2]:  # signature = (shapes, spec, training)
                sig, entry = s, e
                break
        if entry is None:
            tsig, tentry = next(iter(static_entries.items()))
            probe_leaves = [NDArray(jax.numpy.zeros(s, onp.dtype(d)))
                            for s, d in tsig[0]]
            entry = self._cached_op._build(probe_leaves, tentry.in_spec,
                                           training=False)
            sig = (tsig[0], tsig[1], False)
            self._cached_op._entries[sig] = entry
        shapes = sig[0]
        key = jax.random.PRNGKey(0)
        params = [nd._data for nd in entry.param_nds]

        ins = tuple(jax.ShapeDtypeStruct(s, onp.dtype(d))
                    for s, d in shapes)
        pspecs = tuple(jax.ShapeDtypeStruct(p.shape, p.dtype)
                       for p in params)
        jitted = jax.jit(lambda p, i: entry.fwd(key, p, i)[0])
        exported = jax_export.export(jitted)(pspecs, ins)
        mxir_file = f"{path}-symbol.mxir"
        # vjp_order=1 ships the backward program too, so the imported
        # artifact is fine-tunable (parity: the reference's imported
        # SymbolBlock trains; see _ExportedBlock.forward). Integer or
        # otherwise non-differentiable graphs fall back to fwd-only.
        try:
            blob = exported.serialize(vjp_order=1)
        except Exception:  # noqa: BLE001 - fwd-only artifact still valid
            blob = exported.serialize()
        with open(mxir_file, "wb") as f:
            f.write(blob)
        hlo_file = f"{path}-symbol.stablehlo"
        with open(hlo_file, "w") as f:
            f.write(jitted.lower(pspecs, ins).as_text())
        names = list(self.collect_params().keys())
        manifest = {
            "format": "jax.export",
            "artifact": os.path.basename(mxir_file),
            "params": os.path.basename(params_file),
            "param_names": names,
            "param_dtypes": [str(onp.dtype(p.dtype)) for p in params],
            "n_outputs": len(exported.out_avals),
            "input_shapes": [list(s) for s, _ in shapes],
            "input_dtypes": [str(d) for _, d in shapes],
        }
        sym_file = f"{path}-symbol.json"
        with open(sym_file, "w") as f:
            _json.dump(manifest, f, indent=2)
        return sym_file, params_file

    def forward(self, *args, **kwargs):
        raise NotImplementedError
