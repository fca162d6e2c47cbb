"""Optimizers (parity: python/mxnet/optimizer/, 22 classes; fused update
kernels src/operator/optimizer_op.cc, contrib/multi_lamb.cc etc.).

TPU-native design: every optimizer defines a pure functional step
``_step(w, g, state, hyper) -> (new_w, new_state)`` over raw jax arrays.
Steps are jit-compiled once per (optimizer, shape, dtype) — the fused
single-kernel update the reference hand-writes in CUDA falls out of XLA
fusion. Scalar hyperparameters (lr, wd, ...) are passed as traced
scalars so changing the learning rate never triggers recompilation.

Mixed precision (parity: *_mp_* update ops): when a weight is
float16/bfloat16 and multi_precision=True, the state carries an fp32
master copy; math runs in fp32 and the bf16 weight is a cast of the
master.
"""
from __future__ import annotations

import functools

import numpy as onp
import jax
import jax.numpy as jnp

from ..ndarray.ndarray import NDArray
from .. import engine
from ..random_state import next_key

__all__ = ["Optimizer", "create", "register", "SGD", "NAG", "Adam", "AdamW",
           "Adamax", "Nadam", "AdaBelief", "RMSProp", "AdaGrad",
           "GroupAdaGrad", "AdaDelta", "Ftrl", "FTML", "LAMB", "LARS",
           "LANS", "Signum", "SGLD", "DCASGD", "Test", "Updater",
           "get_updater"]

_REGISTRY = {}


def register(klass):
    _REGISTRY[klass.__name__.lower()] = klass
    return klass


def create(name, **kwargs):
    if isinstance(name, Optimizer):
        return name
    return _REGISTRY[name.lower()](**kwargs)


@functools.lru_cache(maxsize=None)
def _jitted_step(cls, mp):
    """One compiled update kernel per optimizer class (+mp flag)."""
    fn = cls._step_mp if mp else cls._step
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _jitted_multi_step(cls, mp):
    """One compiled MULTI-tensor update per optimizer class (+mp flag):
    applies ``cls._step`` to every parameter of a group inside one XLA
    program (the reference's multi_sgd_update/multi_lamb kernel family,
    here by construction instead of hand-written CUDA). Optimizer
    states are donated — they are trainer-internal, so the update
    rewrites them in place instead of allocating a second copy."""
    def multi(ws, gs, states, hypers):
        # hypers is one stacked (n,)-array per hyper field (not one
        # scalar per field per param): the host pays a handful of
        # device_puts per group instead of 5-8 per PARAMETER, which is
        # what made a 48-param dispatch slower than the loop it
        # replaced. Static indexing recovers the exact per-param
        # scalar, so the traced math is unchanged.
        new_ws, new_states = [], []
        for i, (w, g, s) in enumerate(zip(ws, gs, states)):
            h = cls._hyper_at(hypers, i)
            if mp:
                nw, ns = cls._step_mp(w, g, s, h)
            else:
                nw, ns = cls._step(w, jnp.asarray(g, w.dtype), s, h)
            new_ws.append(nw)
            new_states.append(ns)
        return tuple(new_ws), tuple(new_states)
    # weights are NOT donated: user code may hold live aliases of a
    # weight buffer (detach() snapshots, set_data-shared params) that
    # donation would invalidate, and the per-param path never donated
    # them either — memory profile is unchanged (the loop also
    # allocates fresh weight buffers). States are trainer-internal.
    return jax.jit(multi, donate_argnums=(2,))


class Optimizer:
    """Base optimizer (parity: mxnet.optimizer.Optimizer)."""

    def __init__(self, rescale_grad=1.0, param_idx2name=None, wd=0.0,
                 clip_gradient=None, learning_rate=None, lr_scheduler=None,
                 multi_precision=False, param_dict=None, aggregate_num=0,
                 use_fused_step=True, **kwargs):
        self.rescale_grad = rescale_grad
        self.lr = learning_rate if learning_rate is not None else 0.01
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None and learning_rate is not None:
            self.lr_scheduler.base_lr = learning_rate
        self.wd = wd
        self.clip_gradient = clip_gradient
        self.multi_precision = multi_precision
        self.aggregate_num = aggregate_num
        self.param_dict = param_dict or {}
        self.idx2name = dict(param_idx2name or {})
        self.lr_mult = {}
        self.wd_mult = {}
        self.begin_num_update = 0
        self.num_update = 0
        self._index_update_count = {}

    # -- lr/wd plumbing ------------------------------------------------
    @property
    def learning_rate(self):
        if self.lr_scheduler is not None:
            return self.lr_scheduler(self.num_update)
        return self.lr

    def set_learning_rate(self, lr):
        if self.lr_scheduler is not None:
            raise UserWarning("LRScheduler of the optimizer has already been "
                              "defined. Note that set_learning_rate can mutate "
                              "the value of the learning rate of the optimizer "
                              "only when the LRScheduler of the optimizer is "
                              "undefined.")
        self.lr = lr

    def set_lr_mult(self, args_lr_mult):
        self.lr_mult = dict(args_lr_mult)

    def set_wd_mult(self, args_wd_mult):
        self.wd_mult = dict(args_wd_mult)

    def _update_count(self, index):
        if not isinstance(index, (list, tuple)):
            index = [index]
        for idx in index:
            if idx not in self._index_update_count:
                self._index_update_count[idx] = self.begin_num_update
            self._index_update_count[idx] += 1
            self.num_update = max(self._index_update_count[idx],
                                  self.num_update)

    def _get_lr(self, index):
        lr = self.learning_rate
        if index in self.param_dict:
            p = self.param_dict[index]
            lr *= getattr(p, "lr_mult", 1.0)
        elif index in self.lr_mult:
            lr *= self.lr_mult[index]
        elif index in self.idx2name:
            lr *= self.lr_mult.get(self.idx2name[index], 1.0)
        return lr

    def _get_wd(self, index):
        wd = self.wd
        if index in self.param_dict:
            p = self.param_dict[index]
            wd *= getattr(p, "wd_mult", 1.0)
        elif index in self.wd_mult:
            wd *= self.wd_mult[index]
        elif index in self.idx2name:
            wd *= self.wd_mult.get(self.idx2name[index], 1.0)
        return wd

    # -- state ---------------------------------------------------------
    def _use_mp(self, weight):
        return self.multi_precision and (
            weight.dtype == onp.float16 or str(weight.dtype) == "bfloat16")

    def create_state(self, index, weight):
        """Return the optimizer state pytree (raw jax arrays) for weight."""
        return ()

    def create_state_multi_precision(self, index, weight):
        if self._use_mp(weight):
            master = jnp.asarray(weight._data, jnp.float32)
            return (master, self.create_state(index, NDArray(master)))
        return self.create_state(index, weight)

    def _migrate_state(self, state):
        """Hook for adapting serialized states from an older layout
        (Updater.set_states); default: unchanged."""
        return state

    def __setstate__(self, d):
        """Unpickling restores __dict__ without __init__, so instances
        serialized before a hyperparameter existed would lack it. Fill
        missing attributes from the class __init__ defaults — one fix
        for every optimizer and every future added knob."""
        import inspect
        self.__dict__.update(d)
        for klass in type(self).__mro__:
            ctor = klass.__dict__.get("__init__")
            if ctor is None:
                continue
            for name, p in inspect.signature(ctor).parameters.items():
                if p.default is inspect.Parameter.empty:
                    continue
                if name not in self.__dict__ and not name.startswith("_"):
                    self.__dict__.setdefault(name, p.default)

    # -- hypers passed into the jitted step ----------------------------
    def _hyper(self, index):
        t = self._index_update_count.get(index, self.num_update)
        return {
            "lr": onp.float32(self._get_lr(index)),
            "wd": onp.float32(self._get_wd(index)),
            "rescale": onp.float32(self.rescale_grad),
            "clip": (onp.float32(self.clip_gradient)
                     if self.clip_gradient is not None else None),
            "t": onp.int32(t),
        }

    def _stack_hypers(self, dicts, check=False):
        """The `_hyper` dicts of several parameters as ONE dict: each
        numpy scalar field becomes an ``(n,)`` numpy array of its own
        dtype (``t`` stays int32, the rest float32), so a compiled
        multi-parameter update takes a handful of host arguments, not
        5-8 per PARAMETER (each host scalar is a host-to-device
        transfer of its own). A field that is no numpy scalar — ``None``,
        or AdamW's Python-float ``correct`` — says how the update is
        traced, by presence or as a weakly typed scalar; it is the same
        for every parameter of an optimizer and passes through as it
        is (``check`` holds the optimizer to that: for whoever builds a
        program once, not for every step). `_hyper_at` is the inverse,
        inside the program."""
        first = dicts[0] if dicts else self._hyper(0)
        if check:
            for f, v0 in first.items():
                if not isinstance(v0, onp.generic) \
                        and any(d[f] != v0 for d in dicts):
                    raise TypeError(
                        f"{type(self).__name__}._hyper: field {f!r} "
                        f"differs between parameters but is no numpy "
                        f"scalar ({type(v0).__name__}); only numpy "
                        f"scalars are stacked")
        return {f: (onp.array([d[f] for d in dicts], dtype=v0.dtype)
                    if isinstance(v0, onp.generic) else v0)
                for f, v0 in first.items()}

    @staticmethod
    def _hyper_at(hypers, i):
        """Parameter ``i``'s hyper dict out of `_stack_hypers`' (traced:
        a static index into each ``(n,)`` field, so the update sees the
        exact per-parameter scalar)."""
        return {f: v[i] if getattr(v, "ndim", 0) else v
                for f, v in hypers.items()}

    @staticmethod
    def _pre(g, w, hyper, wd_in_grad=True):
        """rescale → clip → (optionally) add L2 wd into the gradient."""
        g = g * hyper["rescale"]
        if hyper["clip"] is not None:
            g = jnp.clip(g, -hyper["clip"], hyper["clip"])
        if wd_in_grad:
            g = g + hyper["wd"] * w
        return g

    # -- update API (parity: update / update_multi_precision) ----------
    def update(self, index, weight, grad, state):
        if not isinstance(index, (list, tuple)):
            index, weight, grad, state = [index], [weight], [grad], [state]
        self._update_count(index)
        cls = type(self)
        for i, w, g, s in zip(index, weight, grad, state):
            hyper = self._hyper(i)
            new_w, new_s = _jitted_step(cls, False)(
                w._data, jnp.asarray(g._data, w._data.dtype), s, hyper)
            w._install(new_w)
            self._set_state(i, s, new_s)

    def update_multi_precision(self, index, weight, grad, state):
        if type(self).update is not Optimizer.update:
            # Optimizer subclasses with a custom update() (e.g. SGLD)
            # must not be silently replaced by the base jitted _step.
            return self.update(index, weight, grad, state)
        if not isinstance(index, (list, tuple)):
            index, weight, grad, state = [index], [weight], [grad], [state]
        self._update_count(index)
        cls = type(self)
        for i, w, g, s in zip(index, weight, grad, state):
            hyper = self._hyper(i)
            if self._use_mp(w) and isinstance(s, tuple) and len(s) == 2 and \
                    isinstance(s[0], jax.Array) and s[0].dtype == jnp.float32:
                new_w, new_s = _jitted_step(cls, True)(
                    w._data, g._data, s, hyper)
            else:
                new_w, new_s = _jitted_step(cls, False)(
                    w._data, jnp.asarray(g._data, w._data.dtype), s, hyper)
            w._install(new_w)
            self._set_state(i, s, new_s)

    def fused_update_multi_precision(self, index, weight, grad, state):
        """Multi-tensor update: ONE jitted, donation-friendly program
        per (dtype, multi-precision) group applies this optimizer's
        ``_step`` to all grouped parameters and their states at once
        (2 host dispatches per group instead of 2 per parameter).

        Bit-identical to calling ``update_multi_precision`` per
        parameter: the per-index hypers (lr_mult/wd_mult/update count)
        are computed the same way and the traced math is the same
        ``_step`` — XLA compiles N independent elementwise chains side
        by side. Optimizers overriding ``update()`` (e.g. SGLD) or
        ``update_multi_precision`` itself fall back to the
        per-parameter path, called exactly the way the non-fused
        Trainer loop calls it.

        Returns True when the multi-tensor path ran, False when it
        fell back (so callers label their timing correctly)."""
        if type(self).update is not Optimizer.update or \
                type(self).update_multi_precision is not \
                Optimizer.update_multi_precision:
            for i, w, g, st in zip(index, weight, grad, state):
                self.update_multi_precision([i], [w], [g], [st])
            return False
        cls = type(self)
        # count + hyper interleaved PER INDEX in list order — exactly
        # the per-param loop's sequence, so scheduler-driven lr reads
        # the same num_update even when per-index counts are unequal
        hyper_dicts = []
        for i in index:
            self._update_count([i])
            hyper_dicts.append(self._hyper(i))
        groups = {}
        for pos, (w, s) in enumerate(zip(weight, state)):
            mp = self._use_mp(w) and isinstance(s, tuple) \
                and len(s) == 2 and isinstance(s[0], jax.Array) \
                and s[0].dtype == jnp.float32
            groups.setdefault((str(w._data.dtype), mp), []).append(pos)
        for (_, mp), poss in groups.items():
            hypers = self._stack_hypers([hyper_dicts[p] for p in poss])
            ws = tuple(weight[p]._data for p in poss)
            gs = tuple(grad[p]._data for p in poss)
            ss = tuple(state[p] for p in poss)
            # donated (state) leaves must not alias: XLA rejects
            # donating one buffer twice. State pytrees can share
            # buffers (a user-built state, a loaded checkpoint) —
            # copy repeats once; steady-state steps see distinct
            # buffers and skip this. Weights are NOT donated (see
            # _jitted_multi_step), so weight aliasing is fine.
            seen = set()

            def _dealias(x):
                if isinstance(x, jax.Array):
                    if id(x) in seen:
                        return jnp.array(x, copy=True)
                    seen.add(id(x))
                return x
            ss = jax.tree_util.tree_map(_dealias, ss)
            new_ws, new_ss = _jitted_multi_step(cls, mp)(ws, gs, ss,
                                                         hypers)
            for p, nw, ns in zip(poss, new_ws, new_ss):
                weight[p]._install(nw)
                self._set_state(index[p], state[p], ns)
        return True

    def _set_state(self, index, old, new):
        # states are stored by the caller (Trainer/Updater hold the dict);
        # mutate the container in place when it is a list
        self._last_states = getattr(self, "_last_states", {})
        self._last_states[index] = new

    # The functional step; subclasses override. Default: plain SGD.
    @staticmethod
    def _step(w, g, state, hyper):
        g = Optimizer._pre(g, w, hyper)
        return w - hyper["lr"] * g, state

    @classmethod
    def _step_mp(cls, w, g, state, hyper):
        master, inner = state
        g32 = jnp.asarray(g, jnp.float32)
        new_master, new_inner = cls._step(master, g32, inner, hyper)
        return jnp.asarray(new_master, w.dtype), (new_master, new_inner)


@register
class Test(Optimizer):
    """Trivial optimizer used by tests (parity: mx.optimizer.Test)."""

    def create_state(self, index, weight):
        return (jnp.zeros_like(weight._data),)

    @staticmethod
    def _step(w, g, state, hyper):
        (acc,) = state
        g = Optimizer._pre(g, w, hyper)
        return w - hyper["lr"] * g, (acc + g,)


@register
class SGD(Optimizer):
    """SGD with momentum (parity: optimizer/sgd.py; kernels
    src/operator/optimizer_op.cc sgd_update/sgd_mom_update)."""

    def __init__(self, learning_rate=0.01, momentum=0.0, lazy_update=False,
                 **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return ()
        return (jnp.zeros_like(weight._data),)

    def _hyper(self, index):
        h = super()._hyper(index)
        h["momentum"] = onp.float32(self.momentum)
        return h

    @staticmethod
    def _step(w, g, state, hyper):
        g = Optimizer._pre(g, w, hyper)
        if not state:
            return w - hyper["lr"] * g, state
        (mom,) = state
        mom = hyper["momentum"] * mom - hyper["lr"] * g
        return w + mom, (mom,)


@register
class NAG(Optimizer):
    """Nesterov accelerated SGD (parity: optimizer/nag.py)."""

    def __init__(self, learning_rate=0.1, momentum=0.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum

    def create_state(self, index, weight):
        return (jnp.zeros_like(weight._data),)

    def _hyper(self, index):
        h = super()._hyper(index)
        h["momentum"] = onp.float32(self.momentum)
        return h

    @staticmethod
    def _step(w, g, state, hyper):
        g = Optimizer._pre(g, w, hyper)
        (mom,) = state
        mom = hyper["momentum"] * mom + g
        return w - hyper["lr"] * (g + hyper["momentum"] * mom), (mom,)


@register
class Adam(Optimizer):
    """Adam (parity: optimizer/adam.py)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, lazy_update=False, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def create_state(self, index, weight):
        return (jnp.zeros_like(weight._data), jnp.zeros_like(weight._data))

    def _hyper(self, index):
        h = super()._hyper(index)
        h.update(beta1=onp.float32(self.beta1), beta2=onp.float32(self.beta2),
                 eps=onp.float32(self.epsilon))
        return h

    @staticmethod
    def _step(w, g, state, hyper):
        g = Optimizer._pre(g, w, hyper)
        m, v = state
        b1, b2, t = hyper["beta1"], hyper["beta2"], hyper["t"]
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        coef1 = 1.0 - jnp.power(b1, t.astype(jnp.float32))
        coef2 = 1.0 - jnp.power(b2, t.astype(jnp.float32))
        lr_t = hyper["lr"] * jnp.sqrt(coef2) / coef1
        return w - lr_t * m / (jnp.sqrt(v) + hyper["eps"]), (m, v)


@register
class AdamW(Adam):
    """Adam with decoupled weight decay (parity: optimizer/adamW.py —
    the reference applies the wd term with the SAME bias-corrected lr,
    to the already-updated weight)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-6, correct_bias=True, **kwargs):
        super().__init__(learning_rate=learning_rate, beta1=beta1,
                         beta2=beta2, epsilon=epsilon, **kwargs)
        self.correct_bias = correct_bias

    def _hyper(self, index):
        h = super()._hyper(index)
        # None/1.0 keeps the flag a static pytree leaf (AdaBelief trick)
        h["correct"] = 1.0 if self.correct_bias else None
        return h

    @staticmethod
    def _step(w, g, state, hyper):
        g = Optimizer._pre(g, w, hyper, wd_in_grad=False)
        m, v = state
        b1, b2, t = hyper["beta1"], hyper["beta2"], hyper["t"]
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        lr_t = hyper["lr"]
        if hyper.get("correct") is not None:
            coef1 = 1.0 - jnp.power(b1, t.astype(jnp.float32))
            coef2 = 1.0 - jnp.power(b2, t.astype(jnp.float32))
            lr_t = lr_t * jnp.sqrt(coef2) / coef1
        w = w - lr_t * m / (jnp.sqrt(v) + hyper["eps"])
        return w - lr_t * hyper["wd"] * w, (m, v)


@register
class Adamax(Adam):
    """AdaMax (parity: optimizer/adamax.py)."""

    @staticmethod
    def _step(w, g, state, hyper):
        g = Optimizer._pre(g, w, hyper)
        m, u = state
        b1, b2, t = hyper["beta1"], hyper["beta2"], hyper["t"]
        m = b1 * m + (1 - b1) * g
        u = jnp.maximum(b2 * u, jnp.abs(g))
        lr_t = hyper["lr"] / (1.0 - jnp.power(b1, t.astype(jnp.float32)))
        return w - lr_t * m / (u + hyper["eps"]), (m, u)


@register
class Nadam(Adam):
    """Nesterov Adam (parity: optimizer/nadam.py — the reference's
    WARMING momentum schedule mu_t = b1*(1 - 0.5*0.96^(t*sd)) with the
    running product m_schedule carried as optimizer state, not the
    torch-style closed-form variant).

    Documented deviation: the reference keeps ONE m_schedule on the
    optimizer object, advanced once per parameter per step — with N
    parameters it grows by mu_t^N each step, coupling every
    parameter's bias correction to the parameter iteration order.
    Here m_schedule is per-parameter (advanced once per update), which
    matches the published algorithm and the reference's own single-
    parameter behavior exactly.
    """

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, schedule_decay=0.004, **kwargs):
        super().__init__(learning_rate=learning_rate, beta1=beta1,
                         beta2=beta2, epsilon=epsilon, **kwargs)
        self.schedule_decay = schedule_decay

    def create_state(self, index, weight):
        return (jnp.zeros_like(weight._data),
                jnp.zeros_like(weight._data),
                jnp.ones((), jnp.float32))  # running m_schedule

    def _migrate_state(self, state):
        # pre-round-5 checkpoints stored (m, v); append m_schedule=1.
        # A multi-precision state is (master, inner_tuple) — recurse.
        if isinstance(state, tuple) and len(state) == 2:
            if isinstance(state[1], tuple):
                return (state[0], self._migrate_state(state[1]))
            return state + (onp.ones((), onp.float32),)
        return state

    def _hyper(self, index):
        h = super()._hyper(index)
        h["sd"] = onp.float32(self.schedule_decay)
        return h

    @staticmethod
    def _step(w, g, state, hyper):
        g = Optimizer._pre(g, w, hyper)
        m, v, msched = state
        b1, b2, t = hyper["beta1"], hyper["beta2"], hyper["t"]
        tf = t.astype(jnp.float32)
        sd = hyper["sd"]
        coef2 = 1.0 - jnp.power(b2, tf)
        mu_t = b1 * (1.0 - 0.5 * jnp.power(0.96, tf * sd))
        mu_t1 = b1 * (1.0 - 0.5 * jnp.power(0.96, (tf + 1.0) * sd))
        msched = msched * mu_t
        msched_next = msched * mu_t1
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        g_prime = g / (1.0 - msched)
        m_prime = m / (1.0 - msched_next)
        v_prime = v / coef2
        m_bar = mu_t1 * m_prime + (1.0 - mu_t) * g_prime
        return w - hyper["lr"] * m_bar / (jnp.sqrt(v_prime)
                                          + hyper["eps"]), \
            (m, v, msched)


@register
class AdaBelief(Adam):
    """AdaBelief — second moment tracks the *surprise* ``(g - m)**2``
    instead of ``g**2`` (parity: optimizer/adabelief.py). The
    reference folds epsilon into the variance accumulator each step
    and adds it again in the denominator; kept for numeric parity."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-6, correct_bias=True, **kwargs):
        super().__init__(learning_rate=learning_rate, beta1=beta1,
                         beta2=beta2, epsilon=epsilon, **kwargs)
        self.correct_bias = correct_bias

    def _hyper(self, index):
        h = super()._hyper(index)
        # None/1.0 so the flag stays a static pytree leaf (same trick
        # as hyper["clip"]) — a bool leaf would be traced by jit
        h["correct"] = 1.0 if self.correct_bias else None
        return h

    @staticmethod
    def _step(w, g, state, hyper):
        g = Optimizer._pre(g, w, hyper)
        m, s = state
        b1, b2, t = hyper["beta1"], hyper["beta2"], hyper["t"]
        m = b1 * m + (1 - b1) * g
        s = b2 * s + (1 - b2) * jnp.square(g - m) + hyper["eps"]
        lr_t = hyper["lr"]
        if hyper["correct"] is not None:
            tf = t.astype(jnp.float32)
            lr_t = lr_t * jnp.sqrt(1.0 - jnp.power(b2, tf)) \
                / (1.0 - jnp.power(b1, tf))
        return w - lr_t * m / (jnp.sqrt(s) + hyper["eps"]), (m, s)


@register
class RMSProp(Optimizer):
    """RMSProp, optionally centered (parity: optimizer/rmsprop.py)."""

    def __init__(self, learning_rate=0.001, rho=0.9, momentum=0.9,
                 epsilon=1e-8, centered=False, clip_weights=None, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.rho, self.momentum, self.epsilon = rho, momentum, epsilon
        self.centered = centered

    def create_state(self, index, weight):
        def z():
            return jnp.zeros_like(weight._data)
        if self.centered:
            # three DISTINCT buffers: the fused update donates states,
            # and one buffer may not be donated twice
            return (z(), z(), z())  # n, g_avg, delta
        return (z(),)

    def _hyper(self, index):
        h = super()._hyper(index)
        h.update(rho=onp.float32(self.rho), mom=onp.float32(self.momentum),
                 eps=onp.float32(self.epsilon))
        return h

    @staticmethod
    def _step(w, g, state, hyper):
        g = Optimizer._pre(g, w, hyper)
        rho, eps = hyper["rho"], hyper["eps"]
        if len(state) == 1:
            (n,) = state
            n = rho * n + (1 - rho) * jnp.square(g)
            return w - hyper["lr"] * g / jnp.sqrt(n + eps), (n,)
        n, gavg, delta = state
        n = rho * n + (1 - rho) * jnp.square(g)
        gavg = rho * gavg + (1 - rho) * g
        delta = hyper["mom"] * delta - hyper["lr"] * g / \
            jnp.sqrt(n - jnp.square(gavg) + eps)
        return w + delta, (n, gavg, delta)


@register
class AdaGrad(Optimizer):
    """AdaGrad (parity: optimizer/adagrad.py)."""

    def __init__(self, learning_rate=0.01, epsilon=1e-7, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (jnp.zeros_like(weight._data),)

    def _hyper(self, index):
        h = super()._hyper(index)
        h["eps"] = onp.float32(self.epsilon)
        return h

    @staticmethod
    def _step(w, g, state, hyper):
        g = Optimizer._pre(g, w, hyper)
        (h,) = state
        h = h + jnp.square(g)
        return w - hyper["lr"] * g / (jnp.sqrt(h) + hyper["eps"]), (h,)


adagrad = AdaGrad
_REGISTRY["adagrad"] = AdaGrad


@register
class GroupAdaGrad(Optimizer):
    """AdaGrad with one accumulator per ROW (embedding-friendly;
    parity: optimizer/contrib.py GroupAdaGrad). Weight decay is not
    supported, matching the reference's assertion."""

    def __init__(self, learning_rate=0.01, epsilon=1e-6, **kwargs):
        if kwargs.get("wd"):
            raise ValueError(
                "Weight decay is not supported for GroupAdaGrad")
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.epsilon = epsilon

    def create_state(self, index, weight):
        if weight._data.ndim != 2:
            raise ValueError("GroupAdaGrad requires 2D weights "
                             f"(got shape {tuple(weight.shape)})")
        return (jnp.zeros((weight.shape[0], 1), weight._data.dtype),)

    def _hyper(self, index):
        h = super()._hyper(index)
        h["eps"] = onp.float32(self.epsilon)
        return h

    @staticmethod
    def _step(w, g, state, hyper):
        g = Optimizer._pre(g, w, hyper, wd_in_grad=False)
        (h,) = state
        h = h + jnp.mean(jnp.square(g), axis=1, keepdims=True)
        return w - hyper["lr"] * g / (jnp.sqrt(h) + hyper["eps"]), (h,)


@register
class AdaDelta(Optimizer):
    """AdaDelta (parity: optimizer/adadelta.py)."""

    def __init__(self, learning_rate=1.0, rho=0.90, epsilon=1e-5, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.rho, self.epsilon = rho, epsilon

    def create_state(self, index, weight):
        # distinct buffers — see RMSProp.create_state
        return (jnp.zeros_like(weight._data),
                jnp.zeros_like(weight._data))

    def _hyper(self, index):
        h = super()._hyper(index)
        h.update(rho=onp.float32(self.rho), eps=onp.float32(self.epsilon))
        return h

    @staticmethod
    def _step(w, g, state, hyper):
        g = Optimizer._pre(g, w, hyper)
        acc_g, acc_d = state
        rho, eps = hyper["rho"], hyper["eps"]
        acc_g = rho * acc_g + (1 - rho) * jnp.square(g)
        delta = jnp.sqrt(acc_d + eps) / jnp.sqrt(acc_g + eps) * g
        acc_d = rho * acc_d + (1 - rho) * jnp.square(delta)
        return w - hyper["lr"] * delta, (acc_g, acc_d)


@register
class Ftrl(Optimizer):
    """FTRL (parity: optimizer/ftrl.py)."""

    def __init__(self, learning_rate=0.1, lamda1=0.01, beta=1.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1, self.beta = lamda1, beta

    def create_state(self, index, weight):
        # distinct buffers — see RMSProp.create_state
        return (jnp.zeros_like(weight._data),
                jnp.zeros_like(weight._data))  # z, n

    def _hyper(self, index):
        h = super()._hyper(index)
        h.update(lamda1=onp.float32(self.lamda1), beta=onp.float32(self.beta))
        return h

    @staticmethod
    def _step(w, g, state, hyper):
        g = Optimizer._pre(g, w, hyper, wd_in_grad=False)
        z, n = state
        lr, l1, beta, wd = hyper["lr"], hyper["lamda1"], hyper["beta"], hyper["wd"]
        sigma = (jnp.sqrt(n + jnp.square(g)) - jnp.sqrt(n)) / lr
        z = z + g - sigma * w
        n = n + jnp.square(g)
        denom = (beta + jnp.sqrt(n)) / lr + wd
        new_w = jnp.where(jnp.abs(z) > l1,
                          -(z - jnp.sign(z) * l1) / denom,
                          jnp.zeros_like(w))
        return new_w, (z, n)


@register
class FTML(Optimizer):
    """FTML (parity: optimizer/ftml.py)."""

    def __init__(self, learning_rate=0.0025, beta1=0.6, beta2=0.999,
                 epsilon=1e-8, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def create_state(self, index, weight):
        # distinct buffers — see RMSProp.create_state
        return (jnp.zeros_like(weight._data),
                jnp.zeros_like(weight._data),
                jnp.zeros_like(weight._data))  # d, v, z

    def _hyper(self, index):
        h = super()._hyper(index)
        h.update(beta1=onp.float32(self.beta1), beta2=onp.float32(self.beta2),
                 eps=onp.float32(self.epsilon))
        return h

    @staticmethod
    def _step(w, g, state, hyper):
        g = Optimizer._pre(g, w, hyper)
        d, v, z = state
        b1, b2, eps, t = hyper["beta1"], hyper["beta2"], hyper["eps"], \
            hyper["t"].astype(jnp.float32)
        v = b2 * v + (1 - b2) * jnp.square(g)
        d_t = (1 - jnp.power(b1, t)) / hyper["lr"] * \
            (jnp.sqrt(v / (1 - jnp.power(b2, t))) + eps)
        sigma = d_t - b1 * d
        z = b1 * z + (1 - b1) * g - sigma * w
        return -z / d_t, (d_t, v, z)


@register
class LAMB(Optimizer):
    """LAMB layerwise-adaptive large-batch optimizer
    (parity: optimizer/lamb.py; kernels src/operator/contrib/multi_lamb.cc)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-6, lower_bound=None, upper_bound=None,
                 bias_correction=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.lower_bound, self.upper_bound = lower_bound, upper_bound
        self.bias_correction = bias_correction

    def create_state(self, index, weight):
        return (jnp.zeros_like(weight._data), jnp.zeros_like(weight._data))

    def _hyper(self, index):
        h = super()._hyper(index)
        h.update(beta1=onp.float32(self.beta1), beta2=onp.float32(self.beta2),
                 eps=onp.float32(self.epsilon),
                 lb=onp.float32(self.lower_bound if self.lower_bound is not None else 0.0),
                 ub=onp.float32(self.upper_bound if self.upper_bound is not None else 1e30),
                 bc=onp.float32(1.0 if self.bias_correction else 0.0))
        return h

    @staticmethod
    def _step(w, g, state, hyper):
        g = Optimizer._pre(g, w, hyper, wd_in_grad=False)
        m, v = state
        b1, b2, eps = hyper["beta1"], hyper["beta2"], hyper["eps"]
        t = hyper["t"].astype(jnp.float32)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        m_hat = jnp.where(hyper["bc"] > 0, m / (1 - jnp.power(b1, t)), m)
        v_hat = jnp.where(hyper["bc"] > 0, v / (1 - jnp.power(b2, t)), v)
        r = m_hat / (jnp.sqrt(v_hat) + eps) + hyper["wd"] * w
        w_norm = jnp.linalg.norm(w)
        r_norm = jnp.linalg.norm(r)
        w_norm_c = jnp.clip(w_norm, hyper["lb"], hyper["ub"])
        ratio = jnp.where((w_norm_c > 0) & (r_norm > 0), w_norm_c / r_norm, 1.0)
        return w - hyper["lr"] * ratio * r, (m, v)


@register
class LARS(Optimizer):
    """LARS (parity: optimizer/lars.py; multi_lars.cc)."""

    def __init__(self, learning_rate=0.1, momentum=0.9, eta=0.001,
                 epsilon=1e-8, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum, self.eta, self.epsilon = momentum, eta, epsilon

    def create_state(self, index, weight):
        return (jnp.zeros_like(weight._data),)

    def _hyper(self, index):
        h = super()._hyper(index)
        h.update(mom=onp.float32(self.momentum), eta=onp.float32(self.eta),
                 eps=onp.float32(self.epsilon))
        return h

    @staticmethod
    def _step(w, g, state, hyper):
        g = g * hyper["rescale"]
        if hyper["clip"] is not None:
            g = jnp.clip(g, -hyper["clip"], hyper["clip"])
        (mom,) = state
        w_norm = jnp.linalg.norm(w)
        g_norm = jnp.linalg.norm(g)
        trust = jnp.where(
            (w_norm > 0) & (g_norm > 0),
            hyper["eta"] * w_norm / (g_norm + hyper["wd"] * w_norm + hyper["eps"]),
            1.0)
        lr_l = hyper["lr"] * trust
        mom = hyper["mom"] * mom + lr_l * (g + hyper["wd"] * w)
        return w - mom, (mom,)


@register
class LANS(LAMB):
    """LANS: LAMB with per-block gradient normalization + Nesterov
    (parity: optimizer/lans.py; multi_lans.cc)."""

    @staticmethod
    def _step(w, g, state, hyper):
        g = g * hyper["rescale"]
        if hyper["clip"] is not None:
            g = jnp.clip(g, -hyper["clip"], hyper["clip"])
        g_norm = jnp.linalg.norm(g)
        g = jnp.where(g_norm > 0, g / g_norm, g)
        m, v = state
        b1, b2, eps = hyper["beta1"], hyper["beta2"], hyper["eps"]
        t = hyper["t"].astype(jnp.float32)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        m_hat = m / (1 - jnp.power(b1, t))
        v_hat = v / (1 - jnp.power(b2, t))
        w_norm = jnp.linalg.norm(w)
        r1 = m_hat / (jnp.sqrt(v_hat) + eps) + hyper["wd"] * w
        r2 = g / (jnp.sqrt(v_hat) + eps) + hyper["wd"] * w
        r1n, r2n = jnp.linalg.norm(r1), jnp.linalg.norm(r2)
        rat1 = jnp.where((w_norm > 0) & (r1n > 0), w_norm / r1n, 1.0)
        rat2 = jnp.where((w_norm > 0) & (r2n > 0), w_norm / r2n, 1.0)
        upd = b1 * rat1 * r1 + (1 - b1) * rat2 * r2
        return w - hyper["lr"] * upd, (m, v)


@register
class Signum(Optimizer):
    """SignSGD / Signum (parity: optimizer/signum.py)."""

    def __init__(self, learning_rate=0.01, momentum=0.9, wd_lh=0.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.wd_lh = wd_lh

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return ()
        return (jnp.zeros_like(weight._data),)

    def _hyper(self, index):
        h = super()._hyper(index)
        h.update(mom=onp.float32(self.momentum), wd_lh=onp.float32(self.wd_lh))
        return h

    @staticmethod
    def _step(w, g, state, hyper):
        g = Optimizer._pre(g, w, hyper)
        if not state:
            return w * (1 - hyper["lr"] * hyper["wd_lh"]) - \
                hyper["lr"] * jnp.sign(g), state
        (mom,) = state
        mom = hyper["mom"] * mom - (1 - hyper["mom"]) * g
        return w * (1 - hyper["lr"] * hyper["wd_lh"]) + \
            hyper["lr"] * jnp.sign(mom), (mom,)


@register
class SGLD(Optimizer):
    """Stochastic Gradient Langevin Dynamics (parity: optimizer/sgld.py)."""

    def update(self, index, weight, grad, state):
        if not isinstance(index, (list, tuple)):
            index, weight, grad, state = [index], [weight], [grad], [state]
        self._update_count(index)
        for i, w, g, s in zip(index, weight, grad, state):
            hyper = self._hyper(i)
            key = next_key()
            gg = Optimizer._pre(jnp.asarray(g._data, w._data.dtype),
                                w._data, hyper)
            noise = jnp.sqrt(hyper["lr"]) * \
                jax.random.normal(key, w.shape, jnp.float32).astype(w._data.dtype)
            w._install(w._data - hyper["lr"] / 2 * gg + noise)
            self._set_state(i, s, s)


@register
class DCASGD(Optimizer):
    """Delay-compensated async SGD (parity: optimizer/dcasgd.py)."""

    def __init__(self, learning_rate=0.01, momentum=0.0, lamda=0.04, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum, self.lamda = momentum, lamda

    def create_state(self, index, weight):
        return (jnp.zeros_like(weight._data), jnp.array(weight._data))

    def _hyper(self, index):
        h = super()._hyper(index)
        h.update(mom=onp.float32(self.momentum), lamda=onp.float32(self.lamda))
        return h

    @staticmethod
    def _step(w, g, state, hyper):
        g = Optimizer._pre(g, w, hyper)
        mom, prev_w = state
        comp = g + hyper["lamda"] * g * g * (w - prev_w)
        mom = hyper["mom"] * mom - hyper["lr"] * comp
        return w + mom, (mom, jnp.array(w))


# ---------------------------------------------------------------------------
# Updater: serializable update-on-kvstore helper (parity: optimizer.Updater)
# ---------------------------------------------------------------------------
class Updater:
    def __init__(self, optimizer: Optimizer):
        self.optimizer = optimizer
        self.states = {}
        self.states_synced = {}

    def __call__(self, index, grad, weight):
        if not isinstance(index, (list, tuple)):
            indices, grads, weights = [index], [grad], [weight]
        else:
            indices, grads, weights = index, grad, weight
        for i, w, g in zip(indices, weights, grads):
            if i not in self.states:
                self.states[i] = \
                    self.optimizer.create_state_multi_precision(i, w)
            self.optimizer.update_multi_precision([i], [w], [g],
                                                  [self.states[i]])
            self.states[i] = self.optimizer._last_states[i]

    def get_states(self, dump_optimizer=False):
        import pickle
        host_states = jax.tree_util.tree_map(
            lambda x: onp.asarray(x) if isinstance(x, jax.Array) else x,
            self.states)
        return pickle.dumps((host_states, self.optimizer)
                            if dump_optimizer else host_states)

    def set_states(self, states):
        import pickle
        obj = pickle.loads(states)
        if isinstance(obj, tuple) and len(obj) == 2 and \
                isinstance(obj[1], Optimizer):
            states, self.optimizer = obj
        else:
            states = obj
        states = {k: self.optimizer._migrate_state(v)
                  for k, v in states.items()} \
            if isinstance(states, dict) else states
        self.states = jax.tree_util.tree_map(
            lambda x: jnp.asarray(x) if isinstance(x, onp.ndarray) else x,
            states)


def get_updater(optimizer):
    return Updater(optimizer)
