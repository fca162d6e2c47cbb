"""NDArray: the imperative, async array type.

Capability parity with the reference's NDArray
(include/mxnet/ndarray.h:82; python/mxnet/numpy/multiarray.py), mapped
onto JAX:

- The payload is a ``jax.Array`` — an asynchronous future on device.
  Creating/operating returns immediately (the reference's engine-push
  contract); ``wait_to_read``/``asnumpy`` are the sync points where
  deferred device errors also surface.
- Immutability + functional updates replace the engine's write-var
  discipline: an "in-place" op installs a new buffer and bumps
  ``_version`` (the reference bumps its engine var instead).
- ``_grad``/``_grad_req``/``_node`` are the autograd attachment points
  (parity: AGInfo, include/mxnet/imperative.h:54).
- Views/slices are functional copies, not aliases (XLA arrays cannot
  alias); ``x[i:j] = v`` still works because it rewrites the base.
- Storage types: dense only on device. The stype slot is kept so
  sparse (row_sparse/CSR) can land later without API churn
  (SURVEY.md §7 stage 2).
"""
from __future__ import annotations

import numpy as onp

import jax
import jax.numpy as jnp

from .. import engine
from ..base import resolve_dtype
from ..context import Context, current_context


def _coerce_index_dtype(arr):
    """Float index arrays truncate to int (reference parity: the
    mx.np default dtype is float32, so `a[np.array([0, 2])]` arrives
    float and the reference accepts it — for reads AND writes)."""
    if jnp.issubdtype(arr.dtype, jnp.inexact):
        return arr.astype(jnp.int64 if jax.config.jax_enable_x64
                          else jnp.int32)
    return arr


def _to_jax_index(key):
    """Convert an index expression possibly containing NDArrays."""
    if isinstance(key, NDArray):
        return _coerce_index_dtype(key._data)
    if isinstance(key, tuple):
        return tuple(_to_jax_index(k) for k in key)
    if isinstance(key, list):
        return [_to_jax_index(k) for k in key]
    return key


class NDArray:
    """An async, device-resident n-dimensional array."""

    __slots__ = ("_data", "_ctx", "_grad", "_grad_req", "_node",
                 "_fresh_grad", "_version", "_bucket_pad", "__weakref__")

    # Make `ndarray op numpy_array` hit our reflected ops, not numpy's.
    __array_priority__ = 1000.0

    def __init__(self, data, ctx: Context = None, _track: bool = False):
        if _track:
            data = engine.track(data)
        self._data = data
        self._ctx = ctx if ctx is not None else current_context()
        self._grad = None
        self._grad_req = "null"
        self._node = None
        self._fresh_grad = False
        self._version = 0

    # ------------------------------------------------------------------
    # basic properties
    # ------------------------------------------------------------------
    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return onp.dtype(self._data.dtype)

    @property
    def size(self):
        n = 1
        for s in self.shape:
            n *= s
        return n

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def ctx(self) -> Context:
        return self._ctx

    context = ctx

    @property
    def device(self) -> Context:
        return self._ctx

    @property
    def stype(self) -> str:
        return "default"

    @property
    def T(self):
        return self.transpose()

    @property
    def grad(self):
        return self._grad

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __repr__(self):
        try:
            arr = self.asnumpy()
        except Exception as e:  # async error surfaced at print time
            return f"NDArray<error: {e}>"
        return f"array({arr}, ctx={self._ctx})"

    def __str__(self):
        return str(self.asnumpy())

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __bool__(self):
        if self.size != 1:
            raise ValueError(
                "The truth value of an array with more than one element is "
                "ambiguous.")
        return bool(self.item())

    def __int__(self):
        return int(self.item())

    def __float__(self):
        return float(self.item())

    def __index__(self):
        if self.ndim == 0 and onp.issubdtype(self.dtype, onp.integer):
            return int(self.item())
        raise TypeError("only integer scalar arrays can be converted to an index")

    def __hash__(self):
        return id(self)

    def __format__(self, fmt):
        if self.size == 1:
            return format(self.item(), fmt)
        return repr(self)

    # ------------------------------------------------------------------
    # sync / conversion
    # ------------------------------------------------------------------
    def wait_to_read(self):
        """Block until computed; re-raise deferred device errors."""
        engine.wait_to_read(self._data)
        return self

    wait_to_write = wait_to_read

    def asnumpy(self) -> onp.ndarray:
        d = engine.wait_to_read(self._data)
        if str(d.dtype) == "bfloat16":
            return onp.asarray(d.astype(jnp.float32)).astype(onp.float32)
        return onp.asarray(d)

    def item(self):
        return self.asnumpy().item()

    def asscalar(self):
        return self.item()

    def tolist(self):
        return self.asnumpy().tolist()

    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    def __array_function__(self, func, types, args, kwargs):
        """NumPy dispatch protocol: numpy.foo(mx_arr) routes to the
        mx.np implementation when one exists, host fallback otherwise
        (parity: python/mxnet/numpy_dispatch_protocol.py +
        numpy/fallback.py)."""
        from ..numpy import dispatch
        return dispatch.array_function(self, func, types, args, kwargs)

    def __array_ufunc__(self, ufunc, method, *inputs, **kwargs):
        from ..numpy import dispatch
        return dispatch.array_ufunc(self, ufunc, method, *inputs, **kwargs)

    def __dlpack__(self, stream=None):
        return self._data.__dlpack__()

    def __dlpack_device__(self):
        return self._data.__dlpack_device__()

    def astype(self, dtype, copy=True):
        dtype = resolve_dtype(dtype)
        if not copy and self.dtype == dtype:
            return self
        from ..ops import apply_op
        return apply_op(lambda x: jnp.asarray(x, _jdt(dtype)), self,
                        name="astype")

    def as_np_ndarray(self):
        return self

    def as_nd_ndarray(self):
        return self

    # ------------------------------------------------------------------
    # context movement
    # ------------------------------------------------------------------
    def as_in_context(self, ctx: Context):
        if ctx == self._ctx:
            return self
        return self.copyto(ctx)

    as_in_ctx = as_in_context
    to_device = as_in_context

    def copyto(self, other):
        """Copy to a Context or into another NDArray (parity:
        NDArray::CopyFromTo, src/ndarray/ndarray.cc:1331)."""
        if isinstance(other, Context):
            data = jax.device_put(self._data, other.jax_device)
            return NDArray(engine.track(data), ctx=other)
        if isinstance(other, NDArray):
            data = jax.device_put(self._data, other.ctx.jax_device)
            other._install(jnp.asarray(data, other._data.dtype))
            return other
        raise TypeError(f"copyto expects Context or NDArray, got {type(other)}")

    def copy(self):
        # A genuinely distinct buffer: jax arrays are immutable, so an
        # alias would normally do — but fused-step buffer donation
        # (parallel/train_step.py) can invalidate donated buffers, and a
        # copy() result must survive that.
        return NDArray(engine.track(jnp.array(self._data, copy=True)),
                       ctx=self._ctx)

    # ------------------------------------------------------------------
    # autograd attachment
    # ------------------------------------------------------------------
    def attach_grad(self, grad_req: str = "write", stype=None):
        """Allocate a gradient buffer and mark this array as a variable."""
        self._grad = NDArray(engine.track(jnp.zeros(self.shape, self._data.dtype)),
                             ctx=self._ctx)
        self._grad_req = grad_req
        self._node = None

    def drop_grad(self):
        self._grad = None
        self._grad_req = "null"

    def release_grad(self):
        """Free the gradient BUFFER but stay a variable: ``.grad``
        reads as a scalar zero until the next ``backward`` writes a
        full gradient over it (``write``) or adds one to it (``add``:
        zero + g broadcasts to g). For owners that differentiate
        inside their own compiled program (``parallel.TrainStep``) and
        never touch the imperative buffer — one dead array the size
        of every parameter otherwise."""
        if self._grad is not None:
            self._grad = NDArray(
                engine.track(jnp.zeros((), self._data.dtype)),
                ctx=self._ctx)

    def zero_grad(self):
        if self._grad is not None:
            self._grad._install(jnp.zeros_like(self._grad._data))

    def detach(self):
        out = NDArray(self._data, ctx=self._ctx)
        return out

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        from .. import autograd
        autograd.backward([self], head_grads=[out_grad] if out_grad is not None
                          else None, retain_graph=retain_graph,
                          train_mode=train_mode)

    # ------------------------------------------------------------------
    # mutation (functional under the hood)
    # ------------------------------------------------------------------
    def _install(self, new_data):
        """Install a new buffer (the write-var version bump)."""
        self._data = engine.track(new_data)
        self._version += 1
        return self

    def _stateful_update(self, fn, new):
        """Apply ``fn(old_raw, new_raw)`` as a state update.

        Used for auxiliary (non-differentiable) state like BatchNorm
        running statistics. Eagerly this installs the new buffer; inside
        a hybridize trace the update is registered with the tracer so
        the compiled graph threads it as an extra output and writes it
        back after each call (the reference mutates aux NDArrays from
        inside the kernel instead).
        """
        import jax as _jax
        newd = fn(self._data, new._data if isinstance(new, NDArray) else new)
        # aux state must keep its dtype: stats math may upcast (e.g.
        # bf16 nets accumulate in f32) and a dtype flip would retrace
        # every compiled step that threads this buffer through.
        if newd.dtype != self._data.dtype:
            newd = jnp.asarray(newd, self._data.dtype)
        if isinstance(newd, _jax.core.Tracer):
            from ..gluon import _deferred
            _deferred.register_state_update(self, newd)
        else:
            self._install(newd)
        return self

    def __setitem__(self, key, value):
        if isinstance(value, NDArray):
            value = value._data
        idx = _to_jax_index(key)
        if idx is Ellipsis or (isinstance(idx, slice) and idx == slice(None)):
            new = jnp.broadcast_to(jnp.asarray(value, self._data.dtype),
                                   self.shape)
        else:
            new = self._data.at[idx].set(jnp.asarray(value).astype(self._data.dtype)
                                         if not onp.isscalar(value) else value)
        if new.shape != self.shape:
            raise ValueError("setitem cannot change shape")
        self._install(jnp.asarray(new, self._data.dtype))

    def __getitem__(self, key):
        from ..ops import apply_op
        nd_keys = []
        if isinstance(key, NDArray):
            nd_keys = [key]
        elif isinstance(key, tuple):
            nd_keys = [k for k in key if isinstance(k, NDArray)]

        def do_index(x, *keys):
            kit = iter(keys)
            if isinstance(key, NDArray):
                k = _coerce_index_dtype(next(kit))
            elif isinstance(key, tuple):
                k = tuple(_coerce_index_dtype(next(kit))
                          if isinstance(kk, NDArray) else kk
                          for kk in key)
            else:
                k = key
            return x[k]

        return apply_op(do_index, self, *nd_keys, name="getitem")

    # ------------------------------------------------------------------
    # arithmetic — delegate to the mx.np namespace (single source of truth)
    # ------------------------------------------------------------------
    def _np(self):
        from .. import numpy as _mnp
        return _mnp

    def __add__(self, o): return self._np().add(self, o)
    def __radd__(self, o): return self._np().add(o, self)
    def __sub__(self, o): return self._np().subtract(self, o)
    def __rsub__(self, o): return self._np().subtract(o, self)
    def __mul__(self, o): return self._np().multiply(self, o)
    def __rmul__(self, o): return self._np().multiply(o, self)
    def __truediv__(self, o): return self._np().true_divide(self, o)
    def __rtruediv__(self, o): return self._np().true_divide(o, self)
    def __floordiv__(self, o): return self._np().floor_divide(self, o)
    def __rfloordiv__(self, o): return self._np().floor_divide(o, self)
    def __mod__(self, o): return self._np().mod(self, o)
    def __rmod__(self, o): return self._np().mod(o, self)
    def __divmod__(self, o): return (self // o, self % o)
    def __pow__(self, o): return self._np().power(self, o)
    def __rpow__(self, o): return self._np().power(o, self)
    def __matmul__(self, o): return self._np().matmul(self, o)
    def __rmatmul__(self, o): return self._np().matmul(o, self)
    def __neg__(self): return self._np().negative(self)
    def __pos__(self): return self
    def __abs__(self): return self._np().abs(self)
    def __invert__(self): return self._np().invert(self)
    def __and__(self, o): return self._np().bitwise_and(self, o)
    def __rand__(self, o): return self._np().bitwise_and(o, self)
    def __or__(self, o): return self._np().bitwise_or(self, o)
    def __ror__(self, o): return self._np().bitwise_or(o, self)
    def __xor__(self, o): return self._np().bitwise_xor(self, o)
    def __rxor__(self, o): return self._np().bitwise_xor(o, self)
    def __lshift__(self, o): return self._np().left_shift(self, o)
    def __rshift__(self, o): return self._np().right_shift(self, o)

    def __eq__(self, o): return self._np().equal(self, o)
    def __ne__(self, o): return self._np().not_equal(self, o)
    def __lt__(self, o): return self._np().less(self, o)
    def __le__(self, o): return self._np().less_equal(self, o)
    def __gt__(self, o): return self._np().greater(self, o)
    def __ge__(self, o): return self._np().greater_equal(self, o)

    # in-place: functional rebind (new buffer, version bump)
    def __iadd__(self, o): return self._inplace(self._np().add(self, o))
    def __isub__(self, o): return self._inplace(self._np().subtract(self, o))
    def __imul__(self, o): return self._inplace(self._np().multiply(self, o))
    def __itruediv__(self, o): return self._inplace(self._np().true_divide(self, o))
    def __ifloordiv__(self, o): return self._inplace(self._np().floor_divide(self, o))
    def __imod__(self, o): return self._inplace(self._np().mod(self, o))
    def __ipow__(self, o): return self._inplace(self._np().power(self, o))

    def _inplace(self, result):
        self._data = result._data
        self._node = result._node
        self._version += 1
        return self

    # ------------------------------------------------------------------
    # shape / reduction methods (delegate to mx.np)
    # ------------------------------------------------------------------
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        return self._np().reshape(self, shape)

    def reshape_like(self, other):
        return self._np().reshape(self, other.shape)

    def transpose(self, *axes):
        if len(axes) == 0:
            axes = None
        elif len(axes) == 1 and (axes[0] is None or isinstance(axes[0], (tuple, list))):
            axes = axes[0]
        return self._np().transpose(self, axes)

    def swapaxes(self, a1, a2): return self._np().swapaxes(self, a1, a2)
    def flatten(self): return self.reshape(-1)
    def ravel(self): return self.reshape(-1)
    def squeeze(self, axis=None): return self._np().squeeze(self, axis)
    def expand_dims(self, axis): return self._np().expand_dims(self, axis)
    def broadcast_to(self, shape): return self._np().broadcast_to(self, shape)
    def broadcast_like(self, other): return self._np().broadcast_to(self, other.shape)
    def repeat(self, repeats, axis=None): return self._np().repeat(self, repeats, axis)
    def tile(self, reps): return self._np().tile(self, reps)
    def flip(self, axis=None): return self._np().flip(self, axis)
    def split(self, indices_or_sections, axis=0):
        return self._np().split(self, indices_or_sections, axis)
    def take(self, indices, axis=None, mode="clip"):
        return self._np().take(self, indices, axis=axis, mode=mode)
    def pad(self, pad_width, mode="constant", **kw):
        return self._np().pad(self, pad_width, mode=mode, **kw)
    def clip(self, a_min=None, a_max=None): return self._np().clip(self, a_min, a_max)
    def round(self, decimals=0): return self._np().round(self, decimals)

    def sum(self, axis=None, dtype=None, out=None, keepdims=False):
        return self._np().sum(self, axis=axis, dtype=dtype, out=out, keepdims=keepdims)
    def mean(self, axis=None, dtype=None, out=None, keepdims=False):
        return self._np().mean(self, axis=axis, dtype=dtype, out=out, keepdims=keepdims)
    def prod(self, axis=None, dtype=None, out=None, keepdims=False):
        return self._np().prod(self, axis=axis, dtype=dtype, out=out, keepdims=keepdims)
    def max(self, axis=None, out=None, keepdims=False):
        return self._np().max(self, axis=axis, out=out, keepdims=keepdims)
    def min(self, axis=None, out=None, keepdims=False):
        return self._np().min(self, axis=axis, out=out, keepdims=keepdims)
    def std(self, axis=None, dtype=None, out=None, ddof=0, keepdims=False):
        return self._np().std(self, axis=axis, dtype=dtype, out=out, ddof=ddof, keepdims=keepdims)
    def var(self, axis=None, dtype=None, out=None, ddof=0, keepdims=False):
        return self._np().var(self, axis=axis, dtype=dtype, out=out, ddof=ddof, keepdims=keepdims)
    def cumsum(self, axis=None, dtype=None): return self._np().cumsum(self, axis, dtype)
    def argmax(self, axis=None): return self._np().argmax(self, axis)
    def argmin(self, axis=None): return self._np().argmin(self, axis)
    def argsort(self, axis=-1): return self._np().argsort(self, axis)
    def sort(self, axis=-1):
        return self._inplace(self._np().sort(self, axis))
    def all(self, axis=None, keepdims=False): return self._np().all(self, axis, keepdims=keepdims)
    def any(self, axis=None, keepdims=False): return self._np().any(self, axis, keepdims=keepdims)
    def nonzero(self): return self._np().nonzero(self)
    def dot(self, other): return self._np().dot(self, other)

    def abs(self): return self._np().abs(self)
    def exp(self): return self._np().exp(self)
    def log(self): return self._np().log(self)
    def sqrt(self): return self._np().sqrt(self)
    def square(self): return self._np().square(self)
    def sign(self): return self._np().sign(self)
    def sigmoid(self): return self._np()._npx().sigmoid(self)
    def relu(self): return self._np()._npx().relu(self)
    def tanh(self): return self._np().tanh(self)

    def tostype(self, stype):
        if stype == "default":
            return self
        from . import sparse as _sparse
        return _sparse.cast_storage(self, stype)

    def slice_axis(self, axis, begin, end):
        idx = [slice(None)] * self.ndim
        idx[axis] = slice(begin, end)
        return self[tuple(idx)]


def _jdt(dtype):
    """numpy dtype -> value usable as a jnp dtype (bfloat16-safe)."""
    return dtype


def waitall():
    engine.waitall()
