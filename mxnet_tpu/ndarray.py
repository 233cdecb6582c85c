"""NDArray: the framework's tensor handle, backed by a jax.Array (PJRT buffer).

TPU-native re-design of the reference NDArray (include/mxnet/ndarray.h:82,
src/ndarray/ndarray.cc).  The reference pairs a Storage chunk with an engine
variable for async dependency ordering; here the PJRT buffer *is* the storage
and XLA's async dispatch *is* the engine — every op returns immediately with a
future-backed jax.Array, and ``wait_to_read()`` maps to
``jax.block_until_ready`` (≙ NDArray::WaitToRead, ndarray.h:395).  Exceptions
raised by async device computation surface at the wait point, matching the
reference's capture/rethrow-at-wait contract (src/engine/threaded_engine.cc:440).

Autograd state (attach_grad / .grad / .backward) hangs off the handle exactly
like the reference's autograd entry (ndarray.h:1179), implemented by tape.py.
"""
from __future__ import annotations

import numpy as _onp
import jax
import jax.numpy as jnp

from . import tape
from .context import Context, current_context
from .dispatch_cache import dispatch as _dispatch, fn_token as _fn_token

_SCALAR_TYPES = frozenset((bool, int, float, complex))

__all__ = ["NDArray", "array", "from_jax", "wrap", "invoke_op", "waitall",
           "binary_op", "unary_op"]


def _raw(x):
    return x._data if isinstance(x, NDArray) else x


# jnp dtype → numpy dtype object; the .dtype property is on the hot
# dispatch path and _onp.dtype() allocates a fresh object per call
_DTYPE_CACHE = {}


class NDArray:
    """Multi-dimensional array on a device, with autograd hooks."""

    __slots__ = ("_data", "_grad_edge", "_node", "__weakref__")

    def __init__(self, data):
        self._data = data          # jax.Array (or a jax tracer during tracing)
        self._grad_edge = None     # tape.GradEdge after attach_grad()
        self._node = None          # (TapeNode, out_index) when produced by a taped op

    # ------------------------------------------------------------------ info
    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        d = self._data.dtype
        try:
            return _DTYPE_CACHE[d]
        except (KeyError, TypeError):
            out = _onp.dtype(d)
            try:
                _DTYPE_CACHE[d] = out
            except TypeError:
                pass
            return out

    @property
    def size(self):
        return int(self._data.size)

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def itemsize(self):
        return self.dtype.itemsize

    @property
    def context(self) -> Context:
        try:
            plat = self._data.device.platform
        except Exception:
            return current_context()
        kind = {"cuda": "gpu", "rocm": "gpu"}.get(plat, plat)
        try:
            did = self._data.device.id
        except Exception:
            did = 0
        return Context(kind, did)

    ctx = context
    device = context

    @property
    def T(self):
        return self.transpose()

    # --------------------------------------------------------------- dlpack
    def __dlpack__(self, *, stream=None):
        if stream is not None:
            return self._data.__dlpack__(stream=stream)
        return self._data.__dlpack__()

    def __dlpack_device__(self):
        return self._data.__dlpack_device__()

    # -------------------------------------------------------------- transfer
    def asnumpy(self) -> _onp.ndarray:
        return _fetch(self._data, _onp.asarray)

    def numpy(self):
        return self.asnumpy()

    def __array__(self, dtype=None):
        """NumPy interop (≙ numpy_dispatch_protocol.py): np.asarray(nd)."""
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    def __array_function__(self, func, types, args, kwargs):
        """`__array_function__` protocol (reference
        python/mxnet/numpy_dispatch_protocol.py): dispatch official numpy
        functions called on NDArrays to our mx.np twin when one exists,
        else fall back to host numpy on converted arrays."""
        from . import numpy as mnp
        ours = getattr(mnp, func.__name__, None)

        def conv(x):
            if isinstance(x, NDArray):
                return x.asnumpy()
            if isinstance(x, (list, tuple)):
                # deep-convert so host numpy never re-dispatches on a
                # nested NDArray (np.block/np.einsum_path take sequences)
                return type(x)(conv(v) for v in x)
            return x
        if ours is not None and ours is not func:
            try:
                return ours(*args, **kwargs)
            except (TypeError, NotImplementedError):
                pass        # signature mismatch → host fallback below
        args = [conv(a) for a in args]
        kwargs = {k: conv(v) for k, v in kwargs.items()}
        out = func(*args, **kwargs)
        return NDArray(jnp.asarray(out)) if isinstance(out, _onp.ndarray) \
            else out

    def item(self):
        return _fetch(self._data, _item)

    def tolist(self):
        return self.asnumpy().tolist()

    def asscalar(self):
        return self.item()

    def astype(self, dtype, copy=True):
        return invoke_op(lambda x: x.astype(jnp.dtype(dtype)), self,
                         op="astype",
                         attrs={"dtype": jnp.dtype(dtype).name})

    def copy(self):
        return invoke_op(lambda x: x + 0 if False else jnp.asarray(x), self,
                         op="copy_method", attrs={})

    def copyto(self, other):
        if isinstance(other, Context):
            return self.as_in_context(other)
        other._data = jax.device_put(self._data, other._data.device)
        return other

    def as_in_context(self, ctx: Context):
        return NDArray(jax.device_put(self._data, ctx.jax_device))

    as_in_ctx = as_in_context

    def to_device(self, device):
        return self.as_in_context(device)

    # ------------------------------------------------------------------ sync
    def wait_to_read(self):
        _fetch(self._data, jax.block_until_ready)

    def wait_to_write(self):
        _fetch(self._data, jax.block_until_ready)

    # -------------------------------------------------------------- autograd
    def attach_grad(self, grad_req: str = "write"):
        self._grad_edge = tape.GradEdge(grad_req)

    @property
    def grad(self):
        if self._grad_edge is None or self._grad_edge.grad is None:
            if self._grad_edge is not None:
                # parity: attach_grad initializes grad to zeros (reference
                # mark_variables creates zero grad buffers)
                return NDArray(jnp.zeros(self.shape, self.dtype))
            return None
        return NDArray(self._grad_edge.grad)

    def zero_grad(self):
        if self._grad_edge is not None:
            self._grad_edge.grad = jnp.zeros(self.shape, self.dtype)

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        tape.backward([self], [out_grad] if out_grad is not None else None,
                      retain_graph=retain_graph)

    def detach(self):
        return NDArray(self._data)

    # ------------------------------------------------------------ arithmetic
    def __add__(self, o): return binary_op(jnp.add, self, o)
    def __radd__(self, o): return binary_op(jnp.add, o, self)
    def __sub__(self, o): return binary_op(jnp.subtract, self, o)
    def __rsub__(self, o): return binary_op(jnp.subtract, o, self)
    def __mul__(self, o): return binary_op(jnp.multiply, self, o)
    def __rmul__(self, o): return binary_op(jnp.multiply, o, self)
    def __truediv__(self, o): return binary_op(jnp.divide, self, o)
    def __rtruediv__(self, o): return binary_op(jnp.divide, o, self)
    def __floordiv__(self, o): return binary_op(jnp.floor_divide, self, o)
    def __rfloordiv__(self, o): return binary_op(jnp.floor_divide, o, self)
    def __mod__(self, o): return binary_op(jnp.mod, self, o)
    def __rmod__(self, o): return binary_op(jnp.mod, o, self)
    def __pow__(self, o): return binary_op(jnp.power, self, o)
    def __rpow__(self, o): return binary_op(jnp.power, o, self)
    def __matmul__(self, o): return binary_op(jnp.matmul, self, o)
    def __rmatmul__(self, o): return binary_op(jnp.matmul, o, self)
    def __neg__(self): return unary_op(jnp.negative, self)
    def __pos__(self): return self
    def __abs__(self): return unary_op(jnp.abs, self)

    def __iadd__(self, o): return self.__add__(o)
    def __isub__(self, o): return self.__sub__(o)
    def __imul__(self, o): return self.__mul__(o)
    def __itruediv__(self, o): return self.__truediv__(o)

    def __eq__(self, o): return binary_op(jnp.equal, self, o, no_grad=True)
    def __ne__(self, o): return binary_op(jnp.not_equal, self, o, no_grad=True)
    def __lt__(self, o): return binary_op(jnp.less, self, o, no_grad=True)
    def __le__(self, o): return binary_op(jnp.less_equal, self, o, no_grad=True)
    def __gt__(self, o): return binary_op(jnp.greater, self, o, no_grad=True)
    def __ge__(self, o): return binary_op(jnp.greater_equal, self, o, no_grad=True)

    def __hash__(self):
        return id(self)

    # ------------------------------------------------------------- indexing
    def __getitem__(self, key):
        rkey = _index_raw(key)
        return invoke_op(lambda x: x[rkey], self,
                         op="getitem", attrs={"key": key})

    def __setitem__(self, key, value):
        key = _index_raw(key)
        value = _raw(value)
        self._data = self._data.at[key].set(value)
        dc = _dc()
        if dc.is_tracing():
            dc.invalidate(self)   # in-place mutation: stale symbol

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __bool__(self):
        return bool(self._data if self._data.ndim == 0 else self._data.item())

    def __float__(self):
        return float(self._data if self._data.ndim == 0 else self._data.item())

    def __int__(self):
        return int(self._data if self._data.ndim == 0 else self._data.item())

    def __index__(self):
        return self.__int__()

    def __repr__(self):
        return f"{self.asnumpy()!r} <NDArray {self.shape} @{self.context}>"

    def __str__(self):
        return str(self.asnumpy())

    # --------------------------------------------------------- shape methods
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        shape = tuple(int(s) for s in shape)
        return invoke_op(lambda x: jnp.reshape(x, shape), self,
                         op="reshape", attrs={"shape": shape})

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        ax = axes if axes else None
        return invoke_op(lambda x: jnp.transpose(x, ax), self,
                         op="transpose", attrs={"axes": ax})

    def swapaxes(self, a, b):
        return invoke_op(lambda x: jnp.swapaxes(x, a, b), self,
                         op="swapaxes", attrs={"a": a, "b": b})

    def flatten(self):
        return self.reshape(-1)

    def squeeze(self, axis=None):
        return invoke_op(lambda x: jnp.squeeze(x, axis), self,
                         op="squeeze", attrs={"axis": axis})

    def expand_dims(self, axis):
        return invoke_op(lambda x: jnp.expand_dims(x, axis), self,
                         op="expand_dims", attrs={"axis": axis})

    def broadcast_to(self, shape):
        return invoke_op(lambda x: jnp.broadcast_to(x, tuple(shape)), self,
                         op="broadcast_to", attrs={"shape": tuple(shape)})

    def repeat(self, repeats, axis=None):
        return invoke_op(lambda x: jnp.repeat(x, repeats, axis), self,
                         op="repeat", attrs={"repeats": repeats, "axis": axis})

    def take(self, indices, axis=None, mode="clip"):
        idx = _raw(indices)
        # the ORIGINAL indices object goes into the recorded attrs: if it
        # is a traced NDArray the tracer links it to its producing node
        # (a re-wrap would silently bake a stale constant)
        idx_attr = indices if isinstance(indices, NDArray) \
            else NDArray(jnp.asarray(idx))
        return invoke_op(lambda x: jnp.take(x, idx, axis=axis, mode=mode),
                         self, op="take_method",
                         attrs={"idx": idx_attr, "axis": axis,
                                "mode": mode})

    # ------------------------------------------------------------ reductions
    def sum(self, axis=None, keepdims=False, dtype=None):
        attrs = {"axis": axis, "keepdims": keepdims}
        if dtype is not None:
            attrs["dtype"] = jnp.dtype(dtype).name
        return invoke_op(lambda x: jnp.sum(x, axis=axis, keepdims=keepdims, dtype=dtype), self,
                         op="sum", attrs=attrs)

    def mean(self, axis=None, keepdims=False, dtype=None):
        attrs = {"axis": axis, "keepdims": keepdims}
        if dtype is not None:
            attrs["dtype"] = jnp.dtype(dtype).name
        return invoke_op(lambda x: jnp.mean(x, axis=axis, keepdims=keepdims, dtype=dtype), self,
                         op="mean", attrs=attrs)

    def max(self, axis=None, keepdims=False):
        return invoke_op(lambda x: jnp.max(x, axis=axis, keepdims=keepdims), self,
                         op="max", attrs={"axis": axis, "keepdims": keepdims})

    def min(self, axis=None, keepdims=False):
        return invoke_op(lambda x: jnp.min(x, axis=axis, keepdims=keepdims), self,
                         op="min", attrs={"axis": axis, "keepdims": keepdims})

    def prod(self, axis=None, keepdims=False):
        return invoke_op(lambda x: jnp.prod(x, axis=axis, keepdims=keepdims), self,
                         op="prod", attrs={"axis": axis, "keepdims": keepdims})

    def std(self, axis=None, keepdims=False):
        return invoke_op(lambda x: jnp.std(x, axis=axis, keepdims=keepdims), self,
                         op="std", attrs={"axis": axis, "keepdims": keepdims})

    def var(self, axis=None, keepdims=False):
        return invoke_op(lambda x: jnp.var(x, axis=axis, keepdims=keepdims), self,
                         op="var", attrs={"axis": axis, "keepdims": keepdims})

    def argmax(self, axis=None):
        return invoke_op(lambda x: jnp.argmax(x, axis=axis), self, no_grad=True,
                         op="argmax", attrs={"axis": axis})

    def argmin(self, axis=None):
        return invoke_op(lambda x: jnp.argmin(x, axis=axis), self, no_grad=True,
                         op="argmin", attrs={"axis": axis})

    def cumsum(self, axis=None, dtype=None):
        attrs = {"axis": axis}
        if dtype is not None:
            attrs["dtype"] = jnp.dtype(dtype).name
        return invoke_op(lambda x: jnp.cumsum(x, axis=axis, dtype=dtype), self,
                         op="cumsum", attrs=attrs)

    def dot(self, other):
        return binary_op(jnp.dot, self, other)

    def clip(self, a_min=None, a_max=None):
        return invoke_op(lambda x: jnp.clip(x, a_min, a_max), self,
                         op="clip", attrs={"a_min": a_min, "a_max": a_max})

    def round(self, decimals=0):
        return invoke_op(lambda x: jnp.round(x, decimals), self,
                         op="round", attrs={"decimals": decimals})

    # elementwise method parity (mx.np ndarray methods)
    def abs(self): return unary_op(jnp.abs, self)
    def exp(self): return unary_op(jnp.exp, self)
    def log(self): return unary_op(jnp.log, self)
    def sqrt(self): return unary_op(jnp.sqrt, self)
    def square(self): return unary_op(jnp.square, self)
    def tanh(self): return unary_op(jnp.tanh, self)
    def sigmoid(self):
        return unary_op(jax.nn.sigmoid, self)
    def relu(self):
        return unary_op(jax.nn.relu, self)
    def sign(self): return unary_op(jnp.sign, self)
    def floor(self): return unary_op(jnp.floor, self)
    def ceil(self): return unary_op(jnp.ceil, self)

    def sort(self, axis=-1):
        return invoke_op(lambda x: jnp.sort(x, axis=axis), self)

    def argsort(self, axis=-1):
        return invoke_op(lambda x: jnp.argsort(x, axis=axis), self, no_grad=True)

    def any(self, axis=None, keepdims=False):
        return invoke_op(lambda x: jnp.any(x, axis=axis, keepdims=keepdims), self, no_grad=True)

    def all(self, axis=None, keepdims=False):
        return invoke_op(lambda x: jnp.all(x, axis=axis, keepdims=keepdims), self, no_grad=True)


def _index_raw(key):
    if isinstance(key, NDArray):
        return key._data
    if isinstance(key, tuple):
        return tuple(_index_raw(k) for k in key)
    return key


def wrap(raw) -> NDArray:
    return NDArray(raw)


_deferred_mod = None


def _dc():
    global _deferred_mod
    if _deferred_mod is None:
        from .gluon import deferred
        _deferred_mod = deferred
    return _deferred_mod


def invoke_op(fun, *arrays, no_grad=False, op=None, attrs=None,
              cache_key=None):
    """Dispatch a raw-array function over NDArray inputs, taping if
    recording.  `op`/`attrs` name the call for the deferred-compute
    tracer (gluon/deferred.py); outputs of anonymous closures are
    TAINTED during a trace so a downstream record raises instead of
    silently baking a trace-time value as a constant.

    The no-grad path (not recording, or recording with no tracked
    inputs) runs through the executable cache (dispatch_cache.py) so a
    steady-state eager op skips the per-call XLA retrace; `cache_key`
    lets callers that know their own identity (scalar closures, the
    mx.np dispatcher) opt in where the default keying would fall back."""
    if no_grad or not tape.is_recording() or not tape.any_tracked(arrays):
        out = _dispatch(fun, [a._data for a in arrays], op, attrs, cache_key)
        if isinstance(out, (tuple, list)):
            out = tuple(NDArray(o) for o in out)
        else:
            out = NDArray(out)
    else:
        out = tape.invoke(fun, arrays, wrap)
    dc = _dc()
    if dc.is_tracing():
        if op is not None:
            dc.record(op, out, list(arrays), attrs or {})
        else:
            dc.taint(out)
    return out


def binary_op(fun, a, b, no_grad=False):
    a_nd = isinstance(a, NDArray)
    b_nd = isinstance(b, NDArray)
    if a_nd and b_nd:
        out = invoke_op(fun, a, b, no_grad=no_grad)
    elif a_nd:
        # python-scalar operand: the (fun, side, type, value) tuple fully
        # determines the closure, so the executable is cacheable
        ck = ("rs", _fn_token(fun), type(b), b) \
            if type(b) in _SCALAR_TYPES else None
        out = invoke_op(lambda x: fun(x, b), a, no_grad=no_grad,
                        cache_key=ck)
    elif b_nd:
        ck = ("ls", _fn_token(fun), type(a), a) \
            if type(a) in _SCALAR_TYPES else None
        out = invoke_op(lambda y: fun(a, y), b, no_grad=no_grad,
                        cache_key=ck)
    else:
        return NDArray(fun(jnp.asarray(a), jnp.asarray(b)))
    dc = _dc()
    if dc.is_tracing():
        # full (a, b) record with scalar operands in place — overrides
        # the taint invoke_op put on the anonymous-closure output
        dc.record(fun.__name__, out, [a, b], {})
    return out


def unary_op(fun, a, no_grad=False):
    return invoke_op(fun, a, no_grad=no_grad, op=fun.__name__)


def array(obj, dtype=None, ctx: Context = None) -> NDArray:
    if isinstance(obj, NDArray):
        data = obj._data
    else:
        data = jnp.asarray(obj, dtype=jnp.dtype(dtype) if dtype is not None else None)
    if dtype is not None:
        data = data.astype(jnp.dtype(dtype))
    elif data.dtype == jnp.float64:
        data = data.astype(jnp.float32)
    if ctx is not None:
        data = jax.device_put(data, ctx.jax_device)
    return NDArray(data)


def from_jax(x) -> NDArray:
    return NDArray(x)


def waitall():
    """Block until all launched work completes (≙ mx.nd.waitall)."""
    try:
        jax.effects_barrier()
    except Exception:
        pass


# ------------------------------------------------- the wait for the device
# After the last line on purpose: every line above keeps its number, and
# with it the compile cache's key of each program traced through this file
# (PERF.md, PR 27).
from . import telemetry as _telemetry  # noqa: E402


def _item(data):
    return data.item()


def _fetch(data, how):
    """``how(data)`` where the host asks for an array's value or waits for
    it.  An array that has landed records nothing; one that has not is a
    ``nd.fetch`` span around the wait (``bytes=``).  An array without
    ``is_ready`` counts as landed.  With MXNET_TRACE=0 it is ``how(data)``
    alone."""
    if not _telemetry.trace_enabled():
        return how(data)
    landed = getattr(data, "is_ready", None)
    if landed is None or landed():
        return how(data)
    with _telemetry.span("nd.fetch", bytes=int(getattr(data, "nbytes", 0))):
        return how(data)
