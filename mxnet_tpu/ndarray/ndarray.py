"""NDArray: the imperative tensor.

TPU-native equivalent of the reference NDArray (include/mxnet/ndarray.h:69,
src/ndarray/ndarray.cc) and the imperative dispatcher
(src/imperative/imperative.cc Invoke/InvokeOp, imperative_utils.h:82-341).

Design: an NDArray is a *mutable handle* over an immutable ``jax.Array``.
The reference's engine-var read/write dependency system
(threaded_engine.h:112-214) is replaced by two facts about JAX/XLA:
 (1) dispatch is already async — ops return futures (jax.Array) immediately
     and ``wait_to_read`` is ``block_until_ready``;
 (2) values are immutable, so "mutation" = swapping the handle's payload and
     issuing a fresh identity token (``_handle``) used by the autograd tape
     for versioning.
"""
from __future__ import annotations

import numbers
from contextlib import nullcontext as _nullcontext
from typing import List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from ..base import MXNetError, env
from ..context import Context, current_context, cpu
from .. import autograd as _ag
from .. import profiler as _prof
from .. import random as _rnd
from .. import tracing as _tracing
from ..ops import registry as _reg


def _default_dtype():
    return np.dtype(env("MXNET_DEFAULT_DTYPE", "float32"))


class NDArray:
    __slots__ = ("_payload", "_thunk", "_handle", "_ctx", "_grad",
                 "_grad_req", "_deferred_init", "__weakref__")
    # make NumPy defer to our reflected operators (a + nd works)
    __array_priority__ = 1000.0

    def __init__(self, data, ctx: Optional[Context] = None, dtype=None):
        self._thunk = None
        if isinstance(data, NDArray):
            data = data._data
        if not isinstance(data, jax.Array):
            data = np.asarray(data)
            if dtype is None and data.dtype == np.float64:
                dtype = _default_dtype()
            if dtype is not None:
                data = data.astype(dtype)
            if ctx is not None:
                data = jax.device_put(data, ctx.jax_device())
            else:
                data = jnp.asarray(data)
        elif dtype is not None and data.dtype != jnp.dtype(dtype):
            data = data.astype(jnp.dtype(dtype))
        self._payload = data
        self._handle = object()
        self._ctx = ctx
        self._grad = None
        self._grad_req = "null"

    # -- lazy payload (engine-style deferred execution) ---------------------
    # An executor may hand out output handles whose value is produced by a
    # not-yet-dispatched fused XLA program (reference analog: engine vars
    # whose value exists only after the pushed opr completes).  Reading
    # ``_data`` forces the producer; ``_set_data`` fulfils it.
    @property
    def _data(self):
        if self._thunk is not None:
            thunk, self._thunk = self._thunk, None
            thunk()  # expected to _set_data on this (and sibling) arrays
        return self._payload

    @_data.setter
    def _data(self, value):
        self._payload = value
        self._thunk = None

    def _set_lazy(self, thunk, aval=None):
        self._thunk = thunk
        if aval is not None:
            self._payload = aval  # ShapeDtypeStruct placeholder for .shape

    # -- engine sync points (reference: NDArray::WaitToRead/WaitToWrite) ----
    def wait_to_read(self):
        _prof.record_host_sync("ndarray.wait_to_read")
        with _tracing.span("mx.sync.ndarray.wait_to_read", "sync"):
            self._data.block_until_ready()
        return self

    wait_to_write = wait_to_read

    # -- basic properties (read the placeholder aval, never force) ----------
    @property
    def shape(self):
        return tuple(self._payload.shape)

    @property
    def dtype(self):
        return np.dtype(str(self._payload.dtype)) \
            if self._payload.dtype != jnp.bfloat16 else self._payload.dtype

    @property
    def size(self):
        return int(np.prod(self._payload.shape)) if self._payload.shape else 1

    @property
    def ndim(self):
        return len(self._payload.shape)

    @property
    def context(self) -> Context:
        if self._ctx is not None:
            return self._ctx
        try:
            dev = list(self._payload.devices())[0]
            return Context("cpu" if dev.platform == "cpu" else "tpu", dev.id)
        except Exception:
            return cpu()

    ctx = context

    @property
    def stype(self):
        return "default"

    @property
    def T(self):
        return self.transpose()

    @property
    def grad(self):
        return self._grad

    # -- conversions --------------------------------------------------------
    def asnumpy(self) -> np.ndarray:
        # every asnumpy is a host-blocking device readback — the thing the
        # sync-free training loop exists to avoid (profiler.host_syncs is
        # the regression gate; see metric.EvalMetric.sync)
        _prof.record_host_sync("ndarray.asnumpy")
        data = self._data
        with _tracing.span("mx.sync.ndarray.asnumpy", "sync"):
            if (hasattr(data, "sharding")
                    and not getattr(data, "is_fully_addressable", True)):
                # global array from a multi-process SPMD mesh: gather the
                # non-addressable shards over the coordination backend
                # (the analog of the reference's kvstore pull to host)
                from jax.experimental import multihost_utils
                return np.asarray(
                    multihost_utils.process_allgather(data, tiled=True))
            return np.asarray(data)

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("The current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    def __dlpack__(self, **kw):
        return self._data.__dlpack__(**kw)

    def astype(self, dtype, copy=True):
        return _invoke("Cast", [self], {"dtype": np.dtype(dtype).name
                                        if dtype is not jnp.bfloat16 else "bfloat16"})

    def copy(self):
        return _invoke("_copy", [self], {})

    def copyto(self, other):
        """reference: NDArray::CopyFromTo (ndarray.cc:513)."""
        if isinstance(other, NDArray):
            other._set_data(jax.device_put(self._data,
                                           other.context.jax_device()))
            return other
        if isinstance(other, Context):
            return NDArray(jax.device_put(self._data, other.jax_device()),
                           ctx=other)
        raise TypeError(type(other))

    def as_in_context(self, context: Context):
        if context == self.context:
            return self
        return NDArray(jax.device_put(self._data, context.jax_device()),
                       ctx=context)

    def tostype(self, stype):
        if stype == "default":
            return self
        from .sparse import cast_storage
        return cast_storage(self, stype)

    def detach(self):
        out = NDArray(self._data)
        return out

    # -- autograd -----------------------------------------------------------
    def attach_grad(self, grad_req="write", stype=None):
        """reference: ndarray.py attach_grad → MXAutogradMarkVariables.

        ``stype='row_sparse'`` requests a row_sparse gradient: autograd
        will produce values+indices for only the touched rows (supported
        when this array is consumed via Embedding/take — the reference's
        sparse-grad ops) instead of a dense (shape) gradient."""
        if stype == "row_sparse":
            from .sparse import zeros as sp_zeros
            self._grad = sp_zeros("row_sparse", self.shape,
                                  dtype=self._data.dtype)
        else:
            self._grad = zeros(self.shape, dtype=self._data.dtype)
        self._grad_req = grad_req

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        _ag.backward([self], [out_grad] if out_grad is not None else None,
                     retain_graph=retain_graph, train_mode=train_mode)

    # -- mutation (engine write-dependency equivalent) ----------------------
    def _set_data(self, value):
        self._data = value
        self._handle = object()  # new version token

    def __setitem__(self, key, value):
        if isinstance(value, NDArray):
            value = value._data
        if isinstance(key, NDArray):
            key = key._data
        if key is Ellipsis or (isinstance(key, slice) and key == slice(None)):
            v = jnp.broadcast_to(jnp.asarray(value, self._data.dtype),
                                 self.shape)
            self._set_data(jnp.asarray(v))
            return
        self._set_data(self._data.at[key].set(
            value if not isinstance(value, np.ndarray) else jnp.asarray(value)))

    def __getitem__(self, key):
        if isinstance(key, NDArray):
            key = key._data.astype(jnp.int32)
        if isinstance(key, numbers.Integral):
            return _invoke_fn(lambda d, **kw: d[int(key)], [self], {})
        return _invoke_fn(lambda d, **kw: d[key], [self], {})

    # -- python protocol ----------------------------------------------------
    def __len__(self):
        return self.shape[0] if self.ndim else 0

    def __bool__(self):
        if self.size != 1:
            raise ValueError("ambiguous truth value of multi-element NDArray")
        return bool(self.asscalar())

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __repr__(self):
        return f"\n{self.asnumpy()}\n<NDArray {'x'.join(map(str, self.shape))} " \
               f"@{self.context}>"

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    # -- arithmetic (routed through the op registry so autograd sees them) --
    def _binop(self, other, op, scalar_op, rop=False):
        if isinstance(other, NDArray):
            a, b = (other, self) if rop else (self, other)
            return _invoke(op, [a, b], {})
        if isinstance(other, numbers.Number):
            return _invoke(scalar_op, [self], {"scalar": float(other)})
        if isinstance(other, np.ndarray):
            a = NDArray(other)
            a2, b = (a, self) if rop else (self, a)
            return _invoke(op, [a2, b], {})
        return NotImplemented

    def __add__(self, o): return self._binop(o, "broadcast_add", "_plus_scalar")
    __radd__ = __add__
    def __sub__(self, o): return self._binop(o, "broadcast_sub", "_minus_scalar")
    def __rsub__(self, o): return self._binop(o, "broadcast_sub", "_rminus_scalar", rop=True)
    def __mul__(self, o): return self._binop(o, "broadcast_mul", "_mul_scalar")
    __rmul__ = __mul__
    def __truediv__(self, o): return self._binop(o, "broadcast_div", "_div_scalar")
    def __rtruediv__(self, o): return self._binop(o, "broadcast_div", "_rdiv_scalar", rop=True)
    __div__ = __truediv__
    __rdiv__ = __rtruediv__
    def __mod__(self, o): return self._binop(o, "broadcast_mod", "_mod_scalar")
    def __rmod__(self, o): return self._binop(o, "broadcast_mod", "_rmod_scalar", rop=True)
    def __pow__(self, o): return self._binop(o, "broadcast_power", "_power_scalar")
    def __rpow__(self, o): return self._binop(o, "broadcast_power", "_rpower_scalar", rop=True)
    def __neg__(self): return _invoke("negative", [self], {})
    def __abs__(self): return _invoke("abs", [self], {})
    def __matmul__(self, o): return _invoke("dot", [self, o], {})

    def __eq__(self, o): return self._binop(o, "broadcast_equal", "_equal_scalar")
    def __ne__(self, o): return self._binop(o, "broadcast_not_equal", "_not_equal_scalar")
    def __gt__(self, o): return self._binop(o, "broadcast_greater", "_greater_scalar")
    def __ge__(self, o): return self._binop(o, "broadcast_greater_equal", "_greater_equal_scalar")
    def __lt__(self, o): return self._binop(o, "broadcast_lesser", "_lesser_scalar")
    def __le__(self, o): return self._binop(o, "broadcast_lesser_equal", "_lesser_equal_scalar")

    def __hash__(self):
        return id(self)

    # in-place: swap payload (reference: engine write dep on same var)
    def __iadd__(self, o):
        out = self.__add__(o)
        self._set_data(out._data)
        return self

    def __isub__(self, o):
        out = self.__sub__(o)
        self._set_data(out._data)
        return self

    def __imul__(self, o):
        out = self.__mul__(o)
        self._set_data(out._data)
        return self

    def __itruediv__(self, o):
        out = self.__truediv__(o)
        self._set_data(out._data)
        return self

    # -- method versions of common ops -------------------------------------
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (list, tuple)):
            shape = tuple(shape[0])
        return _invoke("Reshape", [self], {"shape": shape, **kwargs})

    def reshape_like(self, other):
        return self.reshape(other.shape)

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (list, tuple)):
            axes = tuple(axes[0])
        return _invoke("transpose", [self], {"axes": axes})

    def flatten(self):
        return _invoke("Flatten", [self], {})

    def expand_dims(self, axis):
        return _invoke("expand_dims", [self], {"axis": axis})

    def squeeze(self, axis=None):
        return _invoke("squeeze", [self], {"axis": axis})

    def broadcast_to(self, shape):
        return _invoke("broadcast_to", [self], {"shape": shape})

    def slice(self, begin, end, step=()):
        return _invoke("slice", [self], {"begin": begin, "end": end, "step": step})

    def slice_axis(self, axis, begin, end):
        return _invoke("slice_axis", [self], {"axis": axis, "begin": begin, "end": end})

    def take(self, indices, axis=0, mode="clip"):
        return _invoke("take", [self, indices], {"axis": axis, "mode": mode})

    def pick(self, index, axis=-1, keepdims=False):
        return _invoke("pick", [self, index], {"axis": axis, "keepdims": keepdims})

    def one_hot(self, depth, **kw):
        return _invoke("one_hot", [self], {"depth": depth, **kw})

    def clip(self, a_min, a_max):
        return _invoke("clip", [self], {"a_min": a_min, "a_max": a_max})

    def abs(self): return _invoke("abs", [self], {})
    def sign(self): return _invoke("sign", [self], {})
    def sqrt(self): return _invoke("sqrt", [self], {})
    def square(self): return _invoke("square", [self], {})
    def exp(self): return _invoke("exp", [self], {})
    def log(self): return _invoke("log", [self], {})
    def tanh(self): return _invoke("tanh", [self], {})
    def sigmoid(self): return _invoke("sigmoid", [self], {})
    def relu(self): return _invoke("relu", [self], {})
    def softmax(self, axis=-1): return _invoke("softmax", [self], {"axis": axis})
    def log_softmax(self, axis=-1): return _invoke("log_softmax", [self], {"axis": axis})

    def _reduce(self, name, axis=None, keepdims=False, **kw):
        return _invoke(name, [self], {"axis": axis, "keepdims": keepdims, **kw})

    def sum(self, axis=None, keepdims=False):
        return self._reduce("sum", axis, keepdims)

    def mean(self, axis=None, keepdims=False):
        return self._reduce("mean", axis, keepdims)

    def prod(self, axis=None, keepdims=False):
        return self._reduce("prod", axis, keepdims)

    def max(self, axis=None, keepdims=False):
        return self._reduce("max", axis, keepdims)

    def min(self, axis=None, keepdims=False):
        return self._reduce("min", axis, keepdims)

    def norm(self, ord=2, axis=None, keepdims=False):
        return _invoke("norm", [self], {"ord": ord, "axis": axis,
                                        "keepdims": keepdims})

    def argmax(self, axis=None, keepdims=False):
        return _invoke("argmax", [self], {"axis": axis, "keepdims": keepdims})

    def argmin(self, axis=None, keepdims=False):
        return _invoke("argmin", [self], {"axis": axis, "keepdims": keepdims})

    def argsort(self, axis=-1, is_ascend=True):
        return _invoke("argsort", [self], {"axis": axis, "is_ascend": is_ascend})

    def topk(self, axis=-1, k=1, ret_typ="indices", is_ascend=False):
        return _invoke("topk", [self], {"axis": axis, "k": k,
                                        "ret_typ": ret_typ, "is_ascend": is_ascend})

    def sort(self, axis=-1, is_ascend=True):
        return _invoke("sort", [self], {"axis": axis, "is_ascend": is_ascend})

    def swapaxes(self, dim1, dim2):
        return _invoke("SwapAxis", [self], {"dim1": dim1, "dim2": dim2})

    def flip(self, axis):
        return _invoke("flip", [self], {"axis": axis})

    def tile(self, reps):
        return _invoke("tile", [self], {"reps": reps})

    def repeat(self, repeats, axis=None):
        return _invoke("repeat", [self], {"repeats": repeats, "axis": axis})

    def split(self, num_outputs, axis=1, squeeze_axis=False):
        return _invoke("SliceChannel", [self],
                       {"num_outputs": num_outputs, "axis": axis,
                        "squeeze_axis": squeeze_axis})

    def dot(self, other, **kw):
        return _invoke("dot", [self, other], kw)


# ===========================================================================
# The imperative dispatcher (reference: Imperative::Invoke, imperative.cc:86)
# ===========================================================================
def _naive_mode():
    return env("MXNET_ENGINE_TYPE", "Async") == "NaiveEngine"


def _invoke_fn(fn, inputs: Sequence[NDArray], attrs, n_out: Optional[int] = None,
               rng_key=None, out=None, n_keep=None):
    """Low-level: run pure fn over input payloads, wrap, record on tape."""
    vals = [x._data for x in inputs]
    if rng_key is not None:
        outs = fn(rng_key, *vals, **attrs)
    else:
        outs = fn(*vals, **attrs)
    single = not isinstance(outs, (tuple, list))
    if single:
        outs = (outs,)
    keep = n_keep if n_keep is not None else len(outs)
    visible = outs[:keep]
    if out is not None:
        out_arrays = [out] if isinstance(out, NDArray) else list(out)
        for oa, v in zip(out_arrays, visible):
            oa._set_data(v)
    else:
        out_arrays = [NDArray(v) for v in visible]
    if _ag.is_recording():
        _ag._record(fn, dict(attrs), list(inputs), vals, out_arrays,
                    rng_key=rng_key, n_keep=keep)
    if _naive_mode():
        for oa in out_arrays:
            oa._data.block_until_ready()
    if single or len(out_arrays) == 1:
        return out_arrays[0]
    return out_arrays


def _invoke(op_name: str, inputs, attrs, out=None):
    """Dispatch a registered op imperatively (handles rng/aux/is_train)."""
    opdef = _reg.get(op_name)
    _reg.record_execution(op_name)
    inputs = [x if isinstance(x, NDArray) else NDArray(x) for x in inputs]
    attrs = {k: v for k, v in attrs.items() if v is not None or k in ("axis",)}
    kwargs = dict(attrs)
    is_train = _ag.is_training()
    if opdef.takes_is_train:
        kwargs["is_train"] = is_train
    rng_key = _rnd.next_key() if opdef.needs_rng else None

    n_aux_updates = 0
    if opdef.num_aux and opdef.takes_is_train and is_train:
        n_aux_updates = opdef.num_aux

    vals = [x._data for x in inputs]
    fn = opdef.fn
    with _prof.scope(opdef.name, require_mode="all"):
        if rng_key is not None:
            outs = fn(rng_key, *vals, **kwargs)
        else:
            outs = fn(*vals, **kwargs)
    single = not isinstance(outs, (tuple, list))
    if single:
        outs = (outs,)

    # aux writeback (BatchNorm moving stats): trailing outputs -> aux inputs
    if n_aux_updates:
        aux_arrays = inputs[-opdef.num_aux:]
        for aa, v in zip(aux_arrays, outs[-n_aux_updates:]):
            aa._set_data(v)
        outs = outs[:-n_aux_updates]

    nvis = getattr(opdef, "num_visible", None)
    if callable(nvis):  # attr-dependent (reference NumVisibleOutputs)
        nvis = nvis(attrs)
    keep = len(outs)
    if out is not None:
        out_arrays = [out] if isinstance(out, NDArray) else list(out)
        for oa, v in zip(out_arrays, outs[:len(out_arrays)]):
            oa._set_data(v)
    else:
        out_arrays = [NDArray(v) for v in outs]

    if _ag.is_recording():
        # the recorded closure hides aux-update outputs; n_keep maps the
        # visible outputs only
        def pure(*a, _fn=fn, _kw=kwargs, _n=n_aux_updates, **_ignored):
            r = _fn(*a, **_kw)
            if not isinstance(r, (tuple, list)):
                r = (r,)
            return tuple(r[:len(r) - _n] if _n else r)
        _ag._record(pure, dict(attrs), list(inputs), vals, out_arrays,
                    rng_key=rng_key, n_keep=keep, op_name=opdef.name)

    if _naive_mode():
        for oa in out_arrays:
            oa._data.block_until_ready()

    if nvis is not None and nvis < len(out_arrays):
        out_arrays = out_arrays[:nvis]
    return out_arrays[0] if len(out_arrays) == 1 else out_arrays


# ===========================================================================
# creation / free functions (reference: python/mxnet/ndarray/ndarray.py tail)
# ===========================================================================
def array(source_array, ctx=None, dtype=None) -> NDArray:
    if dtype is None and not hasattr(source_array, "dtype"):
        # reference semantics (ndarray.py array): python lists/scalars
        # default to float32; arrays keep their dtype
        dtype = np.float32
    return NDArray(source_array, ctx=ctx or current_context(), dtype=dtype)


def empty(shape, ctx=None, dtype=None):
    return zeros(shape, ctx=ctx, dtype=dtype)


def zeros(shape, ctx=None, dtype=None, **kw):
    if isinstance(shape, numbers.Integral):
        shape = (shape,)
    dtype = np.dtype(dtype).name if dtype is not None and dtype is not jnp.bfloat16 \
        else ("bfloat16" if dtype is jnp.bfloat16 else "float32")
    out = _invoke("_zeros", [], {"shape": tuple(shape), "dtype": dtype})
    if ctx is not None:
        out._set_data(jax.device_put(out._data, ctx.jax_device()))
    return out


def ones(shape, ctx=None, dtype=None, **kw):
    if isinstance(shape, numbers.Integral):
        shape = (shape,)
    dtype = np.dtype(dtype).name if dtype is not None else "float32"
    out = _invoke("_ones", [], {"shape": tuple(shape), "dtype": dtype})
    if ctx is not None:
        out._set_data(jax.device_put(out._data, ctx.jax_device()))
    return out


def full(shape, val, ctx=None, dtype=None, **kw):
    if isinstance(shape, numbers.Integral):
        shape = (shape,)
    dtype = np.dtype(dtype).name if dtype is not None else "float32"
    return _invoke("_full", [], {"shape": tuple(shape), "dtype": dtype,
                                 "value": float(val)})


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=None):
    dtype = np.dtype(dtype).name if dtype is not None else "float32"
    return _invoke("_arange", [], {"start": start, "stop": stop, "step": step,
                                   "repeat": repeat, "dtype": dtype})


def concatenate(arrays, axis=0, always_copy=True):
    return _invoke("Concat", list(arrays), {"dim": axis})


def stack_arrays(arrays, axis=0):
    return _invoke("stack", list(arrays), {"axis": axis})


def onehot_encode(indices, out):
    depth = out.shape[1]
    res = _invoke("one_hot", [indices], {"depth": depth})
    out._set_data(res._data)
    return out


def moveaxis(tensor, source, destination):
    return _invoke_fn(lambda d, **kw: jnp.moveaxis(d, source, destination),
                      [tensor], {})


def waitall():
    """reference: Engine::WaitForAll — drain all async work."""
    import jax as _jax
    try:
        _jax.effects_barrier()
    except Exception:
        pass


def load(fname):
    from ..serialization import load_ndarrays
    return load_ndarrays(fname)


def save(fname, data):
    from ..serialization import save_ndarrays
    save_ndarrays(fname, data)
