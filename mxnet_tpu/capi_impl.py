"""Python side of the native C ABI (``native/c_api.{h,cc}``).

TPU-native answer to the reference's flat C boundary
(reference: include/mxnet/c_api.h — ~152 ``MX*`` functions over a C++
core; include/mxnet/c_predict_api.h — the predict-only deployment API).
Our core is Python-over-XLA, so the boundary inverts: the C library
embeds CPython and dispatches into this module, while the compute still
runs through jit/XLA exactly as it does for Python users.  The C side
stays dumb on purpose — every function here takes/returns only ints,
strings, and raw buffer addresses, and objects cross the boundary as
opaque integer handles owned by this module's registry (the analog of
the reference's ``NDArrayHandle``/``SymbolHandle`` void pointers).

Nothing in here is a public Python API; Python users import
``mxnet_tpu`` directly.
"""
from __future__ import annotations

import ast
import ctypes
import threading

import numpy as np

_lock = threading.Lock()
_handles: dict[int, object] = {}
_next = [1]


def _new_handle(obj) -> int:
    with _lock:
        h = _next[0]
        _next[0] += 1
        _handles[h] = obj
    return h


def _get(h: int):
    try:
        return _handles[h]
    except KeyError:
        raise ValueError("invalid or freed handle: %d" % h) from None


def free_handle(h: int) -> None:
    with _lock:
        _handles.pop(h, None)


def _ctx(dev_type: int, dev_id: int):
    import mxnet_tpu as mx
    # 1 = cpu, 2 = accelerator — mirrors the reference's dev_type ints
    # (cpu/gpu, include/mxnet/base.h Context); here the accelerator is TPU.
    return mx.cpu(dev_id) if dev_type == 1 else mx.tpu(dev_id)


def _np_from_addr(addr: int, shape, dtype: str) -> np.ndarray:
    n = int(np.prod(shape)) if shape else 1
    cbuf = (ctypes.c_char * (n * np.dtype(dtype).itemsize)).from_address(addr)
    return np.frombuffer(cbuf, dtype=dtype).reshape(shape).copy()


# ---------------------------------------------------------------- ndarray

def ndarray_create(shape, dtype: str, dev_type: int, dev_id: int) -> int:
    from mxnet_tpu import ndarray as nd
    return _new_handle(nd.zeros(tuple(shape), ctx=_ctx(dev_type, dev_id),
                                dtype=dtype))


def ndarray_from_data(addr: int, shape, dtype: str,
                      dev_type: int, dev_id: int) -> int:
    from mxnet_tpu import ndarray as nd
    arr = _np_from_addr(addr, tuple(shape), dtype)
    return _new_handle(nd.array(arr, ctx=_ctx(dev_type, dev_id)))


def ndarray_ndim(h: int) -> int:
    return len(_get(h).shape)


def ndarray_shape(h: int) -> tuple:
    return tuple(int(d) for d in _get(h).shape)


def ndarray_dtype(h: int) -> str:
    return str(np.dtype(_get(h).dtype).name)


def ndarray_nbytes(h: int) -> int:
    a = _get(h)
    return int(np.prod(a.shape or (1,))) * np.dtype(a.dtype).itemsize


def ndarray_copy_from(h: int, addr: int, nbytes: int) -> None:
    """Synchronous host->device copy INTO an existing handle — in-place
    value update, version-handle semantics preserved (reference:
    MXNDArraySyncCopyFromCPU, c_api.cc)."""
    a = _get(h)
    want = ndarray_nbytes(h)
    if want != nbytes:
        raise ValueError("buffer size %d != array bytes %d"
                         % (nbytes, want))
    arr = _np_from_addr(addr, a.shape, np.dtype(a.dtype).name)
    import jax
    # keep the handle's placement: jnp.asarray would silently move the
    # value to the default device (copyto() shows the same pattern)
    dev = a.context.jax_device()
    a._set_data(jax.device_put(arr, dev))


def ndarray_copy_to(h: int, addr: int, nbytes: int) -> None:
    """Synchronous device->host copy into a caller-owned buffer
    (reference: MXNDArraySyncCopyToCPU, c_api.cc)."""
    a = _get(h).asnumpy()
    if a.nbytes != nbytes:
        raise ValueError("buffer size %d != array bytes %d"
                         % (nbytes, a.nbytes))
    ctypes.memmove(addr, a.ctypes.data, a.nbytes)


def ndarray_save(path: str, handles, names) -> None:
    from mxnet_tpu.ndarray import save as nd_save
    if names:
        nd_save(path, {n: _get(h) for n, h in zip(names, handles)})
    else:
        nd_save(path, [_get(h) for h in handles])


def ndarray_load(path: str):
    """Returns (names_or_None, [handles])."""
    from mxnet_tpu.ndarray import load as nd_load
    got = nd_load(path)
    if isinstance(got, dict):
        names = list(got.keys())
        return names, [_new_handle(got[n]) for n in names]
    return None, [_new_handle(a) for a in got]


def wait_all() -> None:
    import mxnet_tpu as mx
    mx.nd.waitall()


def random_seed(seed: int) -> None:
    import mxnet_tpu as mx
    mx.random.seed(seed)


# ------------------------------------------------------------- imperative

def _parse_scalar(v: str):
    """Reference convention: all op hyper-params cross the C boundary as
    strings (c_api_ndarray.cc MXImperativeInvoke keys/vals) and are parsed
    by dmlc::Parameter.  Here the autogenerated op wrappers take Python
    values, so parse conservatively: literals (ints, floats, bools,
    tuples) decode, anything else stays a string."""
    try:
        return ast.literal_eval(v)
    except (ValueError, SyntaxError):
        low = v.strip().lower()
        if low in ("true", "false"):
            return low == "true"
        if low in ("none", "null"):
            return None
        return v


def imperative_invoke(op_name: str, in_handles, keys, vals):
    """Invoke any registered op by name — the whole ~319-op surface from C
    (reference: MXImperativeInvoke, c_api_ndarray.cc:165).  op_name is
    validated against the op registry — the same source MXTListAllOpNames
    reports — so a C caller cannot reach arbitrary module-level callables
    (save/load/array/...) through the op path."""
    from mxnet_tpu import ndarray as nd
    from mxnet_tpu.ops import registry
    if registry.find(op_name) is None:  # O(1), same names list_ops sorts
        raise ValueError("unknown op: %r" % op_name)
    fn = getattr(nd, op_name, None)
    if fn is None or not callable(fn):
        raise ValueError("unknown op: %r" % op_name)
    args = [_get(h) for h in in_handles]
    kwargs = {k: _parse_scalar(v) for k, v in zip(keys, vals)}
    out = fn(*args, **kwargs)
    outs = out if isinstance(out, (list, tuple)) else [out]
    return [_new_handle(o) for o in outs]


def list_all_op_names() -> str:
    from mxnet_tpu.ops import registry
    return "\n".join(sorted(registry.list_ops()))


# ----------------------------------------------------------------- symbol

def symbol_create_from_json(js: str) -> int:
    from mxnet_tpu import symbol as sym
    return _new_handle(sym.load_json(js))


def symbol_create_from_file(path: str) -> int:
    from mxnet_tpu import symbol as sym
    return _new_handle(sym.load(path))


def symbol_save_json(h: int) -> str:
    return _get(h).tojson()


def symbol_list_arguments(h: int) -> str:
    return "\n".join(_get(h).list_arguments())


def symbol_list_outputs(h: int) -> str:
    return "\n".join(_get(h).list_outputs())


# -------------------------------------------------------------- predictor

class _Predictor:
    """Predict-only executor: symbol JSON + saved params -> forward
    (reference: c_predict_api.h MXPredCreate/SetInput/Forward/GetOutput —
    the API the matlab/amalgamation deployments consumed).  Bound through
    Module with for_training=False so inference runs the same fused-jit
    path as Python users get."""

    def __init__(self, symbol_json: str, param_path: str,
                 dev_type: int, dev_id: int, input_names, input_shapes):
        import mxnet_tpu as mx
        from mxnet_tpu import symbol as sym_mod
        from mxnet_tpu.ndarray import load as nd_load
        symbol = sym_mod.load_json(symbol_json)
        arg_params, aux_params = {}, {}
        loaded = nd_load(param_path)
        if not isinstance(loaded, dict):
            # nd.save of a bare list round-trips as a list — useless here
            raise ValueError(
                "predictor needs a NAMED .params file (dict of "
                "name->array, e.g. saved via Module.save_checkpoint); "
                "%r contains an unnamed list" % param_path)
        for k, v in loaded.items():
            if ":" in k:
                tp, name = k.split(":", 1)
                (arg_params if tp == "arg" else aux_params)[name] = v
            else:
                arg_params[k] = v
        # Args that are neither inputs nor saved params and look like
        # loss-head labels get dummy bindings — a deployed symbol often
        # still carries its SoftmaxOutput head, whose label is unused at
        # inference (the reference's predict API tolerated this the same
        # way: c_predict_api.h consumers fed no labels).
        known = set(input_names) | set(arg_params) | set(aux_params)
        labels = [n for n in symbol.list_arguments()
                  if n not in known and n.endswith("label")]
        self._shapes = [tuple(s) for s in input_shapes]
        batch = self._shapes[0][0] if self._shapes[0] else 1
        self._mod = mx.mod.Module(symbol, data_names=tuple(input_names),
                                  label_names=tuple(labels) or None,
                                  context=_ctx(dev_type, dev_id))
        self._order = list(input_names)
        self._labels = labels
        self._mod.bind(data_shapes=list(zip(input_names, self._shapes)),
                       label_shapes=[(n, (batch,)) for n in labels] or None,
                       for_training=False)
        self._apply_shapes(self._shapes)
        self._mod.set_params(arg_params, aux_params,
                             allow_missing=False, allow_extra=True)

    def _apply_shapes(self, shapes):
        """Shared post-bind/post-reshape bookkeeping: current shapes,
        label zero-fills, pending-input and output state."""
        import mxnet_tpu as mx
        self._shapes = [tuple(s) for s in shapes]
        batch = self._shapes[0][0] if self._shapes[0] else 1
        self._label_zeros = [mx.nd.zeros((batch,)) for _ in self._labels]
        self._inputs = {n: None for n in self._order}
        self._outputs = None

    def reshape(self, input_names, input_shapes):
        """New input shapes, parameters kept (reference: MXPredReshape,
        c_predict_api.h — batch-size switch without re-creating the
        predictor)."""
        if list(input_names) != self._order:
            raise ValueError(
                f"reshape: input names {list(input_names)!r} must match "
                f"the predictor's {self._order!r}")
        shapes = [tuple(s) for s in input_shapes]
        batch = shapes[0][0] if shapes[0] else 1
        self._mod.reshape(
            data_shapes=list(zip(self._order, shapes)),
            label_shapes=[(n, (batch,))
                          for n in self._labels] or None)
        self._apply_shapes(shapes)

    def set_input(self, name: str, addr: int, size: int):
        import mxnet_tpu as mx
        if name not in self._inputs:
            raise ValueError("unknown input %r (have %r)"
                             % (name, self._order))
        shape = self._shapes[self._order.index(name)]
        n = int(np.prod(shape))
        if size != n:
            raise ValueError("input %r: got %d floats, shape %r needs %d"
                             % (name, size, shape, n))
        arr = _np_from_addr(addr, shape, "float32")
        self._inputs[name] = mx.nd.array(arr)

    def forward(self):
        from mxnet_tpu.io import DataBatch
        missing = [n for n, v in self._inputs.items() if v is None]
        if missing:
            raise ValueError("inputs not set: %r" % missing)
        batch = DataBatch(data=[self._inputs[n] for n in self._order],
                          label=self._label_zeros or None)
        self._mod.forward(batch, is_train=False)
        self._outputs = [o.asnumpy() for o in self._mod.get_outputs()]

    def output_shape(self, i: int):
        if self._outputs is None:
            raise ValueError("call forward first")
        return tuple(self._outputs[i].shape)

    def get_output(self, i: int, addr: int, size: int):
        if self._outputs is None:
            raise ValueError("call forward first")
        a = np.ascontiguousarray(self._outputs[i], dtype=np.float32)
        if size != a.size:
            raise ValueError("output %d: buffer %d floats, need %d"
                             % (i, size, a.size))
        ctypes.memmove(addr, a.ctypes.data, a.nbytes)


def predictor_create(symbol_json: str, param_path: str, dev_type: int,
                     dev_id: int, input_names, input_shapes) -> int:
    return _new_handle(_Predictor(symbol_json, param_path, dev_type,
                                  dev_id, input_names, input_shapes))


def predictor_set_input(h: int, name: str, addr: int, size: int) -> None:
    _get(h).set_input(name, addr, size)


def predictor_reshape(h: int, input_names, input_shapes) -> None:
    _get(h).reshape(input_names, input_shapes)


def predictor_forward(h: int) -> None:
    _get(h).forward()


def predictor_num_outputs(h: int) -> int:
    p = _get(h)
    if p._outputs is None:
        raise ValueError("call forward first")
    return len(p._outputs)


def predictor_output_shape(h: int, i: int) -> tuple:
    return _get(h).output_shape(i)


def predictor_get_output(h: int, i: int, addr: int, size: int) -> None:
    _get(h).get_output(i, addr, size)


# -------------------------------------------------------------- autograd

def autograd_set_recording(flag: int) -> int:
    from mxnet_tpu import autograd
    return int(autograd.set_recording(bool(flag)))


def autograd_set_training(flag: int) -> int:
    from mxnet_tpu import autograd
    return int(autograd.set_training(bool(flag)))


def autograd_is_recording() -> int:
    from mxnet_tpu import autograd
    return int(autograd.is_recording())


def ndarray_attach_grad(h: int, grad_req: str) -> None:
    if grad_req not in ("write", "add"):
        # the reference errors on unknown grad_req strings
        # (MXAutogradMarkVariables); a typo must not silently become
        # "write" — or "null", which yields all-zero "gradients"
        raise ValueError(f"grad_req must be 'write' or 'add', "
                         f"got {grad_req!r}")
    _get(h).attach_grad(grad_req=grad_req)


def ndarray_get_grad(h: int) -> int:
    g = _get(h).grad
    if g is None:
        raise ValueError("no gradient: attach_grad + backward first")
    return _new_handle(g)


def autograd_backward(handles, retain: int, train: int) -> None:
    from mxnet_tpu import autograd
    try:
        autograd.backward([_get(h) for h in handles],
                          retain_graph=bool(retain),
                          train_mode=bool(train))
    except Exception:
        # a failed backward must not pin the recorded snapshots: the C
        # surface has no record() scope whose exit would clear the tape
        autograd._clear_tape()
        raise


def autograd_clear_tape() -> None:
    """Drop recorded state without a backward — for C clients that
    abandon a recorded graph (Python users get this from the record()
    scope exit)."""
    from mxnet_tpu import autograd
    autograd._clear_tape()


# ----------------------------------------------------- module (training)
# The reference C API could TRAIN from bindings: MXExecutorSimpleBind +
# the updater loop (src/c_api/c_api_executor.cc:219, c_api.cc MXKVStore*).
# Here the training engine is Module's fused forward/backward/update —
# the same one XLA program Python users run — exposed row by row.

def module_create(sym_h: int, data_names, label_names,
                  dev_type: int, dev_id: int) -> int:
    import mxnet_tpu as mx
    mod = mx.mod.Module(_get(sym_h), data_names=tuple(data_names),
                        label_names=tuple(label_names) or None,
                        context=_ctx(dev_type, dev_id))
    return _new_handle(mod)


def module_bind(h: int, data_names, data_shapes, label_names,
                label_shapes, for_training: int) -> None:
    _get(h).bind(
        data_shapes=list(zip(data_names,
                             [tuple(s) for s in data_shapes])),
        label_shapes=list(zip(label_names,
                              [tuple(s) for s in label_shapes])) or None,
        for_training=bool(for_training))


def module_init_params(h: int, initializer: str, keys, vals) -> None:
    from mxnet_tpu import initializer as init_mod
    kwargs = {k: _parse_scalar(v) for k, v in zip(keys, vals)}
    _get(h).init_params(init_mod.create(initializer, **kwargs))


def module_init_optimizer(h: int, optimizer: str, keys, vals) -> None:
    params = {k: _parse_scalar(v) for k, v in zip(keys, vals)}
    _get(h).init_optimizer(optimizer=optimizer, optimizer_params=params)


def module_forward(h: int, data_handles, label_handles,
                   is_train: int) -> None:
    from mxnet_tpu.io import DataBatch
    batch = DataBatch(data=[_get(d) for d in data_handles],
                      label=[_get(l) for l in label_handles] or None)
    _get(h).forward(batch, is_train=bool(is_train))


def module_backward(h: int) -> None:
    _get(h).backward()


def module_update(h: int) -> None:
    _get(h).update()


def module_num_outputs(h: int) -> int:
    return len(_get(h).get_outputs())


def module_get_output(h: int, i: int) -> int:
    return _new_handle(_get(h).get_outputs()[i])


def module_save_checkpoint(h: int, prefix: str, epoch: int) -> None:
    _get(h).save_checkpoint(prefix, epoch)


def module_set_params_from_file(h: int, param_path: str) -> None:
    """Load a Module.save_checkpoint .params file into a bound module
    (reference flow: MXNDArrayLoad + ExecutorCopyFromParams)."""
    from mxnet_tpu.ndarray import load as nd_load
    loaded = nd_load(param_path)
    if not isinstance(loaded, dict):
        raise ValueError("need a named .params file")
    arg, aux = {}, {}
    for k, v in loaded.items():
        if ":" in k:
            tp, name = k.split(":", 1)
            (arg if tp == "arg" else aux)[name] = v
        else:
            arg[k] = v
    _get(h).set_params(arg, aux, allow_missing=False, allow_extra=True)


# ---------------------------------------------------------------- kvstore
# reference: MXKVStoreCreate/Init(Ex)/Push(Ex)/Pull(Ex)/SetOptimizer/
# GetRank/GetGroupSize/GetType/Free (src/c_api/c_api.cc)

def kvstore_create(kvtype: str) -> int:
    from mxnet_tpu import kvstore as kvs
    return _new_handle(kvs.create(kvtype))


def kvstore_init(h: int, keys, val_handles) -> None:
    kv = _get(h)
    for k, vh in zip(keys, val_handles):
        kv.init(k, _get(vh))


def kvstore_push(h: int, keys, val_handles, priority: int) -> None:
    kv = _get(h)
    for k, vh in zip(keys, val_handles):
        kv.push(k, _get(vh), priority=priority)


def kvstore_pull(h: int, keys, out_handles, priority: int) -> None:
    kv = _get(h)
    for k, oh in zip(keys, out_handles):
        kv.pull(k, out=_get(oh), priority=priority)


def kvstore_set_optimizer(h: int, optimizer: str, keys, vals) -> None:
    from mxnet_tpu import optimizer as opt_mod
    params = {k: _parse_scalar(v) for k, v in zip(keys, vals)}
    _get(h).set_optimizer(opt_mod.create(optimizer, **params))


def kvstore_rank(h: int) -> int:
    return int(_get(h).rank)


def kvstore_num_workers(h: int) -> int:
    return int(_get(h).num_workers)


def kvstore_type(h: int) -> str:
    return str(_get(h).type)


# --------------------------------------------------------------- dataiter
# reference: MXListDataIters/MXDataIterCreateIter (by-name + string
# kwargs, src/c_api/c_api.cc) and the Next/BeforeFirst/GetData/GetLabel/
# GetPadNum iteration protocol our DataIter already mirrors (io.py).

def _iter_classes():
    from mxnet_tpu import io as io_mod
    from mxnet_tpu.image_record_iter import (ImageRecordIter,
                                             ImageRecordUInt8Iter)
    return {
        "NDArrayIter": io_mod.NDArrayIter,
        "CSVIter": io_mod.CSVIter,
        "MNISTIter": io_mod.MNISTIter,
        "LibSVMIter": io_mod.LibSVMIter,
        "ImageRecordIter": ImageRecordIter,
        "ImageRecordUInt8Iter": ImageRecordUInt8Iter,
    }


def list_data_iters() -> str:
    return "\n".join(sorted(_iter_classes()))


def dataiter_create(name: str, keys, vals) -> int:
    cls = _iter_classes().get(name)
    if cls is None:
        raise ValueError("unknown data iter: %r (have: %s)"
                         % (name, ", ".join(sorted(_iter_classes()))))
    kwargs = {k: _parse_scalar(v) for k, v in zip(keys, vals)}
    return _new_handle(cls(**kwargs))


def dataiter_from_arrays(data_h: int, label_h: int, batch_size: int,
                         shuffle: int, last_batch_handle: str) -> int:
    from mxnet_tpu import io as io_mod
    label = _get(label_h) if label_h else None
    return _new_handle(io_mod.NDArrayIter(
        _get(data_h), label, batch_size=batch_size, shuffle=bool(shuffle),
        last_batch_handle=last_batch_handle))


def dataiter_before_first(h: int) -> None:
    _get(h).reset()


def dataiter_next(h: int) -> int:
    return 1 if _get(h).iter_next() else 0


def dataiter_get_data(h: int) -> int:
    return _new_handle(_get(h).getdata()[0])


def dataiter_get_label(h: int) -> int:
    lab = _get(h).getlabel()
    if not lab:
        raise ValueError("iterator has no labels")
    return _new_handle(lab[0])


def dataiter_get_pad(h: int) -> int:
    return int(_get(h).getpad() or 0)


# --------------------------------------------------------------- recordio
# reference: MXRecordIOWriterCreate/WriteRecord/Free,
# MXRecordIOReaderCreate/ReadRecord/Free (src/c_api/c_api.cc over
# dmlc::RecordIO) — same container format recordio.py implements.

def recordio_writer_create(path: str) -> int:
    from mxnet_tpu.recordio import MXRecordIO
    return _new_handle(MXRecordIO(path, "w"))


class _RecordReader:
    """Peeking reader: the C size-query protocol calls ReadRecord twice
    per record (size, then payload) — a second ``read()`` would consume
    the NEXT record, so the pending one is cached until delivered."""

    def __init__(self, path):
        from mxnet_tpu.recordio import MXRecordIO
        self.rio = MXRecordIO(path, "r")
        self.pending = None

    def peek(self):
        if self.pending is None:
            self.pending = self.rio.read()
        return self.pending

    def advance(self):
        self.pending = None


def recordio_reader_create(path: str) -> int:
    return _new_handle(_RecordReader(path))


def recordio_write(h: int, addr: int, nbytes: int) -> None:
    buf = (ctypes.c_char * nbytes).from_address(addr)
    _get(h).write(bytes(buf))


def recordio_peek(h: int):
    """Bytes of the pending record, or None at end of file."""
    return _get(h).peek()


def recordio_advance(h: int) -> None:
    _get(h).advance()


def recordio_close(h: int) -> None:
    obj = _get(h)
    (obj.rio if isinstance(obj, _RecordReader) else obj).close()
