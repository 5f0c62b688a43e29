"""Bucketed pre-compiled predict executables.

# analysis: hot-path

The serving analog of the trainer's fused-step discipline: a replica
must never compile in the request path more than once per BUCKET.  The
predictor pre-compiles one XLA forward program per configured batch
size (``MXNET_SERVING_BUCKETS``); a batch of n requests pads to the
smallest covering bucket and slices the padded rows off before the
reply, so serving N distinct request sizes costs ``len(buckets)``
compiles, not N (TF-Serving's bucketed-batching shape,
arXiv:1605.08695 §4; the reference analog is BucketingModule's
per-bucket executor sharing one parameter set, module/
bucketing_module.py).

Weight refresh is a data swap, not a recompile: parameters enter the
jitted forward as ARGUMENTS, so :meth:`BucketedPredictor.set_params`
replaces the value tuple under a lock and every later predict serves
the new version — the live train-and-serve path rides this.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional

import numpy as np

from ..base import MXNetError, env
from ..executor import build_interpreter
from .. import profiler as _prof


def parse_buckets(spec=None) -> List[int]:
    """Canonical bucket list from a spec string/iterable (default: the
    ``MXNET_SERVING_BUCKETS`` knob): sorted, deduped, all positive."""
    if spec is None:
        spec = env("MXNET_SERVING_BUCKETS", "1,2,4,8,16,32")
    if isinstance(spec, str):
        items = [s for s in spec.replace(" ", "").split(",") if s]
    else:
        items = list(spec)
    try:
        buckets = sorted({int(b) for b in items})
    except (TypeError, ValueError):
        raise MXNetError(f"bad serving bucket spec {spec!r}: expected "
                         "comma-separated positive batch sizes")
    if not buckets or buckets[0] < 1:
        raise MXNetError(f"bad serving bucket spec {spec!r}: buckets "
                         "must be >= 1")
    return buckets


class BucketedPredictor:
    """Checkpoint -> bucketed predict executables with hot weight swap.

    ``data_shapes`` maps each data input name to its per-example
    FEATURE shape (no batch dim); every other symbol input (labels a
    loss head declares) is fed cached zeros — eval-mode loss heads
    (SoftmaxOutput & co.) ignore labels, exactly like
    ``Module.predict``.
    """

    def __init__(self, symbol, data_shapes: Dict[str, tuple], arg_params,
                 aux_params=None, buckets=None, compute_dtype=None,
                 data_dtypes: Optional[Dict[str, object]] = None):
        import jax
        self._sym = symbol
        self._run, self._arg_names, self._aux_names = build_interpreter(
            symbol, compute_dtype)
        self._data_shapes = {n: tuple(int(d) for d in s)
                             for n, s in dict(data_shapes).items()}
        unknown = [n for n in self._data_shapes
                   if n not in self._arg_names]
        if unknown:
            raise MXNetError(f"data_shapes name(s) {unknown} are not "
                             f"inputs of the symbol ({self._arg_names})")
        self._data_names = [n for n in self._arg_names
                            if n in self._data_shapes]
        self._data_dtypes = {
            n: np.dtype((data_dtypes or {}).get(n, np.float32))
            for n in self._data_names}
        self._param_names = [n for n in self._arg_names
                             if n not in self._data_shapes
                             and n in dict(arg_params)]
        self._extra_inputs = [n for n in self._arg_names
                              if n not in self._data_shapes
                              and n not in self._param_names]
        self.buckets = parse_buckets(buckets)
        self._lock = threading.Lock()
        self._params: Dict[str, object] = {}
        self._aux: Dict[str, object] = {}
        self.version = 0
        self._bucket_inputs: Dict[int, Dict[str, object]] = {}
        self._compiled = set()   # buckets whose executable was built
        self._key = jax.random.PRNGKey(0)   # eval mode: RNG ops inert

        def _fwd(arg_vals, aux_vals, key):
            outs, _new_aux = self._run(arg_vals, aux_vals, key, False)
            return outs

        self._jit = jax.jit(_fwd)
        self.set_params(arg_params, aux_params, version=0)

    # -- weights -------------------------------------------------------------
    def set_params(self, arg_params, aux_params=None, version=None):
        """Swap the served weights IN PLACE (no recompile: params are
        jit arguments).  Values are cast to the incumbent dtype/shape —
        a refresh can change numbers, never the compiled signature."""
        import jax.numpy as jnp
        arg_params = dict(arg_params)
        missing = [n for n in self._param_names if n not in arg_params]
        if missing:
            raise MXNetError(f"set_params: missing parameter(s) {missing}")
        new_p, new_a = {}, {}
        for name in self._param_names:
            v = jnp.asarray(_raw(arg_params[name]))
            old = self._params.get(name)
            if old is not None:
                if tuple(v.shape) != tuple(old.shape):
                    raise MXNetError(
                        f"set_params: shape of {name!r} changed "
                        f"{tuple(old.shape)} -> {tuple(v.shape)} — a "
                        "weight refresh cannot re-architect the model")
                if v.dtype != old.dtype:
                    v = v.astype(old.dtype)
            new_p[name] = v
        for name in self._aux_names:
            src = (aux_params or {}).get(name)
            if src is None:
                src = self._aux.get(name)
            if src is None:
                raise MXNetError(f"set_params: missing aux state {name!r}")
            v = jnp.asarray(_raw(src))
            old = self._aux.get(name)
            if old is not None and v.dtype != old.dtype:
                v = v.astype(old.dtype)
            new_a[name] = v
        with self._lock:
            self._params = new_p
            self._aux = new_a
            self.version = int(self.version + 1 if version is None
                               else version)

    def param_specs(self) -> Dict[str, tuple]:
        """{name: (shape, dtype_str)} of the served parameters — what a
        weight-refresh pull needs to allocate its out arrays."""
        with self._lock:
            return {n: (tuple(v.shape), str(v.dtype))
                    for n, v in self._params.items()}

    def current_params(self) -> Dict[str, object]:
        """Snapshot of the served parameter values (for a partial
        refresh to merge fresh pulls over)."""
        with self._lock:
            return dict(self._params)

    # -- buckets -------------------------------------------------------------
    def select_bucket(self, n: int) -> int:
        """Smallest bucket covering ``n`` rows (the largest bucket for
        oversized batches — the caller chunks).  Pure and deterministic:
        tests pin it directly."""
        if n < 1:
            raise MXNetError(f"select_bucket: need >= 1 row, got {n}")
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    def _bucket_extra_inputs(self, bucket: int) -> Dict[str, object]:
        """Cached zero arrays for the non-data, non-param inputs at this
        bucket's batch size (label inputs of loss heads; ignored in eval
        mode)."""
        cached = self._bucket_inputs.get(bucket)
        if cached is not None:
            return cached
        import jax.numpy as jnp
        shapes = {n: (bucket,) + s for n, s in self._data_shapes.items()}
        arg_shapes, _out, _aux = self._sym.infer_shape(**shapes)
        by_name = dict(zip(self._arg_names, arg_shapes))
        extras = {n: jnp.zeros(tuple(by_name[n]), jnp.float32)
                  for n in self._extra_inputs}
        self._bucket_inputs[bucket] = extras
        return extras

    # -- predict -------------------------------------------------------------
    def predict(self, data: Dict[str, np.ndarray]):
        """Run one padded-bucket forward per <= max(buckets)-row chunk;
        returns ``(version, [np outputs sliced to the true row count])``.

        ``data`` maps every data input name to an (n, *feature) array;
        rows beyond n are zero padding and are sliced off HERE — padding
        is an executable-shape artifact that must never leak into a
        reply."""
        datas = {}
        n = None
        for name in self._data_names:
            if name not in data:
                raise MXNetError(f"predict: missing data input {name!r}")
            # analysis: allow(host-sync): request payloads arrive as HOST numpy views off the wire frame — nothing here reads a device buffer back
            arr = np.asarray(_raw(data[name]))
            want = self._data_shapes[name]
            if tuple(arr.shape[1:]) != want:
                raise MXNetError(
                    f"predict: {name!r} feature shape {tuple(arr.shape[1:])}"
                    f" != served shape {want}")
            if n is None:
                n = int(arr.shape[0])
            elif int(arr.shape[0]) != n:
                raise MXNetError("predict: data inputs disagree on the "
                                 "row count")
            # dtype is part of the compiled signature: cast instead of
            # letting a float64 client request force a recompile
            datas[name] = np.ascontiguousarray(
                arr, dtype=self._data_dtypes[name])
        if n is None or n < 1:
            raise MXNetError("predict: empty request")
        chunks = []
        version = None
        max_b = self.buckets[-1]
        for lo in range(0, n, max_b):
            hi = min(n, lo + max_b)
            v, outs = self._predict_chunk(
                {name: arr[lo:hi] for name, arr in datas.items()}, hi - lo)
            version = v if version is None else version
            chunks.append(outs)
        if len(chunks) == 1:
            return version, chunks[0]
        return version, [np.concatenate(parts, axis=0)
                         for parts in zip(*chunks)]

    def _predict_chunk(self, datas, n):
        import jax
        bucket = self.select_bucket(n)
        pad = bucket - n
        with self._lock:
            params = self._params
            aux = self._aux
            version = self.version
        extras = self._bucket_extra_inputs(bucket)
        arg_vals = []
        for name in self._arg_names:
            if name in datas:
                arr = datas[name]
                if pad:
                    arr = np.concatenate(
                        [arr, np.zeros((pad,) + arr.shape[1:], arr.dtype)],
                        axis=0)
                arg_vals.append(arr)
            elif name in params:
                arg_vals.append(params[name])
            else:
                arg_vals.append(extras[name])
        aux_vals = tuple(aux[name] for name in self._aux_names)
        if bucket not in self._compiled:
            # one executable build per bucket, ever — THE serving compile
            # pin (tests assert dispatch_counts()["serving.predict_compile"]
            # <= len(buckets) after any request mix)
            self._compiled.add(bucket)
            _prof.record_dispatch("serving.predict_compile")
        _prof.record_dispatch("serving.predict")
        with _prof.span("mx.serving.predict.call", "serving"):
            outs = self._jit(tuple(arg_vals), aux_vals, self._key)
        host = jax.device_get(outs)
        # the reply crosses the wire as host bytes: this readback is the
        # serving loop's one deliberate sync, counted like every other
        # contract site
        _prof.record_host_sync("serving.predict_readback")
        return version, [np.asarray(o)[:n] for o in host]

    def warmup(self):
        """Pre-compile every bucket with a zero batch, so the first real
        request never pays a compile (the 'pre-compiled' half of the
        tentpole).  Returns the number of buckets built."""
        for b in self.buckets:
            self._predict_chunk(
                {name: np.zeros((b,) + s, self._data_dtypes[name])
                 for name, s in self._data_shapes.items()}, b)
        return len(self.buckets)


def _raw(v):
    """Underlying array of an NDArray / jax.Array / numpy value."""
    data = getattr(v, "_data", None)
    return data if data is not None else v
