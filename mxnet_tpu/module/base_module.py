"""BaseModule: the high-level train/predict interface
(reference: python/mxnet/module/base_module.py).
"""
from __future__ import annotations

import logging
import time
from typing import List, Optional

import numpy as np

from ..base import MXNetError
from .. import metric as metric_mod
from .. import io as io_mod
from .. import tracing as _tracing
from ..model import BatchEndParam
from ..initializer import Uniform
from ..ndarray import NDArray


def _check_input_names(symbol, names, typename, throw):
    """reference: base_module.py _check_input_names."""
    args = symbol.list_arguments()
    for name in names:
        if name in args:
            continue
        candidates = [arg for arg in args if
                      not arg.endswith('_weight') and
                      not arg.endswith('_bias') and
                      not arg.endswith('_gamma') and
                      not arg.endswith('_beta')]
        msg = "\033[91mYou created Module with Module(..., %s_names=%s) but " \
              "input with name '%s' is not found in symbol.list_arguments(). " \
              "Did you mean one of:\n\t%s\033[0m" % (
                  typename, str(names), name, '\n\t'.join(candidates))
        if throw:
            raise ValueError(msg)
        logging.warning(msg)


def _parse_data_desc(data_names, label_names, data_shapes, label_shapes):
    """reference: base_module.py _parse_data_desc."""
    data_shapes = [x if isinstance(x, io_mod.DataDesc)
                   else io_mod.DataDesc(*x) for x in data_shapes]
    _check_names_match(data_names, data_shapes, 'data', True)
    if label_shapes is not None:
        label_shapes = [x if isinstance(x, io_mod.DataDesc)
                        else io_mod.DataDesc(*x) for x in label_shapes]
        _check_names_match(label_names, label_shapes, 'label', False)
    else:
        _check_names_match(label_names, [], 'label', False)
    return data_shapes, label_shapes


def _check_names_match(data_names, data_shapes, name, throw):
    actual = [x[0] for x in data_shapes]
    if sorted(data_names) != sorted(actual):
        msg = "Data provided by %s_shapes don't match names specified by " \
              "%s_names (%s vs. %s)" % (name, name, str(data_shapes),
                                        str(data_names))
        if throw:
            raise ValueError(msg)
        logging.warning(msg)


def _canon_step_inputs(names, value, what, k=None):
    """Canonicalize ``run_steps`` inputs to a list of K-stacked arrays
    aligned with ``names`` (each element shaped ``(k,) + per_step_shape``).

    Accepts a dict name->array, a list aligned with ``names``, a single
    array (one input), or — for a single input name — a list of K
    per-step batches (stacked here).  Returns (arrays, k)."""
    import jax.numpy as jnp

    def _as_val(v):
        if isinstance(v, NDArray):
            return v._data
        if isinstance(v, (np.ndarray, jnp.ndarray)):
            return v
        # analysis: allow(host-sync): v is user feed data that is NOT an NDArray/jnp array (those returned above) — host lists/scalars only
        return np.asarray(v)

    if value is None:
        if names:
            raise MXNetError(f"run_steps: {what} is required "
                             f"(names: {names})")
        return [], k
    if isinstance(value, dict):
        missing = [n for n in names if n not in value]
        if missing:
            raise MXNetError(f"run_steps: missing {what}: {missing}")
        arrays = [_as_val(value[n]) for n in names]
    elif isinstance(value, (list, tuple)):
        if len(value) == len(names):
            arrays = [_as_val(v) for v in value]
        elif len(names) == 1:
            # list of K per-step batches for the single input
            # analysis: allow(host-sync): K-superbatch staging at run_steps entry — one host stack per K-step dispatch, amortized 1/K per step
            arrays = [np.stack([np.asarray(_as_val(v)) for v in value])]
        else:
            raise MXNetError(
                f"run_steps: expected {len(names)} {what} arrays, "
                f"got {len(value)}")
    else:
        if len(names) != 1:
            raise MXNetError(
                f"run_steps: {what} must be a dict/list covering "
                f"{names}")
        arrays = [_as_val(value)]
    ks = {int(a.shape[0]) for a in arrays if a.ndim}
    if len(ks) != 1:
        raise MXNetError(f"run_steps: inconsistent leading (step) dims "
                         f"for {what}: {sorted(ks)}")
    inferred = ks.pop()
    if inferred == 0:
        raise MXNetError(
            f"run_steps: {what} stacks ZERO steps (empty leading axis) "
            "— a mis-built superbatch (e.g. a KBatchIter tail)?")
    if k is not None and k != inferred:
        raise MXNetError(
            f"run_steps: k={k} but {what} arrays stack "
            f"{inferred} steps (leading dim)")
    return arrays, inferred


class BaseModule:
    """reference: base_module.py BaseModule."""

    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None
        self._total_exec_bytes = 0

    # -- high-level ----------------------------------------------------------
    def run_steps(self, data, label=None, k=None, eval_metric=None):
        """Run K training steps (forward + backward + optimizer update).

        ``data``/``label`` carry K stacked batches (leading axis = step;
        see :func:`_canon_step_inputs` for accepted forms).  This base
        implementation is the EAGER driver — one dispatch per step — and
        serves as the universal fallback (BucketingModule, K=1, shape
        changes, non-pure optimizers).  :class:`Module` overrides it with
        the scanned single-dispatch program.  Returns the per-step
        outputs stacked on a leading K axis, one NDArray per output."""
        data_arrays, k = _canon_step_inputs(
            self.data_names, data, "data", k)
        label_arrays, k = _canon_step_inputs(
            getattr(self, "label_names", []), label, "label", k)
        return self._run_steps_eager(data_arrays, label_arrays, k,
                                     eval_metric)

    def _run_steps_eager(self, data_arrays, label_arrays, k, eval_metric):
        import jax.numpy as jnp
        outs_steps = []
        for j in range(k):
            batch = io_mod.DataBatch(
                data=[NDArray(jnp.asarray(a[j])) for a in data_arrays],
                label=[NDArray(jnp.asarray(a[j])) for a in label_arrays]
                if label_arrays else None)
            self.forward(batch, is_train=True)
            self.update()
            if eval_metric is not None:
                self.update_metric(eval_metric, batch.label)
            outs_steps.append([o._data for o in self.get_outputs()])
        return [NDArray(jnp.stack([s[i] for s in outs_steps]))
                for i in range(len(outs_steps[0]))]

    def forward_backward(self, data_batch):
        self.forward(data_batch, is_train=True)
        self.backward()

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None, reset=True,
              epoch=0):
        """reference: base_module.py score."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        if not isinstance(eval_metric, metric_mod.EvalMetric):
            eval_metric = metric_mod.create(eval_metric)
        eval_metric.reset()
        actual_num_batch = 0
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            # device-resident accumulation: the loop never blocks on a
            # readback — the metric syncs ONCE at get_name_value below
            # (or whenever a batch_end_callback reads it)
            self.update_metric(eval_metric, eval_batch.label)
            if batch_end_callback is not None:
                batch_end_params = BatchEndParam(epoch=epoch, nbatch=nbatch,
                                                 eval_metric=eval_metric,
                                                 locals=locals())
                for callback in _as_list(batch_end_callback):
                    callback(batch_end_params)
            actual_num_batch += 1
        if score_end_callback:
            params = BatchEndParam(epoch=epoch, nbatch=actual_num_batch,
                                   eval_metric=eval_metric, locals=locals())
            for callback in _as_list(score_end_callback):
                callback(params)
        return eval_metric.get_name_value()

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        """reference: base_module.py iter_predict."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - pad] for out in
                       self.get_outputs()]
            yield (outputs, nbatch, eval_batch)

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False):
        """reference: base_module.py predict."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        output_list = []
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - pad].copy()
                       for out in self.get_outputs()]
            output_list.append(outputs)
        if len(output_list) == 0:
            return output_list
        if merge_batches:
            num_outputs = len(output_list[0])
            for out in output_list:
                assert len(out) == num_outputs, \
                    'Cannot merge batches, as num of outputs is not the same ' \
                    'in mini-batches. Maybe bucketing is used?'
            # pad slicing already happened on device (above); batches
            # come back in chunked stacked readbacks — one sync per
            # MXNET_PREDICT_READBACK_BATCHES batches instead of one
            # device->host copy per batch per output.  The NDArray
            # wrappers are dropped first so each fetched chunk's device
            # buffers free immediately (the old streaming memory
            # profile, at a fraction of its sync cost).
            groups = [[o._data for o in outs] for outs in output_list]
            del output_list, outputs, out
            host = chunked_device_get(groups, "predict.readback")
            output_list2 = [
                NDArray(np.concatenate([h[i] for h in host]))
                for i in range(num_outputs)]
            if num_outputs == 1 and not always_output_list:
                return output_list2[0]
            return output_list2
        return output_list

    def fit(self, train_data, eval_data=None, eval_metric='acc',
            epoch_end_callback=None, batch_end_callback=None, kvstore='local',
            optimizer='sgd', optimizer_params=(('learning_rate', 0.01),),
            eval_end_callback=None,
            eval_batch_end_callback=None, initializer=Uniform(0.01),
            arg_params=None, aux_params=None, allow_missing=False,
            force_rebind=False, force_init=False, begin_epoch=0,
            num_epoch=None, validation_metric=None, monitor=None):
        """Full training loop (reference: base_module.py:376-520)."""
        assert num_epoch is not None, 'please specify number of epochs'

        self.bind(data_shapes=train_data.provide_data,
                  label_shapes=train_data.provide_label,
                  for_training=True, force_rebind=force_rebind)
        if monitor is not None:
            self.install_monitor(monitor)
        self.init_params(initializer=initializer, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)
        self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                            optimizer_params=optimizer_params)

        if validation_metric is None:
            validation_metric = eval_metric
        if not isinstance(eval_metric, metric_mod.EvalMetric):
            eval_metric = metric_mod.create(eval_metric)

        ################################################################
        # training loop
        ################################################################
        for epoch in range(begin_epoch, num_epoch):
            tic = time.time()
            eval_metric.reset()
            nbatch = 0
            data_iter = iter(train_data)
            end_of_batch = False
            with _tracing.span("mx.fit.next_batch", "fit"):
                next_data_batch = next(data_iter)
            while not end_of_batch:
                data_batch = next_data_batch
                if monitor is not None:
                    monitor.tic()
                self.forward_backward(data_batch)
                self.update()
                try:
                    # the iterator wait: what the step loses to input
                    with _tracing.span("mx.fit.next_batch", "fit"):
                        next_data_batch = next(data_iter)
                    self.prepare(next_data_batch)
                except StopIteration:
                    end_of_batch = True
                # device-resident metric accumulation: nothing here
                # blocks on the device.  The ONLY host syncs in this
                # loop happen when a batch_end_callback reads the
                # metric (EvalMetric.sync via get_name_value — e.g.
                # Speedometer every `frequent` batches) and at the
                # epoch-end log below: <= nbatch/frequent + 1 syncs
                # per epoch, asserted by tests/test_sync_free.py.
                with _tracing.span("mx.fit.update_metric", "fit"):
                    self.update_metric(eval_metric, data_batch.label)
                if monitor is not None:
                    monitor.toc_print()
                if batch_end_callback is not None:
                    with _tracing.span("mx.fit.callbacks", "fit"):
                        batch_end_params = BatchEndParam(
                            epoch=epoch, nbatch=nbatch,
                            eval_metric=eval_metric, locals=locals())
                        for callback in _as_list(batch_end_callback):
                            callback(batch_end_params)
                nbatch += 1

            for name, val in eval_metric.get_name_value():
                self.logger.info('Epoch[%d] Train-%s=%f', epoch, name, val)
            toc = time.time()
            self.logger.info('Epoch[%d] Time cost=%.3f', epoch, (toc - tic))

            arg_params_, aux_params_ = self.get_params()
            self.set_params(arg_params_, aux_params_)
            if epoch_end_callback is not None:
                for callback in _as_list(epoch_end_callback):
                    callback(epoch, self.symbol, arg_params_, aux_params_)

            if eval_data:
                res = self.score(eval_data, validation_metric,
                                 score_end_callback=eval_end_callback,
                                 batch_end_callback=eval_batch_end_callback,
                                 epoch=epoch)
                for name, val in res:
                    self.logger.info('Epoch[%d] Validation-%s=%f', epoch,
                                     name, val)
            train_data.reset()

    # -- abstract interface ---------------------------------------------------
    @property
    def symbol(self):
        return self._symbol

    @property
    def data_names(self):
        raise NotImplementedError()

    @property
    def output_names(self):
        raise NotImplementedError()

    @property
    def data_shapes(self):
        raise NotImplementedError()

    @property
    def label_shapes(self):
        raise NotImplementedError()

    @property
    def output_shapes(self):
        raise NotImplementedError()

    def get_params(self):
        raise NotImplementedError()

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False,
                    allow_extra=False):
        raise NotImplementedError()

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init, allow_extra=allow_extra)

    def save_params(self, fname):
        """reference: base_module.py save_params."""
        arg_params, aux_params = self.get_params()
        save_dict = {('arg:%s' % k): v for k, v in arg_params.items()}
        save_dict.update({('aux:%s' % k): v for k, v in aux_params.items()})
        from ..serialization import save_ndarrays
        save_ndarrays(fname, save_dict)

    def load_params(self, fname):
        """reference: base_module.py load_params."""
        from ..serialization import load_ndarrays
        save_dict = load_ndarrays(fname)
        arg_params = {}
        aux_params = {}
        for k, value in save_dict.items():
            arg_type, name = k.split(':', 1)
            if arg_type == 'arg':
                arg_params[name] = value
            elif arg_type == 'aux':
                aux_params[name] = value
            else:
                raise ValueError("Invalid param file " + fname)
        self.set_params(arg_params, aux_params)

    def get_states(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        return []

    def set_states(self, states=None, value=None):
        assert self.binded and self.params_initialized
        assert not states and not value

    def install_monitor(self, mon):
        raise NotImplementedError()

    def prepare(self, data_batch):
        pass

    def forward(self, data_batch, is_train=None):
        raise NotImplementedError()

    def backward(self, out_grads=None):
        raise NotImplementedError()

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError()

    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError()

    def update(self):
        raise NotImplementedError()

    def update_metric(self, eval_metric, labels):
        raise NotImplementedError()

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req='write'):
        raise NotImplementedError()

    def init_optimizer(self, kvstore='local', optimizer='sgd',
                       optimizer_params=(('learning_rate', 0.01),),
                       force_init=False):
        raise NotImplementedError()


def _as_list(obj):
    if isinstance(obj, (list, tuple)):
        return obj
    return [obj]


def chunked_device_get(groups, tag, chunk=None):
    """Fetch a list of per-batch value groups to host in CHUNKS of
    ``MXNET_PREDICT_READBACK_BATCHES`` batches (default 64): each chunk
    is one stacked ``jax.device_get`` (one host sync, recorded under
    ``tag``), and the chunk's device buffers are released before the
    next chunk is touched.  This keeps predict-style loops at O(1)
    syncs per chunk WITHOUT retaining the whole dataset's outputs in
    device memory the way a single end-of-run device_get would —
    the memory profile the old per-batch asnumpy streaming had, at
    1/chunk of its sync cost.  Mutates ``groups`` in place (device
    values -> numpy) and returns it."""
    import jax
    from ..base import env
    from .. import profiler as _prof
    if chunk is None:
        chunk = max(1, int(env("MXNET_PREDICT_READBACK_BATCHES", 64)))
    for lo in range(0, len(groups), chunk):
        with _tracing.span("mx.sync." + tag, "sync"):
            host = jax.device_get(groups[lo:lo + chunk])
        _prof.record_host_sync(tag)
        groups[lo:lo + chunk] = host
    return groups
