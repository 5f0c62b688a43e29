"""Evaluation metrics (reference: python/mxnet/metric.py).

Two accumulation paths:

* **host path** (``update``/``update_dict``): numpy on host, one
  device->host readback per batch — the classic reference contract, kept
  bit-compatible for custom metrics and direct callers.
* **device path** (``device_update``/``update_device``/``sync``): pure
  jax ops over a ``(sum_metric, num_inst)`` pytree state that stays ON
  the async engine.  The training/eval loops accumulate through
  ``accumulate_dict`` (device when possible), and the host counters only
  see the state at ``sync()`` — ONE readback per log interval instead of
  one (or three) per step.  This is the MXNet paper's "everything stays
  on the async engine" discipline applied to scoring: per-batch
  ``EvalMetric.update`` readbacks were the last host serialization in
  ``fit``/``score``.

``device_update`` is functional (state in, state out) so the same math
rides a ``lax.scan`` carry: ``Module.run_steps`` folds K steps of
metrics into the one scanned program with zero extra dispatches.
"""
from __future__ import annotations

import logging
import math
from typing import List, Optional, Sequence

import numpy
import numpy as np  # shadowed below by metric.np(); use `numpy` internally

from .base import MXNetError, Registry, env
from .ndarray import NDArray

_METRIC_REGISTRY = Registry("metric")

# jitted per-batch device folds, keyed by EvalMetric._device_sig —
# shared across metric INSTANCES (see _device_update_jitted)
_DEVICE_JIT_CACHE: dict = {}


def check_label_shapes(labels, preds, shape=0):
    if shape == 0:
        label_shape, pred_shape = len(labels), len(preds)
    else:
        label_shape, pred_shape = labels.shape, preds.shape
    if label_shape != pred_shape:
        raise ValueError(
            f"Shape of labels {label_shape} does not match shape of "
            f"predictions {pred_shape}")


def _np(x):
    # analysis: allow(host-sync): legacy host-metric fallback path (one sync per batch BY DESIGN, pinned >=N by test_sync_free); NDArray.asnumpy records itself
    return x.asnumpy() if isinstance(x, NDArray) else numpy.asarray(x)


class EvalMetric:
    """Base metric (reference: metric.py EvalMetric)."""

    def __init__(self, name, output_names=None, label_names=None, **kwargs):
        self.name = str(name)
        self.output_names = output_names
        self.label_names = label_names
        self._kwargs = kwargs
        self.reset()

    def __str__(self):
        return f"EvalMetric: {dict(zip(*self.get()))}"

    def get_config(self):
        config = self._kwargs.copy()
        config.update({
            'metric': self.__class__.__name__,
            'name': self.name,
            'output_names': self.output_names,
            'label_names': self.label_names})
        return config

    def _select_dict(self, label, pred):
        """output_names/label_names selection shared by the host
        (update_dict) and device (device_update_dict) entry points."""
        if self.output_names is not None:
            pred = [pred[name] for name in self.output_names]
        else:
            pred = list(pred.values())
        if self.label_names is not None:
            label = [label[name] for name in self.label_names]
        else:
            label = list(label.values())
        return label, pred

    def update_dict(self, label, pred):
        label, pred = self._select_dict(label, pred)
        self.update(label, pred)

    def update(self, labels, preds):
        raise NotImplementedError()

    # -- device-resident accumulation ---------------------------------------
    # Converted metrics set ``device_capable`` and implement
    # ``device_update`` as pure jax ops; everything else (custom metrics,
    # Pearson) keeps the host path and the loops fall back with a
    # one-time warning.  State default: scalar (sum_metric f32,
    # num_inst i32) — shapes/dtypes must stay FIXED across updates
    # because the state rides lax.scan carries (Module.run_steps).
    device_capable = False
    _device_state = None   # class default so subclasses never AttributeError

    def device_init(self):
        """Zero accumulation state for the device path."""
        import jax.numpy as jnp
        return (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32))

    def device_update(self, state, labels, preds):
        """Functional device update: fold one batch of already-on-device
        ``labels``/``preds`` (lists of jax arrays) into ``state`` and
        return the new state.  Pure — jit/scan-traceable, no
        data-dependent host control flow, no readbacks.

        Subclasses: any hyperparameter this reads must flow through
        ``EvalMetric.__init__(**kwargs)`` — compiled folds are cached by
        ``_device_sig()``, which only sees those kwargs."""
        raise NotImplementedError(
            f"{type(self).__name__} has no device form")

    def device_update_dict(self, state, label, pred):
        """``update_dict`` in functional device form (the shape
        Module.run_steps folds into its scan body)."""
        label, pred = self._select_dict(label, pred)
        return self.device_update(state, label, pred)

    @staticmethod
    def _as_device(x):
        import jax.numpy as jnp
        return x._data if isinstance(x, NDArray) else jnp.asarray(x)

    def update_device(self, labels, preds):
        """Stateful device-resident update (the sync-free analog of
        ``update``): accumulation is buffered on the async engine;
        nothing crosses to the host until ``sync()``.

        The whole per-batch fold dispatches as ONE jitted program
        (cached per input shapes), not one eager op at a time — a
        per-batch metric costs a single async dispatch, the same
        discipline as the fused training step."""
        labels = [self._as_device(x) for x in labels]
        preds = [self._as_device(x) for x in preds]
        st = self._device_state if self._device_state is not None \
            else self.device_init()
        self._device_state = self._device_update_jitted()(st, labels,
                                                          preds)

    def _device_kwargs_shareable(self):
        """True when every hyperparameter kwarg is primitive — i.e. the
        signature fully determines the traced math and a compiled fold
        may be shared across instances."""
        return all(isinstance(v, (int, float, str, bool, type(None)))
                   for v in self._kwargs.values())

    def _device_update_jitted(self, dict_form=False):
        """Jitted device_update shared ACROSS instances with the same
        _device_sig (every fit()/score() creates fresh metrics — a
        per-instance jit would retrace the fold per call site; the
        signature key makes Accuracy compile once per shape, globally).
        Metrics with non-primitive hyperparameters keep their jit on
        the INSTANCE instead: the global cache stays bounded by the set
        of distinct primitive configs, never growing per instance.
        ``dict_form`` jits :meth:`device_update_dict` instead (name
        selection runs at trace time) — the composite fold uses it so
        every child's selection rides the same one program."""
        def _make():
            import jax
            return jax.jit(
                lambda st, l, p, m=self, d=dict_form:
                (m.device_update_dict if d else m.device_update)(st, l, p))
        if not self._device_kwargs_shareable():
            attr = "_device_jit_dict" if dict_form else "_device_jit"
            fn = self.__dict__.get(attr)
            if fn is None:
                fn = _make()
                setattr(self, attr, fn)
            return fn
        key = (self._device_sig(), dict_form)
        fn = _DEVICE_JIT_CACHE.get(key)
        if fn is None:
            # closing over THIS instance is safe: an equal signature
            # means equal hyperparameters, hence identical traced math
            fn = _DEVICE_JIT_CACHE[key] = _make()
        return fn

    def device_enabled(self):
        """THE enablement rule for device-resident accumulation —
        the single predicate shared by accumulate/accumulate_dict and
        the fused drivers (Module.run_steps, Trainer.step_k), so the
        ``MXNET_DEVICE_METRICS`` kill-switch contract can never diverge
        between the eager loops and the scanned ones."""
        return self.device_capable and env("MXNET_DEVICE_METRICS", True)

    def accumulate(self, labels, preds):
        """``update``, minus the per-batch host sync: routes to the
        device form when available (and ``MXNET_DEVICE_METRICS`` isn't
        0), else falls back to the classic host update with a one-time
        warning.  The framework training/eval loops accumulate through
        this (and :meth:`accumulate_dict`)."""
        if self.device_enabled():
            self.update_device(labels, preds)
            return
        self._warn_host_fallback()
        self.update(labels, preds)

    def accumulate_dict(self, label, pred):
        """``update_dict`` without the per-batch host sync (see
        :meth:`accumulate`)."""
        if self.device_enabled():
            label, pred = self._select_dict(label, pred)
            self.update_device(label, pred)
            return
        self._warn_host_fallback()
        self.update_dict(label, pred)

    def _warn_host_fallback(self):
        if not env("MXNET_DEVICE_METRICS", True):
            return   # explicitly disabled: per-batch syncs are intentional
        if getattr(self, "_host_sync_warned", False):
            return
        self._host_sync_warned = True
        logging.warning(
            "metric %r has no device form: accumulating on host costs one "
            "device->host sync per batch (implement device_update()/"
            "device_init() to keep the training loop sync-free)", self.name)

    def sync(self, state=None):
        """Fold device-resident accumulation into the classic host
        counters with ONE device->host readback (counted by
        profiler.record_host_sync).  Without ``state`` this drains the
        pending internal state from update_device; with ``state`` it
        folds an external functional state (a scan carry).  get()/
        get_name_value() call this, so callbacks that observe the metric
        (Speedometer, LogValidationMetricsCallback) are the loop's only
        sync points."""
        if state is None:
            state, self._device_state = self._device_state, None
            if state is None:
                return self
        import jax
        from . import profiler as _prof
        with _prof.span("mx.sync.metric.sync", "sync"):
            host = jax.device_get(state)
        _prof.record_host_sync("metric.sync")
        self._fold_synced(host)
        return self

    def _fold_synced(self, host_state):
        """Fold one already-read-back state into the host counters —
        bit-compatible with what get()/get_name_value() report."""
        s, n = host_state
        # the device accumulator is (f32, i32) — without jax x64 there
        # is no wider dtype to carry.  The f32 sum keeps integer counts
        # exact only to 2^24 and the i32 count wraps (negative) at
        # 2^31: a log interval that long has already lost precision
        # relative to the host counters, so say so instead of silently
        # diverging (sync more often — any callback reading the metric
        # does — or MXNET_DEVICE_METRICS=0).  A large count alone is
        # fine: i32 is exact all the way to the wrap.
        # analysis: allow(host-sync): s/n are host scalars — sync() already read them back (recorded as metric.sync) before folding here
        if (abs(float(s)) >= 2 ** 24 or int(n) < 0) \
                and not getattr(self, "_range_warned", False):
            self._range_warned = True
            logging.warning(
                "metric %r: device-resident accumulation exceeded the "
                "exact range of its (float32 sum, int32 count) state "
                "(sum=%s, count=%s); values may have lost precision vs "
                "the host path — sync at shorter intervals (any callback "
                "reading the metric) or set MXNET_DEVICE_METRICS=0",
                self.name, s, n)
        # analysis: allow(host-sync): same already-synced host scalars as above
        self.sum_metric += float(s)
        self.num_inst += int(n)

    def _device_state_or_init(self):
        """Pending device state if any, else a fresh zero state — the
        initial value a scan carry starts from, so K-step accumulation
        continues (not restarts) an in-progress interval."""
        return self._device_state if self._device_state is not None \
            else self.device_init()

    def _take_device_state(self):
        """:meth:`_device_state_or_init` with OWNERSHIP TRANSFER: the
        pending state is detached from the metric before it is handed
        to a donating scan dispatch (run_steps/step_k donate the carry
        — its buffers are deleted by XLA).  If the dispatch then fails
        at execution time, the metric holds None instead of pointing
        at donated-and-deleted buffers, so a later sync() degrades to
        a lost interval rather than a jax 'Array has been deleted'
        crash; on success _absorb_device_state installs the new
        carry."""
        state = self._device_state_or_init()
        self._device_state = None
        return state

    def _absorb_device_state(self, state):
        """Adopt a functional state (a finished scan carry) as this
        metric's pending accumulation.  The carry was seeded by
        _device_state_or_init, so it supersedes the old pending state."""
        self._device_state = state

    def _device_sig(self):
        """Hashable identity of the traced device-update math — joins
        jit/scan cache keys so two differently-configured metrics can
        never share a compiled program.

        Non-primitive hyperparameters (lists, arrays, callables) key by
        OBJECT IDENTITY: the signature cannot prove two of them equal,
        so such metrics simply never share a cache entry.  This is safe
        against id() reuse because every cache holding a _device_sig key
        (the global fold cache below, Module._run_steps_cache,
        Trainer._step_k_cache) stores a closure over the metric, pinning
        it — and through ``self._kwargs`` the keyed object — alive for
        the cache entry's lifetime."""
        kw = []
        for k, v in sorted(self._kwargs.items()):
            if isinstance(v, (int, float, str, bool, type(None))):
                kw.append((k, v))
            else:
                kw.append((k, f"id:{id(v)}"))
        cls = type(self)
        return (f"{cls.__module__}.{cls.__qualname__}",
                tuple(self.output_names or ()),
                tuple(self.label_names or ()), tuple(kw))

    def reset(self):
        self.num_inst = 0
        self.sum_metric = 0.0
        self._device_state = None

    def get(self):
        self.sync()
        if self.num_inst == 0:
            return (self.name, float('nan'))
        return (self.name, self.sum_metric / self.num_inst)

    def get_name_value(self):
        name, value = self.get()
        if not isinstance(name, list):
            name = [name]
        if not isinstance(value, list):
            value = [value]
        return list(zip(name, value))


register = _METRIC_REGISTRY.register


def create(metric, *args, **kwargs):
    """reference: metric.py create."""
    if callable(metric):
        return CustomMetric(metric, *args, **kwargs)
    if isinstance(metric, EvalMetric):
        return metric
    if isinstance(metric, list):
        composite_metric = CompositeEvalMetric()
        for child_metric in metric:
            composite_metric.add(create(child_metric, *args, **kwargs))
        return composite_metric
    return _METRIC_REGISTRY.get(metric)(*args, **kwargs)


@register
class CompositeEvalMetric(EvalMetric):
    """reference: metric.py CompositeEvalMetric."""

    def __init__(self, metrics=None, name='composite',
                 output_names=None, label_names=None):
        super().__init__(name, output_names=output_names,
                         label_names=label_names)
        if metrics is None:
            metrics = []
        self.metrics = [create(i) for i in metrics]

    def add(self, metric):
        self.metrics.append(create(metric))

    def get_metric(self, index):
        try:
            return self.metrics[index]
        except IndexError:
            return ValueError(f"Metric index {index} is out of range 0 and "
                              f"{len(self.metrics)}")

    def update_dict(self, labels, preds):
        for metric in self.metrics:
            metric.update_dict(labels, preds)

    def update(self, labels, preds):
        for metric in self.metrics:
            metric.update(labels, preds)

    # -- device path: capable iff EVERY child is (a scan carry must hold
    # the whole composite); state = tuple of child states -----------------
    @property
    def device_capable(self):
        return bool(self.metrics) and \
            all(m.device_capable for m in self.metrics)

    def device_init(self):
        return tuple(m.device_init() for m in self.metrics)

    def device_update(self, state, labels, preds):
        return tuple(m.device_update(st, labels, preds)
                     for m, st in zip(self.metrics, state))

    def device_update_dict(self, state, label, pred):
        return tuple(m.device_update_dict(st, label, pred)
                     for m, st in zip(self.metrics, state))

    def update_device(self, labels, preds):
        """ONE jitted fold per batch for the WHOLE composite — k child
        metrics never mean k dispatches on the training hot path (the
        same dispatch discipline as a plain metric's fused fold).
        Pending state still lives on the CHILDREN (sync gathers it from
        there in one device_get) — never on the composite itself."""
        labels = [self._as_device(x) for x in labels]
        preds = [self._as_device(x) for x in preds]
        state = self._device_state_or_init()
        self._absorb_device_state(
            self._device_update_jitted()(state, labels, preds))

    def accumulate(self, labels, preds):
        if self.device_enabled():
            self.update_device(labels, preds)
            return
        for metric in self.metrics:
            metric.accumulate(labels, preds)

    def accumulate_dict(self, label, pred):
        if self.device_enabled():
            # dict form: every child's output_names/label_names
            # selection happens at trace time inside the ONE program
            label = {k: self._as_device(v) for k, v in label.items()}
            pred = {k: self._as_device(v) for k, v in pred.items()}
            state = self._device_state_or_init()
            self._absorb_device_state(
                self._device_update_jitted(dict_form=True)(
                    state, label, pred))
            return
        for metric in self.metrics:
            metric.accumulate_dict(label, pred)

    def _device_state_or_init(self):
        return tuple(m._device_state_or_init() for m in self.metrics)

    def _take_device_state(self):
        return tuple(m._take_device_state() for m in self.metrics)

    def _absorb_device_state(self, state):
        for m, st in zip(self.metrics, state):
            m._absorb_device_state(st)

    def _device_sig(self):
        return (type(self).__name__,) + \
            tuple(m._device_sig() for m in self.metrics)

    def _device_kwargs_shareable(self):
        # the composite's own _kwargs is always empty — whether its
        # fused fold may live in the unbounded global cache is decided
        # by the CHILDREN: an id-keyed child signature must pin the jit
        # on the instance, or per-epoch composites would grow the
        # global cache (and pin themselves alive) without limit
        return all(m._device_kwargs_shareable() for m in self.metrics)

    def sync(self, state=None):
        """ONE readback for the whole composite: every child's pending
        state travels in a single device_get instead of one per child."""
        if state is not None:
            self._absorb_device_state(state)
        pend = [m for m in self.metrics if m._device_state is not None]
        if not pend:
            return self
        import jax
        from . import profiler as _prof
        with _prof.span("mx.sync.metric.sync", "sync"):
            host = jax.device_get([m._device_state for m in pend])
        _prof.record_host_sync("metric.sync")
        for m, h in zip(pend, host):
            m._device_state = None
            m._fold_synced(h)
        return self

    def reset(self):
        try:
            for metric in self.metrics:
                metric.reset()
        except AttributeError:
            pass

    def get(self):
        self.sync()
        names = []
        values = []
        for metric in self.metrics:
            name, value = metric.get()
            if isinstance(name, str):
                name = [name]
            if isinstance(value, (float, int, numpy.generic)):
                value = [value]
            names.extend(name)
            values.extend(value)
        return (names, values)


@register
class Accuracy(EvalMetric):
    """reference: metric.py Accuracy."""

    def __init__(self, axis=1, name='accuracy',
                 output_names=None, label_names=None):
        super().__init__(name, axis=axis, output_names=output_names,
                         label_names=label_names)
        self.axis = axis

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred_label in zip(labels, preds):
            pred_label = _np(pred_label)
            label = _np(label)
            if pred_label.shape != label.shape:
                pred_label = numpy.argmax(pred_label, axis=self.axis)
            pred_label = pred_label.astype('int32').flatten()
            label = label.astype('int32').flatten()
            check_label_shapes(label, pred_label, shape=1)
            self.sum_metric += (pred_label == label).sum()
            self.num_inst += len(pred_label)

    device_capable = True

    def device_update(self, state, labels, preds):
        import jax.numpy as jnp
        check_label_shapes(labels, preds)
        s, n = state
        for label, pred_label in zip(labels, preds):
            if pred_label.shape != label.shape:
                pred_label = jnp.argmax(pred_label, axis=self.axis)
            pred_label = pred_label.astype(jnp.int32).ravel()
            label = label.astype(jnp.int32).ravel()
            check_label_shapes(label, pred_label, shape=1)
            s = s + (pred_label == label).sum().astype(jnp.float32)
            n = n + pred_label.shape[0]
        return (s, n)


@register
class TopKAccuracy(EvalMetric):
    """reference: metric.py TopKAccuracy."""

    def __init__(self, top_k=1, name='top_k_accuracy',
                 output_names=None, label_names=None):
        super().__init__(name, top_k=top_k, output_names=output_names,
                         label_names=label_names)
        self.top_k = top_k
        assert self.top_k > 1, 'Please use Accuracy if top_k is no more than 1'
        self.name += '_%d' % self.top_k

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            assert len(pred.shape) <= 2, \
                'Predictions should be no more than 2 dims'
            pred = _np(pred).astype('float32')
            label = _np(label).astype('int32').ravel()
            check_label_shapes(label, pred)
            if pred.ndim == 1:
                self.sum_metric += int((pred.astype('int32') == label)
                                       .sum())
            else:
                k = min(pred.shape[1], self.top_k)
                # top-k SET membership via stable descending sort: on
                # ties at the k-th boundary the LOWER index wins —
                # the exact tie rule jax.lax.top_k documents, so the
                # host and device paths agree bit-for-bit even on tied
                # scores (argpartition's tie choice is unspecified).
                # NaN counts as MAXIMAL (lax.top_k's total order, and
                # what argpartition's sort-NaN-last did for the "k
                # largest"); plain argsort(-pred) would instead sort
                # NaN last and silently EXCLUDE it from the top k.
                # One documented gap: a row holding BOTH NaN and +inf
                # ties them here (NaN maps onto inf, lower index wins)
                # while lax.top_k ranks NaN strictly above +inf — the
                # two paths can pick different members of such a row
                key = numpy.where(numpy.isnan(pred), numpy.inf, pred)
                top = numpy.argsort(-key, axis=1, kind='stable')[:, :k]
                self.sum_metric += int(
                    (top == label[:, None]).any(axis=1).sum())
            self.num_inst += pred.shape[0]

    device_capable = True

    def device_update(self, state, labels, preds):
        import jax
        import jax.numpy as jnp
        check_label_shapes(labels, preds)
        s, n = state
        for label, pred in zip(labels, preds):
            assert len(pred.shape) <= 2, \
                'Predictions should be no more than 2 dims'
            label = label.astype(jnp.int32).ravel()
            if pred.ndim == 1:
                s = s + (pred.astype(jnp.int32) == label).sum() \
                    .astype(jnp.float32)
            else:
                k = min(pred.shape[1], self.top_k)
                # lax.top_k breaks ties in favor of the lower index —
                # the same rule the host path's stable descending sort
                # applies, so both paths pick the SAME member set even
                # on tied scores (bit-identical counts)
                _, top = jax.lax.top_k(pred.astype(jnp.float32), k)
                s = s + (top == label[:, None]).any(axis=1).sum() \
                    .astype(jnp.float32)
            n = n + pred.shape[0]
        return (s, n)


class _DeferredBadLabels:
    """Mixin for device paths whose label validation cannot run
    mid-trace: the state grows a third slot counting out-of-range
    labels — ``(sum_metric f32, num_inst i32, bad i32)`` — and the
    error the host path raises per batch surfaces at the interval's
    sync point instead (get/callback), STICKY until reset() so a
    caught first error can't turn into silently-clean later reads.
    Subclass ``device_update`` must exclude a bad batch's score/count
    contributions entirely (the host path raises BEFORE accumulating
    the batch, so counters match it up to and including the bad
    batch).  Known asymmetry of deferral: good batches folded AFTER a
    bad one still count here, while the host loop died at the bad
    batch and never saw them — a caller that catches the error and
    keeps reading counters can observe the difference."""

    _bad_exc = ValueError
    _bad_msg = "out-of-range labels in device-accumulated metric"

    def device_init(self):
        import jax.numpy as jnp
        return (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32),
                jnp.zeros((), jnp.int32))

    def _fold_synced(self, host_state):
        # fold the good batches FIRST (the host path keeps previously
        # accumulated batches when a bad one raises), then flag — the
        # raise itself happens in sync() below
        s, n, bad = host_state
        if int(bad):
            self._bad_label_seen = True
        super()._fold_synced((s, n))

    def sync(self, state=None):
        out = super().sync(state)
        if getattr(self, "_bad_label_seen", False):
            raise self._bad_exc(self._bad_msg)
        return out

    def reset(self):
        super().reset()
        self._bad_label_seen = False


@register
class F1(_DeferredBadLabels, EvalMetric):
    """Binary-classification F1 (reference: metric.py F1)."""

    _bad_msg = "F1 currently only supports binary classification."

    def __init__(self, name='f1', output_names=None, label_names=None):
        super().__init__(name, output_names=output_names,
                         label_names=label_names)

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            pred = _np(pred)
            # ravel BEFORE the vectorized compares: an (n,1) label would
            # broadcast against the (n,) argmax into an (n,n) matrix
            label = _np(label).astype('int32').ravel()
            pred_label = numpy.argmax(pred, axis=1)
            check_label_shapes(label, pred)
            if label.size and (label.min() < 0 or label.max() > 1):
                raise ValueError(
                    "F1 currently only supports binary classification.")
            # ONE pass over the confusion cells: 2*pred+label indexes
            # them (3=tp, 2=fp, 1=fn, 0=tn) — a single bincount replaces
            # three separate masked-sum reductions.  2*tp/(2*tp+fp+fn) is
            # the precision/recall harmonic mean, 0/0 -> 0 convention.
            c = numpy.bincount(pred_label * 2 + label, minlength=4)
            tp, fp, fn = float(c[3]), float(c[2]), float(c[1])
            denom = 2 * tp + fp + fn
            self.sum_metric += (2 * tp / denom) if denom > 0 else 0.
            self.num_inst += 1

    device_capable = True

    def device_update(self, state, labels, preds):
        import jax.numpy as jnp
        check_label_shapes(labels, preds)
        s, n, bad = state
        for label, pred in zip(labels, preds):
            label = label.astype(jnp.int32).ravel()
            nbad = ((label < 0) | (label > 1)).sum().astype(jnp.int32)
            bad = bad + nbad
            # a batch with ANY out-of-range label contributes NOTHING —
            # the host path raises before accumulating it, so excluding
            # it keeps sum_metric/num_inst identical after the deferred
            # error fires at sync (labels are clipped only so the
            # bincount below stays well-defined for the excluded batch)
            ok = (nbad == 0).astype(jnp.float32)
            pred_label = jnp.argmax(pred, axis=1).astype(jnp.int32)
            # same one-pass confusion bincount as the host path, as one
            # fused reduction in the jit
            c = jnp.bincount(pred_label * 2 + jnp.clip(label, 0, 1),
                             length=4)
            tp = c[3].astype(jnp.float32)
            fp = c[2].astype(jnp.float32)
            fn = c[1].astype(jnp.float32)
            denom = 2 * tp + fp + fn
            s = s + ok * jnp.where(denom > 0,
                                   2 * tp / jnp.maximum(denom, 1.0), 0.0)
            n = n + ok.astype(jnp.int32)
        return (s, n, bad)


@register
class Perplexity(_DeferredBadLabels, EvalMetric):
    """reference: metric.py Perplexity."""

    def __init__(self, ignore_label, axis=-1, name='perplexity',
                 output_names=None, label_names=None):
        super().__init__(name, ignore_label=ignore_label, axis=axis,
                         output_names=output_names, label_names=label_names)
        self.ignore_label = ignore_label
        self.axis = axis

    def update(self, labels, preds):
        assert len(labels) == len(preds)
        loss = 0.
        num = 0
        for label, pred in zip(labels, preds):
            label = _np(label)
            pred = _np(pred)
            assert label.size == pred.size / pred.shape[-1], \
                f"shape mismatch: {label.shape} vs. {pred.shape}"
            label = label.reshape((label.size,)).astype('int32')
            probs = numpy.take_along_axis(
                pred.reshape(-1, pred.shape[-1]), label[:, None],
                axis=-1).squeeze(-1)
            if self.ignore_label is not None:
                ignore = (label == self.ignore_label).astype(probs.dtype)
                num -= int(ignore.sum())
                probs = probs * (1 - ignore) + ignore
            loss -= numpy.sum(numpy.log(numpy.maximum(1e-10, probs)))
            num += probs.size
        # accumulate total loss/count; get() exponentiates the GLOBAL mean
        # (reference: metric.py Perplexity.get)
        self.sum_metric += loss
        self.num_inst += num

    device_capable = True
    _bad_msg = ("label index out of range for the class axis "
                "(detected at metric sync; the host path raises "
                "IndexError per batch)")
    _bad_exc = IndexError

    def device_update(self, state, labels, preds):
        import jax.numpy as jnp
        assert len(labels) == len(preds)
        s, n, bad = state
        for label, pred in zip(labels, preds):
            label = label.reshape((-1,)).astype(jnp.int32)
            nclass = pred.shape[-1]
            # same deferred range check as CrossEntropy: numpy's
            # take_along_axis raises outside [-nclass, nclass) and
            # wraps in-range negatives; bad batches contribute nothing
            nbad = ((label < -nclass) | (label >= nclass)).sum() \
                .astype(jnp.int32)
            bad = bad + nbad
            ok = (nbad == 0)
            oki = ok.astype(jnp.int32)
            probs = jnp.take_along_axis(
                pred.reshape(-1, nclass), (label % nclass)[:, None],
                axis=-1).squeeze(-1)
            if self.ignore_label is not None:
                ignore = (label == self.ignore_label).astype(probs.dtype)
                n = n - oki * ignore.sum().astype(jnp.int32)
                probs = probs * (1 - ignore) + ignore
            s = s - ok.astype(jnp.float32) * \
                jnp.sum(jnp.log(jnp.maximum(1e-10, probs))) \
                .astype(jnp.float32)
            n = n + oki * probs.shape[0]
        return (s, n, bad)

    def get(self):
        self.sync()
        if self.num_inst == 0:
            return (self.name, float('nan'))
        return (self.name, float(numpy.exp(self.sum_metric / self.num_inst)))


class _RegressionMetric(EvalMetric):
    """Shared per-batch regression scoring: subclasses define the batch
    score over the residual; the mean-of-batch-scores accumulation (one
    num_inst per batch) is the reference contract for all three."""

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label = _np(label)
            pred = _np(pred)
            if label.ndim == 1:
                label = label[:, None]
            if pred.ndim == 1:
                pred = pred[:, None]
            self.sum_metric += self._score(label - pred)
            self.num_inst += 1

    device_capable = True

    def device_update(self, state, labels, preds):
        import jax.numpy as jnp
        check_label_shapes(labels, preds)
        s, n = state
        for label, pred in zip(labels, preds):
            if label.ndim == 1:
                label = label[:, None]
            if pred.ndim == 1:
                pred = pred[:, None]
            s = s + self._device_score(label - pred).astype(jnp.float32)
            n = n + 1
        return (s, n)


@register
class MAE(_RegressionMetric):
    """reference: metric.py MAE."""

    def __init__(self, name='mae', output_names=None, label_names=None):
        super().__init__(name, output_names=output_names,
                         label_names=label_names)

    @staticmethod
    def _score(err):
        return numpy.abs(err).mean()

    @staticmethod
    def _device_score(err):
        import jax.numpy as jnp
        return jnp.abs(err).mean()


@register
class MSE(_RegressionMetric):
    """reference: metric.py MSE."""

    def __init__(self, name='mse', output_names=None, label_names=None):
        super().__init__(name, output_names=output_names,
                         label_names=label_names)

    @staticmethod
    def _score(err):
        return (err ** 2.0).mean()

    @staticmethod
    def _device_score(err):
        return (err ** 2.0).mean()


@register
class RMSE(_RegressionMetric):
    """reference: metric.py RMSE."""

    def __init__(self, name='rmse', output_names=None, label_names=None):
        super().__init__(name, output_names=output_names,
                         label_names=label_names)

    @staticmethod
    def _score(err):
        return numpy.sqrt((err ** 2.0).mean())

    @staticmethod
    def _device_score(err):
        import jax.numpy as jnp
        return jnp.sqrt((err ** 2.0).mean())


@register
class CrossEntropy(_DeferredBadLabels, EvalMetric):
    """reference: metric.py CrossEntropy."""

    def __init__(self, eps=1e-12, name='cross-entropy',
                 output_names=None, label_names=None):
        super().__init__(name, eps=eps, output_names=output_names,
                         label_names=label_names)
        self.eps = eps

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label = _np(label)
            pred = _np(pred)
            label = label.ravel()
            assert label.shape[0] == pred.shape[0]
            prob = pred[numpy.arange(label.shape[0]), numpy.int64(label)]
            self.sum_metric += (-numpy.log(prob + self.eps)).sum()
            self.num_inst += label.shape[0]

    device_capable = True
    _bad_msg = ("label index out of range for the class axis "
                "(detected at metric sync; the host path raises "
                "IndexError per batch)")
    _bad_exc = IndexError

    def device_update(self, state, labels, preds):
        import jax.numpy as jnp
        check_label_shapes(labels, preds)
        s, n, bad = state
        for label, pred in zip(labels, preds):
            label = label.ravel().astype(jnp.int32)
            assert label.shape[0] == pred.shape[0]
            nclass = pred.shape[-1]
            # host-path parity on malformed labels: numpy's gather
            # raises on indices outside [-nclass, nclass) and WRAPS
            # in-range negatives; jax would silently clamp, so count
            # the out-of-range ones (deferred raise at sync, batch
            # excluded) and gather modulo nclass (= numpy's wrap)
            nbad = ((label < -nclass) | (label >= nclass)).sum() \
                .astype(jnp.int32)
            bad = bad + nbad
            ok = (nbad == 0)
            prob = pred[jnp.arange(label.shape[0]), label % nclass]
            s = s + ok.astype(jnp.float32) * \
                (-jnp.log(prob + self.eps)).sum().astype(jnp.float32)
            n = n + jnp.where(ok, label.shape[0], 0).astype(jnp.int32)
        return (s, n, bad)


@register
class NegativeLogLikelihood(CrossEntropy):
    """reference: metric.py NegativeLogLikelihood — same per-example
    -log p[label] accumulation as CrossEntropy, under its NLL name."""

    def __init__(self, eps=1e-12, name='nll-loss',
                 output_names=None, label_names=None):
        super().__init__(eps=eps, name=name, output_names=output_names,
                         label_names=label_names)


@register
class PearsonCorrelation(EvalMetric):
    """reference: metric.py PearsonCorrelation."""

    def __init__(self, name='pearsonr', output_names=None, label_names=None):
        super().__init__(name, output_names=output_names,
                         label_names=label_names)

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            check_label_shapes(label, pred, 1)
            label = _np(label).ravel()
            pred = _np(pred).ravel()
            self.sum_metric += numpy.corrcoef(pred, label)[0, 1]
            self.num_inst += 1


@register
class Loss(EvalMetric):
    """Mean of a loss-valued output (reference: metric.py Loss)."""

    def __init__(self, name='loss', output_names=None, label_names=None):
        super().__init__(name, output_names=output_names,
                         label_names=label_names)

    def update(self, _, preds):
        for pred in preds:
            self.sum_metric += float(_np(pred).sum())
            self.num_inst += _np(pred).size if not numpy.isscalar(pred) else 1

    device_capable = True

    def device_update(self, state, _, preds):
        import jax.numpy as jnp
        s, n = state
        for pred in preds:
            s = s + pred.sum().astype(jnp.float32)
            n = n + pred.size
        return (s, n)


@register
class Torch(Loss):
    """reference: metric.py Torch (alias of Loss with torch name)."""

    def __init__(self, name='torch', output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)


@register
class Caffe(Loss):
    def __init__(self, name='caffe', output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)


@register
class CustomMetric(EvalMetric):
    """Wrap feval(label, pred) (reference: metric.py CustomMetric)."""

    def __init__(self, feval, name=None, allow_extra_outputs=False,
                 output_names=None, label_names=None):
        if name is None:
            name = feval.__name__
            if name.find('<') != -1:
                name = 'custom(%s)' % name
        super().__init__(name, feval=feval,
                         allow_extra_outputs=allow_extra_outputs,
                         output_names=output_names, label_names=label_names)
        self._feval = feval
        self._allow_extra_outputs = allow_extra_outputs

    def update(self, labels, preds):
        if not self._allow_extra_outputs:
            check_label_shapes(labels, preds)
        for pred, label in zip(preds, labels):
            label = _np(label)
            pred = _np(pred)
            reval = self._feval(label, pred)
            if isinstance(reval, tuple):
                (sum_metric, num_inst) = reval
                self.sum_metric += sum_metric
                self.num_inst += num_inst
            else:
                self.sum_metric += reval
                self.num_inst += 1

    def get_config(self):
        raise NotImplementedError("CustomMetric cannot be serialized")


_METRIC_REGISTRY.alias('acc', 'accuracy')
_METRIC_REGISTRY.alias('top_k_acc', 'topkaccuracy')
_METRIC_REGISTRY.alias('top_k_accuracy', 'topkaccuracy')
_METRIC_REGISTRY.alias('ce', 'crossentropy')
_METRIC_REGISTRY.alias('cross-entropy', 'crossentropy')
_METRIC_REGISTRY.alias('nll_loss', 'negativeloglikelihood')
_METRIC_REGISTRY.alias('pearsonr', 'pearsoncorrelation')


def np(numpy_feval, name=None, allow_extra_outputs=False):
    """reference: metric.py np — wrap a numpy feval as a metric factory."""
    def feval(label, pred):
        return numpy_feval(label, pred)
    feval.__name__ = numpy_feval.__name__
    return CustomMetric(feval, name, allow_extra_outputs)
