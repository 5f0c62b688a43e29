"""Sync-free training loop: device-resident metrics, one host readback
per log interval (ci/run_ci.sh runs this file as its own gate).

The contract under test: every
device->host readback is counted by profiler.record_host_sync, metric
accumulation in fit/score/run_steps stays on the async engine, and the
ONLY sync points in a training loop are the callbacks that read the
metric (EvalMetric.sync via get_name_value).  A CPU fit() epoch over N
batches with Speedometer(frequent=F) must record <= N/F + 2 syncs —
and the legacy host-metric path is pinned at >= 1 per batch so the
budget stays meaningful.

The heavier variants (legacy-path pin, batch-granular callback proof,
FeedForward replay) are slow-marked: the default tier-1 gate runs the
core budget asserts, and ci/run_ci.sh's dedicated invocation (-m "")
runs everything here.
"""
import logging

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models
from mxnet_tpu import profiler as prof


N_BATCHES = 32
BATCH = 16
FREQ = 8
DIM = 8
NCLASS = 4


def _blob_iter(seed=0, n_batches=N_BATCHES, batch=BATCH):
    rs = np.random.RandomState(seed)
    n = n_batches * batch
    centers = rs.randn(NCLASS, DIM) * 3.0
    y = rs.randint(0, NCLASS, (n,)).astype('float32')
    x = (centers[y.astype(int)] +
         rs.randn(n, DIM)).astype('float32')
    return mx.io.NDArrayIter(x, y, batch)


def _make_module(it):
    mod = mx.mod.Module(models.mlp(num_classes=NCLASS, num_hidden=(16,)),
                        context=mx.cpu(0))
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer='sgd',
                       optimizer_params={'learning_rate': 0.05})
    return mod


def _fit(mod, it, callbacks=None, metric='acc'):
    prof.reset_host_syncs()
    mod.fit(it, num_epoch=1, optimizer='sgd',
            optimizer_params={'learning_rate': 0.05},
            initializer=mx.initializer.Xavier(),
            eval_metric=metric, batch_end_callback=callbacks)
    return prof.host_syncs()


def test_fit_sync_budget_with_speedometer():
    """THE acceptance number: one epoch over N batches with
    Speedometer(frequent=F) records <= N/F + 2 host syncs (was >= N on
    the per-batch host-metric path)."""
    it = _blob_iter()
    mod = _make_module(it)
    syncs = _fit(mod, it,
                 callbacks=mx.callback.Speedometer(BATCH, frequent=FREQ))
    total = sum(syncs.values())
    assert total <= N_BATCHES // FREQ + 2, syncs
    # and every one of them is a deliberate metric sync, not a stray
    # asnumpy from inside the loop
    assert set(syncs) <= {"metric.sync"}, syncs


def test_fit_without_callbacks_syncs_once_per_epoch():
    """No metric-reading callback -> the epoch-end train-metric log is
    the loop's single sync."""
    it = _blob_iter()
    mod = _make_module(it)
    syncs = _fit(mod, it, callbacks=None)
    assert syncs == {"metric.sync": 1}, syncs


@pytest.mark.slow
def test_callbacks_are_the_only_sync_points():
    """Batch-granular proof of the callback.py sync contract: the host
    sync counter only moves on batches where Speedometer reads the
    metric (count % frequent == 0, after its init batch)."""
    it = _blob_iter()
    mod = _make_module(it)
    seen = []

    def spy(param):     # runs AFTER Speedometer (list order)
        seen.append((param.nbatch, prof.host_sync_total()))

    _fit(mod, it, callbacks=[mx.callback.Speedometer(BATCH, frequent=FREQ),
                             spy])
    prev = 0
    for nbatch, total in seen:
        if nbatch % FREQ == 0 and nbatch > 0:
            assert total == prev + 1, (nbatch, seen)
        else:
            assert total == prev, (nbatch, seen)
        prev = total


@pytest.mark.slow
def test_legacy_host_path_pinned_per_batch(monkeypatch):
    """MXNET_DEVICE_METRICS=0 restores the classic per-batch host
    accumulation: >= 1 sync per batch.  This pin keeps the sync budget
    above meaningful — if counting broke, both tests would fail."""
    monkeypatch.setenv("MXNET_DEVICE_METRICS", "0")
    it = _blob_iter()
    mod = _make_module(it)
    syncs = _fit(mod, it,
                 callbacks=mx.callback.Speedometer(BATCH, frequent=FREQ))
    assert sum(syncs.values()) >= N_BATCHES, syncs


def test_score_syncs_once():
    """A whole evaluation pass accumulates on device; the final
    get_name_value is its one readback."""
    it = _blob_iter()
    mod = _make_module(it)
    it.reset()
    prof.reset_host_syncs()
    mod.score(it, 'acc')
    assert prof.host_syncs() == {"metric.sync": 1}, prof.host_syncs()


@pytest.mark.slow
def test_score_composite_still_one_sync():
    """CompositeEvalMetric gathers every child's state in ONE
    device_get — k metrics never mean k readbacks."""
    it = _blob_iter()
    mod = _make_module(it)
    it.reset()
    prof.reset_host_syncs()
    mod.score(it, mx.metric.create(['acc', 'mse']))
    assert prof.host_syncs() == {"metric.sync": 1}, prof.host_syncs()


def test_predict_single_stacked_readback():
    """BaseModule.predict: pad slicing happens on device and ALL batches
    come back in one stacked readback, not one copy per batch."""
    it = _blob_iter(n_batches=6)
    mod = _make_module(it)
    it.reset()
    prof.reset_host_syncs()
    out = mod.predict(it)
    assert prof.host_syncs() == {"predict.readback": 1}, prof.host_syncs()
    assert out.shape == (6 * BATCH, NCLASS)


@pytest.mark.slow
def test_feedforward_predict_return_data_single_readback():
    """FeedForward.predict(return_data=True): the data/label replay loop
    slices padding on device and reads back once (was one asnumpy per
    batch per array)."""
    import warnings
    rs = np.random.RandomState(2)
    x = rs.randn(180, DIM).astype('float32')   # 180 % 32 != 0: pad path
    y = rs.randint(0, NCLASS, (180,)).astype('float32')
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        ff = mx.model.FeedForward(
            models.mlp(num_classes=NCLASS, num_hidden=(16,)),
            num_epoch=1, numpy_batch_size=32, learning_rate=0.05)
        ff.fit(x, y)
    prof.reset_host_syncs()
    preds, data, labels = ff.predict(x, return_data=True)
    syncs = prof.host_syncs()
    assert syncs.get("feedforward.predict.readback") == 1, syncs
    assert syncs.get("predict.readback") == 1, syncs
    # O(1) w.r.t. batch count: the only asnumpy calls are the iterator
    # construction wrap (data+label) and the final merged result — 3
    # total for 6 batches (the old path did 2 PER batch here)
    assert syncs.get("ndarray.asnumpy", 0) <= 3, syncs
    assert preds.shape[0] == data.shape[0] == labels.shape[0] == 180
    np.testing.assert_array_equal(data, x)
    # label-less numpy predict flows zero dummy labels (_init_iter)
    np.testing.assert_array_equal(labels, np.zeros(180, 'float32'))


@pytest.mark.slow
def test_run_steps_metric_matches_k_eager_host_updates():
    """K-step metric accumulation through the scan carry matches K
    eager host-path update() calls bit-for-bit (Accuracy: integer
    counts, exact in both paths)."""
    k, batch = 4, 8
    rs = np.random.RandomState(9)
    data = rs.uniform(-1, 1, (k, batch, DIM)).astype(np.float32)
    label = rs.randint(0, NCLASS, (k, batch)).astype(np.float32)
    it = mx.io.NDArrayIter(data.reshape(-1, DIM), label.reshape(-1), batch)
    mx.random.seed(0)
    m1 = _make_module(it)
    mx.random.seed(0)
    m2 = _make_module(it)
    arg, aux = m1.get_params()
    m2.init_params(
        arg_params={n: mx.nd.array(v.asnumpy().copy())
                    for n, v in arg.items()},
        aux_params={n: mx.nd.array(v.asnumpy().copy())
                    for n, v in aux.items()},
        force_init=True, allow_missing=True)

    host_metric = mx.metric.Accuracy()
    for j in range(k):
        b = mx.io.DataBatch(data=[mx.nd.array(data[j])],
                            label=[mx.nd.array(label[j])])
        m1.forward(b, is_train=True)
        m1.update()
        # classic HOST update — per-batch sync, the old contract
        host_metric.update([b.label[0]], [m1.get_outputs()[0]])

    dev_metric = mx.metric.Accuracy()
    m2.run_steps(data, label, k=k, eval_metric=dev_metric)
    assert host_metric.get() == dev_metric.get()


@pytest.mark.slow
def test_run_steps_metric_carry_spans_calls_and_eager_batches():
    """One log interval may mix eager batches and run_steps calls: the
    pending device state seeds the scan carry, so accumulation is
    continuous and still syncs once."""
    k, batch = 4, 8
    rs = np.random.RandomState(11)
    data = rs.uniform(-1, 1, (k, batch, DIM)).astype(np.float32)
    label = rs.randint(0, NCLASS, (k, batch)).astype(np.float32)
    it = mx.io.NDArrayIter(data.reshape(-1, DIM), label.reshape(-1), batch)
    mod = _make_module(it)
    metric = mx.metric.Accuracy()
    # one eager batch first...
    b = mx.io.DataBatch(data=[mx.nd.array(data[0])],
                        label=[mx.nd.array(label[0])])
    mod.forward(b, is_train=True)
    mod.update()
    mod.update_metric(metric, b.label)
    # ...then a scanned superbatch; then ONE sync reads 5 batches' worth
    prof.reset_host_syncs()
    mod.run_steps(data, label, k=k, eval_metric=metric)
    assert prof.host_sync_total() == 0, prof.host_syncs()
    assert metric.get()[1] is not None
    assert metric.num_inst == (k + 1) * batch
    assert prof.host_syncs() == {"metric.sync": 1}, prof.host_syncs()


def test_host_fallback_warns_once(caplog):
    """A metric without a device form falls back to the host path with
    a single warning naming the metric."""
    m = mx.metric.np(lambda l, p: float((l == p.argmax(1)).mean()),
                     name='my_custom')
    pred = mx.nd.array(np.random.rand(8, NCLASS).astype('float32'))
    label = mx.nd.array(np.zeros(8, 'float32'))
    with caplog.at_level(logging.WARNING):
        m.accumulate([label], [pred])
        m.accumulate([label], [pred])
    warned = [r for r in caplog.records if 'no device form' in r.message]
    assert len(warned) == 1 and 'my_custom' in warned[0].message
    assert m.num_inst == 2
