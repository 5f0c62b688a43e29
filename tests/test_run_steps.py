"""Multi-step on-device training driver (Module.run_steps /
Trainer.step_k): K scanned steps must equal K eager steps.

The scanned driver compiles K fused fwd+bwd+update steps into ONE XLA
program (jax.lax.scan over the SAME step body the eager fused update
traces), so on the fp32 CPU backend the K-step program must reproduce K
eager steps BIT-FOR-BIT — params, optimizer state, aux states (BatchNorm
moving stats), outputs and metrics.  The dispatch-count hook
(profiler.record_dispatch) pins the contract that one run_steps call is
exactly one host dispatch — with a device-capable metric riding the
scan carry, ZERO readbacks (metrics sync lazily at the next
get_name_value); metrics without a device form cost one stacked
readback for all K steps.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler as prof


K = 8
BATCH = 8
NIN = 10
NCLASS = 4


def _make_symbol():
    data = mx.sym.Variable('data')
    net = mx.sym.FullyConnected(data, num_hidden=16, name='fc1')
    net = mx.sym.BatchNorm(net, name='bn1')
    net = mx.sym.Activation(net, act_type='relu', name='relu1')
    net = mx.sym.FullyConnected(net, num_hidden=NCLASS, name='fc2')
    return mx.sym.SoftmaxOutput(net, name='softmax')


def _make_module(optimizer='sgd', opt_params=None, batch=BATCH):
    mod = mx.mod.Module(_make_symbol(), data_names=('data',),
                        label_names=('softmax_label',))
    mod.bind(data_shapes=[('data', (batch, NIN))],
             label_shapes=[('softmax_label', (batch,))])
    mod.init_params(mx.initializer.Xavier(rnd_type='gaussian',
                                          magnitude=2.0))
    mod.init_optimizer(
        optimizer=optimizer,
        optimizer_params=opt_params or {'learning_rate': 0.1,
                                        'momentum': 0.9, 'wd': 1e-4})
    return mod


def _clone_params(src, dst):
    """Copy src's params/aux into dst through HOST numpy (the live jax
    buffers are donated by fused steps — sharing them would alias)."""
    arg, aux = src.get_params()
    dst.init_params(
        arg_params={k: mx.nd.array(v.asnumpy().copy())
                    for k, v in arg.items()},
        aux_params={k: mx.nd.array(v.asnumpy().copy())
                    for k, v in aux.items()},
        force_init=True, allow_missing=True)


def _data(k=K, batch=BATCH, seed=0):
    rs = np.random.RandomState(seed)
    data = rs.uniform(-1, 1, (k, batch, NIN)).astype(np.float32)
    label = rs.randint(0, NCLASS, (k, batch)).astype(np.float32)
    return data, label


def _run_eager(mod, data, label, metric=None):
    for j in range(data.shape[0]):
        b = mx.io.DataBatch(data=[mx.nd.array(data[j])],
                            label=[mx.nd.array(label[j])])
        mod.forward(b, is_train=True)
        mod.update()
        if metric is not None:
            mod.update_metric(metric, b.label)


def _assert_state_equal(m1, m2, exact=True):
    a1, x1 = m1.get_params()
    a2, x2 = m2.get_params()
    for tag, src, dst in (("arg", a1, a2), ("aux", x1, x2)):
        for n in src:
            v1, v2 = src[n].asnumpy(), dst[n].asnumpy()
            if exact:
                np.testing.assert_array_equal(
                    v1, v2, err_msg=f"{tag} {n} diverged")
            else:
                np.testing.assert_allclose(
                    v1, v2, rtol=2e-6, atol=1e-6,
                    err_msg=f"{tag} {n} diverged")
    for n in m1._opt_states:
        for s1, s2 in zip(m1._opt_states[n], m2._opt_states[n]):
            if s1 is None:
                assert s2 is None
                continue
            if exact:
                np.testing.assert_array_equal(
                    s1.asnumpy(), s2.asnumpy(),
                    err_msg=f"opt state {n} diverged")
            else:
                np.testing.assert_allclose(
                    s1.asnumpy(), s2.asnumpy(), rtol=2e-6, atol=1e-6,
                    err_msg=f"opt state {n} diverged")


def test_run_steps_bit_identical_to_eager():
    """K scanned steps == K eager fused steps, bit-for-bit (fp32 CPU):
    params, momentum, BatchNorm aux writeback, outputs, metric."""
    data, label = _data()
    mx.random.seed(0)
    m1 = _make_module()
    mx.random.seed(0)
    m2 = _make_module()
    _clone_params(m1, m2)

    metric1 = mx.metric.Accuracy()
    _run_eager(m1, data, label, metric1)

    metric2 = mx.metric.Accuracy()
    outs = m2.run_steps(data, label, k=K, eval_metric=metric2)

    _assert_state_equal(m1, m2, exact=True)
    assert outs[0].shape == (K, BATCH, NCLASS)
    # last step's outputs visible through get_outputs, same as eager
    np.testing.assert_array_equal(m1.get_outputs()[0].asnumpy(),
                                  m2.get_outputs()[0].asnumpy())
    assert metric1.get() == metric2.get()


def test_run_steps_single_dispatch_and_readback():
    """The acceptance contract: run_steps(k=8) with a device-capable
    metric = exactly ONE host dispatch and ZERO readbacks — the metric
    state rides the scan carry and nothing blocks the host until a
    later sync().  No eager forward/backward/fused-step dispatches
    sneak in either."""
    data, label = _data()
    mod = _make_module()
    metric = mx.metric.Accuracy()
    prof.reset_dispatch_counts()
    prof.reset_host_syncs()
    mod.run_steps(data, label, k=K, eval_metric=metric)
    counts = prof.dispatch_counts()
    # tracing the scanned step runs the BatchNorm node's training path:
    # a trace count beside the dispatches, never one per step
    assert counts.pop("batch_norm.train_vjp") >= 1, counts
    assert counts == {"run_steps.dispatch": 1}, counts
    # accumulating K steps of metrics cost zero host syncs...
    assert prof.host_sync_total() == 0, prof.host_syncs()
    # ...and reading the metric afterwards costs exactly one
    metric.get_name_value()
    assert prof.host_syncs() == {"metric.sync": 1}, prof.host_syncs()


def test_run_steps_host_metric_falls_back_to_one_readback():
    """A metric WITHOUT a device form (CustomMetric) keeps the legacy
    fold: still one scan dispatch, plus exactly ONE stacked readback
    for all K steps' outputs (never one per step)."""
    data, label = _data()
    mod = _make_module()
    metric = mx.metric.np(
        lambda l, p: float((l == p.argmax(1)).mean()))
    prof.reset_dispatch_counts()
    prof.reset_host_syncs()
    mod.run_steps(data, label, k=K, eval_metric=metric)
    counts = prof.dispatch_counts()
    assert counts.pop("batch_norm.train_vjp") >= 1, counts
    assert counts == {"run_steps.dispatch": 1,
                      "run_steps.readback": 1}, counts
    # ONE stacked device readback of the live training state; the
    # legacy NDArray-wrap contract then re-wraps the fetched values for
    # the custom metric, whose own asnumpy calls cost the legacy
    # per-value syncs (free-ish on CPU where np-backed arrays are
    # zero-copy; on a chip this fallback pays legacy prices — convert
    # the metric to device_update to escape them)
    assert prof.host_syncs().get("run_steps.metric_fold") == 1, \
        prof.host_syncs()
    assert metric.num_inst == K


def test_run_steps_jit_cache_reused():
    """Second call with same (K, shapes, param set, hyperparams) reuses
    the compiled scan (cache has exactly one entry)."""
    data, label = _data()
    mod = _make_module()
    mod.run_steps(data, label, k=K)
    assert len(mod._run_steps_cache) == 1
    prof.reset_dispatch_counts()
    mod.run_steps(data, label, k=K)
    assert len(mod._run_steps_cache) == 1
    # nothing is traced again: the BatchNorm node's count stays put
    assert prof.dispatch_counts() == {"run_steps.dispatch": 1}


def test_run_steps_k1_falls_back_to_eager():
    """K=1 runs the eager driver (no scan dispatch) and matches one
    eager step exactly."""
    data, label = _data(k=1)
    mx.random.seed(0)
    m1 = _make_module()
    mx.random.seed(0)
    m2 = _make_module()
    _clone_params(m1, m2)
    _run_eager(m1, data, label)
    prof.reset_dispatch_counts()
    m2.run_steps(data, label, k=1)
    counts = prof.dispatch_counts()
    assert "run_steps.dispatch" not in counts
    assert counts.get("fused_step.dispatch") == 1
    _assert_state_equal(m1, m2, exact=True)


def test_run_steps_shape_change_falls_back_to_eager():
    """A stacked batch whose per-step shape differs from the bound
    shapes (bucketing / variable-shape case) falls back to the eager
    driver — which reshapes per step — instead of mis-tracing."""
    data, label = _data(k=4, batch=BATCH // 2)
    mod = _make_module()   # bound at BATCH
    prof.reset_dispatch_counts()
    outs = mod.run_steps(data, label, k=4)
    counts = prof.dispatch_counts()
    assert "run_steps.dispatch" not in counts
    assert outs[0].shape == (4, BATCH // 2, NCLASS)


def test_run_steps_adam_bias_correction():
    """needs_t optimizers: per-step update counts travel through the
    scan — Adam's bias correction at steps t..t+K matches eager."""
    data, label = _data()
    opt_params = {'learning_rate': 1e-3}
    mx.random.seed(0)
    m1 = _make_module('adam', opt_params)
    mx.random.seed(0)
    m2 = _make_module('adam', opt_params)
    _clone_params(m1, m2)
    _run_eager(m1, data, label)
    m2.run_steps(data, label, k=K)
    _assert_state_equal(m1, m2, exact=True)


@pytest.mark.slow
def test_run_steps_lr_schedule_advances_like_eager():
    """lr schedules are host maths precomputed per step: a schedule that
    decays INSIDE the K-step window produces the same params as eager."""
    data, label = _data()
    sched = mx.lr_scheduler.FactorScheduler(step=3, factor=0.5)
    mx.random.seed(0)
    m1 = _make_module('sgd', {'learning_rate': 0.1, 'momentum': 0.9,
                              'wd': 0.0, 'lr_scheduler': sched})
    sched2 = mx.lr_scheduler.FactorScheduler(step=3, factor=0.5)
    mx.random.seed(0)
    m2 = _make_module('sgd', {'learning_rate': 0.1, 'momentum': 0.9,
                              'wd': 0.0, 'lr_scheduler': sched2})
    _clone_params(m1, m2)
    _run_eager(m1, data, label)
    m2.run_steps(data, label, k=K)
    _assert_state_equal(m1, m2, exact=True)


@pytest.mark.slow
def test_run_steps_chained_calls_continue_training():
    """Two consecutive run_steps calls == 2K eager steps (state threads
    through host writeback between scans)."""
    data, label = _data(k=2 * K)
    mx.random.seed(0)
    m1 = _make_module()
    mx.random.seed(0)
    m2 = _make_module()
    _clone_params(m1, m2)
    _run_eager(m1, data, label)
    m2.run_steps(data[:K], label[:K], k=K)
    m2.run_steps(data[K:], label[K:], k=K)
    _assert_state_equal(m1, m2, exact=True)


@pytest.mark.slow
def test_run_steps_respects_bulk_exec_env(monkeypatch):
    """MXNET_EXEC_BULK_EXEC_TRAIN=0 forces the eager driver."""
    monkeypatch.setenv("MXNET_EXEC_BULK_EXEC_TRAIN", "0")
    data, label = _data(k=2)
    mod = _make_module()
    prof.reset_dispatch_counts()
    mod.run_steps(data, label, k=2)
    assert "run_steps.dispatch" not in prof.dispatch_counts()


# -- gluon Trainer.step_k ---------------------------------------------------

def _make_gluon(seed=0):
    from mxnet_tpu.gluon import nn
    mx.random.seed(seed)
    net = nn.HybridSequential(prefix='net_')
    with net.name_scope():
        net.add(nn.Dense(16), nn.BatchNorm(), nn.Activation('relu'),
                nn.Dense(NCLASS))
    net.initialize(mx.initializer.Xavier(rnd_type='gaussian',
                                         magnitude=2.0))
    return net


def _clone_gluon(src, dst, probe):
    src(probe)
    dst(probe)   # force deferred init on both
    vals = {k: v.data().asnumpy().copy()
            for k, v in src.collect_params().items()}
    for k, v in dst.collect_params().items():
        v.set_data(mx.nd.array(vals[k]))


def test_trainer_step_k_matches_eager():
    """K scanned gluon steps match K eager record/backward/step loops —
    trainable params, momentum AND BatchNorm running stats carried
    through the scan.  (allclose, not bitwise: the eager path dispatches
    per-op while the scan traces one fused program, so XLA may
    reassociate float math.)"""
    from mxnet_tpu import gluon, autograd
    data, label = _data()
    loss_obj = gluon.loss.SoftmaxCrossEntropyLoss()
    net1 = _make_gluon()
    net2 = _make_gluon()
    _clone_gluon(net1, net2, mx.nd.array(data[0]))
    t1 = gluon.Trainer(net1.collect_params(), 'sgd',
                       {'learning_rate': 0.1, 'momentum': 0.9,
                        'wd': 1e-4}, kvstore=None)
    t2 = gluon.Trainer(net2.collect_params(), 'sgd',
                       {'learning_rate': 0.1, 'momentum': 0.9,
                        'wd': 1e-4}, kvstore=None)

    losses1 = []
    for j in range(K):
        x, y = mx.nd.array(data[j]), mx.nd.array(label[j])
        with autograd.record():
            loss = loss_obj(net1(x), y)
        loss.backward()
        t1.step(BATCH)
        losses1.append(loss.asnumpy())

    prof.reset_dispatch_counts()
    losses2 = t2.step_k(lambda x, y: loss_obj(net2(x), y), data, label,
                        k=K, batch_size=BATCH)
    counts = prof.dispatch_counts()
    assert counts.pop("batch_norm.train_vjp") >= 1, counts
    assert counts == {"step_k.dispatch": 1}, counts

    np.testing.assert_allclose(np.stack(losses1), losses2.asnumpy(),
                               rtol=2e-6, atol=1e-6)
    for k2, v in net1.collect_params().items():
        np.testing.assert_allclose(
            v.data().asnumpy(),
            net2.collect_params()[k2].data().asnumpy(),
            rtol=2e-6, atol=1e-6, err_msg=f"{k2} diverged")


def test_trainer_step_k_metric_carry():
    """A device-capable metric passed to step_k rides the scan carry:
    zero host syncs across the K steps, ONE at the next read, and the
    value equals the eager fold of the same (label, loss) pairs."""
    from mxnet_tpu import gluon
    data, label = _data()
    loss_obj = gluon.loss.SoftmaxCrossEntropyLoss()
    net1 = _make_gluon()
    net2 = _make_gluon()
    _clone_gluon(net1, net2, mx.nd.array(data[0]))
    t1 = gluon.Trainer(net1.collect_params(), 'sgd',
                       {'learning_rate': 0.1}, kvstore=None)
    t2 = gluon.Trainer(net2.collect_params(), 'sgd',
                       {'learning_rate': 0.1}, kvstore=None)

    m1 = mx.metric.Loss()
    from mxnet_tpu import autograd
    for j in range(K):
        x, y = mx.nd.array(data[j]), mx.nd.array(label[j])
        with autograd.record():
            loss = loss_obj(net1(x), y)
        loss.backward()
        t1.step(BATCH)
        m1.update([y], [loss])

    m2 = mx.metric.Loss()
    prof.reset_host_syncs()
    t2.step_k(lambda x, y: loss_obj(net2(x), y), data, label,
              k=K, batch_size=BATCH, eval_metric=m2)
    assert prof.host_sync_total() == 0, prof.host_syncs()
    v2 = m2.get()[1]
    assert prof.host_syncs() == {"metric.sync": 1}, prof.host_syncs()
    np.testing.assert_allclose(v2, m1.get()[1], rtol=2e-6)


@pytest.mark.slow
def test_trainer_step_k_host_metric_one_readback():
    """A metric WITHOUT a device form still folds from ONE stacked
    readback of the K losses — never one readback per step."""
    from mxnet_tpu import gluon
    data, label = _data()
    loss_obj = gluon.loss.SoftmaxCrossEntropyLoss()
    net = _make_gluon()
    net(mx.nd.array(data[0]))
    tr = gluon.Trainer(net.collect_params(), 'sgd',
                       {'learning_rate': 0.1}, kvstore=None)
    m = mx.metric.np(lambda l, p: float(p.mean()), name='mean_loss')
    prof.reset_host_syncs()
    tr.step_k(lambda x, y: loss_obj(net(x), y), data, label,
              k=K, batch_size=BATCH, eval_metric=m)
    assert prof.host_syncs().get("step_k.metric_fold") == 1, \
        prof.host_syncs()
    assert m.num_inst == K


@pytest.mark.slow
def test_trainer_step_k_k1_eager_fallback():
    """K=1 takes the eager loop (record/backward/step) — same result,
    per-step dispatches."""
    from mxnet_tpu import gluon
    data, label = _data(k=1)
    loss_obj = gluon.loss.SoftmaxCrossEntropyLoss()
    net = _make_gluon()
    net(mx.nd.array(data[0]))
    tr = gluon.Trainer(net.collect_params(), 'sgd',
                       {'learning_rate': 0.1}, kvstore=None)
    prof.reset_dispatch_counts()
    losses = tr.step_k(lambda x, y: loss_obj(net(x), y), data, label,
                       k=1, batch_size=BATCH)
    assert "step_k.dispatch" not in prof.dispatch_counts()
    assert losses.shape == (1, BATCH)


def test_trainer_step_k_schedule_and_cache():
    """Update counts advance like K step() calls, and a second call
    reuses the compiled scan."""
    from mxnet_tpu import gluon
    data, label = _data()
    loss_obj = gluon.loss.SoftmaxCrossEntropyLoss()
    net = _make_gluon()
    net(mx.nd.array(data[0]))
    tr = gluon.Trainer(net.collect_params(), 'sgd',
                       {'learning_rate': 0.1}, kvstore=None)
    # the natural per-iteration call shape: a FRESH lambda object each
    # loop pass (same code, same closure) must hit the cache — keying on
    # loss_fn identity would silently recompile the whole K-step
    # program every call
    for _ in range(2):
        tr.step_k(lambda x, y: loss_obj(net(x), y), data, label, k=K,
                  batch_size=BATCH)
    assert tr._optimizer.num_update == 2 * K
    assert len(tr._step_k_cache) == 1


def test_trainer_step_k_deferred_init_raises():
    """Deferred-init params (no in_units, no eager forward yet) must
    fail clearly instead of materializing inside the trace — which
    would silently train nothing and leak tracers."""
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.base import MXNetError
    data, label = _data(k=2)
    net = nn.Dense(NCLASS)       # in_units unknown -> deferred init
    net.initialize()
    tr = gluon.Trainer(net.collect_params(), 'sgd',
                       {'learning_rate': 0.1}, kvstore=None)
    loss_obj = gluon.loss.SoftmaxCrossEntropyLoss()
    with pytest.raises(MXNetError, match="deferred init"):
        tr.step_k(lambda x, y: loss_obj(net(x), y), data, label, k=2,
                  batch_size=BATCH)


# -- the K-batch feed -------------------------------------------------------

def test_kbatch_iter_stacks_and_discards_partial():
    x = np.arange(20 * NIN, dtype=np.float32).reshape(20, NIN)
    y = np.arange(20, dtype=np.float32)
    it = mx.io.KBatchIter(mx.io.NDArrayIter(x, y, batch_size=4,
                                            last_batch_handle='discard'),
                          k=2)
    groups = list(it)
    assert len(groups) == 2   # 5 batches -> 2 full groups, 1 discarded
    assert groups[0].data[0].shape == (2, 4, NIN)
    np.testing.assert_array_equal(groups[0].data[0].asnumpy()[0], x[:4])
    np.testing.assert_array_equal(groups[0].data[0].asnumpy()[1], x[4:8])
    assert groups[0].provide_data[0].shape == (2, 4, NIN)
    # keep mode emits the short tail group, with descs stating the
    # ACTUAL leading dim
    it2 = mx.io.KBatchIter(mx.io.NDArrayIter(x, y, batch_size=4,
                                             last_batch_handle='discard'),
                           k=2, last_group='keep')
    it2.reset()
    tail = list(it2)[-1]
    assert tail.data[0].shape[0] == 1
    assert tail.provide_data[0].shape == (1, 4, NIN)
    # PrefetchingIter over a KBatchIter reports the inner BATCH size,
    # not the step count k (consumers normalize updates by batch_size)
    pre = mx.io.PrefetchingIter(
        mx.io.KBatchIter(mx.io.NDArrayIter(x, y, batch_size=4), k=2))
    assert pre.batch_size == 4


@pytest.mark.slow
def test_kbatch_feeds_run_steps():
    """End-to-end: KBatchIter superbatches drive run_steps; equals the
    same batches trained eagerly."""
    x = np.random.RandomState(3).uniform(
        -1, 1, (4 * BATCH, NIN)).astype(np.float32)
    y = np.random.RandomState(4).randint(
        0, NCLASS, (4 * BATCH,)).astype(np.float32)
    mx.random.seed(0)
    m1 = _make_module()
    mx.random.seed(0)
    m2 = _make_module()
    _clone_params(m1, m2)
    for b in mx.io.NDArrayIter(x, y, batch_size=BATCH):
        m1.forward(b, is_train=True)
        m1.update()
    it = mx.io.KBatchIter(mx.io.NDArrayIter(x, y, batch_size=BATCH), k=4)
    for g in it:
        m2.run_steps(g.data[0], g.label[0])
    _assert_state_equal(m1, m2, exact=True)


def test_kbatch_short_superbatch_takes_eager_fallback():
    """A superbatch cut short mid-epoch ('keep' tail): run_steps must
    route the short group through the EAGER driver (different leading
    dim than the compiled scan) and still produce the state a pure eager
    run over the same batches produces — bit-for-bit."""
    n_batches = 5           # K=2 -> 2 full groups + 1 short tail
    x = np.random.RandomState(5).uniform(
        -1, 1, (n_batches * BATCH, NIN)).astype(np.float32)
    y = np.random.RandomState(6).randint(
        0, NCLASS, (n_batches * BATCH,)).astype(np.float32)
    mx.random.seed(0)
    m1 = _make_module()
    mx.random.seed(0)
    m2 = _make_module()
    _clone_params(m1, m2)
    for b in mx.io.NDArrayIter(x, y, batch_size=BATCH):
        m1.forward(b, is_train=True)
        m1.update()
    it = mx.io.KBatchIter(mx.io.NDArrayIter(x, y, batch_size=BATCH),
                          k=2, last_group='keep')
    prof.reset_dispatch_counts()
    for g in it:
        m2.run_steps(g.data[0], g.label[0])
    counts = prof.dispatch_counts()
    # 2 full groups scanned, the short tail ran eagerly (k=1 fallback)
    assert counts.get("run_steps.dispatch") == 2, counts
    assert "fused_step.dispatch" in counts, counts
    _assert_state_equal(m1, m2, exact=True)


class _CrashingIter(mx.io.DataIter):
    """Wraps an iterator; raises mid-epoch after n good batches — the
    transport/decoder crash stand-in for the fault-path tests."""

    def __init__(self, inner, crash_after):
        super().__init__(inner.batch_size)
        self.inner = inner
        self.crash_after = crash_after
        self.count = 0

    @property
    def provide_data(self):
        return self.inner.provide_data

    @property
    def provide_label(self):
        return self.inner.provide_label

    def reset(self):
        self.inner.reset()

    def next(self):
        if self.count == self.crash_after:
            raise RuntimeError("injected iterator crash")
        self.count += 1
        return self.inner.next()


def test_kbatch_crash_resume_with_run_steps_carry():
    """Crash/resume across the K-step carry: an inner-iterator crash
    MID-GROUP must surface (never hand run_steps a silently-partial
    superbatch), and resuming from the first untrained batch must land
    on exactly the uninterrupted run's params."""
    n_batches = 8
    k = 2
    x = np.random.RandomState(7).uniform(
        -1, 1, (n_batches * BATCH, NIN)).astype(np.float32)
    y = np.random.RandomState(8).randint(
        0, NCLASS, (n_batches * BATCH,)).astype(np.float32)
    mx.random.seed(0)
    m1 = _make_module()
    mx.random.seed(0)
    m2 = _make_module()
    _clone_params(m1, m2)
    for b in mx.io.NDArrayIter(x, y, batch_size=BATCH):
        m1.forward(b, is_train=True)
        m1.update()

    # crash on batch index 3: group 0 (batches 0,1) trains, group 1 dies
    # after pulling batch 2 — that group must be LOST ENTIRELY, not
    # emitted short
    crashy = _CrashingIter(mx.io.NDArrayIter(x, y, batch_size=BATCH),
                           crash_after=3)
    it = mx.io.KBatchIter(crashy, k=k)
    trained_batches = 0
    with pytest.raises(RuntimeError, match="injected iterator crash"):
        for g in it:
            m2.run_steps(g.data[0], g.label[0], k=k)
            trained_batches += k
    assert trained_batches == 2   # only group 0 reached the module

    # resume: re-feed from the first UNTRAINED batch (2), tail included
    resume = mx.io.KBatchIter(
        mx.io.NDArrayIter(x[trained_batches * BATCH:],
                          y[trained_batches * BATCH:], batch_size=BATCH),
        k=k, last_group='keep')
    for g in resume:
        m2.run_steps(g.data[0], g.label[0])
    _assert_state_equal(m1, m2, exact=True)


def test_prefetching_iter_device_put_stage():
    """device_put=True transfers batches in the prefetch thread; values
    are unchanged and arrays are device-resident."""
    x = np.random.RandomState(0).uniform(
        -1, 1, (4 * BATCH, NIN)).astype(np.float32)
    y = np.zeros((4 * BATCH,), np.float32)
    plain = list(mx.io.NDArrayIter(x, y, batch_size=BATCH))
    pre = mx.io.PrefetchingIter(
        mx.io.NDArrayIter(x, y, batch_size=BATCH), device_put=True)
    got = list(pre)
    assert len(got) == len(plain)
    for a, b in zip(plain, got):
        np.testing.assert_array_equal(a.data[0].asnumpy(),
                                      b.data[0].asnumpy())


@pytest.mark.slow
def test_run_steps_large_k_chip_config():
    """Chip-session smoke: a larger K at the bench's step composition
    (SGD momentum, BN network).  Slow-marked — CI runs it, the default
    gate skips it; on a real chip this is the dispatch-amortization
    measurement path."""
    data, label = _data(k=32)
    mx.random.seed(0)
    m1 = _make_module()
    mx.random.seed(0)
    m2 = _make_module()
    _clone_params(m1, m2)
    _run_eager(m1, data, label)
    prof.reset_dispatch_counts()
    m2.run_steps(data, label, k=32)
    counts = prof.dispatch_counts()
    assert counts.pop("batch_norm.train_vjp") >= 1, counts
    assert counts == {"run_steps.dispatch": 1}, counts
    _assert_state_equal(m1, m2, exact=True)
