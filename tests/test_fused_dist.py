"""Fused dist_async K-step driver (Module.run_steps / Trainer.step_k on
update-on-kvstore): the chunked scan with the wire overlapped behind
compute.

The contracts pinned here, all CPU-provable:

* **no eager fallback** — a dist_async run_steps is exactly one host
  dispatch per MXNET_KVSTORE_FUSED_CHUNK steps (profiler.record_dispatch
  "run_steps.dist_chunk"), never the per-step executor.fwd_bwd loop.
* **staleness 0 == eager dist loop, bit-for-bit** — the worker-local
  update replica and the server's updater share Optimizer._update_impl,
  so with integer gradients and a power-of-two lr every quantity is
  exactly representable and the barrier'd chunked run must EQUAL the
  eager per-step push/pull loop.
* **staleness 1 == the analytic async-SGD golden** — the adopted pull
  lags exactly one chunk boundary (deterministic by design, never
  "freshest available"), so a numpy simulation of the chunk/adoption
  arithmetic predicts the final server weights bit-for-bit.
* **transport kills stay invisible** — a mid-window connection kill
  (faultinject.kill_when_unacked) rides the window replay + server
  dedup underneath the driver; the run stays bit-identical to an
  uninterrupted one.
* **overlap accounting** — executor.drive_chunked_dist's wire_wait /
  wire_round clocks: staleness 1 must block strictly less than
  staleness 0 and report a positive overlap fraction (the CPU
  regression gate ci/run_ci.sh asserts cross-process too).
"""
import math
import os
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler as prof

K = 6
BATCH = 2
NIN = 3
NH = 2
LR = 0.25          # power of two: every update exact in fp32


def _int_data(seed=0, k=K):
    rs = np.random.RandomState(seed)
    data = rs.randint(-1, 2, (k, BATCH, NIN)).astype(np.float32)
    label = rs.randint(-2, 3, (k, BATCH, NH)).astype(np.float32)
    w0 = rs.randint(-2, 3, (NH, NIN)).astype(np.float32)
    return data, label, w0


def _make_module(w0):
    data = mx.sym.Variable('data')
    net = mx.sym.FullyConnected(data, num_hidden=NH, no_bias=True,
                                name='fc')
    sym = mx.sym.LinearRegressionOutput(net, name='lro')
    mod = mx.mod.Module(sym, data_names=('data',),
                        label_names=('lro_label',))
    mod.bind(data_shapes=[('data', (BATCH, NIN))],
             label_shapes=[('lro_label', (BATCH, NH))])
    mod.init_params(arg_params={'fc_weight': mx.nd.array(w0.copy())})
    mod.init_optimizer(
        kvstore='dist_async', optimizer='sgd',
        optimizer_params={'learning_rate': LR, 'momentum': 0.0,
                          'wd': 0.0, 'rescale_grad': 1.0})
    return mod


def _serve(monkeypatch, n=1, **kw):
    """n fresh in-process servers; every run gets its own (the server
    keeps weight state)."""
    from mxnet_tpu.kvstore_server import KVStoreServer
    srvs = [KVStoreServer(server_id=i, num_workers=1, **kw)
            for i in range(n)]
    for s in srvs:
        s.start_background()
    monkeypatch.setenv("MXT_SERVER_URIS",
                       ",".join(f"127.0.0.1:{s.port}" for s in srvs))
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    monkeypatch.setenv("DMLC_WORKER_ID", "0")
    return srvs


def _run_module(monkeypatch, w0, data, label, staleness, chunk,
                fused=True, n_servers=1):
    """One full run against fresh servers; returns final weights."""
    srvs = _serve(monkeypatch, n=n_servers)
    try:
        monkeypatch.setenv("MXNET_KVSTORE_FUSED", "1" if fused else "0")
        monkeypatch.setenv("MXNET_KVSTORE_FUSED_STALENESS",
                           str(staleness))
        monkeypatch.setenv("MXNET_KVSTORE_FUSED_CHUNK", str(chunk))
        mod = _make_module(w0)
        mod.run_steps(data, label, k=data.shape[0])
        w = mod.get_params()[0]['fc_weight'].asnumpy().copy()
        mod._kvstore.close(stop_servers=True)
        return w
    finally:
        for s in srvs:
            s.stop()


def _simulate_chunked(w0, data, label, lr, chunk, staleness):
    """Numpy twin of the chunked-driver semantics (the analytic golden):
    chunk j adopts the pull issued after chunk j-1-S's pushes — the
    server's state after exactly those chunks (single worker) — and the
    in-chunk trajectory evolves through the local update replica.  The
    server applies every pushed gradient; the final pull is its state
    after all of them.  All quantities are exact dyadics, so float32
    reproduces the runtime bit-for-bit."""
    k = data.shape[0]
    n_chunks = math.ceil(k / chunk)
    srv = w0.astype(np.float32).copy()
    local = w0.astype(np.float32).copy()
    pulls = {}
    for j in range(n_chunks):
        due = j - 1 - staleness
        if due in pulls:
            local = pulls.pop(due).copy()
        lo, hi = j * chunk, min(k, (j + 1) * chunk)
        for s in range(lo, hi):
            pred = data[s] @ local.T
            g = ((pred - label[s]).T @ data[s]).astype(np.float32)
            local = local - np.float32(lr) * g
            srv = srv - np.float32(lr) * g
        pulls[j] = srv.copy()
    return srv


def test_staleness0_bit_identical_to_eager_dist_loop(monkeypatch):
    """Staleness 0 (barrier'd chunk boundary) == the eager per-step
    push/pull loop, bit-for-bit: the local replica and the server apply
    identical update sequences, and every quantity is an exact dyadic."""
    data, label, w0 = _int_data(seed=1)
    w_eager = _run_module(monkeypatch, w0, data, label, staleness=0,
                          chunk=2, fused=False)
    w_fused = _run_module(monkeypatch, w0, data, label, staleness=0,
                          chunk=2, fused=True)
    np.testing.assert_array_equal(w_fused, w_eager)
    # and both match the analytic simulation of the eager loop
    np.testing.assert_array_equal(
        w_fused, _simulate_chunked(w0, data, label, LR, 1, 0))


def test_staleness1_matches_analytic_async_golden(monkeypatch):
    """Staleness 1 == the numpy simulation of the chunk/adoption
    arithmetic, bit-for-bit — the lag is EXACT (chunk j always adopts
    chunk j-2's pull), which is what makes the golden computable."""
    data, label, w0 = _int_data(seed=2)
    sim_s0 = _simulate_chunked(w0, data, label, LR, 2, 0)
    sim_s1 = _simulate_chunked(w0, data, label, LR, 2, 1)
    # precondition: the data must actually expose the staleness (a
    # dataset where stale and fresh gradients coincide proves nothing)
    assert not np.array_equal(sim_s0, sim_s1)
    w_fused = _run_module(monkeypatch, w0, data, label, staleness=1,
                          chunk=2, fused=True)
    np.testing.assert_array_equal(w_fused, sim_s1)


def test_one_dispatch_per_chunk_no_eager_fallback(monkeypatch):
    """The acceptance pin: dist_async run_steps is ONE dispatch per
    chunk — never the per-step eager loop's executor.fwd_bwd — and the
    kill switch restores exactly that loop."""
    data, label, w0 = _int_data(seed=3)
    srvs = _serve(monkeypatch)
    try:
        monkeypatch.setenv("MXNET_KVSTORE_FUSED_STALENESS", "1")
        monkeypatch.setenv("MXNET_KVSTORE_FUSED_CHUNK", "2")
        mod = _make_module(w0)
        prof.reset_dispatch_counts()
        outs = mod.run_steps(data, label, k=K)
        counts = prof.dispatch_counts()
        assert counts.get("run_steps.dist_chunk") == math.ceil(K / 2)
        assert "executor.fwd_bwd" not in counts
        assert "run_steps.dispatch" not in counts
        assert outs[0].shape == (K, BATCH, NH)
        mod._kvstore.close(stop_servers=True)
    finally:
        for s in srvs:
            s.stop()
    # kill switch: MXNET_KVSTORE_FUSED=0 restores the eager dist loop
    srvs = _serve(monkeypatch)
    try:
        monkeypatch.setenv("MXNET_KVSTORE_FUSED", "0")
        mod = _make_module(w0)
        prof.reset_dispatch_counts()
        mod.run_steps(data, label, k=K)
        counts = prof.dispatch_counts()
        assert "run_steps.dist_chunk" not in counts
        assert counts.get("executor.fwd_bwd") == K
        mod._kvstore.close(stop_servers=True)
    finally:
        for s in srvs:
            s.stop()


def _serve_elastic(monkeypatch, n=2):
    """n elastic in-process servers sharing a roster (the
    tests/test_membership.py harness shape), env wired for fast
    retry/heartbeat budgets."""
    from mxnet_tpu.kvstore_server import KVStoreServer
    monkeypatch.setenv("MXNET_KVSTORE_ELASTIC", "1")
    monkeypatch.setenv("MXNET_KVSTORE_RETRY_MAX", "2")
    monkeypatch.setenv("MXNET_KVSTORE_RETRY_INITIAL_MS", "10")
    monkeypatch.setenv("MXNET_KVSTORE_RETRY_MAX_MS", "50")
    monkeypatch.setenv("MXNET_KVSTORE_HEARTBEAT_INTERVAL", "0.1")
    monkeypatch.setenv("MXNET_KVSTORE_HEARTBEAT_TIMEOUT", "0.5")
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    monkeypatch.setenv("DMLC_WORKER_ID", "0")
    srvs = [KVStoreServer(server_id=i, num_workers=1, elastic=True)
            for i in range(n)]
    uris = ",".join(f"127.0.0.1:{s.port}" for s in srvs)
    monkeypatch.setenv("MXT_SERVER_URIS", uris)
    for s in srvs:
        s._roster_servers = uris.split(",")
        s.start_background()
    return srvs


def test_elastic_rides_fused_driver(monkeypatch):
    """MXNET_KVSTORE_ELASTIC no longer gates the chunked driver off:
    an elastic run_steps is one dispatch per chunk (never the eager
    per-step loop) and lands bit-identical to the analytic staleness
    golden — the fused×elastic composition the _PullHandle replan
    bought (docs/ROBUSTNESS.md replan contract)."""
    data, label, w0 = _int_data(seed=6)
    srvs = _serve_elastic(monkeypatch)
    try:
        monkeypatch.setenv("MXNET_KVSTORE_FUSED_STALENESS", "1")
        monkeypatch.setenv("MXNET_KVSTORE_FUSED_CHUNK", "2")
        mod = _make_module(w0)
        assert mod._kvstore._elastic
        prof.reset_dispatch_counts()
        mod.run_steps(data, label, k=K)
        counts = prof.dispatch_counts()
        assert counts.get("run_steps.dist_chunk") == math.ceil(K / 2), \
            counts
        assert "executor.fwd_bwd" not in counts
        w = mod.get_params()[0]['fc_weight'].asnumpy()
        np.testing.assert_array_equal(
            w, _simulate_chunked(w0, data, label, LR, 2, 1))
        mod._kvstore.close(stop_servers=True)
    finally:
        for s in srvs:
            s.stop()


def test_elastic_fused_survives_server_death(monkeypatch):
    """A server death BETWEEN chunked runs repairs mid-drive (the push
    leg re-routes, the pull handle replans) and the job completes
    bit-identical to the static golden: the surviving layout's final
    weights equal the simulation of every applied gradient."""
    monkeypatch.setenv("MXNET_KVSTORE_BIGARRAY_BOUND", "4")
    data, label, w0 = _int_data(seed=7)
    srvs = _serve_elastic(monkeypatch)
    try:
        monkeypatch.setenv("MXNET_KVSTORE_FUSED_STALENESS", "0")
        monkeypatch.setenv("MXNET_KVSTORE_FUSED_CHUNK", "2")
        mod = _make_module(w0)
        kv = mod._kvstore
        # fc_weight stripes across both servers under the tiny bound
        assert kv._stripe_plan('fc_weight', w0.shape) is not None
        half = K // 2
        mod.run_steps(data[:half], label[:half], k=half)
        kv.barrier()
        srvs[1].stop()   # SIGKILL-equivalent: stripe state lost
        prof.reset_dispatch_counts()
        mod.run_steps(data[half:], label[half:], k=half)
        counts = prof.dispatch_counts()
        assert counts.get("run_steps.dist_chunk") == math.ceil(half / 2)
        assert kv._roster_gen >= 1 and len(kv._conns) == 1
        w = mod.get_params()[0]['fc_weight'].asnumpy()
        np.testing.assert_array_equal(
            w, _simulate_chunked(w0, data, label, LR, 2, 0),
            err_msg="elastic fused run diverged from the static golden")
        mod._kvstore.close(stop_servers=True)
    finally:
        for s in srvs:
            s.stop()


def test_striped_keys_ride_the_fused_driver(monkeypatch):
    """A big weight striped across 2 servers pushes per-stripe and
    reassembles through pull_async exactly like the eager path: fused
    staleness-0 == eager, bit-for-bit, over a striped layout."""
    monkeypatch.setenv("MXNET_KVSTORE_BIGARRAY_BOUND", "4")
    data, label, w0 = _int_data(seed=4)
    w_eager = _run_module(monkeypatch, w0, data, label, staleness=0,
                          chunk=2, fused=False, n_servers=2)
    w_fused = _run_module(monkeypatch, w0, data, label, staleness=0,
                          chunk=2, fused=True, n_servers=2)
    np.testing.assert_array_equal(w_fused, w_eager)


def test_mid_window_kill_bit_identical(monkeypatch):
    """A connection kill with unacked envelopes in flight mid-run rides
    the window replay + server dedup underneath the fused driver: the
    interrupted run must EQUAL the uninterrupted one bit-for-bit (the
    eager path's existing guarantee, now on the chunked driver).
    Momentum is on — the replay must not double-advance server state."""
    from mxnet_tpu import faultinject

    def run(kill):
        data, label, w0 = _int_data(seed=5)
        srvs = _serve(monkeypatch)
        try:
            monkeypatch.setenv("MXNET_KVSTORE_FUSED_STALENESS", "1")
            monkeypatch.setenv("MXNET_KVSTORE_FUSED_CHUNK", "2")
            mod = _make_module(w0)
            ctx = faultinject.kill_when_unacked(3) if kill else None
            if ctx is not None:
                with ctx:
                    mod.run_steps(data, label, k=K)
            else:
                mod.run_steps(data, label, k=K)
            w = mod.get_params()[0]['fc_weight'].asnumpy().copy()
            stats = dict(prof.channel_counts())
            mod._kvstore.close(stop_servers=True)
            return w, stats
        finally:
            for s in srvs:
                s.stop()

    prof.reset_channel_counts()
    w_clean, _ = run(kill=False)
    prof.reset_channel_counts()
    w_killed, stats = run(kill=True)
    # the kill really happened and really recovered
    assert stats.get("kvstore.reconnect", 0) >= 1
    assert stats.get("kvstore.replay", 0) >= 1
    np.testing.assert_array_equal(w_killed, w_clean)


def test_trainer_step_k_dist_fused_matches_eager(monkeypatch):
    """Gluon twin: step_k on dist_async no longer falls back — one
    dispatch per chunk, and staleness 0 equals K eager step() calls
    bit-for-bit (same integer-exactness argument as the Module test)."""
    import mxnet_tpu.gluon as gluon
    from mxnet_tpu import autograd

    rs = np.random.RandomState(7)
    data = rs.randint(-1, 2, (K, BATCH, NIN)).astype(np.float32)
    label = rs.randint(-2, 3, (K, BATCH, 1)).astype(np.float32)
    w0 = rs.randint(-2, 3, (1, NIN)).astype(np.float32)

    def make_net():
        net = gluon.nn.Dense(1, use_bias=False, in_units=NIN)
        net.initialize()
        net.weight.data()._set_data(mx.nd.array(w0.copy())._data)
        return net

    def loss_of(net):
        def loss_fn(x, y):
            d = net(x) - y
            return (d * d).sum()
        return loss_fn

    # eager reference: K record/backward/step() round trips
    srvs = _serve(monkeypatch)
    try:
        net = make_net()
        tr = gluon.Trainer(net.collect_params(), 'sgd',
                           {'learning_rate': LR, 'momentum': 0.0,
                            'wd': 0.0}, kvstore='dist_async')
        fn = loss_of(net)
        for j in range(K):
            with autograd.record():
                loss = fn(mx.nd.array(data[j]), mx.nd.array(label[j]))
            loss.backward()
            tr.step(batch_size=1)
        w_eager = net.weight.data().asnumpy().copy()
        tr._kvstore.close(stop_servers=True)
    finally:
        for s in srvs:
            s.stop()

    # fused: one step_k call, chunked, staleness 0
    srvs = _serve(monkeypatch)
    try:
        monkeypatch.setenv("MXNET_KVSTORE_FUSED_STALENESS", "0")
        monkeypatch.setenv("MXNET_KVSTORE_FUSED_CHUNK", "2")
        net = make_net()
        tr = gluon.Trainer(net.collect_params(), 'sgd',
                           {'learning_rate': LR, 'momentum': 0.0,
                            'wd': 0.0}, kvstore='dist_async')
        prof.reset_dispatch_counts()
        losses = tr.step_k(loss_of(net), data, label, batch_size=1)
        counts = prof.dispatch_counts()
        assert counts.get("step_k.dist_chunk") == math.ceil(K / 2)
        assert "step_k.dispatch" not in counts
        assert losses.shape == (K,)
        np.testing.assert_array_equal(
            net.weight.data().asnumpy(), w_eager)
        tr._kvstore.close(stop_servers=True)
    finally:
        for s in srvs:
            s.stop()


def test_drive_chunked_dist_overlap_accounting():
    """The wire-overlap clocks, in isolation: with a synthetic 60 ms
    wire round and 30 ms chunks, staleness 1 must hide the computed
    fraction (wait strictly below staleness 0's, overlap_pct strictly
    positive) and staleness 0 must expose ~the whole round."""
    from mxnet_tpu.executor import drive_chunked_dist

    COMPUTE, RTT = 0.03, 0.06

    class _Handle:
        def __init__(self):
            self._t0 = time.monotonic()
            self._ready = self._t0 + RTT
            self._done = False

        def wait(self):
            if self._done:
                return {}
            t_wait = time.monotonic()
            if self._ready > t_wait:
                time.sleep(self._ready - t_wait)
            t1 = time.monotonic()
            prof.record_wire_wait(t1 - t_wait)
            prof.record_wire_round(t1 - self._t0)
            self._done = True
            return {}

    def run(staleness):
        prof.reset_wire_counters()
        adoptions = []

        def dispatch(j, lo, hi, adopted):
            adoptions.append((j, adopted is not None))
            time.sleep(COMPUTE)
            return [None]

        def ship(j, grads):
            return _Handle()

        drive_chunked_dist(6, 1, staleness, dispatch, ship)
        assert prof.wire_rounds() == 6          # every round resolved
        return (prof.wire_wait_ms(), prof.wire_overlap_pct(), adoptions)

    wait0, overlap0, adopt0 = run(0)
    wait1, overlap1, adopt1 = run(1)
    # staleness 0 adopts at every boundary after the first; staleness 1
    # starts one later (the exact-lag schedule)
    assert [a for _j, a in adopt0] == [False] + [True] * 5
    assert [a for _j, a in adopt1] == [False, False] + [True] * 4
    assert wait1 < wait0
    assert overlap1 > overlap0
    assert overlap1 > 25.0   # ~half of each round hides behind compute
    assert overlap0 < 25.0   # barrier'd boundaries expose the wire


def test_fused_epoch_serializes_zero_pickled_bytes(monkeypatch):
    """ISSUE 16 acceptance pin: with the binary codec negotiated
    (MXNET_KVSTORE_CODEC=binary forced), a fused dist_async run_steps
    epoch records pickle_bytes == 0 — every push/pull envelope and ack
    in the steady-state window rides the generated binary frame."""
    monkeypatch.setenv("MXNET_KVSTORE_CODEC", "binary")
    data, label, w0 = _int_data(seed=3)
    srvs = _serve(monkeypatch)
    try:
        monkeypatch.setenv("MXNET_KVSTORE_FUSED", "1")
        monkeypatch.setenv("MXNET_KVSTORE_FUSED_STALENESS", "0")
        monkeypatch.setenv("MXNET_KVSTORE_FUSED_CHUNK", "2")
        mod = _make_module(w0)
        # warm-up epoch: init/optimizer shipping is cold-path pickle
        mod.run_steps(data, label, k=data.shape[0])
        prof.reset_serialization()
        mod.run_steps(data, label, k=data.shape[0])
        counts = prof.serialization_counts()
        assert counts.get("pickle_bytes", 0) == 0, counts
        assert counts.get("codec_bytes", 0) > 0, counts
        mod._kvstore.close(stop_servers=True)
    finally:
        for s in srvs:
            s.stop()
