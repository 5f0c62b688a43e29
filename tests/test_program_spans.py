"""The program's own spans and scopes (docs/OBSERVABILITY.md, "One span
API"): ``tracing.span`` is the one entry point and feeds three sinks (the
profiler's xplane, the MXNET_TRACE ring, the chrome trace), the Module
step path names where its host time goes (``mx.*``), every symbol node is
a ``jax.named_scope`` in the fused step, set-up phases feed an always-on
clock, and a fused step that compiles again says which argument leaf moved
in what ``jit`` keys on."""
import glob
import json
import re
import subprocess
import sys
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import profiler, tracing

NODES = ("conv1", "bn1", "relu1", "flat", "fc1", "softmax")
STEP_SPANS = ("mx.module.forward", "mx.executor.place", "mx.module.update",
              "mx.module.update.prep", "mx.module.update.call",
              "mx.module.update.writeback")


def tiny_net():
    net = mx.sym.Variable("data")
    net = mx.sym.Convolution(net, num_filter=4, kernel=(3, 3), pad=(1, 1),
                             name="conv1")
    net = mx.sym.BatchNorm(net, name="bn1")
    net = mx.sym.Activation(net, act_type="relu", name="relu1")
    net = mx.sym.Flatten(net, name="flat")
    net = mx.sym.FullyConnected(net, num_hidden=5, name="fc1")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def batch(seed=0):
    rs = np.random.RandomState(seed)
    return mx.io.DataBatch(
        data=[mx.nd.array(rs.rand(2, 3, 8, 8).astype("f"))],
        label=[mx.nd.array(np.array([1, 2], "f"))])


def tiny_module():
    mod = mx.mod.Module(tiny_net(), context=mx.cpu())
    mod.bind(data_shapes=[("data", (2, 3, 8, 8))],
             label_shapes=[("softmax_label", (2,))])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    return mod


def step(mod, b):
    mod.forward(b, is_train=True)
    mod.update()


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv("MXNET_TRACE", raising=False)
    monkeypatch.delenv("MXNET_TRACE_DIR", raising=False)
    tracing.reconfigure()
    profiler.reset_all()
    yield
    with monkeypatch.context() as m:
        m.delenv("MXNET_TRACE", raising=False)
        tracing.reconfigure()
    profiler.reset_all()


def _trace_on(monkeypatch):
    monkeypatch.setenv("MXNET_TRACE", "1")
    tracing.reconfigure()


# -- device scopes: locations only ---------------------------------------------
def test_every_node_is_a_scope_of_the_lowered_step_and_the_program_is_unchanged():
    mod = tiny_module()
    mod.forward(batch(), is_train=True)
    located = mod._lower_fused_step().as_text(debug_info=True)
    paths = set(re.findall(r'"(jit\(mx_fused_step\)/[^"]*)"', located))
    for node in NODES:
        assert any("/jvp(%s)/" % node in p for p in paths), node
        assert any("/transpose(jvp(%s))/" % node in p for p in paths), node
    assert any(p.startswith("jit(mx_fused_step)/optimizer/") for p in paths)
    # what tests/test_amp_hlo.py pins carries none of it: the scopes are
    # locations, and the program is the one without them
    plain = mod.fused_step_hlo()
    assert "module @jit_mx_fused_step" in plain
    for word in ("jvp(", "transpose(jvp", "optimizer") + NODES:
        assert word not in plain, word


def test_the_scan_driver_traces_the_same_scopes():
    mod = tiny_module()
    data = np.random.RandomState(0).rand(2, 2, 3, 8, 8).astype("f")
    label = np.array([[1, 2], [3, 4]], "f")
    mod.run_steps(mx.nd.array(data), mx.nd.array(label), k=2)
    (fn,) = mod._run_steps_cache.values()
    assert fn.__name__ == "mx_run_steps" and fn._cache_size() == 1


# -- host spans: the ring sink -------------------------------------------------
def test_three_steps_leave_the_step_spans_with_parents_and_a_rising_step(
        monkeypatch):
    mod = tiny_module()
    _trace_on(monkeypatch)
    for i in range(3):
        step(mod, batch(i))
    recs = tracing.ring_records()
    by_name = {n: [r for r in recs if r["name"] == n] for n in STEP_SPANS}
    for n in STEP_SPANS:
        assert len(by_name[n]) == 3, n
    for i in range(3):
        fwd, upd = by_name["mx.module.forward"][i], \
            by_name["mx.module.update"][i]
        assert by_name["mx.executor.place"][i]["parent"] == fwd["span"]
        for child in ("prep", "call", "writeback"):
            assert by_name["mx.module.update." + child][i]["parent"] \
                == upd["span"], child
        assert fwd["parent"] is None and fwd["trace"] != upd["trace"]
    # the first update is also a set-up phase and parents the span
    first = [r for r in recs if r["name"] == "mx.module.first_update"]
    assert len(first) == 1
    assert by_name["mx.module.update"][0]["parent"] == first[0]["span"]
    steps = [r["args"]["step"] for r in by_name["mx.module.update"]]
    assert steps == sorted(steps) and len(set(steps)) == 3
    # a value that has to be committed or moved is moved inside the
    # placement walk, and counted there
    puts = [r for r in recs if r["name"] == "mx.executor.device_put"]
    places = {r["span"] for r in by_name["mx.executor.place"]}
    assert puts and {r["parent"] for r in puts} <= places


def test_fit_names_the_iterator_wait_the_metric_fold_and_the_callbacks(
        monkeypatch):
    rs = np.random.RandomState(0)
    it = mx.io.NDArrayIter(rs.rand(4, 3, 8, 8).astype("f"),
                           np.array([0, 1, 2, 3], "f"), batch_size=2)
    nexts = []
    real_next = type(it).__next__

    class Counting(type(it)):
        def __next__(self):
            nexts.append(1)
            return real_next(self)

    it.__class__ = Counting
    _trace_on(monkeypatch)
    mod = mx.mod.Module(tiny_net(), context=mx.cpu())
    seen = []
    mod.fit(it, num_epoch=1, optimizer_params={"learning_rate": 0.1},
            batch_end_callback=lambda p: seen.append(p.nbatch))
    names = [r["name"] for r in tracing.ring_records()]
    # two batches: three next() calls, the last one raising StopIteration
    assert len(nexts) == 3
    assert names.count("mx.fit.next_batch") == len(nexts)
    assert names.count("mx.fit.update_metric") == 2
    assert names.count("mx.fit.callbacks") == 2 and seen == [0, 1]
    assert names.count("mx.module.bind") == 1
    assert names.count("mx.module.init_optimizer") == 1
    # once from fit, once from the epoch-end set_params (force_init)
    assert names.count("mx.module.init_params") == 2


def test_a_host_sync_is_a_span_named_after_its_counter(monkeypatch):
    _trace_on(monkeypatch)
    x = mx.nd.array(np.ones((2, 2), "f"))
    x.asnumpy()
    x.wait_to_read()
    names = {r["name"] for r in tracing.ring_records()}
    assert {"mx.sync." + k for k in profiler.host_syncs()} <= names
    assert "mx.sync.ndarray.asnumpy" in names
    assert "mx.sync.ndarray.wait_to_read" in names


# -- host spans: the profiler's own trace ---------------------------------------
def test_spans_land_on_the_host_plane_of_the_xplane_with_tracing_off(tmp_path):
    from jax.profiler import ProfileData
    mod = tiny_module()
    step(mod, batch())
    step(mod, batch())
    assert not tracing.enabled()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(3):
            step(mod, batch(i))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    (host,) = [p for p in ProfileData.from_file(path).planes
               if p.name == "/host:CPU"]
    counts = {}
    for line in host.lines:
        for e in line.events:
            if e.name.startswith("mx."):
                counts[e.name] = counts.get(e.name, 0) + 1
                assert e.duration_ns >= 0
    assert {n: counts.get(n) for n in STEP_SPANS} \
        == dict.fromkeys(STEP_SPANS, 3)
    # and nothing reached the ring: the annotation sits beneath the switch
    assert tracing.ring_records() == []


def test_one_entry_point_three_sinks(tmp_path):
    from jax.profiler import TraceAnnotation
    # everything off: the annotation alone, yielding None
    ctx = tracing.span("mx.test.off")
    assert isinstance(ctx, TraceAnnotation)
    with ctx as sp:
        assert sp is None
    assert isinstance(profiler.scope("mx.test.alias"), TraceAnnotation)
    # the eager per-operator site keeps its meaning: mode "all" only
    assert not isinstance(profiler.scope("dot", require_mode="all"),
                          TraceAnnotation)
    # the chrome sink takes the span's own times, on tracing's clock
    out = tmp_path / "profile.json"
    profiler.profiler_set_config(filename=str(out))
    profiler.profiler_set_state("run")
    try:
        before = tracing.now_us()
        with tracing.span("mx.test.chrome", "test") as sp:
            assert sp is None       # MXNET_TRACE is still off
            time.sleep(0.002)
        after = tracing.now_us()
    finally:
        profiler.profiler_set_state("stop")
    profiler.dump_profile()
    (ev,) = [e for e in json.loads(out.read_text())["traceEvents"]
             if e["name"] == "mx.test.chrome"]
    assert ev["cat"] == "test" and ev["ph"] == "X"
    assert before <= ev["ts"] <= ev["ts"] + ev["dur"] <= after
    assert ev["dur"] >= 2000
    # stopped again: back to the annotation alone
    assert isinstance(tracing.span("mx.test.off"), TraceAnnotation)


# -- set-up phases ---------------------------------------------------------------
def test_setup_phases_feed_an_always_on_clock():
    assert profiler.phase_seconds() == {}
    mod = tiny_module()
    step(mod, batch())
    step(mod, batch())
    phases = profiler.phase_seconds()
    # the second update compiles the step again (its momentum turned
    # committed), found afterwards and filed as a phase of its own
    assert set(phases) == {"mx.module.bind", "mx.module.init_params",
                           "mx.module.init_optimizer",
                           "mx.module.build_step", "mx.module.first_update",
                           "mx.module.recompile"}
    assert all(len(v) == 1 and v[0] > 0 for v in phases.values())
    # the step is built, traced and compiled inside the first update
    assert phases["mx.module.first_update"][0] \
        > phases["mx.module.build_step"][0]
    # a second Module appends, in order; a repeated init is not a phase
    other = tiny_module()
    other.init_params(mx.initializer.Xavier())
    assert len(profiler.phase_seconds()["mx.module.bind"]) == 2
    assert len(profiler.phase_seconds()["mx.module.init_params"]) == 2
    assert profiler.snapshot()["trace"]["phases"] == profiler.phase_seconds()
    out = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.profiler", "--dump"],
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-500:]
    assert json.loads(out.stdout)["trace"]["phases"] == {}


# -- the recompile, caught where it happens --------------------------------------
def test_a_recompile_names_the_leaf_and_the_field(capfd):
    mod = tiny_module()
    b = batch()
    step(mod, b)
    assert "fused_step.recompile" not in profiler.dispatch_counts()
    capfd.readouterr()
    # today every Module compiles its step twice: the momentum made by
    # init_optimizer is uncommitted until the first step hands it back
    step(mod, b)
    assert profiler.dispatch_counts()["fused_step.recompile"] == 1
    err = capfd.readouterr().err
    assert "the fused step compiled again (jit cache entry 2)" in err
    assert "committed False -> True on 6: optimizer state 0 of conv1_weight" \
        in err
    step(mod, b)
    assert profiler.dispatch_counts()["fused_step.recompile"] == 1
    assert capfd.readouterr().err == ""
    # one leaf, one field: an uncommitted momentum for fc1_weight
    state = mod._opt_states["fc1_weight"][0]
    state._set_data(jnp.zeros(state.shape, jnp.float32))
    step(mod, b)
    assert profiler.dispatch_counts()["fused_step.recompile"] == 2
    err = capfd.readouterr().err
    assert "(jit cache entry 3)" in err
    assert "committed True -> False on 1: optimizer state 0 of fc1_weight" \
        in err
    assert "conv1_weight" not in err
    # the same arguments as two calls ago: an entry jit already has
    step(mod, b)
    step(mod, b)
    assert profiler.dispatch_counts()["fused_step.recompile"] == 2
    assert capfd.readouterr().err == ""


def test_a_recompile_is_an_instant_in_the_ring(monkeypatch):
    mod = tiny_module()
    _trace_on(monkeypatch)
    step(mod, batch())
    step(mod, batch())
    (rec,) = [r for r in tracing.ring_records()
              if r["name"] == "mx.module.update.recompile"]
    (moved,) = rec["args"]["moved"]
    assert (moved["field"], moved["before"], moved["after"]) \
        == ("committed", "False", "True")
    assert "optimizer state 0 of fc1_bias" in moved["leaves"]
    assert rec["parent"] is not None


def test_a_moved_hyperparameter_rebuilds_the_step_and_says_which(capfd):
    mod = tiny_module()
    step(mod, batch())
    step(mod, batch())
    capfd.readouterr()
    mod._optimizer.momentum = 0.5
    step(mod, batch())
    err = capfd.readouterr().err
    assert "the fused step is rebuilt" in err
    assert "momentum 0.9 -> 0.5 on 1: optimizer" in err
    assert profiler.dispatch_counts()["fused_step.rebuild"] == 1
    assert len(profiler.phase_seconds()["mx.module.build_step"]) == 2
    assert len(profiler.phase_seconds()["mx.module.first_update"]) == 1


# -- the compile ledger ----------------------------------------------------------
def fresh_module(hidden):
    """A Module whose shapes no other test compiles: its init_params and
    bind trace and compile programs of their own."""
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=hidden,
                                name="fc_fresh")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (3, hidden + 2))],
             label_shapes=[("softmax_label", (3,))])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    return mod


def fresh_batch(hidden):
    rs = np.random.RandomState(hidden)
    return mx.io.DataBatch(
        data=[mx.nd.array(rs.rand(3, hidden + 2).astype("f"))],
        label=[mx.nd.array(np.array([0, 1, 2], "f"))])


def test_setup_is_top_level_records_under_the_phase_that_asked():
    mod = fresh_module(37)
    step(mod, fresh_batch(37))
    recs = tracing.compile_records()
    phases = {r["phase"] for r in recs}
    assert {"mx.module.bind", "mx.module.init_params",
            "mx.module.first_update"} <= phases
    first = [(r["stage"], r["program"]) for r in recs
             if r["phase"] == "mx.module.first_update"
             and r["occurrence"] == 0]
    assert ("trace", "mx_fused_step") in first
    assert ("lower", "jit(mx_fused_step)") in first
    assert ("compile", "jit(mx_fused_step)") in first
    for r in recs:
        assert r["stage"] in ("trace", "lower", "compile")
        assert r["end_us"] >= r["start_us"]
        assert (r.get("cache") in ("hit", "miss", "none")) \
            == (r["stage"] == "compile")
    # the anchored clock's epoch: the records end before now
    assert max(r["end_us"] for r in recs) <= tracing.now_us() + 1e5
    summary = tracing.stats()["compiles"]
    assert summary["records"] == len(recs) and summary["folded"] > 0
    assert summary["seconds"]["compile"] > 0 and summary["listener_s"] > 0


def test_nested_traces_fold_into_the_one_that_encloses_them():
    mod = fresh_module(41)
    n0 = tracing.compile_count()
    t = time.perf_counter()
    step(mod, fresh_batch(41))
    wall = time.perf_counter() - t
    recs = tracing.compile_records()[n0:]
    traces = [r for r in recs if r["stage"] == "trace"]
    (fused,) = [r for r in traces if r["program"] == "mx_fused_step"]
    assert fused["nested"] > 0
    assert sum((r["end_us"] - r["start_us"]) / 1e6 for r in traces) <= wall
    # no trace record lies inside another of the same thread
    for a in traces:
        for b in traces:
            assert a is b or a["tid"] != b["tid"] or not (
                b["start_us"] <= a["start_us"]
                and a["end_us"] <= b["end_us"])


def test_later_steps_add_no_record():
    mod = fresh_module(43)
    step(mod, fresh_batch(43))
    step(mod, fresh_batch(43))
    n = tracing.compile_count()
    for i in range(3):
        step(mod, fresh_batch(43))
    assert tracing.compile_count() == n


def test_the_committed_flip_is_a_recompile_with_a_duration(monkeypatch,
                                                          capfd):
    mod = fresh_module(47)
    _trace_on(monkeypatch)
    step(mod, fresh_batch(47))
    assert "mx.module.recompile" not in profiler.phase_seconds()
    capfd.readouterr()
    step(mod, fresh_batch(47))
    (secs,) = profiler.phase_seconds()["mx.module.recompile"]
    assert secs > 0
    again = [r for r in tracing.compile_records()
             if r["phase"] == "mx.module.recompile"]
    assert {r["stage"] for r in again} == {"trace", "lower", "compile"}
    assert tracing.union_seconds(again) == pytest.approx(secs)
    (rec,) = [r for r in tracing.ring_records()
              if r["name"] == "mx.module.update.recompile"]
    assert rec["args"]["seconds"] == pytest.approx(secs, abs=1e-6)
    assert rec["args"]["step"] == 2
    assert rec["args"]["cache"] in ("hit", "miss", "none")
    assert "[seconds %s, step 2, cache " % rec["args"]["seconds"] \
        in capfd.readouterr().err
    step(mod, fresh_batch(47))
    assert len(profiler.phase_seconds()["mx.module.recompile"]) == 1


def test_compiles_are_spans_under_the_switch(monkeypatch):
    _trace_on(monkeypatch)
    n0 = tracing.compile_count()
    mod = fresh_module(53)
    step(mod, fresh_batch(53))
    ledger = tracing.compile_records()[n0:]
    spans = [r for r in tracing.ring_records()
             if r["name"].startswith("mx.compile.")]
    # the top-level records alone, each once, under the phase's span
    assert len(spans) == len(ledger)
    assert sorted(s["name"] for s in spans) \
        == sorted("mx.compile." + r["stage"] for r in ledger)
    (first,) = [r for r in tracing.ring_records()
                if r["name"] == "mx.module.first_update"]
    (lower,) = [s for s in spans if s["name"] == "mx.compile.lower"
                and s["args"]["program"] == "jit(mx_fused_step)"]
    assert lower["args"]["phase"] == "mx.module.first_update"
    # under the jitted call's span, in the first update's trace
    (call,) = [r for r in tracing.ring_records()
               if r["name"] == "mx.module.update.call"]
    assert lower["parent"] == call["span"]
    assert lower["trace"] == first["trace"]
    assert {s["args"]["cache"] for s in spans
            if s["name"] == "mx.compile.compile"} <= {"hit", "miss", "none"}


def test_the_listeners_are_registered_once():
    from jax._src import monitoring

    def count():
        return (monitoring.get_scalar_listeners().count(tracing._on_open),
                monitoring.get_event_time_span_listeners().count(
                    tracing._on_span),
                monitoring.get_event_listeners().count(tracing._on_cache))
    assert count() == (1, 1, 1)
    tracing.reconfigure()
    tracing.reconfigure()
    assert count() == (1, 1, 1)
