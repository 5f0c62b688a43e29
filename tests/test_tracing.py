"""mxnet_tpu.tracing — cluster-wide span tracing (docs/OBSERVABILITY.md).

Tier-1 coverage of the ISSUE 12 surface, in-process:

* span begin/end nesting, thread-local parenting, the bounded ring;
* MXNET_TRACE=0 is a true no-op: null contexts, no records, and the
  kvstore envelope stays the classic 4-tuple — ZERO added wire bytes,
  pinned against an exact frame-size computation via
  ``profiler.channel_bytes``;
* worker→server span propagation over a real socket: the server-side
  handling span is a CHILD of the worker-side call (same trace id,
  parent = the caller's span id), with the client send stamp along for
  the merge tool's clock-offset estimate;
* a connection kill + replay annotates the ORIGINAL trace (the
  ``srv.dedup_hit`` instant lands in it) instead of starting a new one;
* the universal ``("stats",)`` op and ``distributed.cluster_stats()``;
* the elastic stats bank (beat piggyback → ledger, outlives eviction);
* the span journal: fsync'd append, ``<role>-<rank>`` naming, and a
  torn trailing line tolerated by the reader AND by
  ``tools/trace_merge.py --spans``, whose merged chrome trace must
  carry per-process tracks, cross-process flow arrows and a clock
  offset recovered from the send/recv pairs;
* the serving replica's deferred predict path under tracing (detached
  ``srv.predict`` slot spans + the batcher's ``serving.batch`` span).

The 2-process launcher acceptance (spans from every role in one merged
file, stats sweep across real process boundaries) runs in
ci/run_ci.sh via tests/dist/dist_tracing_smoke.py.
"""
import json
import os
import pickle
import subprocess
import sys
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import faultinject, profiler, tracing
from mxnet_tpu.base import MXNetError
from mxnet_tpu.kvstore import _ServerConn
from mxnet_tpu.kvstore_server import KVStoreServer, _pack

SHAPE = (3,)

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "tools"))
import trace_merge  # noqa: E402  (tools/trace_merge.py, span mode)


@pytest.fixture(autouse=True)
def _trace_reset(monkeypatch):
    """Every test starts traced-off with a clean ring and fast retries;
    teardown re-reads the (restored) env so no test leaks a trace
    config into the suite."""
    monkeypatch.setenv("MXNET_KVSTORE_RETRY_MAX", "8")
    monkeypatch.setenv("MXNET_KVSTORE_RETRY_INITIAL_MS", "10")
    monkeypatch.setenv("MXNET_KVSTORE_RETRY_MAX_MS", "50")
    monkeypatch.setenv("MXNET_KVSTORE_HEARTBEAT_INTERVAL", "0")
    monkeypatch.delenv("MXNET_TRACE", raising=False)
    monkeypatch.delenv("MXNET_TRACE_DIR", raising=False)
    tracing.reconfigure()
    tracing.reset()
    try:
        yield
    finally:
        faultinject.reset()
        with monkeypatch.context() as m:
            m.delenv("MXNET_TRACE", raising=False)
            m.delenv("MXNET_TRACE_DIR", raising=False)
            tracing.reconfigure()
        tracing.reset()


def _trace_on(monkeypatch, tmp_path=None, **env):
    monkeypatch.setenv("MXNET_TRACE", "1")
    if tmp_path is not None:
        monkeypatch.setenv("MXNET_TRACE_DIR", str(tmp_path))
    for k, v in env.items():
        monkeypatch.setenv(k, str(v))
    tracing.reconfigure()


def _serve(monkeypatch, n=1):
    srvs = [KVStoreServer(server_id=i, num_workers=1) for i in range(n)]
    for s in srvs:
        s.start_background()
    monkeypatch.setenv("MXT_SERVER_URIS",
                       ",".join(f"127.0.0.1:{s.port}" for s in srvs))
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    monkeypatch.setenv("DMLC_WORKER_ID", "0")
    return srvs


def _by_name(name, recs=None):
    return [r for r in (tracing.ring_records() if recs is None else recs)
            if r["name"] == name]


# -- span primitives ---------------------------------------------------------
def test_span_nesting_and_ring(monkeypatch):
    _trace_on(monkeypatch)
    with tracing.span("outer") as outer:
        with tracing.span("inner") as inner:
            assert tracing.current_ctx() == (inner.trace, inner.span)
            tracing.instant("mark")
        assert tracing.current_ctx() == (outer.trace, outer.span)
    recs = tracing.ring_records()
    names = [r["name"] for r in recs]
    assert names == ["mark", "inner", "outer"]   # end order
    mark, inner_r, outer_r = recs
    assert inner_r["trace"] == outer_r["trace"] == mark["trace"]
    assert inner_r["parent"] == outer_r["span"]
    assert mark["parent"] == inner_r["span"]
    assert outer_r["parent"] is None
    assert mark["dur"] == 0.0
    assert outer_r["dur"] >= inner_r["dur"] >= 0
    st = tracing.stats()
    assert st["enabled"] and st["recorded"] == 3 and st["ring"] == 3


def test_spans_parent_per_thread(monkeypatch):
    """The current-span stack is thread-local: a span opened on another
    thread must not become this thread's parent."""
    _trace_on(monkeypatch)
    seen = {}

    def other():
        with tracing.span("other.root") as sp:
            seen["ctx"] = tracing.current_ctx()
            assert sp is not None

    with tracing.span("main.root") as main_sp:
        t = threading.Thread(target=other)
        t.start()
        t.join()
        assert tracing.current_ctx() == (main_sp.trace, main_sp.span)
    other_r = _by_name("other.root")[0]
    main_r = _by_name("main.root")[0]
    assert other_r["parent"] is None
    assert other_r["trace"] != main_r["trace"]


def test_ring_bounded(monkeypatch):
    _trace_on(monkeypatch, MXNET_TRACE_RING="16")
    for i in range(40):
        tracing.instant("e%d" % i)
    st = tracing.stats()
    assert st["ring"] == 16 and st["recorded"] == 40
    assert tracing.ring_records()[-1]["name"] == "e39"


def test_disabled_is_noop():
    assert not tracing.enabled()
    with tracing.span("nope") as sp:
        assert sp is None
        assert tracing.current_ctx() is None
    tracing.instant("nope2")
    assert tracing.span_begin("x") is None
    tracing.span_end(None)   # must not raise
    assert tracing.ring_records() == []
    assert tracing.stats()["recorded"] == 0


# -- the wire: envelope bytes, propagation, replay ---------------------------
def _frame_nbytes(obj):
    """Exact wire size of one framed message — the arithmetic of
    kvstore_server._send_msg (8-byte total + 4-byte skel length +
    skeleton pickle + raw buffers), recomputed independently so the
    zero-added-bytes pin cannot drift with the implementation."""
    bufs = []
    skel = pickle.dumps(_pack(obj, bufs),
                        protocol=pickle.HIGHEST_PROTOCOL)
    return 8 + 4 + len(skel) + sum(a.nbytes for a in bufs)


def _captured_sends(monkeypatch):
    """Spy on EVERY framed send in this process (client envelopes AND
    the in-process server's replies — both feed the one 'sent' byte
    counter).  Returns (all_objects, req_envelopes)."""
    from mxnet_tpu import kvstore_server as srvmod
    real = srvmod._send_msg
    every, reqs = [], []

    def spy(sock, obj, fi_role=None, byte_kind="sent"):
        every.append(obj)
        if isinstance(obj, tuple) and obj and obj[0] == "req":
            reqs.append(obj)
        return real(sock, obj, fi_role=fi_role, byte_kind=byte_kind)

    monkeypatch.setattr(srvmod, "_send_msg", spy)
    return every, reqs


def test_trace_off_adds_zero_envelope_bytes(monkeypatch):
    """MXNET_TRACE=0: every request envelope is the classic 4-tuple and
    the measured sent bytes equal the independently-computed frame
    sizes EXACTLY — the feature is provably free when off.

    Pinned to the pickle codec: _frame_nbytes recomputes the LEGACY
    frame arithmetic, and hot envelopes otherwise negotiate the binary
    frame (tests/test_wirecodec.py owns that layout's arithmetic)."""
    monkeypatch.setenv("MXNET_KVSTORE_CODEC", "pickle")
    srv = _serve(monkeypatch)[0]
    every, reqs = _captured_sends(monkeypatch)
    try:
        conn = _ServerConn(f"127.0.0.1:{srv.port}")
        sent0 = profiler.channel_bytes().get("sent", 0)
        conn.submit(("init", "w", np.ones(SHAPE, np.float32)), wait=True)
        conn.submit(("push", "w", np.ones(SHAPE, np.float32)), wait=True)
        conn.submit(("pull", "w"), wait=True)
        sent = profiler.channel_bytes().get("sent", 0) - sent0
        assert len(reqs) == 3
        assert all(len(env) == 4 for env in reqs)
        assert sent == sum(_frame_nbytes(obj) for obj in every)
        conn.close()
    finally:
        srv.stop()


def test_trace_on_stamps_envelope_only_under_a_span(monkeypatch):
    """Tracing on: an op issued under a span carries the 5th trace
    element (trace id, parent span id, send stamp); an op with no
    active span stays a 4-tuple — no context, no bytes."""
    _trace_on(monkeypatch)
    srv = _serve(monkeypatch)[0]
    _every, captured = _captured_sends(monkeypatch)
    try:
        conn = _ServerConn(f"127.0.0.1:{srv.port}")
        conn.submit(("init", "w", np.ones(SHAPE, np.float32)), wait=True)
        with tracing.span("client.op") as sp:
            conn.submit(("pull", "w"), wait=True)
        assert len(captured) == 2
        assert len(captured[0]) == 4          # no active span
        assert len(captured[1]) == 5
        trace_id, span_id, send_us = captured[1][4]
        assert (trace_id, span_id) == (sp.trace, sp.span)
        assert send_us == pytest.approx(tracing.now_us(), abs=60e6)
        conn.close()
    finally:
        srv.stop()


def test_worker_server_parent_child_linkage(monkeypatch):
    """The tentpole contract, in-process over a real socket: kv ops run
    under auto-created client spans, and the server-side handling spans
    are their CHILDREN — same trace, parent = the worker-side span —
    with the updater apply nested one level deeper."""
    _trace_on(monkeypatch)
    srv = _serve(monkeypatch)[0]
    try:
        kv = mx.kv.create("dist_async")
        kv.set_optimizer(mx.optimizer.SGD(learning_rate=1.0))
        kv.init("w", mx.nd.zeros(SHAPE))
        kv.push("w", mx.nd.ones(SHAPE))
        out = mx.nd.zeros(SHAPE)
        kv.pull("w", out=out)
        np.testing.assert_allclose(out.asnumpy(), -1.0)

        recs = tracing.ring_records()
        for client_name, server_name in [("kv.init", "srv.init"),
                                         ("kv.push", "srv.push"),
                                         ("kv.pull", "srv.pull")]:
            client = _by_name(client_name, recs)[0]
            server = [r for r in _by_name(server_name, recs)
                      if r["trace"] == client["trace"]]
            assert server, (client_name, server_name)
            assert server[0]["parent"] == client["span"]
            assert server[0]["args"]["client_send_us"] <= server[0]["ts"]
        push_srv = [r for r in _by_name("srv.push", recs)][0]
        apply_r = _by_name("srv.updater_apply", recs)
        assert apply_r and apply_r[0]["parent"] == push_srv["span"]
        assert apply_r[0]["trace"] == push_srv["trace"]
        kv.close(stop_servers=True)
    finally:
        srv.stop()


def test_replay_annotates_original_trace(monkeypatch):
    """A connection killed after the push was sent replays the SAME
    envelope — trace field included: the server's dedup hit lands as an
    instant in the ORIGINAL trace instead of opening a new one."""
    monkeypatch.setenv("MXNET_KVSTORE_WINDOW", "1")
    _trace_on(monkeypatch)
    srv = _serve(monkeypatch)[0]
    try:
        kv = mx.kv.create("dist_async")
        kv.init("w", mx.nd.zeros(SHAPE))
        with faultinject.kill_connection_after(2, point="after_send"):
            kv.push("w", mx.nd.ones(SHAPE) * 2)   # applied, ack lost
            out = mx.nd.zeros(SHAPE)
            kv.pull("w", out=out)
        np.testing.assert_allclose(out.asnumpy(), 2.0)
        assert srv.dedup_count >= 1
        recs = tracing.ring_records()
        client_traces = {r["trace"]: r["name"] for r in recs
                         if r["name"] in ("kv.push", "kv.pull")}
        hits = [r for r in _by_name("srv.dedup_hit", recs)
                if r["trace"] in client_traces]
        assert hits, "dedup hit did not annotate the original trace"
        # the replayed handling opened a SECOND server span in the same
        # trace as the worker-side call (original + replay), instead of
        # rooting a fresh trace
        t = hits[0]["trace"]
        srv_spans = [r for r in recs if r["trace"] == t
                     and r["name"].startswith("srv.")
                     and r["name"] != "srv.dedup_hit"]
        assert len(srv_spans) >= 2
        kv.close(stop_servers=True)
    finally:
        srv.stop()


# -- the universal stats op --------------------------------------------------
def test_snapshot_shape_and_reset():
    snap = profiler.snapshot()
    for key in ("channel", "channel_bytes", "wire", "dispatch",
                "host_syncs", "latency", "trace", "role", "rank", "pid"):
        assert key in snap, key
    compact = profiler.snapshot(compact=True)
    assert set(compact) == {"channel", "channel_bytes", "wire", "health"}
    # the piggybacked health block is the compact form: status + counts
    assert compact["health"]["status"] in ("OK", "DEGRADED", "CRITICAL")
    json.dumps(snap, default=str)   # wire/CLI-serializable
    profiler.record_dispatch("t.reset")
    profiler.reset_all()
    assert profiler.snapshot()["dispatch"] == {}


def test_stats_op_and_cluster_stats(monkeypatch):
    srvs = _serve(monkeypatch, n=2)
    try:
        kv = mx.kv.create("dist_async")
        kv.init("w", mx.nd.ones(SHAPE))
        st = kv.server_stats(0)
        assert st["server"]["server_id"] == 0
        assert st["server"]["uri"].endswith(str(srvs[0].port))
        assert st["channel_bytes"].get("recv", 0) > 0
        with pytest.raises(MXNetError, match="out of range"):
            kv.server_stats(7)
        cs = mx.distributed.cluster_stats()
        assert set(cs) == {"workers", "servers", "stats_bank"}
        assert "0" in cs["workers"]
        assert cs["workers"]["0"]["channel_bytes"].get("sent", 0) > 0
        uris = {f"127.0.0.1:{s.port}" for s in srvs}
        assert set(cs["servers"]) == uris
        for uri in uris:
            assert cs["servers"][uri]["server"]["uri"] == uri
        compact = mx.distributed.cluster_stats(compact=True)
        for uri in uris:
            assert set(compact["servers"][uri]) <= \
                {"channel", "channel_bytes", "wire", "server", "health"}
        kv.close(stop_servers=True)
    finally:
        for s in srvs:
            s.stop()


def test_local_store_server_stats():
    kv = mx.kv.create("local")
    st = kv.server_stats(0)
    assert "channel" in st and "dispatch" in st
    with pytest.raises(MXNetError, match="no server rank"):
        kv.server_stats(1)


def test_register_op_reserves_stats():
    srv = KVStoreServer(server_id=0, num_workers=1)
    try:
        with pytest.raises(ValueError, match="core kvstore op"):
            srv.register_op("stats", lambda msg, rank: None)
    finally:
        srv.stop()


def test_ledger_stats_bank_outlives_eviction():
    """The beat-piggybacked counter bank on the coordinator ledger:
    newest seq wins, and — like the state snapshot bank — eviction does
    NOT forget a member's last-known counters."""
    from mxnet_tpu.membership import MembershipCoordinator
    m = MembershipCoordinator(["a:1", "b:2"], [0])
    m.note_server_beat("b:2", seq=3, snapshot=None,
                       stats={"channel": {"x": 1}})
    m.note_server_beat("b:2", seq=2, snapshot=None,
                       stats={"channel": {"x": 99}})   # stale: ignored
    assert m.stats_of("b:2") == {"channel": {"x": 1}}
    m.report_dead_server("b:2")
    assert m.stats_of("b:2") == {"channel": {"x": 1}}
    assert m.stats_bank()["b:2"][0] == 3
    assert m.stats_of("a:1") is None


def test_profiler_cli_dump_one_json_line():
    """``python -m mxnet_tpu.profiler --dump`` prints the snapshot as
    exactly one JSON line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("DMLC_ROLE", None)
    out = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.profiler", "--dump"],
        capture_output=True, text=True, timeout=240, env=env,
        cwd=os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         ".."))
    assert out.returncode == 0, out.stderr
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    assert len(lines) == 1, out.stdout
    snap = json.loads(lines[0])
    assert "channel" in snap and "trace" in snap


def test_profiler_cli_reset_inprocess():
    profiler.record_dispatch("t.cli")
    assert profiler._main(["--reset"]) == 0
    assert profiler.dispatch_counts() == {}


# -- span journal + merge ----------------------------------------------------
def test_trace_file_flush_and_torn_line(monkeypatch, tmp_path):
    _trace_on(monkeypatch, tmp_path=tmp_path, MXNET_TRACE_FLUSH_N="1")
    with tracing.span("file.op"):
        pass
    tracing.flush()
    path = tracing.trace_file_path()
    assert os.path.basename(path) == "local-0.trace.jsonl"
    recs = tracing.read_trace_file(path)
    assert [r["name"] for r in recs] == ["file.op"]
    # a SIGKILL mid-append leaves a torn tail: the reader skips it
    with open(path, "a") as f:
        f.write('{"name": "torn", "half":')
    assert [r["name"] for r in tracing.read_trace_file(path)] \
        == ["file.op"]


def _mk_span(name, trace, span, parent, ts, dur, pid, tid=7, role="w",
             rank="0", args=None):
    rec = {"name": name, "cat": "span", "trace": trace, "span": span,
           "parent": parent, "ts": ts, "dur": dur, "pid": pid,
           "tid": tid, "role": role, "rank": rank}
    if args:
        rec["args"] = args
    return rec


def test_trace_merge_spans_flows_and_offset(tmp_path):
    """Two synthesized journals with a known 5000 µs clock skew: the
    merge must produce per-process tracks, ONE cross-process flow
    (s/f pair keyed by the child span), recover the skew from the
    client_send_us pair, and tolerate a torn trailing line."""
    skew = 5000.0
    wfile = tmp_path / "worker-0.trace.jsonl"
    sfile = tmp_path / "server-0.trace.jsonl"
    parent = _mk_span("kv.pull", "t1", "aaaa", None,
                      ts=1000.0, dur=400.0, pid=100)
    child = _mk_span("srv.pull", "t1", "bbbb", "aaaa",
                     ts=1100.0 + skew, dur=200.0, pid=200,
                     role="s", args={"client_send_us": 1010.0})
    local_child = _mk_span("kv.cache", "t1", "cccc", "aaaa",
                           ts=1420.0, dur=10.0, pid=100)
    wfile.write_text(json.dumps(parent) + "\n"
                     + json.dumps(local_child) + "\n")
    sfile.write_text(json.dumps(child) + "\n" + '{"torn": ')
    merged = trace_merge.merge_spans([str(wfile), str(sfile)])
    md = merged["metadata"]
    assert md["spans"] == 3 and md["cross_process_flows"] == 1
    assert md["files"] == ["worker-0", "server-0"]
    # skew recovered: min(child.ts - send_us) = 1100+5000-1010
    assert md["clock_offsets_us"]["server-0"] == pytest.approx(
        skew + 90.0)
    evs = merged["traceEvents"]
    x = [e for e in evs if e.get("ph") == "X"]
    assert {e["pid"] for e in x} == {1, 2}
    srv_x = [e for e in x if e["name"] == "srv.pull"][0]
    # the child lands back inside the parent's window after adjustment
    assert parent["ts"] <= srv_x["ts"] <= parent["ts"] + parent["dur"]
    flows = [e for e in evs if e.get("cat") == "flow"]
    assert {e["ph"] for e in flows} == {"s", "f"}
    s_ev = [e for e in flows if e["ph"] == "s"][0]
    f_ev = [e for e in flows if e["ph"] == "f"][0]
    assert s_ev["id"] == f_ev["id"] == "t1:bbbb"
    assert s_ev["pid"] == 1 and f_ev["pid"] == 2
    # in-process parent/child (aaaa -> cccc) must NOT grow a flow
    assert len(flows) == 2
    names = {e["args"]["name"] for e in evs if e.get("ph") == "M"
             and e["name"] == "process_name"}
    assert names == {"worker-0", "server-0"}


def test_trace_merge_cli_spans_dir(tmp_path):
    d = tmp_path / "traces"
    d.mkdir()
    (d / "worker-0.trace.jsonl").write_text(json.dumps(
        _mk_span("a", "t", "s1", None, 0.0, 1.0, 1)) + "\n")
    out = tmp_path / "merged.json"
    root = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    res = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "trace_merge.py"),
         "--spans", str(d), "-o", str(out)],
        capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr
    merged = json.loads(out.read_text())
    assert merged["metadata"]["spans"] == 1


# -- end-to-end: pull handle + serving spans ---------------------------------
def test_pull_async_wire_spans(monkeypatch):
    """The fused driver's wire becomes visible: handle.wait() records a
    kv.wire_wait span (the exposed residue) and a kv.wire_round span
    anchored at ENQUEUE time — wait ⊆ round on the timeline."""
    _trace_on(monkeypatch)
    srv = _serve(monkeypatch)[0]
    try:
        kv = mx.kv.create("dist_async")
        kv.init("w", mx.nd.ones(SHAPE))
        with tracing.span("driver.chunk"):
            h = kv.pull_async("w", SHAPE)
            vals = h.wait()
        np.testing.assert_allclose(vals["w"], 1.0)
        recs = tracing.ring_records()
        wait_r = _by_name("kv.wire_wait", recs)[0]
        round_r = _by_name("kv.wire_round", recs)[0]
        chunk_r = _by_name("driver.chunk", recs)[0]
        assert wait_r["trace"] == round_r["trace"] == chunk_r["trace"]
        assert round_r["parent"] == chunk_r["span"]
        assert round_r["ts"] <= wait_r["ts"]
        assert round_r["ts"] + round_r["dur"] >= wait_r["ts"]
        kv.close(stop_servers=True)
    finally:
        srv.stop()


def test_serving_predict_spans(monkeypatch):
    """The deferred predict path under tracing: each request gets a
    detached srv.predict span covering its whole replica stay (child of
    the client-side call), and the batcher records a serving.batch
    device span with the queue-wait split out."""
    from mxnet_tpu.serving import ServingClient, ServingReplica
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from test_serving import FEAT, _params, _softmax_symbol
    _trace_on(monkeypatch)
    rep = ServingReplica(_softmax_symbol(), {"data": (FEAT,)}, _params(),
                         buckets=[1, 2], warmup=False)
    rep.start_background()
    cli = ServingClient(f"127.0.0.1:{rep.port}", window=4)
    try:
        with tracing.span("client.predict") as sp:
            out = cli.predict(np.zeros((1, FEAT), np.float32))
        assert out[0].shape[0] == 1
        recs = tracing.ring_records()
        pred = [r for r in _by_name("srv.predict", recs)
                if r["trace"] == sp.trace]
        assert pred and pred[0]["parent"] == sp.span
        assert "queue_wait_ms" in pred[0]["args"]
        batch = _by_name("serving.batch", recs)
        assert batch and batch[0]["args"]["rows"] >= 1
    finally:
        cli.close()
        rep.stop()
