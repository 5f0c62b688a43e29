"""mxnet_tpu.analysis: the lint rules, the allow-annotation machinery,
the knob registry, and the runtime lock-order sanitizer.

Static half: every rule family has a positive fixture (must flag) and
a negative fixture (must pass) under tests/analysis_fixtures/, the
annotation fixtures prove suppression requires a reason, and the LIVE
package must lint clean under the full rule set — the in-process twin
of the `python -m mxnet_tpu.analysis --strict` CI gate, whose exit
codes are pinned by subprocess below.

Runtime half: OrderedLock/LockGraph catch a synthetic two-lock
inversion (strict raise + recorded-violation modes), stay quiet on
reentrant RLock use, survive threading.Condition integration, and —
the acceptance scenario — the window=8 kill-and-replay fault-injection
run under the full `threading` shim records an ACYCLIC lock-order
graph while the replay arithmetic still comes out exact.
"""
import subprocess
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

from mxnet_tpu.analysis import (
    LockGraph, LockOrderError, OrderedLock, lint_paths, run_lint, shim)
from mxnet_tpu.analysis import knobs as knobs_mod
from mxnet_tpu.analysis.lint import package_root
from mxnet_tpu.analysis.rules import RULE_NAMES

FIXTURES = Path(__file__).parent / "analysis_fixtures"


def rules_of(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# static rules: fixture coverage (one positive + one negative per family)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fixture,rule,min_hits", [
    ("host_sync_bad.py", "host-sync", 5),
    ("pickle_bad.py", "unsafe-pickle", 3),
    ("lock_order_bad.py", "lock-order", 2),
    ("lock_order_call_bad.py", "lock-order", 2),
    ("knobs_bad.py", "env-knob", 5),
    ("thread_bad.py", "bare-thread", 2),
    ("protocol_ops_bad.py", "protocol-op", 5),
    ("protocol_newops_bad.py", "protocol-op", 6),
    ("raw_send_bad.py", "raw-send", 4),
    ("blocking_lock_bad.py", "blocking-under-lock", 3),
    ("codec_bad.py", "codec-coverage", 3),
])
def test_positive_fixture_is_flagged(fixture, rule, min_hits):
    findings = run_lint([FIXTURES / fixture])
    hits = [f for f in findings if f.rule == rule]
    assert len(hits) >= min_hits, (fixture, findings)
    assert all(f.path.endswith(fixture) for f in hits)
    assert all(f.line > 0 for f in hits)


@pytest.mark.parametrize("fixture", [
    "host_sync_ok.py",
    "host_sync_not_hot.py",
    "pickle_ok.py",
    "lock_order_ok.py",
    "knobs_ok.py",
    "thread_ok.py",
    "protocol_ops_ok.py",
    "protocol_newops_ok.py",
    "raw_send_ok.py",
    "blocking_lock_ok.py",
    "codec_ok.py",
])
def test_negative_fixture_is_clean(fixture):
    findings = run_lint([FIXTURES / fixture])
    assert findings == [], [f.render() for f in findings]


def test_every_rule_family_has_fixture_coverage():
    """The parametrizations above must span the full rule catalog."""
    covered = {"host-sync", "unsafe-pickle", "lock-order", "env-knob",
               "bare-thread", "protocol-op", "raw-send",
               "blocking-under-lock", "codec-coverage"}
    assert covered == set(RULE_NAMES)


# ---------------------------------------------------------------------------
# allow-annotation machinery
# ---------------------------------------------------------------------------
def test_annotated_violations_are_suppressed_with_reasons():
    active, suppressed = lint_paths([FIXTURES / "annotated_bad.py"])
    assert active == [], [f.render() for f in active]
    # one suppression per rule family, each carrying its reason
    assert rules_of(suppressed) == set(RULE_NAMES)
    assert all(f.reason for f in suppressed)


def test_annotation_without_reason_suppresses_nothing():
    findings = run_lint([FIXTURES / "annotated_noreason.py"])
    assert rules_of(findings) == {"unsafe-pickle"}


# ---------------------------------------------------------------------------
# the live package passes the full rule set (the CI gate, in process)
# ---------------------------------------------------------------------------
def test_live_package_passes_strict():
    active, suppressed = lint_paths(None)
    assert active == [], "\n".join(f.render() for f in active)
    # every in-tree suppression must carry a reviewable reason
    assert all(f.reason for f in suppressed)


def test_knob_registry_is_complete_and_documented():
    reg = knobs_mod.registry()
    # spot-check knobs from every subsystem generation
    for name in ("MXNET_KVSTORE_WINDOW", "MXNET_DEVICE_METRICS",
                 "MXNET_FI_KILL_UNACKED", "MXNET_FUSED_DONATE"):
        assert name in reg, name
    table = knobs_mod.markdown_table()
    assert all(k in table for k in reg)
    missing, docs_path = knobs_mod.docs_missing(package_root())
    assert docs_path.exists(), "repo checkout should carry docs/"
    assert missing == [], missing


def test_docs_check_is_not_fooled_by_prefix_knobs():
    """RETRY_MAX must not count as documented just because the
    RETRY_MAX_MS row exists (backtick-delimited matching)."""
    text = "| `MXNET_KVSTORE_RETRY_MAX_MS` | int | `2000` | cap |"
    missing = knobs_mod.missing_in_text(text)
    assert "MXNET_KVSTORE_RETRY_MAX" in missing
    assert "MXNET_KVSTORE_RETRY_MAX_MS" not in missing


# ---------------------------------------------------------------------------
# entry-point exit codes (the acceptance contract of the CI gate)
# ---------------------------------------------------------------------------
def _run_analysis(*args):
    return subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.analysis", *args],
        capture_output=True, text=True, timeout=300,
        cwd=str(package_root().parent))


@pytest.mark.slow
def test_entry_point_strict_fails_on_fixture_violations():
    res = _run_analysis("--strict", str(FIXTURES))
    assert res.returncode != 0, res.stdout + res.stderr
    for rule in RULE_NAMES:
        assert "[%s]" % rule in res.stdout, (rule, res.stdout)


@pytest.mark.slow
def test_entry_point_strict_passes_on_live_tree():
    res = _run_analysis("--strict")
    assert res.returncode == 0, res.stdout + res.stderr
    assert "0 finding(s)" in res.stdout


@pytest.mark.slow
def test_entry_point_json_findings_schema():
    """--json: one Finding per line, dataclass fields verbatim —
    the machine interface CI consumes."""
    import dataclasses
    import json
    from mxnet_tpu.analysis.lint import Finding
    res = _run_analysis("--json", str(FIXTURES / "pickle_bad.py"))
    lines = [l for l in res.stdout.splitlines() if l.strip()]
    assert lines, res.stdout + res.stderr
    fields = {f.name for f in dataclasses.fields(Finding)}
    for line in lines:
        obj = json.loads(line)
        assert set(obj) == fields, obj
    assert any(json.loads(l)["rule"] == "unsafe-pickle" for l in lines)


@pytest.mark.slow
def test_entry_point_check_passes_in_sync_on_live_tree():
    res = _run_analysis("--check")
    assert res.returncode == 0, res.stdout + res.stderr
    assert "in sync" in res.stdout


def test_check_drift_detects_stale_and_missing_tables(tmp_path):
    """The drift helpers behind --check, against a SCRATCH docs layout
    (never the checked-in docs — a killed test must not corrupt the
    repo): verbatim copy -> in sync; edited copy -> STALE; file
    missing with docs/ present -> error; no docs checkout -> None."""
    from mxnet_tpu.analysis import protocol
    assert protocol.check_drift(package_root()) is None
    assert knobs_mod.check_drift(package_root()) is None
    pkg = tmp_path / "mxnet_tpu"
    docs = tmp_path / "docs"
    pkg.mkdir()
    # no docs checkout at all: nothing to check
    assert protocol.check_drift(pkg) is None
    assert knobs_mod.check_drift(pkg) is None
    docs.mkdir()
    # docs/ exists but the files are missing
    assert "PROTOCOL.md" in protocol.check_drift(pkg)
    assert "ROBUSTNESS.md" in knobs_mod.check_drift(pkg)
    # the protocol table is extracted from the tree NEXT TO the docs:
    # give the scratch package a real dispatch and check against IT
    (pkg / "srv.py").write_text(
        'class S:\n'
        '    def _handle(self, msg):\n'
        '        op = msg[0]\n'
        '        if op == "peek":'
        '  # protocol: replay(pure) reply(value)\n'
        '            return 1\n')
    scratch_table = protocol.markdown_table(protocol.extract_package(pkg))
    assert "`peek`" in scratch_table
    (docs / "PROTOCOL.md").write_text("# x\n\n%s\n" % scratch_table)
    (docs / "ROBUSTNESS.md").write_text(
        "# x\n\n%s\n" % knobs_mod.markdown_table())
    assert protocol.check_drift(pkg) is None
    assert knobs_mod.check_drift(pkg) is None
    # an edited copy (or a tree whose ops moved on) is stale
    (docs / "PROTOCOL.md").write_text(
        "# x\n\n%s\n" % scratch_table.replace("pure", "PURE", 1))
    assert "STALE" in protocol.check_drift(pkg)


def test_check_exit_code_2_on_drift(monkeypatch):
    """--check maps any drift problem to exit 2 (in-process, with the
    helper stubbed — the real-file stale path is covered above)."""
    from mxnet_tpu.analysis import __main__ as entry
    monkeypatch.setattr(entry.protocol, "check_drift",
                        lambda root: "docs/PROTOCOL.md ... STALE")
    assert entry.main(["--check"]) == 2


# ---------------------------------------------------------------------------
# the wire-protocol registry (mxnet_tpu.analysis.protocol)
# ---------------------------------------------------------------------------
def test_protocol_table_covers_the_wire_surface():
    """The extracted op table names every core dispatch op, the mesh
    fan-in ops and the serving extensions — with a declared replay
    guard on each (the live package lints strict, so none may be
    undeclared)."""
    from mxnet_tpu.analysis import protocol
    table = protocol.extract_package()
    names = table.op_names()
    for op in ("push", "pull", "barrier", "stats", "handoff",
               "roster_join", "roster_beat", "mesh_push",
               "mesh_collect", "predict", "serving_refresh"):
        assert op in names, op
    for op in table.ops:
        assert op.replay in protocol.REPLAY_GUARDS, \
            (op.name, op.path, op.line, op.replay)
    # the reserved tuple mirrors the core dispatch (no shadowable op)
    core = {o.name for o in table.ops
            if o.kind == "core" and o.owner == "KVStoreServer"}
    assert core <= set(table.reserved)
    # client sites only name dispatched ops
    known = names | {protocol.ENVELOPE_OP}
    for site in table.clients:
        assert site.op in known, (site.op, site.path, site.line)
    md = protocol.markdown_table(table)
    assert md.startswith(protocol.DOCS_BEGIN)
    assert "| `push` | core | dedup-window |" in md


# ---------------------------------------------------------------------------
# runtime lock-order sanitizer
# ---------------------------------------------------------------------------
def test_synthetic_inversion_strict_raises_before_deadlock():
    g = LockGraph(strict=True)
    a = OrderedLock("A", graph=g)
    b = OrderedLock("B", graph=g)
    with a:
        with b:
            pass
    # same thread, opposite order: the check fires BEFORE blocking
    with b:
        with pytest.raises(LockOrderError):
            a.acquire()
    assert g.violations()


def test_synthetic_inversion_two_threads_recorded():
    g = LockGraph(strict=False)
    a = OrderedLock("A", graph=g)
    b = OrderedLock("B", graph=g)
    first_done = threading.Event()

    def t1():
        with a:
            with b:
                pass
        first_done.set()

    def t2():
        first_done.wait(5)
        with b:
            with a:
                pass

    th1, th2 = threading.Thread(target=t1), threading.Thread(target=t2)
    th1.start(); th2.start(); th1.join(5); th2.join(5)
    assert ("A", "B") in g.edges() and ("B", "A") in g.edges()
    assert g.violations()
    with pytest.raises(LockOrderError):
        g.assert_acyclic()


def test_reentrant_rlock_is_not_an_inversion():
    g = LockGraph(strict=True)
    r = OrderedLock("R", graph=g, rlock=True)
    with r:
        with r:
            pass
    assert g.violations() == []
    g.assert_acyclic()


def test_consistent_order_stays_clean():
    g = LockGraph(strict=True)
    a = OrderedLock("A", graph=g)
    b = OrderedLock("B", graph=g)
    for _ in range(3):
        with a:
            with b:
                pass
    assert g.edges().keys() == {("A", "B")}
    g.assert_acyclic()


def test_shim_instruments_condition_and_event():
    """Locks built under the shim — including the RLock inside a bare
    threading.Condition() and the Lock inside threading.Event() — must
    record without breaking wait/notify semantics."""
    with shim() as g:
        cond = threading.Condition()
        ready = []

        def waiter():
            with cond:
                while not ready:
                    cond.wait(1.0)

        t = threading.Thread(target=waiter)
        t.start()
        with cond:
            ready.append(1)
            cond.notify_all()
        t.join(5)
        assert not t.is_alive()
        ev = threading.Event()
        ev.set()
        assert ev.wait(1.0)
    g.assert_acyclic()


def test_shim_window8_kill_and_replay_graph_is_acyclic(monkeypatch):
    """THE runtime acceptance scenario: the window=8 kill-and-replay
    fault-injection run (pipelined pushes, mid-window connection kill,
    full-window replay, server dedup) under the full threading shim.
    Every lock in KVStoreServer + _ServerConn (+ queue internals) is
    instrumented; the recorded global lock-order graph must be
    non-trivial and ACYCLIC, and the replay arithmetic must still come
    out exact — instrumentation cannot change transport semantics."""
    import mxnet_tpu as mx
    from mxnet_tpu import faultinject
    from mxnet_tpu.kvstore_server import KVStoreServer

    monkeypatch.setenv("MXNET_KVSTORE_RETRY_MAX", "8")
    monkeypatch.setenv("MXNET_KVSTORE_RETRY_INITIAL_MS", "10")
    monkeypatch.setenv("MXNET_KVSTORE_RETRY_MAX_MS", "50")
    monkeypatch.setenv("MXNET_KVSTORE_HEARTBEAT_INTERVAL", "0")
    monkeypatch.setenv("MXNET_KVSTORE_WINDOW", "8")
    faultinject.reset()
    shape = (2, 3)
    try:
        with shim() as g:
            srv = KVStoreServer(server_id=0, num_workers=1)
            srv.start_background()
            monkeypatch.setenv("MXT_SERVER_URIS",
                               "127.0.0.1:%d" % srv.port)
            monkeypatch.setenv("DMLC_NUM_WORKER", "1")
            monkeypatch.setenv("DMLC_WORKER_ID", "0")
            try:
                kv = mx.kv.create('dist_async')
                kv.init('w', mx.nd.ones(shape))
                kv.set_optimizer(mx.optimizer.SGD(
                    learning_rate=0.5, momentum=0.0, wd=0.0,
                    rescale_grad=1.0))
                out = mx.nd.zeros(shape)
                with faultinject.delay_acks(0.03):
                    with faultinject.kill_when_unacked(4):
                        for i in range(6):
                            kv.push('w', mx.nd.ones(shape) * (i + 1))
                        kv.pull('w', out=out)
                np.testing.assert_allclose(
                    out.asnumpy(), 1.0 - 0.5 * 21, rtol=1e-6)
                assert faultinject.stats()["kills_fired"] == 1
                kv.close(stop_servers=True)
            finally:
                srv.stop()
        # the transport's locking is FLAT on these paths (no lock is
        # taken while holding another instrumented one) — an empty edge
        # set is the correct strong result; acquire_count proves the
        # instrumentation was live, not silently bypassed
        assert g.acquire_count() > 0, "shim instrumented nothing"
        assert g.violations() == []
        g.assert_acyclic()
    finally:
        faultinject.reset()


def test_cross_thread_release_does_not_fabricate_edges():
    """A plain Lock released by a different thread than the acquirer
    (the handoff/signal pattern) must clear the acquirer's held entry —
    otherwise every later acquisition on that thread grows phantom
    edges and a correct program flags a false cycle."""
    g = LockGraph(strict=False)
    sig = OrderedLock("SIG", graph=g)
    x = OrderedLock("X", graph=g)
    sig.acquire()                      # main thread acquires...

    def releaser():
        sig.release()                  # ...worker releases (legal)

    t = threading.Thread(target=releaser)
    t.start()
    t.join(5)
    with x:                            # flat use: must record NO edge
        pass
    assert ("SIG", "X") not in g.edges(), g.edges()
    assert g.violations() == []
    g.assert_acyclic()


def test_shim_restores_threading_factories():
    orig_lock, orig_rlock = threading.Lock, threading.RLock
    with shim():
        assert threading.Lock is not orig_lock
    assert threading.Lock is orig_lock
    assert threading.RLock is orig_rlock
