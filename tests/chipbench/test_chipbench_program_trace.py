"""chipbench/program_trace.py on planes built by hand: a device event's
phase and node from its scope path, a host span's self time with nested
and sibling children, the idle gap named by the innermost ``mx.*`` span,
nothing (never 0) where no event is scoped, a stale file refused."""
import os
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import program_trace as pt  # noqa: E402

MS = 1e6
STEP = "jit(mx_fused_step)/"


@pytest.mark.parametrize("path, phase, node", [
    (STEP + "jvp(stage1_unit1_conv1)/conv_general_dilated",
     "forward", "stage1_unit1_conv1"),
    (STEP + "transpose(jvp(stage1_unit1_bn1))/reduce_sum",
     "backward", "stage1_unit1_bn1"),
    (STEP + "transpose(jvp(h3_attn))/jit(_flash)/pallas_call",
     "backward", "h3_attn"),
    (STEP + "jvp(h3_attn)/jit(_flash)/pallas_call", "forward", "h3_attn"),
    (STEP + "optimizer/mul", "optimizer", None),
    # the update's epilogue counts with the optimizer
    (STEP + "param_constraint/sharding_constraint", "optimizer", None),
    # a weight-gradient fusion whose root is the update is the optimizer's
    (STEP + "optimizer/transpose(jvp(fc1))/add", "optimizer", None),
    # the scan driver puts control flow in front of the node
    ("jit(mx_run_steps)/while/body/transpose(jvp(fc1))/dot_general",
     "backward", "fc1"),
    # recomputation inside the backward is backward time
    (STEP + "transpose(jvp(checkpoint))/rematted_computation/conv1/add",
     "backward", "conv1"),
    # an inference program has plain node scopes
    ("jit(fwd)/conv1/conv_general_dilated", "forward", "conv1"),
    # the parent of PR 25: jax's own transforms with no scope inside
    ("jit(step)/jvp()/dot_general", "unscoped", None),
    ("jit(step)/transpose(jvp())/dot_general", "backward", None),
    ("jit(step)/jvp(jit(_var))/reduce_sum", "unscoped", None),
    ("jit(step)/transpose(jvp(jit(_var)))/mul", "backward", None),
    (STEP + "jvp(bn1)/jit(_var)/reduce_sum", "forward", "bn1"),
    # a primitive outside every scope, and no path at all
    (STEP + "broadcast_in_dim", "unscoped", None),
    (None, "unscoped", None),
    ("", "unscoped", None),
])
def test_phase_and_node_of_a_scope_path(path, phase, node):
    assert pt.classify(path) == (phase, node)


def _pb(number, value):
    """One protobuf field: a varint for an int, else length-delimited."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(number << 3 | 2) + varint(len(value)) + value


def test_scope_paths_are_read_from_the_event_metadata(tmp_path):
    def entry(key, message):            # one entry of a proto map
        return _pb(1, key) + _pb(2, message)

    def stat(metadata_id, text=None, ref=None):
        return _pb(1, metadata_id) + (_pb(5, text) if ref is None
                                      else _pb(7, ref))

    fusion = "%fusion.1 = f32[2] fusion(%p), kind=kLoop"
    custom = "%flash_fwd.24 = (bf16[64,1024,64]) custom-call(%q)"
    plane = (
        _pb(2, "/device:TPU:0")
        # lines are skipped by their length, never parsed
        + _pb(3, b"\xff\xff\xff not a message")
        + _pb(5, entry(1, _pb(1, 1) + _pb(2, "hlo_category")))
        + _pb(5, entry(300, _pb(1, 300) + _pb(2, "tf_op")))
        + _pb(5, entry(9, _pb(1, 9) + _pb(2, STEP + "optimizer/sub:")))
        + _pb(4, entry(7, _pb(1, 7) + _pb(2, fusion)
                       + _pb(5, stat(1, "loop fusion"))
                       + _pb(5, stat(300, STEP + "jvp(fc1)/add:"))))
        + _pb(4, entry(8, _pb(1, 8) + _pb(2, "%copy.2 = f32[2] copy(%p)")
                       + _pb(5, stat(1, "data formatting"))))
        # a string kept once, in the stat metadata, and referred to
        + _pb(4, entry(11, _pb(1, 11) + _pb(2, "%fusion.9 = f32[2]")
                       + _pb(5, stat(300, ref=9))))
        + _pb(4, entry(12, _pb(1, 12) + _pb(2, custom) + _pb(5, stat(
            300, STEP + "jvp(l1_flash)/jit(_flash_fwd)/pallas_call:")))))
    f = tmp_path / "t.xplane.pb"
    f.write_bytes(_pb(1, plane) + _pb(1, _pb(2, "/host:CPU")))
    assert pt.metadata_scopes(str(f)) == {
        "/device:TPU:0": {
            fusion: STEP + "jvp(fc1)/add",
            "%fusion.9 = f32[2]": STEP + "optimizer/sub",
            custom: STEP + "jvp(l1_flash)/jit(_flash_fwd)/pallas_call"},
        "/host:CPU": {}}
    scopes = pt.metadata_scopes(str(f))["/device:TPU:0"]
    assert pt.classify(pt._scope_path(custom, scopes)) \
        == ("forward", "l1_flash")
    assert pt._scope_path("%copy.2 = f32[2] copy(%p)", scopes) is None
    # a dump of the compiled HLO has the path inside the text
    assert pt._scope_path(
        '%fusion.1 = f32[2] fusion(%p), metadata={op_name="' + STEP
        + 'optimizer/sub" source_file="x.py"}', {}) == STEP + "optimizer/sub"


def planes(scoped=True):
    def path(p):
        return p if scoped else None

    ops = [["%fusion.1", 0 * MS, 10 * MS, path(STEP + "jvp(conv1)/conv")],
           ["%fusion.2", 10 * MS, 5 * MS, path(STEP + "jvp(bn1)/mul")],
           ["%fusion.3", 20 * MS, 20 * MS,
            path(STEP + "transpose(jvp(conv1))/conv")],
           ["%fusion.4", 40 * MS, 8 * MS, path(STEP + "optimizer/add")],
           ["%copy.5", 48 * MS, 2 * MS, None],
           ["%fusion.1", 60 * MS, 10 * MS, path(STEP + "jvp(conv1)/conv")]]
    # one thread: step_dispatch [12, 30] holds forward [13, 19] (which
    # holds place [14, 16]) and update [19, 29] (prep, call, writeback
    # side by side); a second thread has a sync span
    main = [["chipbench.step_dispatch", 12 * MS, 18 * MS, None],
            ["mx.module.forward", 13 * MS, 6 * MS, None],
            ["mx.executor.place", 14 * MS, 2 * MS, None],
            ["mx.module.update", 19 * MS, 10 * MS, None],
            ["mx.module.update.prep", 19 * MS, 3 * MS, None],
            ["mx.module.update.call", 22 * MS, 4 * MS, None],
            ["mx.module.update.writeback", 26 * MS, 2 * MS, None],
            ["chipbench.stamp_wait", 45 * MS, 20 * MS, None],
            ["mx.sync.ndarray.asnumpy", 50 * MS, 9 * MS, None]]
    other = [["mx.fit.next_batch", 15.5 * MS, 4 * MS, None]]
    return [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU",
         "lines": [{"name": "python3", "events": main},
                   {"name": "prefetch", "events": other}]}]


def test_device_time_by_phase_and_node():
    got = pt.summarize(planes(), steps=2)
    assert got["scoped"] and got["device_planes"] == 1
    assert got["scoped_s"] == pytest.approx(0.053)
    assert got["phase_s"] == pytest.approx(
        {"forward": 0.025, "backward": 0.020, "optimizer": 0.008,
         "unscoped": 0.002})
    assert got["node_s"][0] == ["forward", "conv1", pytest.approx(0.020)]
    assert ["backward", "conv1", pytest.approx(0.020)] in got["node_s"]
    assert ["forward", "bn1", pytest.approx(0.005)] in got["node_s"]


def test_self_time_with_nested_and_sibling_spans():
    spans = pt.summarize(planes(), steps=2)["spans"]
    fwd, upd = spans["mx.module.forward"], spans["mx.module.update"]
    assert (fwd["total_s"], fwd["self_s"], fwd["count"]) \
        == (pytest.approx(0.006), pytest.approx(0.004), 1)
    # three children side by side cover 9 of its 10 ms
    assert (upd["total_s"], upd["self_s"]) \
        == (pytest.approx(0.010), pytest.approx(0.001))
    # a grandchild is charged to its parent, not to the span above
    outer = spans["chipbench.step_dispatch"]
    assert (outer["total_s"], outer["self_s"]) \
        == (pytest.approx(0.018), pytest.approx(0.002))
    assert spans["mx.executor.place"]["self_s"] == pytest.approx(0.002)
    # another thread's span overlaps in time and is nobody's child
    assert spans["mx.fit.next_batch"]["self_s"] == pytest.approx(0.004)


def test_a_gap_is_named_by_the_innermost_program_span():
    gaps = pt.summarize(planes(), steps=2)["idle_gaps"]
    # [50, 60]: asnumpy covers 9 ms of it, stamp_wait all 10: mx.* first
    assert gaps[0] == ["mx.sync.ndarray.asnumpy", pytest.approx(0.010)]
    # [15, 20]: forward covers 4 ms, the fit thread's span 4 ms and is
    # the shorter (the innermost) of the two
    assert gaps[1] == ["mx.fit.next_batch", pytest.approx(0.005)]
    no_program = [p if p["name"] != "/host:CPU" else {
        "name": "/host:CPU", "lines": [{"name": "python3", "events": [
            e for e in p["lines"][0]["events"]
            if e[0].startswith("chipbench.")]}]} for p in planes()]
    gaps = pt.summarize(no_program, steps=2)["idle_gaps"]
    assert gaps[0][0] == "chipbench.stamp_wait"
    assert gaps[1][0] == "chipbench.step_dispatch"


def record_with(got, steps=2):
    return {"trace": {"steps": steps}, pt._CACHE_KEY: got}


def test_an_unscoped_trace_gives_nothing_not_zero(capsys):
    # paths that carry jax's transforms alone are no program scope
    bare = planes()
    for e in bare[0]["lines"][0]["events"]:
        e[3] = e[3] and "jit(step)/transpose(jvp())/mul"
    got = pt.summarize(bare, steps=2)
    assert not got["scoped"] and got["scoped_s"] == 0
    assert got["phase_s"]["backward"] == pytest.approx(0.053)
    assert pt.device_ms_per_step(record_with(got), "backward") is None
    got = pt.summarize(planes(scoped=False), steps=2)
    assert not got["scoped"]
    assert got["phase_s"]["unscoped"] == pytest.approx(0.055)
    record = record_with(got)
    for phase in ("forward", "backward", "optimizer"):
        assert pt.device_ms_per_step(record, phase) is None
    # the host spans are still read
    assert pt.span_ms_per_step(record, "mx.executor.place") \
        == pytest.approx(1.0)
    pt.report(got)
    assert "no device event carries a program scope" in capsys.readouterr().err
    host_only = [p for p in planes() if p["name"] == "/host:CPU"]
    assert pt.summarize(host_only, steps=2) is None


def test_readers_per_step_and_a_program_without_spans():
    record = record_with(pt.summarize(planes(), steps=2))
    assert pt.device_ms_per_step(record, "backward") == pytest.approx(10.0)
    assert pt.span_ms_per_step(record, "mx.module.update.call") \
        == pytest.approx(2.0)
    assert pt.span_ms_per_step(record, "mx.no.such.span") is None
    # a program with mx.* spans that moved nothing: 0 moves, a count
    assert pt.span_count_per_step(record, "mx.executor.device_put") == 0
    assert pt.span_count_per_step(record, "mx.executor.place") == 0.5
    # the parent of PR 25 writes no mx.* span: nothing, not 0
    parent = [p if p["name"] != "/host:CPU" else {
        "name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["chipbench.step_dispatch", 12 * MS, 18 * MS, None]]}]}
        for p in planes(scoped=False)]
    record = record_with(pt.summarize(parent, steps=2))
    assert pt.span_count_per_step(record, "mx.executor.device_put") is None
    assert pt.span_ms_per_step(record, "mx.executor.place") is None
    # an untraced run, and a rehearsal: nothing is looked for
    assert pt.of({"trace": None}) is None
    assert pt.device_ms_per_step({"trace": None}, "forward") is None


def test_a_stale_trace_is_refused(tmp_path):
    with pytest.raises(FileNotFoundError, match="no .*xplane.pb under"):
        pt.find_xplane(str(tmp_path))
    d = tmp_path / "trace" / "some.cell" / "plugins" / "profile" / "t1"
    d.mkdir(parents=True)
    f = d / "host.xplane.pb"
    f.write_bytes(b"")
    now = time.time()
    assert pt.find_xplane(str(tmp_path), started=now - 60) == str(f)
    assert pt._cell_of(str(f)) == "some.cell"
    with pytest.raises(FileNotFoundError, match="older than this process"):
        pt.find_xplane(str(tmp_path), started=now + 60)
    # this process started before the file was written
    started = pt.process_started()
    assert started is None or started <= now + 1
    assert pt.find_xplane(str(tmp_path)) == str(f)


def test_report_names_nodes_operators_spans_and_gaps(capsys):
    pt.report(pt.summarize(planes(), steps=2),
              {"conv1": "Convolution", "bn1": "BatchNorm"})
    err = capsys.readouterr().err
    assert "forward 12.500, backward 10.000, optimizer 4.000" in err
    assert "heaviest forward nodes, ms a step: conv1 10.000, bn1 2.500" in err
    assert "heaviest backward nodes, ms a step: conv1 10.000" in err
    assert "Convolution forward 10.000" in err and "BatchNorm forward" in err
    assert "mx.module.update.prep" in err
    assert "mx.sync.ndarray.asnumpy 10000.0 us" in err
