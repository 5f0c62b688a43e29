"""chipbench/trace_reduce.py on planes built by hand and on the recorded
sample kept beside it: busy time is the union of ``XLA Ops``, ``Async XLA
Ops`` is ignored, idle gaps are named by the innermost ``mx.*`` span that
covers them and else by the ``chipbench.*`` one, kernel time is found by
name prefix."""
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import trace_reduce as tr  # noqa: E402

FWD = ("%flash_fwd.37 = (bf16[64,1024,64]{2,1,0:T(8,128)(2,1)S(1)}, "
       "f32[64,1024,128]{2,1,0}) custom-call(bf16[64,1024,64]{2,1,0} %x)")
DKV = "%flash_bwd_dkv.2 = (bf16[64,1024,64]{2,1,0}) custom-call(%a)"
DQ = "%flash_bwd_dq.9 = bf16[64,1024,64]{2,1,0} custom-call(%a)"
FUSION = ("%fusion.64 = bf16[256,256,56,56]{1,0,3,2:T(8,128)(2,1)} "
          "fusion(bf16[256,256,56,56]{1,0,3,2} %p), kind=kLoop, "
          "calls=%fused_computation.1574")
TUPLE = ("%multiply_reduce_fusion.3 = (bf16[256]{0:T(256)(128)(2,1)S(1)}, "
         "bf16[256]{0}) fusion(%p), kind=kOutput, calls=%fc.3")


def planes():
    ms = 1e6
    ops = [[FUSION, 0 * ms, 10 * ms], [TUPLE, 5 * ms, 10 * ms],   # overlap
           [FWD, 20 * ms, 4 * ms], [FWD, 24 * ms, 4 * ms],
           [DKV, 40 * ms, 6 * ms], [DQ, 46 * ms, 2 * ms],
           [FUSION, 50 * ms, 50 * ms]]
    async_ops = [["%copy-start.1 = (f32[3]) copy-start(%p)", 0, 1000 * ms]]
    spans = [["chipbench.step_dispatch", 14 * ms, 7 * ms],
             ["mx.module.update", 14.5 * ms, 6 * ms],
             ["mx.module.update.call", 15 * ms, 5 * ms],
             ["mx.module.forward", 26 * ms, 2.5 * ms],
             ["chipbench.stamp_wait", 27 * ms, 14 * ms],
             ["other.span", 0, 100 * ms]]
    return [
        {"name": "/device:TPU:0",
         "stats": {"peak_teraflops_per_second": 202.7},
         "lines": [{"name": "XLA Ops", "events": ops},
                   {"name": "Async XLA Ops", "events": async_ops},
                   {"name": "Steps", "events": [["1", 0, 100 * ms]]}]},
        {"name": "/host:CPU", "stats": {},
         "lines": [{"name": "python3", "events": spans}]},
        {"name": "#Chip0 Misc", "stats": {}, "lines": []},
    ]


def test_busy_is_the_union_of_xla_ops_and_async_is_ignored():
    got = tr.reduce_planes(planes(), steps=1,
                           kernel_prefixes=["flash_bwd_dkv", "flash_bwd_dq",
                                            "flash_fwd"])
    assert got["device_planes"] == 1
    assert got["window_s"] == pytest.approx(0.100)
    # [0,15] + [20,28] + [40,48] + [50,100] ms
    assert got["busy_s"] == pytest.approx(0.081)
    assert got["plane_peaks"] == {"peak_teraflops_per_second": 202.7}
    # kernels by name prefix; dq and dkv are told apart
    assert got["kernel_s"] == pytest.approx(
        {"flash_fwd": 0.008, "flash_bwd_dkv": 0.006, "flash_bwd_dq": 0.002})
    assert got["kernel_calls"] == {"flash_fwd": 2, "flash_bwd_dkv": 1,
                                   "flash_bwd_dq": 1}
    # per-op time under short stable names, kernels folded over call sites
    ops = dict(map(tuple, got["device_ops"]))
    assert ops["fusion.64 bf16[256,256,56,56] kLoop"] == pytest.approx(0.060)
    assert ops["multiply_reduce_fusion.3 bf16[256] kOutput"] \
        == pytest.approx(0.010)
    assert ops["flash_fwd.* bf16[64,1024,64]"] == pytest.approx(0.008)
    assert all(len(name) < 80 for name in ops)
    # gaps, longest first.  [28, 40]: mx.module.forward touches half a
    # millisecond of it, which is not most of it: the yardstick's span
    # names it.  [15, 20]: update and update.call both cover it, the
    # program's spans come first and the innermost names it.  [48, 50]:
    # nothing of the program's or the yardstick's is open
    assert got["idle_gaps"] == [
        ["chipbench.stamp_wait", pytest.approx(0.012)],
        ["mx.module.update.call", pytest.approx(0.005)],
        [tr.NO_SPAN, pytest.approx(0.002)]]
    # without the program's spans the yardstick's name the gaps, as before
    no_program = [dict(p, lines=[dict(ln, events=[
        e for e in ln["events"] if not e[0].startswith("mx.")])
        for ln in p["lines"]]) for p in planes()]
    assert tr.reduce_planes(no_program, steps=1)["idle_gaps"] == [
        ["chipbench.stamp_wait", pytest.approx(0.012)],
        ["chipbench.step_dispatch", pytest.approx(0.005)],
        [tr.NO_SPAN, pytest.approx(0.002)]]


def test_no_device_operation_reduces_to_nothing():
    host_only = [p for p in planes() if p["name"] != "/device:TPU:0"]
    assert tr.reduce_planes(host_only, steps=1) is None


def test_cut_keeps_events_that_start_inside():
    got = tr.cut(planes(), 20e6, 46e6)
    ops = got[0]["lines"][0]["events"]
    assert [tr.op_token(e[0]) for e in ops] == [
        "flash_fwd.37", "flash_fwd.37", "flash_bwd_dkv.2"]


def test_cut_keeps_host_spans_that_overlap():
    host = tr.cut(planes(), 20e6, 46e6)[1]["lines"][0]["events"]
    # update.call ended as the window opened; stamp_wait opened before it
    assert [e[0] for e in host] == [
        "chipbench.step_dispatch", "mx.module.update", "mx.module.forward",
        "chipbench.stamp_wait", "other.span"]


def test_recorded_sample_reduces():
    """25 ms around a step boundary of gpt2m.train.resident's traced slice
    (TPU v5 lite, PR 27's tree): the end of one step's backward, the gaps
    at the boundary, the next step's dispatch with the program's spans."""
    path = os.path.join(os.path.dirname(tr.__file__), "trace_sample.json")
    with open(path) as f:
        sample = json.load(f)
    got = tr.reduce_planes(sample, steps=1, kernel_prefixes=[
        "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"])
    assert 0.0 < got["busy_s"] <= got["window_s"]
    assert got["kernel_calls"]["flash_fwd"] > 0
    assert got["kernel_s"]["flash_fwd"] > 0.0
    assert len(got["device_ops"]) == 10
    assert all(len(name) < 80 for name, _ in got["device_ops"])
    spans = [e for p in sample if p["name"] == tr.HOST_PLANE
             for ln in p["lines"] for e in ln["events"]]
    names = {e[0] for e in spans}
    assert {"mx.module.forward", "mx.module.update", "mx.module.update.call",
            "chipbench.step_dispatch", "chipbench.stamp_wait"} <= names
    # the boundary's gaps fall while the host waits on chipbench's own loss
    # scalar: no span of the program is open, the yardstick's names them
    assert got["idle_gaps"][0][0] == "chipbench.stamp_wait"
    assert 1e-4 < got["idle_gaps"][0][1] < 1e-3
    # an interval inside the jitted call is the program's, innermost first
    _, start, dur = next(e for e in spans if e[0] == "mx.module.update.call")
    assert tr.gap_name(spans, start + 0.25 * dur, start + 0.75 * dur) \
        == "mx.module.update.call"
