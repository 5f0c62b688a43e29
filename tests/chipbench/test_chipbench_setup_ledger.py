"""The readers of the program's set-up ledger (chipbench/layer_metrics/
_setup_ledger.py): each gives a number from a compile ledger and phase
clock, nothing on a program without the ledger (the parent of PR 39), and
they count the system's own Module alone: the first occurrence of each
phase, plus its recompiles."""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import harness  # noqa: E402

READERS = ("module_setup_s", "fused_step_recompile_s", "module_trace_s",
           "module_lower_s", "init_params_programs")
S = 1e6     # microseconds a second


def rec(stage, phase, occ, start, end, program="p"):
    r = {"stage": stage, "program": program, "start_us": start * S,
         "end_us": end * S, "phase": phase, "occurrence": occ, "nested": 0,
         "tid": 1}
    if stage == "compile":
        r["cache"] = "none"
    return r


PHASES = {"mx.module.bind": [1.0, 0.2], "mx.module.init_params": [2.0, 0.9],
          "mx.module.init_optimizer": [0.5, 0.1],
          "mx.module.build_step": [0.01, 0.01],
          "mx.module.first_update": [10.0, 7.0]}
LEDGER = [
    rec("trace", "mx.module.bind", 0, 0.0, 0.5),
    rec("compile", "mx.module.init_params", 0, 1.0, 1.5, "jit(_uniform)"),
    rec("compile", "mx.module.init_params", 0, 1.5, 2.0, "jit(_uniform)"),
    # a trace inside a lowering: another stage, kept apart
    rec("lower", "mx.module.init_params", 0, 2.0, 2.6),
    rec("trace", "mx.module.init_params", 0, 2.1, 2.2),
    rec("trace", "mx.module.first_update", 0, 4.0, 6.0, "mx_fused_step"),
    rec("lower", "mx.module.first_update", 0, 6.0, 7.0),
    rec("compile", "mx.module.first_update", 0, 7.0, 13.0),
    # the float32 reference Module and a program outside every phase
    rec("compile", "mx.module.init_params", 1, 20.0, 21.0),
    rec("trace", "mx.module.first_update", 1, 22.0, 25.0),
    rec("lower", None, None, 30.0, 31.0),
]
RECOMPILE = [rec("trace", "mx.module.recompile", 0, 14.0, 14.0),
             rec("lower", "mx.module.recompile", 0, 14.0, 15.5),
             rec("compile", "mx.module.recompile", 0, 15.5, 17.0)]


@pytest.fixture
def ledger(monkeypatch):
    from mxnet_tpu import tracing

    def install(records, phases):
        monkeypatch.setattr(tracing, "compile_records",
                            lambda: [dict(r) for r in records])
        monkeypatch.setattr(tracing, "phase_seconds",
                            lambda: {k: list(v) for k, v in phases.items()})
    return install


def read(name):
    return harness.Resolver().module("layer_metrics", name).read({})


def test_each_reader_gives_a_number_from_the_ledger(ledger):
    ledger(LEDGER + RECOMPILE, dict(PHASES, **{"mx.module.recompile": [3.0]}))
    got = {n: read(n) for n in READERS}
    assert got == pytest.approx({
        "module_setup_s": 1.0 + 2.0 + 0.5 + 10.0 + 3.0,
        "fused_step_recompile_s": 3.0,
        "module_trace_s": 0.5 + 0.1 + 2.0,
        "module_lower_s": 0.6 + 1.0 + 1.5,
        "init_params_programs": 2})
    assert got["module_trace_s"] + got["module_lower_s"] \
        <= got["module_setup_s"]


def test_nothing_recompiled_reads_zero_not_nothing(ledger):
    ledger(LEDGER, PHASES)
    assert read("fused_step_recompile_s") == 0.0
    assert read("module_setup_s") == pytest.approx(13.5)


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_ledger_gives_nothing(monkeypatch, name):
    from mxnet_tpu import tracing
    monkeypatch.delattr(tracing, "compile_records")
    assert read(name) is None


def test_the_readers_on_a_module_set_up_in_this_process():
    import mxnet_tpu as mx
    import numpy as np
    from mxnet_tpu import profiler, tracing
    profiler.reset_all()
    try:
        net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
            mx.sym.Variable("data"), num_hidden=59, name="fc"), name="softmax")
        mod = mx.mod.Module(net, context=mx.cpu())
        mod.bind(data_shapes=[("data", (2, 61))],
                 label_shapes=[("softmax_label", (2,))])
        mod.init_params(mx.initializer.Xavier())
        mod.init_optimizer(optimizer="sgd", optimizer_params={
            "learning_rate": 0.1, "momentum": 0.9})
        b = mx.io.DataBatch(data=[mx.nd.array(np.ones((2, 61), "f"))],
                            label=[mx.nd.array(np.array([1, 2], "f"))])
        for _ in range(3):
            mod.forward(b, is_train=True)
            mod.update()
        got = {n: read(n) for n in READERS}
        first = tracing.phase_seconds()["mx.module.first_update"][0]
        assert got["fused_step_recompile_s"] > 0
        assert got["init_params_programs"] >= 1
        assert got["module_trace_s"] + got["module_lower_s"] \
            <= got["module_setup_s"]
        assert first <= got["module_setup_s"]
    finally:
        profiler.reset_all()
