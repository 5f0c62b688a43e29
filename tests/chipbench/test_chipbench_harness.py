"""chipbench's harness on the CPU: the tiny cells under data/ reach it as
files alone (they are in no manifest), the rule that decides ``correct``,
and the manifest against the files it names.  No topology is described and
no jax device is touched while this file is imported."""
import json
import math
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

from chipbench import common, correct, harness  # noqa: E402


def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def run_cell_err(capsys, name, trace, root=DATA):
    """(the cell's result line, the run's stderr lines)."""
    rc = harness.main(["--workload", name, "--seed", "3000000019",
                       "--seconds", "1", "--trace", str(trace),
                       "--root", root])
    got = capsys.readouterr()
    assert rc == 0
    return (json.loads(got.out.strip().splitlines()[-1]),
            got.err.strip().splitlines())


def run_cell(capsys, name, trace):
    return run_cell_err(capsys, name, trace)[0]


# -- the harness end to end, one tiny cell per family -------------------------
@pytest.mark.parametrize("name,trace", [("tiny.rn18", 0), ("tiny.lm", 1)])
def test_tiny_cell_prints_a_contract_line(capsys, name, trace):
    line, err = run_cell_err(capsys, name, trace)
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] >= 4
    # a CPU run says so and is never a result
    assert line["device"]["platform"] == "cpu"
    assert "not a result" in line["rehearsal"]
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and math.isfinite(m["value"])
    if trace:
        # timed and device metrics are refused off the chip: counts only
        assert set(line["metrics"]) == {"compiles_in_window",
                                        "host_syncs_per_step"}
        assert "breakdown" not in line
        assert "busy_s" not in line["device"]
    else:
        assert set(line["metrics"]) == {m["name"] for m in
                                        manifest()["end_to_end"]}
    # the plain reference against the Module in float32: tight.  The delta
    # of a tensor is P1 - P0 in float32, so a tensor that barely moves
    # carries rounding of the order ulp(P)/|delta| (bn gammas: 5e-3)
    ref = line["reference"]
    assert ref["loss_rel"] < 1e-5 and ref["out_rel_l2"] < 1e-4
    assert ref["decidable"] == ref["tensors"] and not ref["failing"]
    assert ref["max_e_decidable"] < 2e-2
    assert ref["output_weight_e"] < 1e-3
    assert ref["max_e_over_tol"] < ref["max_e_over_tol_limit"] == 1.0
    # each number compared stands beside its limit under the line's last
    # key and on stderr's last line; the window's verdict says where
    assert list(line)[-1] == "reference"
    assert ref["window"]["first_nonfinite_step"] is None
    assert ref["window"]["loss_last_mean"] < ref["window"]["loss_first_mean"]
    assert err[-1].split("] ", 1)[1].startswith("compared, each beside")
    assert json.dumps(ref["window"]) in err[-1]
    window = [ln for ln in err if "] window on " in ln]
    assert len(window) == 1
    assert '"first_nonfinite_step": null' in window[0]


def test_a_recipe_that_blows_up_is_not_correct_and_names_the_step(
        capsys, tmp_path):
    """What gpt2m.train.resident did past step 160 (PERF.md, PR 27), at a
    learning rate that does it at once: the first step still meets its
    reference, the window's losses stop being finite, the run prints its
    line all the same, and the verdict names the step."""
    root = str(tmp_path / "data")
    shutil.copytree(DATA, root)
    path = os.path.join(root, "configs", "tiny-lm.json")
    with open(path) as f:
        config = json.load(f)
    config["optimizer"]["learning_rate"] = 30.0
    with open(path, "w") as f:
        json.dump(config, f)
    line, err = run_cell_err(capsys, "tiny.lm", 0, root)
    assert line["correct"] is False
    ref = line["reference"]
    assert not ref["failing"] and ref["loss_rel"] < 1e-5
    win = ref["window"]
    assert win["losses_finite"] is False
    assert isinstance(win["first_nonfinite_step"], int)
    assert 0 <= win["first_nonfinite_step"] < line["attempted"]
    # a loss that is no number travels as its name: the line stays JSON
    assert win["loss_last_mean"] in ("inf", "nan")
    assert ('"first_nonfinite_step": %d,' % win["first_nonfinite_step"]
            in [ln for ln in err if "] window on " in ln][0])
    assert err[-1].endswith("correct: False")


def test_a_step_that_leaves_the_state_unchanged_is_not_correct(
        capsys, monkeypatch):
    """The timed path broken underneath: ``Module.update`` does nothing,
    so no tensor moves (rule 2 reads e = 1 on every one) and the loss
    does not fall (rule 3)."""
    import mxnet_tpu as mx
    monkeypatch.setattr(mx.mod.Module, "update", lambda self: None)
    line = run_cell(capsys, "tiny.lm", 0)
    assert line["correct"] is False
    ref = line["reference"]
    assert len(ref["failing"]) == ref["tensors"]
    assert ref["max_e_over_tol"] == pytest.approx(1.0 / 0.05)
    win = ref["window"]
    assert win["first_nonfinite_step"] is None
    assert not win["loss_last_mean"] < win["loss_first_mean"]


def test_float32_step_decides_what_bf16_cannot(capsys):
    """ResNet-18 at bf16 compute: tensors behind a BatchNorm are moved by
    bf16 alone by more than a quarter, so the driver runs the Module's
    step at float32 compute too and holds those to ref32 there."""
    ref = run_cell(capsys, "tiny.rn18.bf16", 0)["reference"]
    assert 0 < ref["decidable"] < ref["tensors"] / 3
    assert ref["decidable"] + ref["decided_in_float32"] == ref["tensors"]
    assert ref["undecidable"] == 0 and not ref["failing"]
    assert ref["max_e32"] < 2e-2


# -- the rule ------------------------------------------------------------------
def test_pair_stats_on_a_constructed_pair():
    import jax.numpy as jnp
    ref = {"a": jnp.array([3.0, 4.0], jnp.float32),
           "b": jnp.zeros((2,), jnp.float32)}
    got = {"a": jnp.array([3.0, 0.0], jnp.float32),
           "b": jnp.zeros((2,), jnp.float32)}
    stats = correct.pair_stats(got, ref)
    assert correct.rel_l2(stats["a"]) == pytest.approx(4.0 / 5.0)
    assert correct.cosine(stats["a"]) == pytest.approx(3.0 / 5.0)
    assert correct.rel_l2(stats["b"]) == 0.0


def rows(**kw):
    """{tensor: (e, c)} or (e, c, e32) where the float32 step was run."""
    return {k: dict(zip(("e", "c", "e32"), v)) for k, v in kw.items()}


@pytest.mark.parametrize("table,ok,undecidable,failing", [
    # bf16 alone moves w2 by 0.4: it decides nothing; the rest pass
    (rows(out=(0.02, 0.01), w1=(0.3, 0.1), w2=(0.97, 0.4)), True,
     ["w2"], []),
    # c small and e large: the Module's arithmetic is the finding
    (rows(out=(0.02, 0.01), w1=(0.5, 0.1), w2=(0.1, 0.1)), False,
     [], ["w1"]),
    # the floor: e up to 0.05 passes however small c is
    (rows(out=(0.049, 0.0), w1=(0.051, 0.0)), False, [], ["w1"]),
    # the output layer's weight must itself be decidable
    (rows(out=(0.3, 0.3), w1=(0.01, 0.01), w2=(0.01, 0.01)), False,
     ["out"], []),
    # ResNet-50 on the chip at bf16 alone: 5 of 157 decide too little
    (rows(out=(0.077, 0.078), b=(0.002, 0.002), g1=(0.073, 0.076),
          b1=(0.085, 0.085), g0=(0.001, 0.0),
          **{"w%03d" % i: (1.12, 1.13) for i in range(152)}), False,
     ["w%03d" % i for i in range(152)], []),
    # the same with the float32 step: what bf16 cannot decide, it does
    (rows(out=(0.077, 0.078, 0.001), b=(0.002, 0.002, 0.001),
          **{"w%03d" % i: (1.12, 1.13, 0.004) for i in range(152)}), True,
     [], []),
    # a backward that is wrong shows in float32 whatever bf16 hides
    (rows(out=(0.01, 0.01, 0.001), w1=(1.1, 1.1, 0.06), w2=(1.1, 1.1, 0.01)),
     False, [], ["w1"]),
    # e32 decides only what the stated precision cannot
    (rows(out=(0.01, 0.01, 0.9), w1=(0.02, 0.01, 0.9)), True, [], []),
])
def test_decidable_rule(table, ok, undecidable, failing):
    got, report = correct.judge_deltas(table, "out")
    assert got is ok
    assert report["undecidable"] == undecidable
    assert report["failing"] == failing


def test_two_percent_of_decidable_may_fail():
    table = rows(out=(0.01, 0.01), **{"w%d" % i: (0.01, 0.01)
                                      for i in range(99)})
    table["w0"] = {"e": 0.9, "c": 0.01}
    table["w1"] = {"e": 0.9, "c": 0.01}
    assert correct.judge_deltas(table, "out")[0] is True     # 2 of 100
    table["w2"] = {"e": 0.9, "c": 0.01}
    assert correct.judge_deltas(table, "out")[0] is False    # 3 of 100


def test_forward_rule_is_self_calibrated():
    assert correct.judge_forward(6.9, 6.9003, e_out=0.03, c_out=0.01)[0]
    assert not correct.judge_forward(6.9, 6.9003, e_out=0.05, c_out=0.01)[0]
    assert correct.judge_forward(6.9, 6.9003, e_out=0.009, c_out=0.0)[0]
    assert not correct.judge_forward(6.9, 7.0, e_out=0.0, c_out=0.0)[0]
    assert not correct.judge_forward(math.nan, 7.0, 0.0, 0.0)[0]


@pytest.mark.parametrize("losses,compiles,attempted,completed,ok", [
    ([5.0] * 10 + [1.0] * 10, 0, 20, 20, True),
    ([5.0] * 10 + [1.0] * 10, 1, 20, 20, False),     # a compile in the window
    ([5.0] * 10 + [1.0] * 10, 0, 21, 20, False),     # a step went missing
    ([1.0] * 10 + [5.0] * 10, 0, 20, 20, False),     # the loss rose
    ([5.0] * 10 + [math.inf] + [1.0] * 10, 0, 21, 21, False),
])
def test_window_rule(losses, compiles, attempted, completed, ok):
    assert correct.judge_window(losses, compiles, attempted,
                                completed)[0] is ok


FALLING = [10.8 - 0.04 * i for i in range(173)]


@pytest.mark.parametrize("losses,ok,where", [
    (FALLING, True, None),                              # finite and falling
    # PR 26's faster step, seed 7: inf at step 163 of 173, finite after it
    (FALLING[:163] + [math.inf] + FALLING[164:], False, 163),
    (FALLING[:-1] + [math.nan], False, 172),            # a nan last
    (FALLING[::-1], False, None),                       # finite but rising
])
def test_window_rule_names_the_first_nonfinite_step(losses, ok, where):
    got, details = correct.judge_window(losses, 0, len(losses), len(losses))
    assert got is ok
    assert details["first_nonfinite_step"] == where
    assert details["losses_finite"] is (where is None)


# -- driven by data --------------------------------------------------------------
def test_every_manifest_entry_resolves_to_its_files():
    m = manifest()
    res = harness.Resolver()
    configs = {c["name"]: c for c in m["configs"]}
    e2e = {x["name"] for x in m["end_to_end"]}
    assert "setup_s" in e2e
    for w in m["workloads"]:
        cell, config, traffic, family, driver = res.cell(w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"], cell["why"]) \
            == (w["config"], w["traffic"], w["chips"], w["why"])
        entry = configs[w["config"]]
        assert os.path.samefile(os.path.join(REPO, entry["file"]),
                                res.path("configs", w["config"]))
        assert entry["reduced"] == config["reduced"]
        assert entry["source"] == config["source"]
        assert callable(driver.run) and callable(family.reference)
        assert family.model_flops(config, traffic) > 0
    assert sorted(res.names("cells")) == sorted(w["name"]
                                                for w in m["workloads"])
    names = {w["name"] for w in m["workloads"]}
    for x in m["per_layer"]:
        mod = res.module("layer_metrics", x["name"])
        assert (mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) \
            == (x["unit"], x["layer"], x["moves"], x["source"])
        assert x["moves"] in e2e
        assert set(x.get("workloads", names)) <= names
    assert sorted(res.names("layer_metrics")) == sorted(
        x["name"] for x in m["per_layer"])


def test_a_missing_file_fails_naming_the_path(tmp_path):
    res = harness.Resolver([str(tmp_path)])
    with pytest.raises(FileNotFoundError) as e:
        res.path("cells", "no.such.cell")
    assert os.path.join(str(tmp_path), "cells", "no.such.cell.json") \
        in str(e.value)
    assert os.path.join(common.PKG_DIR, "cells", "no.such.cell.json") \
        in str(e.value)
    cells = tmp_path / "cells"
    cells.mkdir()
    (cells / "orphan.json").write_text(json.dumps(
        {"config": "absent-config", "traffic": "resident-b256", "chips": 1}))
    with pytest.raises(FileNotFoundError, match="absent-config.json"):
        res.cell("orphan")


def test_a_new_layer_metric_is_a_new_file(tmp_path):
    d = tmp_path / "layer_metrics"
    d.mkdir()
    (d / "steps_in_window.py").write_text(
        'UNIT = "count"\nLAYER = "training driver"\n'
        'MOVES = "train_items_per_s"\nSOURCE = "program_counter"\n'
        'CHIP_ONLY = False\n\n\ndef read(record):\n'
        '    return record["steps"]\n')
    (d / "only_on_chip.py").write_text(
        'UNIT = "ms"\nLAYER = "ops"\nMOVES = "train_items_per_s"\n'
        'SOURCE = "device_trace"\n\n\ndef read(record):\n    return 1.0\n')
    res = harness.Resolver([str(tmp_path)])
    assert "steps_in_window" in res.names("layer_metrics")
    assert "device_idle_pct" in res.names("layer_metrics")
    record = {"device": {"platform": "cpu"}, "steps": 7, "host_syncs": 0,
              "compile": {"window": {"backend_compiles": 0}}, "trace": None,
              "peaks": None}
    got = harness.layer_metrics(res, record, lambda msg: None)
    assert got["steps_in_window"] == {"value": 7.0, "unit": "count"}
    # a metric is chip-only unless its file says otherwise
    assert "only_on_chip" not in got and "device_idle_pct" not in got


def test_seed_key_takes_seeds_past_32_bits():
    import numpy as np
    import jax
    seeds = [1, 2**31 + 11, 3000000019, 2**32 + 1]
    keys = [np.asarray(jax.random.key_data(common.seed_key(s)))
            for s in seeds]
    assert len({k.tobytes() for k in keys}) == len(seeds)


def test_p90_takes_every_step_as_a_sample():
    driver = harness.Resolver().module("drivers", "resident_train")
    stamps = [0.1 * i for i in range(31)]        # 30 steps of 100 ms
    p90, n = driver.p90_ms_per_step(stamps)
    assert n == 30 and p90 == pytest.approx(100.0)
    for k in (5, 12, 19, 26):   # four steps in thirty stall by 60 ms
        stamps[k:] = [t + 0.06 for t in stamps[k:]]
    p90, n = driver.p90_ms_per_step(stamps)
    assert n == 30 and p90 == pytest.approx(160.0)


@pytest.mark.parametrize("who,late,then", [
    # the host slept 450 ms: steps 11 and 12 had finished and are
    # stamped at once when it wakes
    ("host", {10: 0.45, 11: 0.35, 12: 0.25, 13: 0.15, 14: 0.05},
     [0.0, 0.0, 0.0]),
    # the device stalled: every later stamp is late by the same
    ("device", {k: 0.45 for k in range(10, 21)}, [100.0, 100.0, 100.0]),
])
def test_longest_interval_tells_a_host_stall_from_a_device_stall(
        who, late, then):
    driver = harness.Resolver().module("drivers", "resident_train")
    stamps = [0.1 * i + late.get(i, 0.0) for i in range(21)]
    ms, step, after = driver.longest_interval(stamps)
    assert ms == pytest.approx(550.0) and step == 10
    assert after == pytest.approx(then, abs=0.11)


def test_model_flops_of_the_benchmark_cells():
    res = harness.Resolver()
    _, cfg, traffic, fam, _ = res.cell("rn50.train.resident")
    # 4.1 GMACs an image forward; XLA counts 6.221e12 for the compiled step
    assert fam.model_flops(cfg, traffic) == pytest.approx(6.22e12, rel=0.03)
    _, cfg, traffic, fam, _ = res.cell("gpt2m.train.resident")
    dense = 2 * 4096 * (24 * 12 * 1024 ** 2 + 1024 * 50257)
    attn = 24 * 4 * 2 * 1024 ** 3
    assert fam.model_flops(cfg, traffic) == 3.0 * (dense + attn)
    costs = fam.kernel_costs(cfg, traffic)
    assert set(costs) == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    # forward: 2 matmuls over the causal half for 64 heads of 1024 x 64
    assert costs["flash_fwd"]["flops"] == 2 * 64 * 1024 * 1024 * 64
    assert all(c["calls_per_step"] == 24 for c in costs.values())
