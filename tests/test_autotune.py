"""mxnet_tpu.autotune — the measure-and-search harness (ISSUE 11).

Tier-1 coverage of the whole loop on CPU via the deterministic stub
backend:

* search-space derivation strictly from the declare_env registry
  (undeclared / tune-less / out-of-range-restricted knobs all refuse);
* searcher + cost-model determinism: same journal + same seed → the
  SAME next proposal;
* the append-only journal: resume tolerates the truncated line a
  killed sweep leaves behind;
* subprocess executor deadline/kill discipline against a deliberately
  hanging stub target;
* per-topology promotion (schema 2) incl. legacy flat-file back-compat
  and topology isolation — and bench.py's resolver loading the entry
  for ITS topology and only its topology;
* the end-to-end acceptance: a CPU sweep proposes, measures, journals,
  resumes after a kill, and promotes the measured best.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from mxnet_tpu.autotune import (CostModel, Journal, MeasureResult,
                                SubprocessExecutor, Trial, get_target,
                                load_defaults, lookup_defaults, promote,
                                space_for, topology_key)
from mxnet_tpu.autotune import stub_target
from mxnet_tpu.autotune.history import import_history
from mxnet_tpu.autotune.search import (GridSearcher, ModelSearcher,
                                       RandomSearcher, make_searcher)
from mxnet_tpu.autotune.space import axis_for, restrict_axis
from mxnet_tpu.autotune.targets import all_target_knobs, repo_root
from mxnet_tpu.base import MXNetError, declare_env, list_env_tunables

W = "MXNET_KVSTORE_WINDOW"
C = "MXNET_KVSTORE_FUSED_CHUNK"


def _stub_trial(num, window, chunk, target="stub"):
    return Trial(num=num, target=target,
                 config={W: window, C: chunk}, status="ok",
                 objective=stub_target.objective(window, chunk))


# -- space derivation ---------------------------------------------------------
def test_space_derives_from_registry():
    space = get_target("stub").space()
    assert list(space.axes) == [W, C]
    axis = space.axes[W]
    assert axis.kind == "choice" and 8 in axis.choices
    # encoding: one-hot per choice axis
    assert space.feature_width() == len(axis.choices) \
        + len(space.axes[C].choices)
    row = space.encode({W: 8, C: 4})
    assert sum(row) == 2.0 and set(row) == {0.0, 1.0}


def test_undeclared_knob_can_never_be_tuned():
    with pytest.raises(MXNetError, match="never be tuned"):
        space_for(["MXNET_NO_SUCH_KNOB_EVER"])


def test_tuneless_knob_refused():
    # declared (engine type) but carries no tune metadata
    with pytest.raises(MXNetError, match="no tune= metadata"):
        space_for(["MXNET_ENGINE_TYPE"])


def test_declare_env_tune_validation():
    with pytest.raises(MXNetError, match="min < max"):
        declare_env("MXNET_AUTOTUNE_BAD_TMP", int, 1, "tmp",
                    tune={"min": 8, "max": 2})
    with pytest.raises(MXNetError, match="choices OR a min/max"):
        declare_env("MXNET_AUTOTUNE_BAD_TMP", int, 1, "tmp",
                    tune={"choices": [1], "min": 1, "max": 2})
    assert "MXNET_AUTOTUNE_BAD_TMP" not in list_env_tunables()


def test_restriction_outside_declared_choices_refused():
    axis = axis_for(W)
    with pytest.raises(MXNetError, match="outside its declared"):
        restrict_axis(axis, [7])           # 7 is not a declared choice
    narrowed = restrict_axis(axis, ["4", "8"])   # strings coerce
    assert narrowed.choices == (4, 8)


def test_range_axis_sampling_and_encoding():
    axis = axis_for("MXNET_KVSTORE_COMPRESSION_THRESHOLD")
    assert axis.kind == "float" and axis.log
    rng = np.random.RandomState(0)
    for _ in range(20):
        v = axis.sample(rng)
        assert axis.lo <= v <= axis.hi
    lo_enc = axis.encode(axis.lo)[0]
    hi_enc = axis.encode(axis.hi)[0]
    assert lo_enc == 0.0 and hi_enc == 1.0


def test_all_builtin_target_knobs_are_declared():
    tunables = list_env_tunables()
    for target, names in all_target_knobs().items():
        for name in names:
            assert name in tunables, (target, name)


def test_tunable_but_undeclared_is_a_lint_finding(monkeypatch):
    """The env-knob rule flags a built-in target axis that names an
    unregistered knob."""
    from pathlib import Path

    from mxnet_tpu.analysis.rules.env_knobs import RULE
    from mxnet_tpu.autotune import targets as targets_mod
    bogus = dict(targets_mod.TARGETS)
    bogus["bad"] = targets_mod.Target(
        name="bad", knobs=("MXNET_NOT_DECLARED_ANYWHERE",),
        objective="value", maximize=True, doc="x", script="bench.py")
    monkeypatch.setattr(targets_mod, "TARGETS", bogus)

    class _P:
        is_package = True
        scratch = {"env-knob-reads": set()}
        files = ()
        root = Path(targets_mod.repo_root()) / "mxnet_tpu"

    found = [f for f in RULE.finalize(_P())
             if "sweeps knob MXNET_NOT_DECLARED_ANYWHERE" in f.message]
    assert found, "tunable-but-undeclared finding missing"


# -- searcher determinism -----------------------------------------------------
def test_same_journal_same_seed_same_proposal(tmp_path):
    space = get_target("stub").space()
    trials = [_stub_trial(1, 1, 1), _stub_trial(2, 8, 2),
              _stub_trial(3, 16, 8)]
    for cls in (RandomSearcher, GridSearcher, ModelSearcher):
        a = cls(space, maximize=True, seed=7).propose(trials)
        b = cls(space, maximize=True, seed=7).propose(trials)
        assert a == b, cls.__name__
    # and through a real journal round trip (json stringification)
    j = Journal(str(tmp_path / "j.jsonl"))
    for t in trials:
        j.append(t)
    s1 = ModelSearcher(space, maximize=True, seed=7)
    assert s1.propose(j.load()) == \
        ModelSearcher(space, maximize=True, seed=7).propose(trials)


def test_proposals_skip_measured_configs():
    space = get_target("stub").space()
    trials = [_stub_trial(i + 1, w, c)
              for i, (w, c) in enumerate(
                  (w, c) for w in (1, 2, 4, 8, 16, 32)
                  for c in (1, 2, 4, 8, 16))]
    # 30 of 36 configs measured: every proposal must be one of the 6 left
    left = {(w, 32) for w in (1, 2, 4, 8, 16, 32)}
    for seed in range(5):
        cand = ModelSearcher(space, maximize=True, seed=seed) \
            .propose(trials)
        assert (cand[W], cand[C]) in left


def test_grid_searcher_walks_the_grid_in_order():
    space = get_target("stub").space()
    s = GridSearcher(space, maximize=True, seed=0)
    trials = []
    seen = []
    for i in range(4):
        cfg = s.propose(trials)
        seen.append((cfg[W], cfg[C]))
        trials.append(_stub_trial(i + 1, cfg[W], cfg[C]))
    grid = [(w, c) for w in (1, 2, 4, 8, 16, 32)
            for c in (1, 2, 4, 8, 16, 32)]
    assert seen == grid[:4]


def test_unknown_strategy_refused():
    with pytest.raises(MXNetError, match="unknown strategy"):
        make_searcher("annealing", get_target("stub").space(), True, 0)


# -- cost model ---------------------------------------------------------------
def test_cost_model_learns_the_stub_bowl():
    space = get_target("stub").space()
    trials = [_stub_trial(i + 1, w, c)
              for i, (w, c) in enumerate(
                  (w, c) for w in (1, 2, 4, 8, 16, 32)
                  for c in (1, 2, 4, 8, 16, 32))]
    m = CostModel(space)
    assert m.fit(trials)
    configs = [t.config for t in trials]
    pred = m.predict(configs)
    best = configs[int(np.argmax(pred))]
    assert (best[W], best[C]) == (8, 4)     # the known optimum


def test_cost_model_needs_two_ok_trials():
    space = get_target("stub").space()
    m = CostModel(space)
    assert not m.fit([_stub_trial(1, 8, 4)])
    assert not m.fit([Trial(num=1, target="stub", config={W: 8, C: 4},
                            status="timeout", objective=None)])


# -- journal ------------------------------------------------------------------
def test_journal_resume_tolerates_truncated_line(tmp_path):
    j = Journal(str(tmp_path / "j.jsonl"))
    j.append(_stub_trial(1, 8, 4))
    j.append(_stub_trial(2, 1, 1))
    with open(j.path, "a") as f:
        f.write('{"num": 3, "target": "stub", "config": {"MXNET')  # killed
    trials = j.load()
    assert [t.num for t in trials] == [1, 2]
    assert j.next_num() == 3
    # appending after the torn line still yields parseable records
    j.append(_stub_trial(3, 2, 2))
    assert len(j.load()) == 3


def test_imported_unknown_config_does_not_shadow_defaults(tmp_path):
    """config={} marks an imported round with unknown settings: the
    searcher's dedup must NOT treat it as the registry-default config."""
    space = get_target("stub").space()
    unknown = Trial(num=1, target="stub", config={}, status="timeout",
                    objective=None)
    s = RandomSearcher(space, maximize=True, seed=0)
    assert s._measured([unknown]) == set()


# -- subprocess executor ------------------------------------------------------
def test_executor_ok_parses_last_json_line():
    target = get_target("stub")
    res = SubprocessExecutor(timeout_s=60).run(
        target.command(), {W: 8, C: 4})
    assert res.status == "ok"
    assert res.payload["value"] == 100.0
    assert target.objective_value(res.payload) == 100.0


def test_executor_kills_hanging_target():
    target = get_target("stub")
    ex = SubprocessExecutor(timeout_s=1.5)
    res = ex.run(target.command(), {W: 8, C: 4,
                                    "MXT_AUTOTUNE_STUB_SLEEP_S": "60"})
    assert res.status == "timeout"
    assert res.duration_s < 20           # killed, not waited out
    assert "SIGKILL" in res.error


def test_executor_records_crash():
    target = get_target("stub")
    res = SubprocessExecutor(timeout_s=60).run(
        target.command(), {"MXT_AUTOTUNE_STUB_CRASH": "1"})
    assert res.status == "crash"
    assert "rc=7" in res.error


# -- promotion (schema 2) -----------------------------------------------------
def test_promote_per_topology_isolation(tmp_path):
    path = str(tmp_path / "d.json")
    tpu = topology_key("TPU v5 lite")
    cpu = topology_key("cpu")
    assert promote(path, tpu, {"batch": 256}, 2332.5)
    assert lookup_defaults(path, tpu)["batch"] == 256
    assert lookup_defaults(path, cpu) == {}          # no leak
    assert lookup_defaults(path, None) == {}
    # a CPU promotion lands NEXT TO the TPU row, clobbering nothing
    assert promote(path, cpu, {"batch": 8}, 4.4)
    assert lookup_defaults(path, tpu)["batch"] == 256
    assert lookup_defaults(path, cpu)["batch"] == 8
    # MULTICHIP (8 hosts) is its own row too
    multi = topology_key("TPU v5 lite", hosts=8)
    assert promote(path, multi, {"batch": 1024}, 9000.0)
    assert lookup_defaults(path, tpu)["batch"] == 256
    assert lookup_defaults(path, multi)["batch"] == 1024


def test_promote_hysteresis_and_direction(tmp_path):
    path = str(tmp_path / "d.json")
    topo = topology_key("TPU v5 lite")
    assert promote(path, topo, {"batch": 256}, 1000.0)
    assert not promote(path, topo, {"batch": 512}, 1010.0)   # < +2%
    assert lookup_defaults(path, topo)["batch"] == 256
    assert promote(path, topo, {"batch": 512}, 1100.0)       # > +2%
    assert lookup_defaults(path, topo)["batch"] == 512
    # minimize direction (latency-style objectives)
    lat = str(tmp_path / "lat.json")
    assert promote(lat, topo, {"env": {W: 8}}, 5.0, maximize=False)
    assert not promote(lat, topo, {"env": {W: 4}}, 4.95, maximize=False)
    assert promote(lat, topo, {"env": {W: 4}}, 4.0, maximize=False)


def test_legacy_flat_defaults_back_compat(tmp_path):
    """The seed repo's flat dict reads as ONE topology — the one its
    provenance names — and no longer applies anywhere else."""
    path = str(tmp_path / "d.json")
    flat = {"batch": 256, "stem": "conv7", "opt": "sgd",
            "dtype": "bfloat16", "remat": "0",
            "promoted_from": {"value": 2332.52, "device": "TPU v5 lite"}}
    with open(path, "w") as f:
        json.dump(flat, f)
    doc = load_defaults(path)
    assert list(doc["topologies"]) == [topology_key("TPU v5 lite")]
    assert lookup_defaults(path, topology_key("TPU v5 lite"))["batch"] \
        == 256
    assert lookup_defaults(path, topology_key("cpu")) == {}
    # promoting over a legacy file keeps it, migrated
    assert promote(path, topology_key("cpu"), {"batch": 8}, 4.4)
    doc = load_defaults(path)
    assert set(doc["topologies"]) == {topology_key("TPU v5 lite"),
                                      topology_key("cpu")}


# -- bench.py resolver --------------------------------------------------------
def _load_bench():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(repo_root(), "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_resolves_only_its_topology(tmp_path, monkeypatch):
    path = str(tmp_path / "d.json")
    promote(path, topology_key("cpu-stub"),
            {"batch": 64, "env": {W: 16}}, 100.0)
    monkeypatch.setenv("BENCH_DEFAULTS_PATH", path)
    for name in ("BENCH_BATCH", W):
        monkeypatch.delenv(name, raising=False)
    bench = _load_bench()
    try:
        cfg = bench._resolve_config("cpu-stub")
        assert cfg["batch"] == 64
        assert cfg["applied_env"] == {W: 16}
        assert os.environ[W] == "16"
    finally:
        os.environ.pop(W, None)
    # a DIFFERENT topology sees none of it
    cfg = bench._resolve_config("TPU v5 lite")
    assert cfg["batch"] == 256 and cfg["applied_env"] == {}
    assert W not in os.environ
    # explicit env always beats the promoted entry
    monkeypatch.setenv("BENCH_BATCH", "32")
    monkeypatch.setenv(W, "2")
    cfg = bench._resolve_config("cpu-stub")
    assert cfg["batch"] == 32
    assert cfg["applied_env"] == {} and os.environ[W] == "2"


# -- history import -----------------------------------------------------------
def test_import_history_warm_start(tmp_path):
    j = Journal(str(tmp_path / "hist.jsonl"))
    counts = import_history(j, repo_root())
    assert counts["BENCH_LOG.jsonl"] >= 10
    trials = j.load()
    ok = [t for t in trials if t.ok]
    assert ok and all(t.config.get("BENCH_BATCH") for t in ok)
    assert max(t.objective for t in ok) > 2000       # the banked v5e rows
    # idempotent: importing again adds nothing
    assert sum(import_history(j, repo_root()).values()) == 0
    assert len(j.load()) == len(trials)
    # the cost model starts warm from history alone: fits and prefers
    # the measured-best batch among the banked configs
    space = get_target("bench").space()
    m = CostModel(space)
    assert m.fit([t for t in trials if t.ok])


def test_imported_history_never_blocks_proposals():
    """Banked rows warm the model but must not veto re-measuring their
    configs (a new device / post-TCP_NODELAY re-baseline measures the
    historical best again on purpose)."""
    space = get_target("stub").space()
    imported = Trial(num=1, target="stub", config={W: 8, C: 4},
                     status="ok", objective=100.0,
                     source="BENCH_LOG.jsonl")
    mine = _stub_trial(2, 8, 4)
    s = RandomSearcher(space, maximize=True, seed=0)
    assert s._measured([imported]) == set()
    assert s._measured([imported, mine]) == {space.canonical(mine.config)}


def test_sweep_topology_scoping_and_effective_config():
    from mxnet_tpu.autotune.__main__ import (_effective_config,
                                             _topology_for)
    # payload-reported topology wins over re-derivation defaults
    t = Trial(num=1, target="bench", config={}, status="ok",
              objective=1.0,
              metrics={"device": "TPU v5 lite", "hosts": 1,
                       "topology": "TPU v5 lite|hosts=1|n=2|s=2"})
    assert _topology_for(t) == "TPU v5 lite|hosts=1|n=2|s=2"
    # OOM-halved batch: the journal records what really ran ...
    target = get_target("bench")
    space = target.space()
    cfg = _effective_config(
        target, space,
        {"BENCH_BATCH": 1024, "BENCH_REMAT": "0"},
        {"batch": 512, "remat": False})
    assert cfg["BENCH_BATCH"] == 512
    # ... but bench's remat=False rendering of choice "0" is NOT a
    # declared value and must not clobber the proposal
    assert cfg["BENCH_REMAT"] == "0"


# -- end-to-end acceptance ----------------------------------------------------
def _run_cli(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.autotune", *args],
        cwd=repo_root(), env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("{")]
    assert len(line) == 1, proc.stdout      # the one-JSON-line contract
    return json.loads(line[0])


def test_end_to_end_sweep_resume_promote(tmp_path):
    """ISSUE 11 acceptance: propose → measure → journal → (killed) →
    resume → converge to the known best → promote per topology →
    bench.py loads it for that topology and only that topology."""
    journal = str(tmp_path / "trials.jsonl")
    defaults = str(tmp_path / "defaults.json")
    restrict = ("--restrict", "%s=4,8,16" % W,
                "--restrict", "%s=2,4" % C)
    # first leg: 2 trials, then the sweep "dies" mid-append
    out = _run_cli("--target", "stub", "--trials", "2", "--seed", "3",
                   "--journal", journal, "--defaults", defaults,
                   "--no-promote", *restrict)
    assert out["trials_run"] == 2
    with open(journal, "a") as f:
        f.write('{"num": 3, "target": "stub", "config"')   # torn line
    # second leg resumes: 4 more trials = exhaustive over the 6 configs
    out = _run_cli("--target", "stub", "--trials", "4", "--seed", "3",
                   "--journal", journal, "--defaults", defaults,
                   *restrict)
    assert out["trials_total"] == 6 and out["ok"] == 6
    # no config measured twice (resume skipped the first leg's work)
    trials = Journal(journal).load()
    keys = {tuple(sorted(t.config.items())) for t in trials}
    assert len(keys) == 6
    # converged to the analytic optimum and promoted it
    assert out["best_config"] == {W: 8, C: 4}
    assert out["best_objective"] == 100.0
    assert out["promoted"] is True
    topo = topology_key("cpu-stub")
    assert out["topology"] == topo
    entry = lookup_defaults(defaults, topo)
    assert entry["env"] == {W: 8, C: 4}
    assert entry["promoted_from"]["value"] == 100.0
    # bench.py picks the winner up for THIS topology only
    bench = _load_bench()
    os.environ.pop(W, None)
    os.environ.pop(C, None)
    os.environ["BENCH_DEFAULTS_PATH"] = defaults
    try:
        cfg = bench._resolve_config("cpu-stub")
        assert cfg["applied_env"] == {W: 8, C: 4}
    finally:
        os.environ.pop(W, None)
        os.environ.pop(C, None)
        os.environ.pop("BENCH_DEFAULTS_PATH", None)
    cfg = bench._resolve_config("TPU v5 lite")
    assert cfg["applied_env"] == {}
    assert W not in os.environ and C not in os.environ


def test_sweep_promotes_its_own_topology_not_imported_history(tmp_path):
    """An imported other-device row with a huge objective must neither
    become 'the winner' nor hysteresis-shadow the topology this sweep
    actually measured."""
    journal = str(tmp_path / "trials.jsonl")
    defaults = str(tmp_path / "defaults.json")
    j = Journal(journal)
    j.append(Trial(num=1, target="stub", config={W: 1, C: 1},
                   status="ok", objective=99999.0,
                   metrics={"device": "TPU v5 lite"},
                   source="BENCH_LOG.jsonl"))
    out = _run_cli("--target", "stub", "--trials", "2", "--seed", "1",
                   "--journal", journal, "--defaults", defaults,
                   "--restrict", "%s=8" % W, "--restrict", "%s=2,4" % C)
    assert out["topology"] == topology_key("cpu-stub")
    assert out["best_objective"] < 99999.0       # not the imported row
    entry = lookup_defaults(defaults, topology_key("cpu-stub"))
    assert entry["promoted_from"]["value"] == out["best_objective"]
    assert lookup_defaults(defaults, topology_key("TPU v5 lite")) == {}


@pytest.mark.slow
def test_serving_probe_measures(tmp_path):
    """The serving target's probe runs one config in a fresh process
    and lands p50/p99/QPS (the sweep's measurement backend)."""
    target = get_target("serving")
    res = SubprocessExecutor(timeout_s=240).run(
        target.command(),
        {"MXNET_SERVING_BUCKETS": "1,4,16,64",
         "MXNET_SERVING_MAX_WAIT_MS": "0.5",
         "MXT_AUTOTUNE_SERVING_REQUESTS": "64",
         "JAX_PLATFORMS": "cpu"})
    assert res.status == "ok", res.error
    assert res.payload["p99_ms"] > 0 and res.payload["qps"] > 0
    assert target.objective_value(res.payload) == res.payload["p99_ms"]


@pytest.mark.slow
def test_failover_probe_measures(tmp_path):
    """The failover target's probe kills the elastic coordinator and
    reports the rebuild-cost gauge."""
    target = get_target("failover")
    res = SubprocessExecutor(timeout_s=240).run(
        target.command(),
        {"MXNET_KVSTORE_SNAPSHOT_S": "0.25",
         "MXT_AUTOTUNE_FAILOVER_ROWS": "512",
         "JAX_PLATFORMS": "cpu"})
    assert res.status == "ok", res.error
    assert res.payload["failovers"] >= 1
    assert res.payload["failover_rebuild_s"] is not None
    assert target.objective_value(res.payload) \
        == res.payload["failover_rebuild_s"]
