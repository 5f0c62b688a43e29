"""The built-in measurement targets.

A target = (the knobs it sweeps, the command that measures one config,
which payload key is the objective and its sign, and how a winning
config maps into the per-topology BENCH_DEFAULTS.json entry).  The
knobs MUST be registered via ``base.declare_env`` with tune metadata —
``space_for`` raises otherwise, and the ``env-knob`` lint rule flags
any built-in axis naming an unregistered knob (tunable-but-undeclared).

Every command is a fresh subprocess obeying the one-JSON-line stdout
contract (measure.SubprocessExecutor parses the last JSON object
line).  The config rides ONLY in environment variables — exactly the
surface the framework reads the knobs from, so a measured win is by
construction the setting a real run would use.
"""
from __future__ import annotations

import dataclasses
import os
import sys
from typing import Dict, List, Optional, Tuple

from ..base import MXNetError
from .space import SearchSpace, space_for


def repo_root() -> str:
    """The checkout root (two levels above this package)."""
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


@dataclasses.dataclass(frozen=True)
class Target:
    name: str
    knobs: Tuple[str, ...]
    objective: str               # payload key carrying the objective
    maximize: bool
    doc: str
    # env knob -> flat BENCH_DEFAULTS key bench.py resolves directly;
    # knobs NOT mapped here promote under the entry's "env" dict and are
    # os.environ.setdefault-ed by the consumer for that topology
    defaults_map: Tuple[Tuple[str, str], ...] = ()
    module: Optional[str] = None     # python -m entry
    script: Optional[str] = None     # repo-root-relative script

    def command(self) -> List[str]:
        if self.module:
            return [sys.executable, "-m", self.module]
        return [sys.executable, os.path.join(repo_root(), self.script)]

    def space(self, restrict=None) -> SearchSpace:
        return space_for(self.knobs, restrict=restrict)

    def objective_value(self, payload: dict) -> Optional[float]:
        v = payload.get(self.objective)
        try:
            return float(v) if v is not None else None
        except (TypeError, ValueError):
            return None

    def defaults_entry(self, config: dict) -> dict:
        """Project a winning config into the per-topology defaults
        entry shape: mapped knobs become bench.py's flat keys, the rest
        land under "env"."""
        mapped = dict(self.defaults_map)
        entry: dict = {}
        env: dict = {}
        for knob, value in config.items():
            if knob in mapped:
                entry[mapped[knob]] = value
            else:
                env[knob] = value
        if env:
            entry["env"] = env
        return entry


TARGETS: Dict[str, Target] = {t.name: t for t in [
    Target(
        name="stub",
        knobs=("MXNET_KVSTORE_WINDOW", "MXNET_KVSTORE_FUSED_CHUNK"),
        objective="value", maximize=True,
        doc="deterministic CPU stub backend (stub_target.py): a known "
            "analytic bowl over two real registry knobs — exercises the "
            "whole propose/measure/journal/promote loop in tier-1 with "
            "no chip, no jax import, sub-second trials",
        # stdlib-only child run by PATH on purpose: `-m` would import
        # the full mxnet_tpu package (jax) for a 50 ms trial
        script="mxnet_tpu/autotune/stub_target.py"),
    Target(
        name="bench",
        knobs=("BENCH_BATCH", "BENCH_DTYPE", "BENCH_OPT",
               "BENCH_STEPS_PER_CALL", "BENCH_STEM", "BENCH_LAYOUT",
               "BENCH_REMAT"),
        objective="value", maximize=True,
        doc="bench.py ResNet-50 fused-step throughput (imgs/sec) — the "
            "queued steps-per-call x batch x remat x layout sweep",
        defaults_map=(("BENCH_BATCH", "batch"),
                      ("BENCH_DTYPE", "dtype"),
                      ("BENCH_OPT", "opt"),
                      ("BENCH_STEPS_PER_CALL", "steps_per_call"),
                      ("BENCH_STEM", "stem"),
                      ("BENCH_LAYOUT", "layout"),
                      ("BENCH_REMAT", "remat")),
        script="bench.py"),
    Target(
        name="serving",
        knobs=("MXNET_SERVING_BUCKETS", "MXNET_SERVING_MAX_WAIT_MS",
               "MXNET_SERVING_QUEUE_DEPTH",
               "MXNET_SERVING_CLIENT_WINDOW"),
        objective="p99_ms", maximize=False,
        doc="serving_probe.py: in-process replica + pipelined client, "
            "request storm, p50/p99/QPS from the serving_stats "
            "envelope — the serving latency/QPS row of the roadmap",
        module="mxnet_tpu.autotune.serving_probe"),
    Target(
        name="failover",
        knobs=("MXNET_KVSTORE_SNAPSHOT_S", "MXNET_KVSTORE_WINDOW"),
        objective="failover_rebuild_s", maximize=False,
        doc="failover_probe.py: elastic pair + worker, the COORDINATOR "
            "killed mid-job at the faultinject boundary, rebuild cost "
            "from the kvstore.failover_rebuild_s gauge — the elastic "
            "handoff/failover cost curve vs snapshot cadence",
        module="mxnet_tpu.autotune.failover_probe"),
]}


def get_target(name: str) -> Target:
    try:
        return TARGETS[name]
    except KeyError:
        raise MXNetError("autotune: unknown target %r; built-ins: %s"
                         % (name, sorted(TARGETS)))


def all_target_knobs() -> Dict[str, List[str]]:
    """{target name: knob names} — the env-knob lint rule checks every
    entry against the declare_env registry (tunable-but-undeclared)."""
    return {name: list(t.knobs) for name, t in TARGETS.items()}
