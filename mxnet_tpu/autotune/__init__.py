"""mxnet_tpu.autotune — the measure-and-search harness over the knob
registry (docs/AUTOTUNE.md).

TVM-style propose → measure → update loop (arXiv:1802.04799) with a
fit-on-the-fly cost model in the TpuGraphs spirit (arXiv:2308.13490):

* :mod:`space`   — search spaces derived EXCLUSIVELY from the
  ``base.declare_env`` registry's ``tune=`` metadata: an undeclared
  knob can never be tuned (and a target axis naming one is an
  ``env-knob`` lint finding);
* :mod:`measure` — subprocess executors with a hard deadline: a hung
  trial is SIGKILLed (whole process group) and recorded, never
  serializing the sweep;
* :mod:`targets` — the built-in measurement targets: ``bench``
  (bench.py throughput), ``serving`` (p99/QPS via serving_stats),
  ``failover`` (elastic coordinator-kill rebuild cost), and ``stub``
  (deterministic CPU backend that makes the whole loop tier-1-testable
  before a chip session ever runs);
* :mod:`search` / :mod:`model` — random/grid baselines plus the
  epsilon-greedy model searcher over a ridge regressor, seeded so the
  same journal + seed reproduce the same proposal;
* :mod:`journal` — the append-only resumable JSONL trials journal;
* :mod:`promote` — winners banked into the per-topology
  BENCH_DEFAULTS.json schema (device kind x host count x worker/server
  count) that bench.py loads for that topology and only that topology;
* :mod:`history` — seed-import of the banked BENCH_LOG.jsonl rows so
  the cost model starts warm.

Entry point: ``python -m mxnet_tpu.autotune`` (see ``--help``).
"""
from .journal import Journal, Trial                      # noqa: F401
from .measure import MeasureResult, SubprocessExecutor   # noqa: F401
from .model import CostModel                             # noqa: F401
from .promote import (load_defaults, lookup_defaults,    # noqa: F401
                      promote, topology_key)
from .search import make_searcher                        # noqa: F401
from .space import Axis, SearchSpace, space_for          # noqa: F401
from .targets import TARGETS, Target, get_target         # noqa: F401
