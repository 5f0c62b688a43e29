"""``python -m mxnet_tpu.autotune`` — the sweep driver.

Propose → measure (fresh subprocess, deadline) → journal → refit, for
``--trials`` rounds; then promote the measured-best config into the
per-topology BENCH_DEFAULTS.json entry for the topology the
measurements actually ran on.  Resumable by construction: the journal
is append-only and proposals are a pure function of (journal, seed),
so re-running the same command after a kill continues the sweep —
measured configs are never re-proposed.

Prints exactly ONE JSON summary line on stdout (the bench.py output
contract); progress marks go to stderr.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from ..base import env
from .history import import_history
from .journal import Journal, Trial
from .measure import SubprocessExecutor
from .promote import promote, topology_key
from .search import make_searcher
from .targets import TARGETS, get_target, repo_root


def _mark(msg: str) -> None:
    print("[autotune] %s" % msg, file=sys.stderr, flush=True)


def _trial_metrics(payload) -> dict:
    if not isinstance(payload, dict):
        return {}
    return {k: v for k, v in payload.items()
            if isinstance(v, (int, float, str, bool)) or v is None}


def _topology_for(trial: Trial) -> str:
    m = trial.metrics or {}
    # bench.py already computed its own topology (incl. DMLC worker/
    # server counts the payload does not spell out separately) — trust
    # it over re-deriving with single-process defaults
    if m.get("topology"):
        return m["topology"]
    return topology_key(m.get("device"),
                        hosts=m.get("hosts", 1),
                        workers=m.get("workers", 1),
                        servers=m.get("servers", 0))


def _effective_config(target, space, config: dict, payload) -> dict:
    """The config the trial REALLY measured.  bench.py may legally
    deviate from the proposed one (OOM halves the batch) — when the
    payload reports a different, still-declared value for a mapped
    knob, journal that value: the cost model must not attribute batch
    512's throughput to batch 1024, and promotion must never bank an
    always-OOM setting."""
    if not isinstance(payload, dict):
        return config
    out = dict(config)
    for knob, key in target.defaults_map:
        if knob not in out or payload.get(key) is None:
            continue
        axis = space.axes.get(knob)
        if axis is None:
            continue
        eff = axis.coerce(payload[key])
        if eff == axis.coerce(out[knob]):
            continue
        # adopt only values the axis itself could have proposed (e.g.
        # bench reports remat=False for the "0" choice — not a value)
        if axis.kind != "choice" or eff in axis.choices:
            out[knob] = eff
    return out


def main(argv=None) -> int:
    root = repo_root()
    ap = argparse.ArgumentParser(
        prog="python -m mxnet_tpu.autotune",
        description="measure-and-search over the declared knob "
                    "registry (docs/AUTOTUNE.md)")
    ap.add_argument("--target", default="stub", choices=sorted(TARGETS),
                    help="what to measure (default: stub)")
    ap.add_argument("--trials", type=int,
                    default=env("MXNET_AUTOTUNE_TRIALS"),
                    help="measured trials this run")
    ap.add_argument("--seed", type=int,
                    default=env("MXNET_AUTOTUNE_SEED"))
    ap.add_argument("--strategy", default=env("MXNET_AUTOTUNE_STRATEGY"),
                    choices=("model", "random", "grid"))
    ap.add_argument("--epsilon", type=float,
                    default=env("MXNET_AUTOTUNE_EPSILON"))
    ap.add_argument("--candidates", type=int,
                    default=env("MXNET_AUTOTUNE_CANDIDATES"))
    ap.add_argument("--timeout-s", type=float,
                    default=env("MXNET_AUTOTUNE_TRIAL_TIMEOUT_S"),
                    help="hard per-trial deadline (SIGKILL + journal "
                         "status=timeout)")
    ap.add_argument("--journal", default=None,
                    help="trials journal path (default: "
                         "<repo>/autotune_trials.jsonl)")
    ap.add_argument("--defaults", default=None,
                    help="promoted-defaults path (default: "
                         "<repo>/BENCH_DEFAULTS.json)")
    ap.add_argument("--topology", default=None,
                    help="override the promotion topology key "
                         "(default: derived from the best trial's "
                         "device/hosts/workers/servers fields)")
    ap.add_argument("--restrict", action="append", default=[],
                    metavar="KNOB=v1,v2,...",
                    help="narrow one axis to an explicit value list "
                         "(repeatable; values must sit inside the "
                         "knob's DECLARED choices/range — the "
                         "chip-session move for sweeping one corner)")
    ap.add_argument("--no-promote", action="store_true",
                    help="measure and journal only")
    ap.add_argument("--import-history", action="store_true",
                    help="seed-import BENCH_LOG.jsonl into the journal "
                         "and exit")
    args = ap.parse_args(argv)

    journal = Journal(args.journal or
                      ("%s/autotune_trials.jsonl" % root))
    defaults_path = args.defaults or ("%s/BENCH_DEFAULTS.json" % root)

    if args.import_history:
        counts = import_history(journal, root)
        print(json.dumps({"metric": "autotune_import",
                          "journal": journal.path,
                          "imported": counts,
                          "total": sum(counts.values())}))
        return 0

    target = get_target(args.target)
    restrict = {}
    for spec in args.restrict:
        knob, _, vals = spec.partition("=")
        if not vals:
            ap.error("--restrict wants KNOB=v1,v2,..., got %r" % spec)
        restrict[knob] = vals.split(",")
    space = target.space(restrict=restrict)
    searcher = make_searcher(args.strategy, space, target.maximize,
                             args.seed, epsilon=args.epsilon,
                             candidates=args.candidates)
    executor = SubprocessExecutor(args.timeout_s, mark=_mark)
    _mark("target=%s axes=%s strategy=%s trials=%d journal=%s"
          % (target.name, list(space.axes), args.strategy, args.trials,
             journal.path))

    # one parse up front; appends maintain the in-memory view (a
    # history-warmed journal is thousands of lines — re-parsing it per
    # trial would be quadratic)
    all_trials = journal.load()
    past = [t for t in all_trials if t.target == target.name]
    num = max((t.num for t in all_trials), default=0)
    ran = 0
    measured_now = []
    for _ in range(max(0, args.trials)):
        config = searcher.propose(past)
        _mark("trial %d: %s" % (len(past) + 1, config))
        t0 = time.time()
        res = executor.run(target.command(), config)
        objective = (target.objective_value(res.payload)
                     if res.status == "ok" else None)
        status = res.status
        if status == "ok" and objective is None:
            status = "error"
        num += 1
        trial = journal.append(Trial(
            num=num, target=target.name,
            config=_effective_config(target, space, config, res.payload),
            status=status, objective=objective,
            metrics=_trial_metrics(res.payload),
            duration_s=round(res.duration_s, 3), error=res.error,
            source="measured", ts=t0))
        past.append(trial)
        measured_now.append(trial)
        ran += 1
        _mark("trial done: status=%s objective=%s (%.1fs)"
              % (status, objective, res.duration_s))

    ok = [t for t in past if t.ok]
    key = (lambda t: t.objective) if target.maximize \
        else (lambda t: -t.objective)
    # promotion is strictly per topology: pick THE topology this run
    # measured (or --topology), then the best ok trial OF that topology
    # — an imported other-device row must neither become "the winner"
    # for hardware it never ran on nor hysteresis-shadow the topology
    # this sweep actually measured
    topology = args.topology
    if topology is None:
        now_ok = [t for t in measured_now if t.ok]
        if now_ok:
            topology = _topology_for(now_ok[-1])
        elif ok:
            topology = _topology_for(max(ok, key=key))
    cand = [t for t in ok
            if topology is None or _topology_for(t) == topology]
    best = max(cand, key=key) if cand else None

    promoted = False
    if best is not None:
        if not args.no_promote:
            promoted = promote(
                defaults_path, topology, target.defaults_entry(best.config),
                best.objective, maximize=target.maximize,
                provenance={"target": target.name,
                            "objective": target.objective,
                            "metric": best.metrics.get("metric"),
                            "device": best.metrics.get("device"),
                            "trial": best.num, "ts": best.ts,
                            "journal": journal.path})
            _mark("promotion %s for %s"
                  % ("WROTE %s" % defaults_path if promoted
                     else "skipped (hysteresis)", topology))

    print(json.dumps({
        "metric": "autotune_sweep",
        "target": target.name,
        "strategy": args.strategy,
        "trials_run": ran,
        "trials_total": len(past),
        "ok": len(ok),
        "best_objective": best.objective if best else None,
        "best_config": best.config if best else None,
        "topology": topology,
        "promoted": promoted,
        "journal": journal.path,
        "defaults": defaults_path,
    }))
    return 0 if best is not None or args.trials == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
