"""``python -m mxnet_tpu.analysis`` — the static-analysis CI gate.

Default run lints the installed ``mxnet_tpu`` package (plus the
whole-package checks: static lock-order cycles, blocking-under-lock,
the wire-protocol conformance table, knob-registry drift against
docs/ROBUSTNESS.md) and reports findings; ``--strict`` makes any
unannotated finding fatal — that form is the ``analysis`` gate in
ci/run_ci.sh.  Explicit paths lint those files/directories instead
(the fixture tests drive this).

``--knob-table`` / ``--protocol-table`` print the generated markdown
tables docs/ROBUSTNESS.md and docs/PROTOCOL.md fold in;
``--codec-table`` prints the generated hot-op block
mxnet_tpu/wirecodec.py folds in; ``--check`` fails (exit 2) when any
generated copy is STALE instead of silently regenerating — the drift
gate ci/run_ci.sh runs next to ``--strict``.
``--json`` emits one finding per line (the Finding dataclass fields
verbatim) so CI consumes findings without scraping text.
"""
from __future__ import annotations

import argparse
import sys

from . import knobs, protocol
from .lint import lint_paths, package_root


def _run_explorer(args) -> int:
    """--explore / --replay: the interleaving-exploration entrypoint
    (ISSUE 20).  Exit 1 on any finding — CI runs the seven real
    scenarios expecting 0 and the seeded bugs expecting 1."""
    from ..base import env as _env
    from . import sched
    if args.replay:
        r = sched.replay(args.replay, journal_dir=args.journal_dir)
        print("replay %s: scenario=%s %d decisions, %d finding(s)"
              % (args.replay, r.scenario, r.ops, len(r.findings)))
        for kind, detail in r.findings:
            print("[%s] %s" % (kind, detail))
        return 1 if r.findings else 0
    schedules = args.schedules if args.schedules is not None else \
        int(_env("MXNET_SCHED_SCHEDULES", 20))
    seed = args.seed if args.seed is not None else \
        int(_env("MXNET_SCHED_SEED", 0))
    res = sched.explore(args.explore, schedules=schedules, seed=seed,
                        depth=args.depth, journal_dir=args.journal_dir)
    ran = len(res.schedules)
    ops = sum(r.ops for r in res.schedules)
    if not res.findings:
        print("explore %s: %d schedules (seed %d, %d decisions) clean"
              % (args.explore, ran, seed, ops))
        return 0
    bad = res.failing
    print("explore %s: findings at schedule %d of %d (seed %d); "
          "journal: %s" % (args.explore, bad.index, ran, seed,
                           bad.journal_path))
    for kind, detail in bad.findings:
        print("[%s] %s" % (kind, detail))
    print("replay with: python -m mxnet_tpu.analysis --replay %s"
          % bad.journal_path)
    return 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m mxnet_tpu.analysis",
        description="framework-aware lint + invariant gates "
                    "(docs/ANALYSIS.md)")
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to lint (default: the mxnet_tpu "
                         "package + whole-package checks)")
    ap.add_argument("--strict", action="store_true",
                    help="exit nonzero on any unannotated finding "
                         "(the CI gate mode)")
    ap.add_argument("--json", action="store_true",
                    help="one finding per line as JSON (Finding "
                         "dataclass fields; suppressed ones included "
                         "with suppressed=true)")
    ap.add_argument("--knob-table", action="store_true",
                    help="print the generated markdown knob table for "
                         "docs/ROBUSTNESS.md and exit")
    ap.add_argument("--protocol-table", action="store_true",
                    help="print the generated wire-protocol op table "
                         "for docs/PROTOCOL.md and exit")
    ap.add_argument("--codec-table", action="store_true",
                    help="print the generated hot-op codec block for "
                         "mxnet_tpu/wirecodec.py and exit")
    ap.add_argument("--check", action="store_true",
                    help="fail (exit 2) when a generated table "
                         "(ROBUSTNESS.md knobs, PROTOCOL.md ops, "
                         "wirecodec.py hot-op codec block) is stale — "
                         "the CI drift gate")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule catalog and exit")
    ap.add_argument("--explore", metavar="SCENARIO",
                    help="run SCENARIO under N seeded controlled "
                         "schedules (PCT) with race/deadlock/"
                         "starvation detection; exit 1 on any finding")
    ap.add_argument("--schedules", type=int, default=None,
                    help="schedules per --explore run (default "
                         "MXNET_SCHED_SCHEDULES)")
    ap.add_argument("--seed", type=int, default=None,
                    help="schedule seed (default MXNET_SCHED_SEED); "
                         "(seed, scenario, index) names a schedule")
    ap.add_argument("--depth", type=int, default=None,
                    help="PCT priority-change points + 1 (default "
                         "MXNET_SCHED_DEPTH)")
    ap.add_argument("--replay", metavar="JOURNAL",
                    help="re-execute a recorded schedule journal "
                         "decision for decision and exit 1 when its "
                         "findings reproduce")
    ap.add_argument("--journal-dir", default=None,
                    help="where schedule journals land (default "
                         "MXNET_SCHED_JOURNAL_DIR); failing schedules "
                         "keep theirs, clean ones are deleted")
    ap.add_argument("--list-scenarios", action="store_true",
                    help="print the explorer scenario catalog and exit")
    args = ap.parse_args(argv)

    if args.list_scenarios:
        from . import scenarios as _scen
        for name in _scen.names():
            sc = _scen.get(name)
            first = sc.doc.splitlines()[0] if sc.doc else ""
            print("%-16s [%s] %s" % (name, sc.kind, first))
        return 0
    if args.explore or args.replay:
        return _run_explorer(args)

    if args.knob_table:
        print(knobs.markdown_table())
        return 0
    if args.protocol_table:
        print(protocol.markdown_table())
        return 0
    if args.codec_table:
        print(protocol.codec_table_source())
        return 0
    if args.check:
        problems = [p for p in (knobs.check_drift(package_root()),
                                protocol.check_drift(package_root()),
                                protocol.check_codec_drift(
                                    package_root()))
                    if p]
        for p in problems:
            print(p)
        if problems:
            return 2
        print("mxnet_tpu.analysis --check: generated doc tables are "
              "in sync")
        return 0
    if args.list_rules:
        from .rules import ALL_RULES
        for rule in ALL_RULES:
            doc = (sys.modules[type(rule).__module__].__doc__ or
                   "").strip().splitlines()
            print("%-20s %s" % (rule.name, doc[0] if doc else ""))
        return 0

    active, suppressed = lint_paths(args.paths or None)
    if args.json:
        import dataclasses
        import json
        for f in sorted(active + suppressed,
                        key=lambda f: (f.path, f.line, f.rule)):
            print(json.dumps(dataclasses.asdict(f), sort_keys=True))
    else:
        for f in sorted(active, key=lambda f: (f.path, f.line, f.rule)):
            print(f.render())
        print("mxnet_tpu.analysis: %d finding(s), %d suppressed by "
              "allow-annotations" % (len(active), len(suppressed)))
    if active:
        return 1 if args.strict else 0
    return 0


if __name__ == "__main__":
    sys.exit(main())
