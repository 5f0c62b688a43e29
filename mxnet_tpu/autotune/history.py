"""Seed-import of the banked measurement history into the journal.

The repo carries the append-only BENCH_LOG.jsonl of every successful
chip measurement.  Importing its rows as trials
(``python -m mxnet_tpu.autotune --import-history``) starts the cost
model warm — the 2332-imgs/sec v5e rows teach it the b256 bf16 region
before the first new chip minute is spent.

Idempotent per source file: a source already present in the journal is
skipped, so re-running --import-history never duplicates rows.
"""
from __future__ import annotations

import json
import os
from typing import Dict

from .journal import Journal, Trial

_REMAP = (("batch", "BENCH_BATCH"),
          ("dtype", "BENCH_DTYPE"),
          ("opt", "BENCH_OPT"),
          ("steps_per_call", "BENCH_STEPS_PER_CALL"),
          ("stem", "BENCH_STEM"),
          ("layout", "BENCH_LAYOUT"))


def _remat_str(v) -> str:
    if v in (False, None, "0", "", "False", "false", 0):
        return "0"
    if v in (True, "1", "full", "True", "true", 1):
        return "1"
    return str(v)


def _config_from_log_row(d: dict) -> dict:
    cfg = {}
    for field, knob in _REMAP:
        if field in d and d[field] is not None:
            cfg[knob] = d[field]
    cfg["BENCH_REMAT"] = _remat_str(d.get("remat"))
    return cfg


def _float_ts(v):
    try:
        return float(v)
    except (TypeError, ValueError):
        return None


def import_history(journal: Journal, root: str) -> Dict[str, int]:
    """Import BENCH_LOG.jsonl under ``root`` into ``journal``; returns
    {source: rows imported} (0 = already there)."""
    done = journal.sources()
    counts: Dict[str, int] = {}
    num = journal.next_num()

    src = "BENCH_LOG.jsonl"
    log_path = os.path.join(root, src)
    counts[src] = 0
    if src not in done and os.path.exists(log_path):
        with open(log_path) as f:
            for line in f:
                try:
                    d = json.loads(line)
                except ValueError:
                    continue
                if not isinstance(d, dict) or "metric" not in d:
                    continue
                ok = bool(d.get("value"))
                journal.append(Trial(
                    num=num, target="bench",
                    config=_config_from_log_row(d),
                    status="ok" if ok else "error",
                    objective=float(d["value"]) if ok else None,
                    metrics={k: d.get(k) for k in
                             ("metric", "mfu", "step_ms", "device",
                              "data_mode", "tag", "wire_bytes_per_step",
                              "overlap_pct")
                             if d.get(k) is not None},
                    error=None if ok else str(d.get("error", ""))[:400],
                    source=src, ts=_float_ts(d.get("ts"))))
                num += 1
                counts[src] += 1
    return counts
