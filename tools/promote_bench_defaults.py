#!/usr/bin/env python
"""Promote the best measured sweep config to bench defaults.

Scans BENCH_LOG.jsonl for resnet50 synthetic-data measurements and
promotes the winner into its PER-TOPOLOGY entry of BENCH_DEFAULTS.json
(schema 2, mxnet_tpu/autotune/promote.py: device kind x host count x
worker/server count) — bench.py resolves exactly its own topology's
entry, so a b256-TPU winner can never leak into a CPU or MULTICHIP
run.  The >2% hysteresis lives in promote(): noise can't flip defaults
back and forth, and other topologies' rows are never touched.  Safe to
run any time (no log → no file → bench keeps built-in defaults).  The richer sweep
driver (`python -m mxnet_tpu.autotune --target bench`) promotes
through the same schema.
"""
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOG = os.path.join(ROOT, "BENCH_LOG.jsonl")
OUT = os.path.join(ROOT, "BENCH_DEFAULTS.json")


def _promote_mod():
    """autotune.promote loaded BY PATH (stdlib-only module) — this tool
    must stay runnable without importing the full package/jax."""
    spec = importlib.util.spec_from_file_location(
        "_tool_promote",
        os.path.join(ROOT, "mxnet_tpu", "autotune", "promote.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def remat_str(v):
    """Normalize the logged remat field to the BENCH_REMAT string."""
    if v in (False, None, "0", "", "False", "false"):
        return "0"
    if v in (True, "1", "full", "True", "true"):
        return "1"
    return str(v)


def main():
    if not os.path.exists(LOG):
        print("promote: no %s — nothing to do" % LOG)
        return 0
    rows = []
    with open(LOG) as f:
        for line in f:
            try:
                d = json.loads(line)
            except ValueError:
                continue
            if not isinstance(d, dict):
                continue
            if d.get("metric") != "resnet50_train_imgs_per_sec":
                continue
            if not d.get("value"):
                continue
            if d.get("data_mode", "synthetic") != "synthetic":
                continue  # defaults stay on the synthetic headline config
            rows.append(d)
    if not rows:
        print("promote: no successful synthetic measurements yet")
        return 0
    # CPU rows never inform TPU defaults (CI smoke runs once polluted
    # the log before bench.py stopped banking them — filter defensively
    # for logs written by older bench versions)
    sys.path.insert(0, ROOT)
    from benchmark._bench_common import is_cpu_device
    rows = [d for d in rows if not is_cpu_device(d.get("device"))]
    if not rows:
        print("promote: no chip measurements yet")
        return 0
    # only the CURRENT chip's measurements count: a device swap must not
    # leave stale all-time-max defaults (e.g. a batch the new chip OOMs)
    device = rows[-1].get("device")
    rows = [d for d in rows if d.get("device") == device]
    best = None
    for d in rows:
        if best is None or d["value"] > best["value"] or (
                d["value"] == best["value"]
                and d.get("tag") and not best.get("tag")):
            # each successful session run logs twice (bench.py's own
            # append + run_bench's tagged copy): prefer the tagged
            # duplicate so provenance survives
            best = d

    prom = _promote_mod()
    # rows written by the current bench.py carry their topology; older
    # banked rows fall back to the single-host key for their device
    topo = best.get("topology") or prom.topology_key(
        best.get("device"), hosts=int(best.get("hosts", 1)))
    entry = {
        "batch": int(best.get("batch", 256)),
        "stem": best.get("stem", "conv7"),
        "layout": best.get("layout", "nchw"),
        "opt": best.get("opt", "sgd"),
        "dtype": best.get("dtype", "bfloat16"),
        "remat": remat_str(best.get("remat", "0")),
        "steps_per_call": int(best.get("steps_per_call", 1)),
    }
    wrote = prom.promote(
        OUT, topo, entry, float(best["value"]), maximize=True,
        provenance={"mfu": best.get("mfu"), "ts": best.get("ts"),
                    "tag": best.get("tag"), "device": best.get("device"),
                    "metric": best.get("metric")})
    if not wrote:
        print("promote: best %.1f does not beat the promoted value for "
              "%s by >2%% — keeping current defaults"
              % (best["value"], topo))
        return 0
    print("promote: %s <- %s (%.1f imgs/sec, mfu %s)"
          % (topo,
             {k: entry[k] for k in ("batch", "stem", "opt", "remat")},
             best["value"], best.get("mfu")))
    return 0


if __name__ == "__main__":
    sys.exit(main())
