"""Argument parsing, name resolution, the set-up clock and the final line.

Nothing here lists a cell, a configuration, a family, a driver or a
metric: each is a file found by its name under a root (``--root`` first,
then this package), and a missing one fails naming the path."""
import argparse
import importlib.util
import json
import os
import shutil
import time
import traceback
import types

from . import common

EXT = {"cells": ".json", "configs": ".json", "traffic": ".json",
       "families": ".py", "drivers": ".py", "layer_metrics": ".py"}


class Resolver:
    """Finds ``<root>/<kind>/<name><ext>`` in the first root that has it."""

    def __init__(self, roots=()):
        self.roots = [os.path.abspath(r) for r in roots if r]
        if common.PKG_DIR not in self.roots:
            self.roots.append(common.PKG_DIR)
        self._modules = {}

    def path(self, kind, name):
        tried = [os.path.join(r, kind, name + EXT[kind]) for r in self.roots]
        for p in tried:
            if os.path.isfile(p):
                return p
        raise FileNotFoundError(
            "chipbench: no %s named %r: looked for %s"
            % (kind, name, " and ".join(tried)))

    def data(self, kind, name):
        with open(self.path(kind, name)) as f:
            return json.load(f)

    def module(self, kind, name):
        path = self.path(kind, name)
        if path not in self._modules:
            spec = importlib.util.spec_from_file_location(
                "chipbench_%s_%s" % (kind, name.replace(".", "_")
                                     .replace("-", "_")), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._modules[path] = mod
        return self._modules[path]

    def names(self, kind):
        found = set()
        for r in self.roots:
            d = os.path.join(r, kind)
            if os.path.isdir(d):
                found.update(f[:-len(EXT[kind])] for f in os.listdir(d)
                             if f.endswith(EXT[kind])
                             and not f.startswith("_"))
        return sorted(found)

    def cell(self, name):
        """The cell's files, read: (cell, config, traffic, family module,
        driver module)."""
        cell = self.data("cells", name)
        config = self.data("configs", cell["config"])
        traffic = self.data("traffic", cell["traffic"])
        return (cell, config, traffic,
                self.module("families", config["family"]),
                self.module("drivers", traffic["driver"]))


class CompileClock:
    """What the process spent tracing, lowering and compiling, from jax's
    own event stream (the listeners of chip_smoke.py, copied), kept apart
    for set-up, the window and what comes after: the driver sets ``phase``."""

    def __init__(self):
        self.phase = "setup"
        self.t = {p: {"backend_compile_s": 0.0, "trace_lower_s": 0.0,
                      "backend_compiles": 0, "cache_hits": 0,
                      "cache_misses": 0} for p in ("setup", "window", "after")}

    def register(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        t = self.t[self.phase]
        if event.endswith("backend_compile_duration"):
            t["backend_compile_s"] += secs
            t["backend_compiles"] += 1
        elif event.endswith(("jaxpr_trace_duration",
                             "jaxpr_to_mlir_module_duration")):
            t["trace_lower_s"] += secs

    def _event(self, event, **_):
        t = self.t[self.phase]
        if event.endswith("compilation_cache/cache_hits"):
            t["cache_hits"] += 1
        elif event.endswith("compilation_cache/cache_misses"):
            t["cache_misses"] += 1


def device_info(devices):
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices, mark, when):
    """Peak bytes so far on the fullest device.  The TPU client keeps a
    program's temporaries in a reserved region that ``peak_bytes_in_use``
    does not count, and sizes it by the largest program loaded, so the two
    peaks are added and a driver reads them before any program but the
    system's own is loaded; the raw readings go to stderr.  A backend that
    reports nothing (the CPU) gives 0."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        if stats:
            mark("memory_stats of device %d, %s: %s"
                 % (d.id, when, json.dumps(stats)))
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def layer_metrics(resolver, record, mark):
    """Every ``layer_metrics/<name>.py`` reads the record; a reader that
    finds nothing returns None and its metric is left out.  A metric is
    chip-only unless its file says ``CHIP_ONLY = False``: on another
    platform it is "not measured", never a number."""
    out = {}
    on_chip = record["device"]["platform"] == "tpu"
    for name in resolver.names("layer_metrics"):
        mod = resolver.module("layer_metrics", name)
        if getattr(mod, "CHIP_ONLY", True) and not on_chip:
            mark("%s: not measured (needs the chip, this is %s)"
                 % (name, record["device"]["platform"]))
            continue
        value = mod.read(record)
        if value is None:
            continue
        out[name] = {"value": float(value), "unit": mod.UNIT}
    return out


def parse(argv):
    p = argparse.ArgumentParser(prog="python3 -m chipbench")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), required=True)
    p.add_argument("--root", default=None,
                   help="a directory of cells/, configs/, traffic/ ... "
                        "searched before the package's own")
    return p.parse_args(argv)


def run(args, t0):
    mark = common.make_mark("chipbench", t0)
    resolver = Resolver([args.root])
    cell, config, traffic, family, driver = resolver.cell(args.workload)

    # before jax is imported.  A rehearsal on the CPU keeps no cache: what
    # it compiles is not what the chip runs
    pinned = os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip()
    cache_dir = None if pinned.lower() == "cpu" \
        else common.place_compile_cache()
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.context import platform_pinned_to_cpu
    clock = CompileClock()
    clock.register()

    devices = jax.devices()
    info = device_info(devices)
    rehearsal = info["platform"] != "tpu"
    if rehearsal and not platform_pinned_to_cpu():
        raise SystemExit("chipbench: needs a TPU, but jax.devices()[0] is "
                         "platform %r (%s); nothing was run"
                         % (info["platform"], info["kind"]))
    if len(devices) < int(cell["chips"]):
        raise SystemExit("chipbench: cell %r needs %d chip(s), jax reports "
                         "%d device(s)" % (args.workload, cell["chips"],
                                           len(devices)))
    peaks = None if rehearsal else common.load_peaks(info["kind"])
    mark("device: %s x%d (%s)%s; cell %s; compile cache: %s"
         % (info["kind"], info["count"], info["platform"],
            " -- a CPU REHEARSAL, never a result" if rehearsal else "",
            args.workload, cache_dir))

    trace_dir = None
    if args.trace and not rehearsal:
        # a fixed path inside the checkout, emptied first
        trace_dir = os.path.join(common.OUT_DIR, "trace", args.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)

    used = devices[:int(cell["chips"])]
    record = driver.run(types.SimpleNamespace(
        cell=cell, config=config, traffic=traffic, family=family,
        seed=args.seed, seconds=args.seconds, trace_dir=trace_dir, t0=t0,
        mark=mark, clock=clock, peaks=peaks, rehearsal=rehearsal,
        memory_peak=lambda: memory_peak_bytes(used, mark, "the system's own"),
        contexts=[mx.tpu(i) for i in range(len(used))], devices=used))

    record["device"] = info
    record["peaks"] = peaks
    info["memory_peak_bytes"] = int(record["memory_peak_bytes"])
    memory_peak_bytes(used, mark, "the whole process, yardstick included")
    line = {"correct": bool(record["correct"]),
            "attempted": int(record["attempted"]),
            "failed": int(record["failed"])}
    if args.trace:
        line["metrics"] = layer_metrics(resolver, record, mark)
        trace = record.get("trace")
        if trace:
            info["busy_s"] = trace["busy_s"]
            info["window_s"] = trace["window_s"]
            line["breakdown"] = {"device_ops": trace["device_ops"][:10],
                                 "idle_gaps": trace["idle_gaps"][:5]}
    else:
        line["metrics"] = {k: {"value": float(v), "unit": u}
                           for k, (v, u) in record["end_to_end"].items()}
    line["device"] = info
    if rehearsal:
        line["rehearsal"] = "platform %s: not a result" % info["platform"]
    if "reference" in record:
        # not read by the driver.  The line's last key and stderr's last
        # line: each number the check compared, beside its limit
        line["reference"] = record["reference"]
        mark("compared, each beside its limit: %s; correct: %s"
             % (json.dumps(record["reference"]), line["correct"]))
    return line


def main(argv=None, t0=None):
    t0 = time.perf_counter() if t0 is None else t0
    args = parse(argv)
    try:
        # a NaN is no number as measured, and no JSON either
        line = json.dumps(run(args, t0), allow_nan=False)
    except Exception:
        # no result line for a run that did not finish
        traceback.print_exc()
        return 1
    print(line, flush=True)
    return 0
