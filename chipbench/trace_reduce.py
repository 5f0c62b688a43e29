"""From a profiler trace to numbers: device busy time, time per operation
and per named kernel, and the idle gaps with what the host was doing.

The file is ``<dir>/plugins/profile/<time>/*.xplane.pb``, read with jax
alone.  A device plane ``/device:TPU:<n>`` has the lines ``XLA Ops``
(serial: the union of its events is busy time) and ``Async XLA Ops``
(overlapping copies whose sum far exceeds the span: never added).  Host
spans (``jax.profiler.TraceAnnotation``: the program's ``mx.*`` and the
yardstick's ``chipbench.*``) are events on the lines of plane
``/host:CPU``, on the same clock.

``read_planes`` turns the file into plain dicts and lists, which is what
``reduce_planes`` works on and what the recorded sample beside this file
(``trace_sample.json``) holds."""
import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
# host spans: the program's own (mxnet_tpu.tracing.span) and the
# yardstick's, around its calls into the program
PROGRAM_PREFIX = "mx."
SPAN_PREFIX = "chipbench."
NO_SPAN = "(no span)"
PLANE_PEAK_STATS = ("peak_teraflops_per_second",
                    "peak_hbm_bw_gigabytes_per_second")


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError("no *.xplane.pb under %s/plugins/profile/*/"
                                % trace_dir)
    return found[-1]


def read_planes(path):
    """[{"name", "stats": {..}, "lines": [{"name", "events": [[name,
    start_ns, duration_ns], ..]}]}] for the device and host planes."""
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        if not (DEVICE_PLANE.match(plane.name) or plane.name == HOST_PLANE):
            continue
        host = plane.name == HOST_PLANE
        lines = []
        for line in plane.lines:
            if not host and line.name != OPS_LINE:
                continue
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if not host or e.name.startswith((PROGRAM_PREFIX,
                                                        SPAN_PREFIX))]
            if events:
                lines.append({"name": line.name, "events": events})
        stats = {}
        if not host:
            stats = {k: v for k, v in plane.stats if k in PLANE_PEAK_STATS}
        planes.append({"name": plane.name, "stats": stats, "lines": lines})
    return planes


def op_token(event_name):
    """``%fusion.64 = bf16[..]{..} fusion(..), kind=kLoop`` -> fusion.64"""
    return event_name.lstrip("%").split(" ", 1)[0]


def short_name(event_name):
    """``<hlo name> <result shape> <fusion kind>``: short and stable, not
    the kilobytes of the whole HLO line."""
    parts = [op_token(event_name)]
    _, eq, rest = event_name.partition(" = ")
    if eq:
        shape = re.match(r"\(?\s*([a-z0-9]+\[[0-9,]*\])", rest)
        if shape:
            parts.append(shape.group(1))
    kind = re.search(r"kind=(k\w+)", event_name)
    if kind:
        parts.append(kind.group(1))
    return " ".join(parts)


def union(intervals):
    """Merged, sorted [start, end] intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def span_over(spans, t0, t1, least=0.0):
    """Name of the span ([name, start, duration, ..]) covering most of
    [t0, t1], and more than ``least`` of it; of spans covering the same,
    the innermost (the shortest); None where there is none."""
    best, best_key = None, (least, 0.0)
    for e in spans:
        cover = min(t1, e[1] + e[2]) - max(t0, e[1])
        if cover > 0 and (cover, -e[2]) > best_key:
            best, best_key = e[0], (cover, -e[2])
    return best


def gap_name(spans, t0, t1):
    """What the host was doing over [t0, t1]: the innermost ``mx.*`` span
    that covers most of it (over half), which says where in the program
    the device was left waiting; where the program had none open, the
    ``chipbench.*`` span, which says where in the yardstick's loop."""
    program = [e for e in spans if e[0].startswith(PROGRAM_PREFIX)]
    return (span_over(program, t0, t1, least=0.5 * (t1 - t0))
            or span_over(spans, t0, t1) or NO_SPAN)


def reduce_planes(planes, steps, kernel_prefixes=()):
    """Busy time is the union of each device plane's ``XLA Ops`` events,
    averaged over the planes; the window runs from the first such event to
    the end of the last, on any plane.  Times are in seconds over the
    window; ``steps`` (whole steps inside it) only labels the result.
    Returns None when no operation ran on a device."""
    device = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    spans = [e for p in planes if p["name"] == HOST_PLANE
             for ln in p["lines"] for e in ln["events"]
             if e[0].startswith((PROGRAM_PREFIX, SPAN_PREFIX))]
    ops_by_plane = [[e for ln in p["lines"] if ln["name"] == OPS_LINE
                     for e in ln["events"]] for p in device]
    ops_by_plane = [ops for ops in ops_by_plane if ops]
    if not ops_by_plane:
        return None
    n = len(ops_by_plane)
    t0 = min(s for ops in ops_by_plane for _, s, _ in ops)
    t1 = max(s + d for ops in ops_by_plane for _, s, d in ops)
    busy_ns, op_ns, op_label = 0.0, {}, {}
    kernel_ns = {k: 0.0 for k in kernel_prefixes}
    kernel_calls = {k: 0 for k in kernel_prefixes}
    for ops in ops_by_plane:
        busy_ns += sum(e - s for s, e in union([s, s + d] for _, s, d in ops))
        for name, _, d in ops:
            token = op_token(name)
            key = token
            for k in kernel_prefixes:
                if token.startswith(k):
                    kernel_ns[k] += d
                    kernel_calls[k] += 1
                    key = k
            op_ns[key] = op_ns.get(key, 0.0) + d
            if key not in op_label:
                label = short_name(name)
                op_label[key] = label if key == token else \
                    label.replace(token, key + ".*", 1)
    merged = union([s, s + d] for _, s, d in ops_by_plane[0])
    gaps = sorted(((b[0] - a[1], a[1], b[0])
                   for a, b in zip(merged, merged[1:])), reverse=True)
    top = sorted(op_ns.items(), key=lambda kv: -kv[1])
    return {
        "steps": int(steps), "device_planes": n,
        "window_s": (t1 - t0) / 1e9, "busy_s": busy_ns / n / 1e9,
        "device_ops": [[op_label[k], v / n / 1e9] for k, v in top[:10]],
        "idle_gaps": [[gap_name(spans, s, e), g / 1e9]
                      for g, s, e in gaps[:5]],
        "kernel_s": {k: v / n / 1e9 for k, v in kernel_ns.items()},
        "kernel_calls": {k: v // n for k, v in kernel_calls.items()},
        "plane_peaks": device[0]["stats"] if device else {},
    }


def reduce_file(path, steps, kernel_prefixes=()):
    return reduce_planes(read_planes(path), steps, kernel_prefixes)


def cut(planes, t0_ns, t1_ns, name_chars=100):
    """The device events that start inside [t0_ns, t1_ns), their names
    (whole HLO lines, kilobytes each) cut to what ``short_name`` reads, and
    the host spans that overlap it (a gap is named by a span that may have
    opened long before): how the recorded sample was made from a whole
    trace."""
    def compact(name):
        kind = re.search(r"kind=k\w+", name[name_chars:])
        return name[:name_chars] + (" ... " + kind.group(0) if kind else "")

    def keep(plane, s, d):
        if plane["name"] == HOST_PLANE:
            return s < t1_ns and s + d > t0_ns
        return t0_ns <= s < t1_ns

    return [{"name": p["name"], "stats": p["stats"], "lines": [
        {"name": ln["name"], "events": [[compact(n), s, d]
                                        for n, s, d in ln["events"]
                                        if keep(p, s, d)]}
        for ln in p["lines"]]} for p in planes]


if __name__ == "__main__":
    # python3 -m chipbench.trace_reduce <trace dir> [<from ms> <to ms> <out>]
    planes_ = read_planes(find_xplane(sys.argv[1]))
    if len(sys.argv) > 2:
        ops_ = [e for p in planes_ if DEVICE_PLANE.match(p["name"])
                for ln in p["lines"] for e in ln["events"]]
        base = min(e[1] for e in ops_)
        planes_ = cut(planes_, base + float(sys.argv[2]) * 1e6,
                      base + float(sys.argv[3]) * 1e6)
        with open(sys.argv[4], "w") as f:
            json.dump(planes_, f, separators=(",", ":"))
    print(json.dumps(reduce_planes(planes_, steps=0), indent=1))
