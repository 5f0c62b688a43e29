"""The program's own spans and scopes, read from the traced slice's trace.

``trace_reduce.py`` reads what any jitted program leaves in a profiler
trace; this file reads what ``mxnet_tpu`` writes into it on purpose
(docs/OBSERVABILITY.md): a ``jax.named_scope`` per symbol node and one
around the optimizer, which reach every device operation's ``op_name``,
and the ``mx.*`` host spans of ``mxnet_tpu.tracing.span``, which sit on
the ``/host:CPU`` plane of the same file and so on the same clock.

Where ``op_name`` is (looked at by hand, jax 0.9.0 / libtpu 0.0.34, TPU v5
lite, PR 25): not on the event.  An ``XLA Ops`` event carries three stats
(``device_offset_ps``, ``device_duration_ps``, ``Time Scale Multiplier``)
and its name is the HLO instruction's text without metadata.  The path is
the ``tf_op`` stat of the event's *metadata* entry (``XPlane.event_metadata``,
one per HLO instruction, beside ``hlo_category``, ``flops``, ``source`` ..),
with a colon after it:
``jit(mx_fused_step)/transpose(jvp(layer3_ffn1))/dot_general:``.
``jax.profiler.ProfileData`` shows an event's own stats only, so
``metadata_scopes`` reads that one map out of the file with a protobuf
wire reader of its own (``_fields``: the ``lines`` are skipped unread) and
``read_planes`` joins it to ProfileData's events by name.  A fusion
carries its root instruction's path; a Pallas custom call carries its
``pallas_call``'s (``.../jvp(layer19_flash)/jit(_flash_fwd)/flash_fwd/
pallas_call:``); copies and async starts that XLA inserts carry none.

Phase of a device event, from its path: ``optimizer`` if a component is
``optimizer`` or ``param_constraint``, else ``backward`` if the path has
``transpose(``, else ``forward`` if it names a node, else ``unscoped``.
The node is the inside of the path's first scope that is neither a
``jit(..)`` (bare or under a transform: ``jvp(jit(_var))``), nor empty
(``jvp()``: what a program without named scopes leaves), nor jax's own
structure (``while``, ``body``, ``checkpoint`` ..): ``transpose(jvp(conv1))``
names ``conv1``; the last component is the primitive and names nothing.

Every function returns None, and raises nothing, where the program wrote
no such span or scope (the parent of PR 25, or a rehearsal without a
trace): the metric that reads it is then left out of the line.

    python3 -m chipbench.program_trace <trace dir> [steps] [cell]

prints the report of one trace directory (``<dir>/plugins/profile/*/``),
per step where ``steps`` is given, with device time by operator where
``cell`` names a cell whose family can build the symbol.  An operator gets
the same picture without chipbench by tracing with the program's own
switch: ``profiler_set_config(xla_logdir=<dir>)``,
``profiler_set_state('run')`` ... ``('stop')``, then this command."""
import glob
import json
import os
import re
import sys

from . import common
from .trace_reduce import (DEVICE_PLANE, HOST_PLANE, OPS_LINE,
                           PROGRAM_PREFIX, SPAN_PREFIX, gap_name, union)

PHASES = ("forward", "backward", "optimizer", "unscoped")
OPTIMIZER_SCOPES = ("optimizer", "param_constraint")
# the stat of an event's metadata that holds the HLO's op_name
SCOPE_STAT = "tf_op"
# scopes that are jax's own structure, not a node of the symbol
_STRUCTURE = re.compile(r"^(pjit|while|body|cond|branch_\d+_fun|checkpoint|"
                        r"rematted_computation|remat)$")
_OP_NAME = re.compile(r'op_name="([^"]+)"')
_CACHE_KEY = "_program_trace"


def process_started():
    """Epoch seconds at which this process started, or None where /proc
    does not say."""
    try:
        return os.stat("/proc/%d" % os.getpid()).st_ctime
    except OSError:
        return None


def find_xplane(out_dir=None, started=None):
    """The newest ``*.xplane.pb`` under ``<out_dir>/trace/*/plugins/
    profile/*/``: the traced slice this process has just written (the
    record does not carry its directory).  A file older than ``started``
    (this process, by default) is another run's: FileNotFoundError."""
    out_dir = common.OUT_DIR if out_dir is None else out_dir
    found = glob.glob(os.path.join(out_dir, "trace", "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    if not found:
        raise FileNotFoundError("no *.xplane.pb under %s/trace/*/plugins/"
                                "profile/*/" % out_dir)
    newest = max(found, key=os.path.getmtime)
    started = process_started() if started is None else started
    if started is not None and os.path.getmtime(newest) < started:
        raise FileNotFoundError(
            "the newest trace, %s, is older than this process: a stale file "
            "of another run" % newest)
    return newest


def _varint(buf, i):
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview, not parsed, for anything with a length."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError("protobuf wire type %d" % wire)
        yield key >> 3, value


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def metadata_scopes(path):
    """{plane name: {event name: scope path}} from the ``tf_op`` stat of
    each plane's event metadata (tsl/profiler/protobuf/xplane.proto:
    XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 and
    .stat_metadata = 5, maps of id to message; XEventMetadata.name = 2,
    .stats = 5; XStat.metadata_id = 1, .str_value = 5, .ref_value = 7;
    XStatMetadata.name = 2)."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    out = {}
    for number, plane in _fields(data):
        if number != 1:
            continue
        name, events, stat_names = None, [], {}
        for number, value in _fields(plane):
            if number == 2:
                name = _text(value)
            elif number == 4:
                events.append(value)
            elif number == 5:
                entry = dict(_fields(value))
                stat_names[entry[1]] = _text(dict(_fields(entry[2]))[2])
        want = {k for k, v in stat_names.items() if v == SCOPE_STAT}
        scopes = {}
        for entry in events:
            event_name = None
            for number, value in _fields(dict(_fields(entry))[2]):
                if number == 2:
                    event_name = _text(value)
                if number != 5:
                    continue
                stat = dict(_fields(value))
                if stat.get(1) not in want:
                    continue
                if 5 in stat:
                    scope = _text(stat[5])
                else:       # a string kept once, in the stat metadata
                    scope = stat_names.get(stat.get(7), "")
                scope = scope.rsplit(":", 1)[0]
                if scope:       # the name (field 2) precedes the stats (5)
                    scopes[event_name] = scope
        out[name] = scopes
    return out


def _scope_path(name, scopes):
    """The scope path of the event ``name``: from its metadata, or from
    ``metadata={op_name="..."}`` inside the name, where a dump of the
    compiled HLO has it."""
    m = _OP_NAME.search(name)
    return m.group(1) if m else scopes.get(name)


def read_planes(path):
    """[{"name", "lines": [{"name", "events": [[name, start_ns,
    duration_ns, scope path or None], ..]}]}]: the ``XLA Ops`` line of
    every device plane, and of the host plane the ``mx.*`` and
    ``chipbench.*`` spans, thread by thread."""
    from jax.profiler import ProfileData
    by_plane = metadata_scopes(path)
    planes = []
    for plane in ProfileData.from_file(path).planes:
        host = plane.name == HOST_PLANE
        if not (host or DEVICE_PLANE.match(plane.name)):
            continue
        scopes = by_plane.get(plane.name, {})
        lines = []
        for line in plane.lines:
            if host:
                events = [[e.name, float(e.start_ns), float(e.duration_ns),
                           None] for e in line.events
                          if e.name.startswith((PROGRAM_PREFIX,
                                                SPAN_PREFIX))]
            elif line.name == OPS_LINE:
                events = [[e.name, float(e.start_ns), float(e.duration_ns),
                           _scope_path(e.name, scopes)]
                          for e in line.events]
            else:
                continue
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def _inside(component):
    """``transpose(jvp(conv1))`` -> ``conv1``."""
    return component.rsplit("(", 1)[-1].split(")", 1)[0]


def classify(path):
    """(phase, node or None) of one device event's scope path."""
    if not path:
        return "unscoped", None
    parts = path.split("/")
    if any(p in OPTIMIZER_SCOPES for p in parts):
        return "optimizer", None
    node = None
    for p in parts[:-1]:        # the last component is the primitive
        name = _inside(p)       # "" for jax's own jvp() with no scope in it
        # jit(_var), and jvp(jit(_var)) where no scope stands between
        inner_jit = p.rsplit("(", 1)[0].endswith("jit") and "(" in p
        if name and not inner_jit and not _STRUCTURE.match(name):
            node = name
            break
    if "transpose(" in path:
        return "backward", node
    return ("forward", node) if node is not None else ("unscoped", None)


def self_times(events):
    """{name: [total_ns, self_ns, count]} of one thread's spans: a span's
    self time is its duration less what its children cover.  Spans of one
    thread nest or follow one another; a child is charged to its nearest
    enclosing span."""
    out, stack = {}, []
    for name, start, dur, _ in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1]:
            stack.pop()
        if stack:
            out[stack[-1][0]][1] -= dur
        entry = out.setdefault(name, [0.0, 0.0, 0])
        entry[0] += dur
        entry[1] += dur
        entry[2] += 1
        stack.append((name, start + dur))
    return out


def summarize(planes, steps):
    """What the metrics and the report read, from ``read_planes``' form.
    Device times are sums over the ``XLA Ops`` events (the line is
    serial), averaged over the device planes; None when no operation ran
    on a device.  ``scoped_s`` is the time of events under a scope the
    program wrote (a node, or the optimizer's); jax's own ``jvp()`` and
    ``transpose(jvp())`` with nothing inside are none.  ``scoped`` is False
    when there is no such event: the device metrics are then left out,
    not 0."""
    device = [[e for ln in p["lines"] if ln["name"] == OPS_LINE
               for e in ln["events"]]
              for p in planes if DEVICE_PLANE.match(p["name"])]
    device = [ops for ops in device if ops]
    if not device:
        return None
    n = len(device)
    phase_ns = dict.fromkeys(PHASES, 0.0)
    node_ns, scoped_ns = {}, 0.0
    for ops in device:
        for name, _, dur, path in ops:
            phase, node = classify(path)
            phase_ns[phase] += dur
            if node is not None:
                node_ns[(phase, node)] = node_ns.get((phase, node), 0.0) + dur
            if node is not None or phase == "optimizer":
                scoped_ns += dur
    threads = [ln["events"] for p in planes if p["name"] == HOST_PLANE
               for ln in p["lines"]]
    spans = {}
    for events in threads:
        for name, (total, own, count) in self_times(events).items():
            entry = spans.setdefault(name, {"total_s": 0.0, "self_s": 0.0,
                                            "count": 0})
            entry["total_s"] += total / 1e9
            entry["self_s"] += own / 1e9
            entry["count"] += count
    every = [e for events in threads for e in events]
    merged = union([s, s + d] for _, s, d, _ in device[0])
    gaps = sorted(((b[0] - a[1], a[1], b[0])
                   for a, b in zip(merged, merged[1:])), reverse=True)[:5]
    return {
        "steps": int(steps), "device_planes": n,
        "scoped": scoped_ns > 0,
        "scoped_s": scoped_ns / n / 1e9,
        "phase_s": {k: v / n / 1e9 for k, v in phase_ns.items()},
        "node_s": sorted(([phase, node, v / n / 1e9]
                          for (phase, node), v in node_ns.items()),
                         key=lambda r: -r[2]),
        "spans": spans,
        "idle_gaps": [[gap_name(every, s, e), g / 1e9] for g, s, e in gaps],
    }


def of(record):
    """The summary of this run's traced slice, read once and kept on the
    record; None where the run was not traced or nothing can be read."""
    trace = record.get("trace")
    if not trace or not trace.get("steps"):
        return None
    if _CACHE_KEY not in record:
        record[_CACHE_KEY] = None
        try:
            path = find_xplane()
            record[_CACHE_KEY] = summarize(read_planes(path), trace["steps"])
        except Exception as e:  # noqa: BLE001 -- a reader never fails a run
            print("[program_trace] nothing read: %r" % (e,), file=sys.stderr,
                  flush=True)
        else:
            report(record[_CACHE_KEY], node_ops(_cell_of(path)))
    return record[_CACHE_KEY]


def device_ms_per_step(record, phase):
    """ms a step of device time in ``phase``; None unless the trace has
    scopes (never 0 for a program that wrote none)."""
    got = of(record)
    if got is None or not got["scoped"]:
        return None
    return got["phase_s"][phase] / got["steps"] * 1e3


def span_ms_per_step(record, name):
    """ms a step inside the host span ``name``, children included; None
    where the program has no such span."""
    got = of(record)
    if got is None or name not in got["spans"]:
        return None
    return got["spans"][name]["total_s"] / got["steps"] * 1e3


def span_count_per_step(record, name):
    """How often a step enters the host span ``name``: 0 where the
    program has ``mx.*`` spans and this one never ran, None where it has
    none at all."""
    got = of(record)
    if got is None or not any(k.startswith(PROGRAM_PREFIX)
                              for k in got["spans"]):
        return None
    return got["spans"].get(name, {"count": 0})["count"] / got["steps"]


def phase_seconds(name):
    """The first occurrence of set-up phase ``name`` in this process, by
    the program's always-on clock; None where the program keeps none."""
    from mxnet_tpu import profiler
    clock = getattr(profiler, "phase_seconds", None)
    seconds = clock().get(name) if clock else None
    return seconds[0] if seconds else None


def _cell_of(xplane_path):
    """``<out>/trace/<cell>/plugins/profile/<time>/x.xplane.pb`` -> cell."""
    parts = os.path.normpath(xplane_path).split(os.sep)
    return parts[-5] if len(parts) >= 5 and parts[-4] == "plugins" else None


def node_ops(cell):
    """{node name: operator} of the cell's symbol, asked of the program as
    the driver asks (``family.build``); None where the cell's files are
    not the package's own."""
    if not cell:
        return None
    import mxnet_tpu as mx
    from .harness import Resolver
    try:
        _, config, traffic, family, _ = Resolver().cell(cell)
        # nodes named by the counter (_plus0, reshape3 ..) get the names
        # the driver's own first build gave them
        with mx.name.NameManager():
            symbol = family.build(config, traffic)["symbol"]
        return {n["name"]: n["op"]
                for n in json.loads(symbol.tojson())["nodes"]
                if n["op"] != "null"}
    except Exception as e:  # noqa: BLE001 -- the table is left out
        print("[program_trace] no operator table for cell %r: %r"
              % (cell, e), file=sys.stderr, flush=True)
        return None


def report(got, ops=None, out=None):
    """The traced slice as the program sees it, on ``out`` (stderr)."""
    if got is None:
        return
    out = sys.stderr if out is None else out
    steps = max(1, got["steps"])

    def ms(s):
        return s / steps * 1e3

    def say(msg):
        print("[program_trace] " + msg, file=out, flush=True)

    if not got["scoped"]:
        say("no device event carries a program scope: the device split is "
            "left out (a program older than its named scopes, or an "
            "executable cached without them)")
    else:
        say("device ms a step over %d steps: %s; scoped %.2f %%"
            % (got["steps"],
               ", ".join("%s %.3f" % (p, ms(got["phase_s"][p]))
                         for p in PHASES),
               100.0 * got["scoped_s"] / sum(got["phase_s"].values())))
        for phase in ("forward", "backward"):
            rows = [r for r in got["node_s"] if r[0] == phase][:15]
            say("heaviest %s nodes, ms a step: %s"
                % (phase, ", ".join("%s %.3f" % (node, ms(s))
                                    for _, node, s in rows)))
        if ops:
            by_op = {}
            for phase, node, s in got["node_s"]:
                key = (ops.get(node, "(not a node)"), phase)
                by_op[key] = by_op.get(key, 0.0) + s
            say("device ms a step by operator: %s" % ", ".join(
                "%s %s %.3f" % (op, phase, ms(s)) for (op, phase), s
                in sorted(by_op.items(), key=lambda kv: -kv[1])))
    for name in sorted(got["spans"]):
        sp = got["spans"][name]
        say("span %-34s %9.4f ms a step, self %9.4f, %6.2f a step"
            % (name, ms(sp["total_s"]), ms(sp["self_s"]),
               sp["count"] / steps))
    say("longest device idle gaps: %s" % ", ".join(
        "%s %.1f us" % (name, s * 1e6) for name, s in got["idle_gaps"]))


if __name__ == "__main__":
    found_ = sorted(glob.glob(os.path.join(
        sys.argv[1], "plugins", "profile", "*", "*.xplane.pb")))
    if not found_:
        sys.exit("no *.xplane.pb under %s/plugins/profile/*/" % sys.argv[1])
    report(summarize(read_planes(found_[-1]),
                     int(sys.argv[2]) if len(sys.argv) > 2 else 1),
           node_ops(sys.argv[3] if len(sys.argv) > 3 else None),
           out=sys.stdout)
