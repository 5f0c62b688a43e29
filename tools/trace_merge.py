#!/usr/bin/env python
"""Merge traces onto ONE timeline — two modes:

* **host + xplane** (the classic positional form): stitch an
  ``mx.profiler`` chrome-trace with the XLA device (xplane) capture.
* **--spans** (mxnet_tpu.tracing; docs/OBSERVABILITY.md): stitch the
  per-process span journals a traced cluster job leaves in
  ``MXNET_TRACE_DIR`` (``<role>-<rank>.trace.jsonl``) into one
  chrome://tracing JSON — one process track per file, parent/child
  spans nested per thread, and CROSS-PROCESS edges drawn as flow
  arrows keyed by trace_id, so a push reads as worker→server→ack and a
  failover's rebuild window sits on the same axis as the barrier parks
  it stalled.  Per-process clock offset is estimated from envelope
  send/recv pairs: each server-side span carries the client's send
  stamp (``client_send_us``), and min(child start − parent send) over
  the pairs between two processes approximates their skew (network
  delay only ever inflates it, so the min is the tight bound).

Usage:
    python tools/trace_merge.py profile.json <xplane-logdir-or-file> \
        -o merged_trace.json
    python tools/trace_merge.py --spans $MXNET_TRACE_DIR \
        -o merged_trace.json

xplane mode detail (completes the §5.1 profiling story — SURVEY.md:
"emit the same chrome-trace JSON from the host-side scheduler + merge
XLA/TPU profiler (xplane) traces"): ``mx.profiler`` dumps host dispatch
events as chrome://tracing JSON and captures the device xplane; this
tool reads both and writes a single chrome-trace file where each device
plane/line appears as its own process/thread row next to the host rows
— open in chrome://tracing or Perfetto and see dispatch latency above
the device ops it launched.  Alignment: xplane event offsets are
relative to each plane's start; chrome ts is absolute µs.  Device rows
are placed on the host timeline using the xplane's own start timestamp
when present, else aligned so the first device event starts at the
first host event (documented in the output metadata,
"clock_alignment").
"""
import argparse
import glob
import json
import os
import sys


def find_xplane(path):
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(os.path.join(
        path, "**", "*.xplane.pb"), recursive=True))
    if not hits:
        raise SystemExit("no .xplane.pb under %s" % path)
    return hits[-1]  # latest run


def load(path):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    sp = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        sp.ParseFromString(f.read())
    return sp


def device_planes(space):
    """TPU device planes (or CPU-host XLA planes when no TPU present)."""
    tpu = [p for p in space.planes if "/device:TPU" in p.name]
    if tpu:
        return tpu
    return [p for p in space.planes if "Host Threads" not in p.name
            and p.lines]


def xplane_events(space, pid_base):
    """XSpace → chrome trace events; one pid per DEVICE plane (the
    xplane's own Host Threads plane is excluded — mx.profiler's rows are
    the host story, duplicating it mislabeled as device time would lie),
    one tid per line.  ``pid_base`` must sit above every host pid so a
    plane row can never collide with (and relabel) a host process row.

    Each event carries a private ``_anchored`` flag: True when its line
    had a real ``timestamp_ns`` (offsets live on a host-comparable
    clock), False when offsets are only line-relative.  The caller
    aligns unanchored lines and strips the flag before writing."""
    events = []
    meta = []
    for pi, plane in enumerate(device_planes(space)):
        if not plane.lines:
            continue
        pid = pid_base + pi
        meta.append({"ph": "M", "pid": pid, "name": "process_name",
                     "args": {"name": "device: %s" % plane.name}})
        ev_meta = plane.event_metadata
        for line in plane.lines:
            tid = int(line.id) % 100000
            meta.append({"ph": "M", "pid": pid, "tid": tid,
                         "name": "thread_name",
                         "args": {"name": line.name or str(line.id)}})
            # line.timestamp_ns anchors the line's offsets to a clock
            base_us = line.timestamp_ns / 1e3
            for ev in line.events:
                events.append({
                    "name": ev_meta[ev.metadata_id].name,
                    "cat": "device", "ph": "X",
                    "ts": base_us + ev.offset_ps / 1e6,
                    "dur": max(ev.duration_ps / 1e6, 0.001),
                    "pid": pid, "tid": tid,
                    "_anchored": bool(line.timestamp_ns),
                })
    return events, meta


# -- span-journal stitching (mxnet_tpu.tracing) ------------------------------
def read_spans(path):
    """Torn-line-tolerant ``*.trace.jsonl`` reader — standalone twin of
    mxnet_tpu.tracing.read_trace_file, duplicated deliberately: this
    tool must not import the package (a DMLC_ROLE=server environment
    would enter the blocking server loop at import, and jax is a heavy
    dependency for a log stitcher)."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue   # torn tail from a SIGKILL mid-append
            if isinstance(rec, dict) and "span" in rec:
                out.append(rec)
    return out


def span_input_files(inputs):
    """Expand the --spans inputs: a directory means every
    ``*.trace.jsonl`` inside it, sorted for stable pid assignment."""
    files = []
    for p in inputs:
        if os.path.isdir(p):
            files.extend(sorted(
                os.path.join(p, f) for f in os.listdir(p)
                if f.endswith(".trace.jsonl")))
        else:
            files.append(p)
    return files


def estimate_clock_offsets(procs, index):
    """Per-process clock offset (µs) relative to the first process,
    from envelope send/recv pairs: a server-side span's start minus the
    ``client_send_us`` its envelope carried is ``skew + network delay``
    — delay is nonnegative, so min over the pairs between two processes
    is the tight skew bound.  Processes with no pair-path to the
    reference keep offset 0 (same-host anchors are already epoch-
    aligned by mxnet_tpu.tracing)."""
    edges = {}   # (parent_pid, child_pid) -> min(child_ts - send_us)
    for _label, pid, recs in procs:
        for rec in recs:
            args = rec.get("args") or {}
            send_us = args.get("client_send_us")
            parent = rec.get("parent")
            if send_us is None or not parent:
                continue
            phit = index.get((rec.get("trace"), parent))
            if phit is None or phit[1] == pid:
                continue
            key = (phit[1], pid)
            delta = float(rec["ts"]) - float(send_us)
            if key not in edges or delta < edges[key]:
                edges[key] = delta
    # BFS from the reference pid over the (bidirectional) pair graph
    adj = {}
    for (ppid, cpid), delta in edges.items():
        adj.setdefault(ppid, []).append((cpid, delta))
        adj.setdefault(cpid, []).append((ppid, -delta))
    offsets = {}
    if procs:
        ref = procs[0][1]
        offsets[ref] = 0.0
        frontier = [ref]
        while frontier:
            cur = frontier.pop()
            for nxt, delta in adj.get(cur, ()):
                if nxt not in offsets:
                    offsets[nxt] = offsets[cur] + delta
                    frontier.append(nxt)
    return offsets


def merge_spans(paths):
    """Stitch per-process span journals into one chrome-trace dict:
    per-process tracks (pid = file order), X slices per span, flow
    arrows (``ph: s``/``f``) for every parent→child edge that crosses
    processes, clock-offset-adjusted timestamps."""
    procs = []
    index = {}   # (trace, span_id) -> (record, pid)
    for i, path in enumerate(paths):
        recs = read_spans(path)
        label = os.path.basename(path)
        if label.endswith(".trace.jsonl"):
            label = label[:-len(".trace.jsonl")]
        pid = 1 + i
        procs.append((label, pid, recs))
        for rec in recs:
            index[(rec.get("trace"), rec.get("span"))] = (rec, pid)
    offsets = estimate_clock_offsets(procs, index)
    events, meta = [], []
    flows = 0
    for label, pid, recs in procs:
        meta.append({"ph": "M", "pid": pid, "name": "process_name",
                     "args": {"name": label}})
        shift = offsets.get(pid, 0.0)
        tids = set()
        for rec in recs:
            tid = int(rec.get("tid", 0))
            tids.add(tid)
            args = dict(rec.get("args") or {})
            args.update({"trace": rec.get("trace"),
                         "span": rec.get("span")})
            if rec.get("parent"):
                args["parent"] = rec["parent"]
            events.append({
                "name": rec.get("name", "?"),
                "cat": rec.get("cat", "span"), "ph": "X",
                "ts": float(rec["ts"]) - shift,
                "dur": max(float(rec.get("dur", 0.0)), 0.001),
                "pid": pid, "tid": tid, "args": args,
            })
        for tid in sorted(tids):
            meta.append({"ph": "M", "pid": pid, "tid": tid,
                         "name": "thread_name",
                         "args": {"name": "tid %d" % tid}})
    # cross-process flow arrows, one per parent->child edge whose ends
    # live in different processes (in-process edges read off nesting)
    for label, pid, recs in procs:
        shift = offsets.get(pid, 0.0)
        for rec in recs:
            parent = rec.get("parent")
            if not parent:
                continue
            phit = index.get((rec.get("trace"), parent))
            if phit is None or phit[1] == pid:
                continue
            prec, ppid = phit
            pshift = offsets.get(ppid, 0.0)
            flows += 1
            fid = "%s:%s" % (rec.get("trace"), rec.get("span"))
            events.append({
                "ph": "s", "id": fid, "name": "trace", "cat": "flow",
                "pid": ppid, "tid": int(prec.get("tid", 0)),
                "ts": float(prec["ts"]) - pshift,
            })
            events.append({
                "ph": "f", "bp": "e", "id": fid, "name": "trace",
                "cat": "flow", "pid": pid,
                "tid": int(rec.get("tid", 0)),
                "ts": float(rec["ts"]) - shift,
            })
    return {
        "traceEvents": meta + events,
        "displayTimeUnit": "ms",
        "metadata": {
            "mode": "spans",
            "files": [lbl for lbl, _pid, _recs in procs],
            "spans": sum(len(r) for _l, _p, r in procs),
            "cross_process_flows": flows,
            "clock_offsets_us": {
                lbl: offsets.get(pid, 0.0) for lbl, pid, _r in procs},
        },
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("inputs", nargs="+",
                    help="host_trace + xplane (classic mode), or span "
                         "journal files/dirs with --spans")
    ap.add_argument("--spans", action="store_true",
                    help="inputs are mxnet_tpu.tracing span journals "
                         "(*.trace.jsonl files or MXNET_TRACE_DIR "
                         "directories); stitch them into one chrome "
                         "trace with cross-process flow arrows")
    ap.add_argument("-o", "--out", default="merged_trace.json")
    a = ap.parse_args()

    if a.spans:
        files = span_input_files(a.inputs)
        if not files:
            print("trace_merge: no *.trace.jsonl files under %r"
                  % (a.inputs,), file=sys.stderr)
            return 1
        merged = merge_spans(files)
        with open(a.out, "w") as f:
            json.dump(merged, f)
        md = merged["metadata"]
        print("wrote %s (%d spans from %d processes, %d cross-process "
              "flows)" % (a.out, md["spans"], len(md["files"]),
                          md["cross_process_flows"]))
        return 0

    if len(a.inputs) != 2:
        print("trace_merge: classic mode takes exactly 2 inputs: "
              "host_trace xplane (got %d)" % len(a.inputs),
              file=sys.stderr)
        return 2
    a.host_trace, a.xplane = a.inputs

    with open(a.host_trace) as f:
        host = json.load(f)
    host_events = host.get("traceEvents", host)

    host_pids = [e.get("pid", 0) for e in host_events
                 if isinstance(e, dict)]
    pid_base = max(host_pids, default=0) + 1000
    space = load(find_xplane(a.xplane))
    dev_events, meta = xplane_events(space, pid_base)

    notes = []
    host_ts = [e["ts"] for e in host_events if e.get("ph") == "X"]
    if host_ts and dev_events:
        host_min = min(host_ts)
        # unanchored lines (timestamp_ns == 0): offsets are only
        # line-relative — align each line's first event to the first
        # host event, PER LINE (one global shift computed from the
        # minimum would fling correctly anchored lines out of view)
        groups = {}
        for e in dev_events:
            if not e["_anchored"]:
                key = (e["pid"], e["tid"])
                groups.setdefault(key, []).append(e)
        for key, evs in groups.items():
            shift = host_min - min(e["ts"] for e in evs)
            for e in evs:
                e["ts"] += shift
        if groups:
            notes.append("%d unanchored line(s) aligned to first host "
                         "event" % len(groups))
        # anchored lines whose clock lives in a different epoch than the
        # host clock (perf_counter vs unix): shift them as one block so
        # their cross-line relations survive
        anchored = [e for e in dev_events if e["_anchored"]]
        if anchored:
            amin = min(e["ts"] for e in anchored)
            if abs(amin - host_min) > 3600e6:
                shift = host_min - amin
                for e in anchored:
                    e["ts"] += shift
                notes.append("anchored planes shifted %.0f us "
                             "(clock epoch mismatch)" % shift)
    for e in dev_events:
        e.pop("_anchored", None)
    alignment = "; ".join(notes) if notes else "xplane line timestamps"

    merged = {
        "traceEvents": meta + list(host_events) + dev_events,
        "displayTimeUnit": "ms",
        "metadata": {"clock_alignment": alignment,
                     "host_events": len(host_events),
                     "device_events": len(dev_events)},
    }
    with open(a.out, "w") as f:
        json.dump(merged, f)
    print("wrote %s (%d host + %d device events; %s)"
          % (a.out, len(host_events), len(dev_events), alignment))
    return 0


if __name__ == "__main__":
    sys.exit(main())
