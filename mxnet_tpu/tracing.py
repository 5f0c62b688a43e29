"""Dapper-style span tracing for the whole cluster (docs/OBSERVABILITY.md).

Every observability signal the repo had grown — profiler counters, wire
clocks, serving latency rings — was process-local; nothing could show a
push travel worker→server→ack or put a failover's rebuild window on a
timeline.  This module is the cross-process half (the span model of
Dapper, the production shape of TensorFlow's cross-process timelines,
arXiv:1605.08695; MXNet's engine-integrated profiler, arXiv:1512.01274):

* **Spans** — ``span_begin``/``span_end`` (or ``with span(...):``) with a
  thread-local current-span stack, so nested calls build a parent/child
  tree with zero caller plumbing.  Durations come from the MONOTONIC
  clock; wall-clock placement maps through a per-process anchor taken at
  import (``time.time_ns() - time.monotonic_ns()``), so a span's
  duration can never be warped by an NTP step mid-span.
* **Wire propagation** — ``current_ctx()`` is the (trace_id, span_id)
  pair the kvstore client stamps onto request envelopes
  (``kvstore._ServerConn``); the server opens a child span around its
  handling (``kvstore_server._serve_conn``), so one trace spans
  processes.  Replays re-send the ORIGINAL envelope, trace field
  included — a reconnect annotates the same trace instead of starting a
  new one.
* **Flush** — spans land in a bounded in-memory ring and, when
  ``MXNET_TRACE_DIR`` is set, append to
  ``<dir>/<role>-<rank>.trace.jsonl``: append-only, fsync'd every
  ``MXNET_TRACE_FLUSH_N`` spans (and at exit), torn-line tolerant on
  read — a SIGKILLed server loses at most the unflushed tail, never the
  file.  ``tools/trace_merge.py --spans`` stitches the per-process
  files into one chrome://tracing timeline with cross-process flow
  arrows.

Master switch: ``MXNET_TRACE=1``.  Off (the default) every entry point
returns before touching a lock or allocating a record — call sites guard
with ``tracing.enabled()`` or use ``span()`` — and the kvstore envelope
stays byte-identical to the untraced wire (pinned by
tests/test_tracing.py via ``profiler.channel_bytes``).

**One entry point, three sinks.**  ``span(name, cat)`` is the one call
every program site makes (``profiler.scope`` is its alias).  Beneath the
switch it always enters a ``jax.profiler.TraceAnnotation(name)``: while a
JAX profiler session is live (``jax.profiler.start_trace``, or
``profiler_set_state('run')`` with an ``xla_logdir``) the span lands on
the ``/host:CPU`` plane of the same ``.xplane.pb`` as the device
operations, on the same clock by construction.  The other two sinks are
host-side and share :func:`now_us`: the ring/journal above
(``MXNET_TRACE=1``), and the MXNet profiler's chrome-trace events while
it runs (``profiler`` registers itself through :func:`set_chrome_sink`).
Set-up phases (:func:`phase`) also feed an always-on clock,
:func:`phase_seconds`: a handful of entries a process, never per step.

**The compile ledger** (:func:`compile_records`), always on like the
phase clock: jax's own trace, lowering and backend-compile events, one
record each, named by the program jax names and filed under the innermost
set-up phase open on the thread.  Nested events of one stage fold into
the record that encloses them, so every sum over the ledger is a union.
"""
from __future__ import annotations

import atexit
import json
import os
import threading
import time
import uuid
from collections import deque
from typing import Optional

from jax.profiler import TraceAnnotation

from .base import env

# wall-clock anchor for the monotonic span clock: epoch_us(span) =
# (monotonic_ns + anchor) / 1e3.  Taken ONCE at import so every span in
# this process shares one mapping; cross-process residual skew is
# estimated at merge time from envelope send/recv pairs.
_ANCHOR_NS = time.time_ns() - time.monotonic_ns()

_lock = threading.Lock()
_tls = threading.local()


class _State:
    """Module config + ring, re-readable for tests (``reconfigure``)."""

    def __init__(self):
        self.on = False
        self.dir = ""
        self.ring = deque(maxlen=4096)
        self.flush_n = 32
        # cached at reconfigure(): role/rank and the journal path are
        # process-constant — re-deriving them from os.environ per span
        # would tax the hot path for nothing
        self.role = "local"
        self.rank = "0"
        self.path = None
        self.recorded = 0
        self._fh = None
        self._unflushed = 0
        # set when the journal dir proved unwritable: stop retrying the
        # open() on every span (reconfigure() re-arms)
        self._file_dead = False


_state = _State()


def reconfigure():
    """(Re-)read the MXNET_TRACE* env knobs — import calls this once;
    tests call it again after monkeypatching the env.  Closes any open
    trace file so the next span reopens under the new settings."""
    with _lock:
        _close_file_locked()
        _state._file_dead = False
        _state.on = bool(env("MXNET_TRACE", False))
        _state.dir = str(env("MXNET_TRACE_DIR", "") or "")
        _state.flush_n = max(1, int(env("MXNET_TRACE_FLUSH_N", 32)))
        _state.role, _state.rank = role_rank()
        _state.path = os.path.join(
            _state.dir, "%s-%s.trace.jsonl" % (_state.role, _state.rank)
        ) if _state.dir else None
        ring = max(16, int(env("MXNET_TRACE_RING", 4096)))
        if ring != _state.ring.maxlen:
            _state.ring = deque(_state.ring, maxlen=ring)


def enabled() -> bool:
    """The master switch (``MXNET_TRACE=1``) — THE guard every
    instrumentation site checks first, so a disabled trace costs one
    attribute read."""
    return _state.on


def role_rank():
    """This process's (role, rank) from the launcher's DMLC env —
    ``("local", "0")`` outside a launcher job.  THE one derivation,
    shared by span records, ``profiler.snapshot()`` and
    ``distributed.cluster_stats()`` so the three can never disagree on
    how a process is labeled."""
    role = os.environ.get("DMLC_ROLE") or "local"
    rank = os.environ.get("DMLC_SERVER_ID" if role == "server"
                          else "DMLC_WORKER_ID") or "0"
    return role, rank


def trace_file_path() -> Optional[str]:
    """Where this process flushes spans (None when MXNET_TRACE_DIR is
    unset): ``<dir>/<role>-<rank>.trace.jsonl`` — unique per process in
    a launcher job, so the merge tool gets one timeline track each.
    Cached at :func:`reconfigure`, like everything derived from the
    process-constant env."""
    return _state.path


def new_id() -> str:
    return uuid.uuid4().hex[:16]


def now_us() -> float:
    """Epoch microseconds on the anchored monotonic clock (what span
    ``ts`` fields and the envelope send stamp use)."""
    return (time.monotonic_ns() + _ANCHOR_NS) / 1e3


class Span:
    """One in-flight span.  ``args`` may be mutated until span_end."""

    __slots__ = ("name", "cat", "trace", "span", "parent", "t0", "args",
                 "detached")

    def __init__(self, name, cat, trace, span_id, parent, args, detached):
        self.name = name
        self.cat = cat
        self.trace = trace
        self.span = span_id
        self.parent = parent
        self.t0 = time.monotonic_ns()
        self.args = args
        self.detached = detached

    def ctx(self):
        return (self.trace, self.span)


def _stack():
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def current_span() -> Optional[Span]:
    st = getattr(_tls, "stack", None)
    return st[-1] if st else None


def current_ctx() -> Optional[tuple]:
    """(trace_id, span_id) of the thread's innermost open span, or None
    — the value the kvstore client stamps onto request envelopes."""
    sp = current_span()
    return None if sp is None else (sp.trace, sp.span)


def span_begin(name, cat="span", ctx=None, detach=False, args=None
               ) -> Optional[Span]:
    """Open a span.  ``ctx=(trace_id, parent_span_id)`` adopts a remote
    parent (the server side of a traced envelope); otherwise the
    thread's current span is the parent, and with neither this span
    roots a fresh trace.  ``detach=True`` keeps it OFF the thread-local
    stack — for spans that end on another thread (a batcher reply slot).
    Returns None (and does nothing) when tracing is off."""
    if not _state.on:
        return None
    if ctx is not None:
        trace, parent = str(ctx[0]), (str(ctx[1]) if ctx[1] else None)
    else:
        cur = current_span()
        if cur is not None:
            trace, parent = cur.trace, cur.span
        else:
            trace, parent = new_id(), None
    sp = Span(str(name), cat, trace, new_id(), parent, args, detach)
    if not detach:
        _stack().append(sp)
    return sp


def span_end(sp: Optional[Span], args=None) -> None:
    """Close a span opened by :func:`span_begin` (None is a no-op, so
    callers never re-check the master switch)."""
    if sp is None:
        return
    t1 = time.monotonic_ns()
    if not sp.detached:
        st = getattr(_tls, "stack", None)
        if st and sp in st:
            # normally the top; a crossed end (rare) removes in place
            st.remove(sp)
    if args:
        sp.args = dict(sp.args or {}, **args)
    _record(sp.name, sp.cat, sp.trace, sp.span, sp.parent,
            sp.t0, t1, sp.args)


class _Annotation(TraceAnnotation):
    """What ``span()`` returns with both host sinks off: the profiler
    annotation alone, one C++ object, yielding None as the disabled
    contract says."""

    __slots__ = ()

    def __enter__(self):
        TraceAnnotation.__enter__(self)


class _SpanCtx:
    __slots__ = ("_a", "_sp", "_ann", "_t0")

    def __init__(self, name, cat, ctx, args):
        self._a = (name, cat, ctx, args)
        self._sp = None

    def __enter__(self):
        name, cat, ctx, args = self._a
        self._ann = TraceAnnotation(str(name))
        self._ann.__enter__()
        self._t0 = time.monotonic_ns()
        self._sp = span_begin(name, cat=cat, ctx=ctx, args=args)
        return self._sp

    def __exit__(self, *exc):
        t1 = time.monotonic_ns()
        span_end(self._sp)
        sink = _chrome_sink
        if sink is not None:
            sink(self._a[0], self._a[1], (self._t0 + _ANCHOR_NS) / 1e3,
                 (t1 - self._t0) / 1e3)
        self._ann.__exit__(*exc)


# the MXNet profiler's chrome-trace sink while it runs, else None:
# called as sink(name, cat, ts_us, dur_us) with times from now_us()'s clock
_chrome_sink = None


def set_chrome_sink(sink) -> None:
    global _chrome_sink
    _chrome_sink = sink


def span(name, cat="span", ctx=None, args=None):
    """``with tracing.span("kv.pull"):`` — the one span entry point.
    Always a ``jax.profiler.TraceAnnotation`` (a live profiler session
    records it beside the device operations); with ``MXNET_TRACE`` on
    also a ring/journal record, which the context yields (else None);
    while the MXNet profiler runs also a chrome-trace event."""
    if not _state.on and _chrome_sink is None:
        return _Annotation(name)
    return _SpanCtx(name, cat, ctx, args)


# -- set-up phases -----------------------------------------------------------
_phases: dict = {}
# {phase name: occurrences entered}: an occurrence is numbered as it opens,
# so a record filed under it knows its place in phase_seconds()'s list
_phase_entered: dict = {}


def _phase_stack():
    st = getattr(_tls, "phases", None)
    if st is None:
        st = _tls.phases = []
    return st


class _Phase:
    __slots__ = ("_name", "_span", "_t0", "_key")

    def __init__(self, name, cat):
        self._name = name
        self._span = span(name, cat)

    def __enter__(self):
        with _lock:
            occ = _phase_entered.get(self._name, 0)
            _phase_entered[self._name] = occ + 1
        self._key = (self._name, occ)
        _phase_stack().append(self._key)
        self._t0 = time.monotonic_ns()
        return self._span.__enter__()

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        dur = (time.monotonic_ns() - self._t0) / 1e9
        st = _phase_stack()
        if self._key in st:
            st.remove(self._key)
        with _lock:
            _phases.setdefault(self._name, []).append(dur)


def phase(name, cat="setup"):
    """A span around a set-up phase (bind, init_params, the first
    update's compile): a few a process, so its seconds are also kept,
    switch or no switch, for :func:`phase_seconds`."""
    return _Phase(name, cat)


def phase_seconds() -> dict:
    """{phase name: [seconds of each occurrence, in order]}."""
    with _lock:
        return {k: list(v) for k, v in _phases.items()}


# -- the compile ledger -----------------------------------------------------
# jax's set-up events (jax/_src/dispatch.py log_elapsed_time): each opens
# with record_scalar(event, start) and closes with
# record_event_time_span(event, start, end, fun_name=...), on its thread
_STAGES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
           "/jax/core/compile/backend_compile_duration": "compile"}
_CACHE = {"/jax/compilation_cache/cache_hits": "hit",
          "/jax/compilation_cache/cache_misses": "miss"}
# top-level records only: hundreds a process.  Bounded all the same, so a
# program that compiles a new shape forever keeps its newest records
_ledger = deque(maxlen=16384)


class _LedgerCounts:
    __slots__ = ("appended", "folded", "listener_ns")

    def __init__(self):
        self.appended = 0       # records appended
        self.folded = 0         # nested events folded into them
        self.listener_ns = 0    # spent in the listeners


_counts = _LedgerCounts()


def _open_events(stage):
    """The thread's open events of ``stage``, innermost last: for each,
    the count of nested records folded into it so far."""
    opened = getattr(_tls, "opened", None)
    if opened is None:
        opened = _tls.opened = {"trace": [], "lower": [], "compile": []}
    return opened[stage]


def _on_open(event, value, **_):
    stage = _STAGES.get(event)
    if stage is None:
        return
    t = time.perf_counter_ns()
    _open_events(stage).append(0)
    # a lost update under a thread switch costs one event's nanoseconds
    _counts.listener_ns += time.perf_counter_ns() - t


def _on_cache(event, **_):
    outcome = _CACHE.get(event)
    if outcome is not None:
        _tls.cache = outcome


def _on_span(event, start_time, end_time, fun_name="", **_):
    stage = _STAGES.get(event)
    if stage is None:
        return
    t = time.perf_counter_ns()
    opened = _open_events(stage)
    # the inner event always closes first: an event closing while one of
    # its stage is still open on the thread folds into that one
    nested = opened.pop() if opened else 0
    if opened:
        opened[-1] += nested + 1
        _counts.listener_ns += time.perf_counter_ns() - t
        return
    ph = getattr(_tls, "phases", None)
    ph, occ = ph[-1] if ph else (None, None)
    rec = {"stage": stage, "program": str(fun_name),
           "start_us": start_time * 1e6, "end_us": end_time * 1e6,
           "phase": ph, "occurrence": occ, "nested": nested,
           "tid": threading.get_ident()}
    if stage == "compile":
        rec["cache"] = getattr(_tls, "cache", None) or "none"
        _tls.cache = None
    with _lock:
        _ledger.append(rec)
        _counts.appended += 1
        _counts.folded += nested
    if _state.on:
        args = {"program": rec["program"], "phase": ph}
        if "cache" in rec:
            args["cache"] = rec["cache"]
        add_span("mx.compile." + stage, int(start_time * 1e9) - _ANCHOR_NS,
                 int(end_time * 1e9) - _ANCHOR_NS, cat="compile", args=args)
    _counts.listener_ns += time.perf_counter_ns() - t


def _register_compile_listeners():
    """Once, at import (never from :func:`reconfigure`).  The opening
    edge (a scalar event jax sends as each event starts) is what tells a
    nested event from a top-level one as it closes."""
    import jax.monitoring as mon
    mon.register_scalar_listener(_on_open)
    mon.register_event_time_span_listener(_on_span)
    mon.register_event_listener(_on_cache)


def compile_records() -> list:
    """The ledger, oldest first: one dict per top-level trace, lowering
    or backend compile (cache load included) — ``stage`` (trace, lower,
    compile), ``program`` (jax's name for it), ``start_us``/``end_us``
    (:func:`now_us`'s epoch), ``phase`` and ``occurrence`` (the innermost
    set-up phase open on the thread, None outside one), ``nested`` (records
    of the stage folded into this one), ``tid``, and for a compile
    ``cache``: hit, miss or none."""
    with _lock:
        return [dict(r) for r in _ledger]


def compile_count() -> int:
    """Records appended since the last :func:`reset` (a mark for
    :func:`file_compiles`: one integer read)."""
    return _counts.appended


def union_seconds(records) -> float:
    """Seconds covered by the records' [start, end] intervals, overlaps
    counted once."""
    total, end = 0.0, None
    for a, b in sorted((r["start_us"], r["end_us"]) for r in records):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total / 1e6


def file_compiles(name, since):
    """File the records appended since ``compile_count()`` read
    ``since`` (this thread's) under phase ``name``, as its next occurrence:
    they are retagged, and their union goes to the phase clock.  Returns
    (seconds, the cache outcomes of their compiles).  For a compile that
    falls outside every phase, found afterwards (a recompile)."""
    tid = threading.get_ident()
    with _lock:
        k = min(_counts.appended - since, len(_ledger))
        recs = [r for r in list(_ledger)[len(_ledger) - k:]
                if r["tid"] == tid] if k > 0 else []
        occ = _phase_entered.get(name, 0)
        _phase_entered[name] = occ + 1
        for r in recs:
            r["phase"], r["occurrence"] = name, occ
        secs = union_seconds(recs)
        _phases.setdefault(name, []).append(secs)
    return secs, [r["cache"] for r in recs if r["stage"] == "compile"]


def instant(name, cat="instant", args=None) -> None:
    """A zero-duration marker under the current span (dedup hits,
    roster bumps — things with a moment but no extent)."""
    if not _state.on:
        return
    cur = current_span()
    trace = cur.trace if cur is not None else new_id()
    parent = cur.span if cur is not None else None
    t = time.monotonic_ns()
    _record(str(name), cat, trace, new_id(), parent, t, t, args)


def add_span(name, t0_mono_ns, t1_mono_ns, cat="span", ctx=None,
             args=None) -> None:
    """Record an already-timed span (both ends on the monotonic clock)
    — for intervals that cross threads, like a pull handle's
    enqueue→resolved wire round."""
    if not _state.on:
        return
    if ctx is not None:
        trace, parent = str(ctx[0]), (str(ctx[1]) if ctx[1] else None)
    else:
        cur = current_span()
        trace = cur.trace if cur is not None else new_id()
        parent = cur.span if cur is not None else None
    _record(str(name), cat, trace, new_id(), parent,
            int(t0_mono_ns), int(t1_mono_ns), args)


def _record(name, cat, trace, span_id, parent, t0_ns, t1_ns, args):
    rec = {
        "name": name, "cat": cat,
        "trace": trace, "span": span_id, "parent": parent,
        "ts": round((t0_ns + _ANCHOR_NS) / 1e3, 3),
        "dur": round(max(0, t1_ns - t0_ns) / 1e3, 3),
        "pid": os.getpid(),
        "tid": threading.get_ident() % 100000,
        "role": _state.role, "rank": _state.rank,
    }
    if args:
        rec["args"] = args
    # json-encode OUTSIDE the lock: the lock should cover only the ring
    # append and the (ordered) file write, not per-record CPU work.
    # The periodic flush+fsync does stay under the lock — it is what
    # bounds a SIGKILL's span loss to MXNET_TRACE_FLUSH_N, runs once
    # per flush_n records, and keeping it ordered beats a second
    # writer thread for an opt-in debugging feature.
    line = None
    if _state.path is not None and not _state._file_dead:
        line = json.dumps(rec, sort_keys=True)
    with _lock:
        _state.ring.append(rec)
        _state.recorded += 1
        if line is not None:
            _write_locked(line)


def _write_locked(line):
    path = _state.path
    if path is None or _state._file_dead:
        return
    try:
        if _state._fh is None:
            d = os.path.dirname(path)
            if d:
                os.makedirs(d, exist_ok=True)
            _state._fh = open(path, "a")
        _state._fh.write(line + "\n")
        _state._unflushed += 1
        if _state._unflushed >= _state.flush_n:
            _flush_locked()
    except OSError:
        # tracing must never take the job down: close the journal, mark
        # it dead (no per-span open() retries against an unwritable
        # dir) and keep the ring — the stats op still serves counters
        _close_file_locked()
        _state._file_dead = True
        _state._unflushed = 0


def _flush_locked():
    if _state._fh is None:
        return
    try:
        _state._fh.flush()
        os.fsync(_state._fh.fileno())
    except OSError:
        pass
    _state._unflushed = 0


def _close_file_locked():
    _flush_locked()
    if _state._fh is not None:
        try:
            _state._fh.close()
        except OSError:
            pass
        _state._fh = None


def flush() -> None:
    """Force the file buffer to disk (span_end fsyncs every
    MXNET_TRACE_FLUSH_N spans on its own; atexit calls this too)."""
    with _lock:
        _flush_locked()


def ring_records() -> list:
    """The bounded in-memory ring, oldest first (the stats op's and the
    in-process tests' view — no file round trip needed)."""
    with _lock:
        return list(_state.ring)


def stats() -> dict:
    """The tracing block of ``profiler.snapshot()``."""
    phases = phase_seconds()
    records = compile_records()
    with _lock:
        return {
            "enabled": _state.on,
            "recorded": _state.recorded,
            "ring": len(_state.ring),
            "ring_max": _state.ring.maxlen,
            "file": trace_file_path(),
            "phases": phases,
            "compiles": {
                "records": len(records), "folded": _counts.folded,
                "listener_s": _counts.listener_ns / 1e9,
                "seconds": {s: union_seconds(
                    [r for r in records if r["stage"] == s])
                    for s in ("trace", "lower", "compile")},
                "cache": {c: sum(r.get("cache") == c for r in records)
                          for c in ("hit", "miss", "none")}},
        }


def reset() -> None:
    """Clear the ring, counters, phase clock and compile ledger (tests);
    the file, being append-only evidence, is left alone."""
    with _lock:
        _state.ring.clear()
        _state.recorded = 0
        _phases.clear()
        _phase_entered.clear()
        _ledger.clear()
        _counts.__init__()


def read_trace_file(path) -> list:
    """Parse one ``*.trace.jsonl`` — TORN-LINE TOLERANT: a process
    SIGKILLed mid-append leaves at most one undecodable line, which is
    skipped.
    Returns the span records in file order."""
    out = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue  # torn tail from a SIGKILL mid-write
                if isinstance(rec, dict):
                    out.append(rec)
    except OSError:
        pass
    return out


reconfigure()
_register_compile_listeners()
atexit.register(flush)
