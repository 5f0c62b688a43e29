"""Profiler: chrome://tracing JSON + XLA (xplane) trace capture.

TPU-native re-design of the reference profiler (src/engine/profiler.h:79
OprExecStat collection inside the engine; python/mxnet/profiler.py:27-55
set_config/set_state/dump_profile).  Two layers:

* **host events** — every ``tracing.span`` in the program (eager
  `_invoke`, Executor forward/backward, fused Module steps, kvstore,
  serving) becomes a {name, start µs, dur µs} event while the profiler
  runs, exactly like the reference's per-opr stats, dumped in
  chrome://tracing format so the same tooling opens both.  This module
  is a SINK: the spans, their clock (``tracing.now_us``) and their
  other two sinks live in :mod:`mxnet_tpu.tracing`.
* **device truth** — `start()/stop()` also drive `jax.profiler`
  (``MXNET_PROFILER_XLA_LOGDIR``), capturing the XLA/TPU xplane trace;
  per-op names survive into HLO metadata.

Env parity: ``MXNET_PROFILER_AUTOSTART=1`` begins profiling at import
(reference: src/engine/profiler.cc autostart).
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import List, Optional

from .base import MXNetError, env
from . import tracing
from . import health as _health

PROFILER_STATE_STOP = 0
PROFILER_STATE_RUN = 1

_MODE_SYMBOLIC = "symbolic"
_MODE_ALL = "all"


class _Profiler:
    def __init__(self):
        self.state = PROFILER_STATE_STOP
        # reference env parity: MXNET_PROFILER_MODE=all widens capture
        # beyond dispatch events; any other value (incl. the reference
        # spelling "symbolic_only") is the symbolic default.
        # profiler_set_config overrides at runtime.
        self.mode = _MODE_ALL \
            if env("MXNET_PROFILER_MODE", "symbolic_only") == _MODE_ALL \
            else _MODE_SYMBOLIC
        self.filename = "profile.json"
        self.continuous_dump = False
        self._events: List[dict] = []
        self._lock = threading.Lock()
        self._xla_logdir: Optional[str] = None
        self._xla_running = False

    # -- event capture -----------------------------------------------------
    def record(self, name, category, start_us, dur_us):
        """The chrome-trace sink ``tracing.span`` feeds while the profiler
        runs; times are on ``tracing.now_us()``'s clock."""
        if self.state != PROFILER_STATE_RUN:
            return
        with self._lock:
            self._events.append({
                "name": name, "cat": category, "ph": "X",
                "ts": start_us, "dur": dur_us,
                "pid": os.getpid(),
                "tid": threading.get_ident() % 100000,
            })

    def record_ended(self, name, category, dur_s):
        """An interval of ``dur_s`` seconds that ends now (the wire and
        latency clocks, which time themselves)."""
        if self.state == PROFILER_STATE_RUN:
            dur_us = float(dur_s) * 1e6
            self.record(name, category, tracing.now_us() - dur_us, dur_us)

    # -- lifecycle ---------------------------------------------------------
    def set_state(self, state):
        if state == PROFILER_STATE_RUN and \
                self.state != PROFILER_STATE_RUN:
            self._maybe_start_xla()
        if state == PROFILER_STATE_STOP and \
                self.state == PROFILER_STATE_RUN:
            self._maybe_stop_xla()
            if self.continuous_dump:
                self.state = state
                self.dump()
        self.state = state
        tracing.set_chrome_sink(
            self.record if state == PROFILER_STATE_RUN else None)

    def _maybe_start_xla(self):
        logdir = self._xla_logdir or env("MXNET_PROFILER_XLA_LOGDIR", None)
        if logdir:
            import jax
            jax.profiler.start_trace(logdir)
            self._xla_running = True

    def _maybe_stop_xla(self):
        if self._xla_running:
            import jax
            jax.profiler.stop_trace()
            self._xla_running = False

    def dump(self, finished=True):
        with self._lock:
            events = list(self._events)
            if finished:
                self._events = []
        with open(self.filename, "w") as f:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)


_profiler = _Profiler()


def profiler_set_config(mode="symbolic", filename="profile.json",
                        continuous_dump=False, xla_logdir=None, **kwargs):
    """reference: profiler.py:27 profiler_set_config / MXSetProfilerConfig.

    ``xla_logdir``: directory for the device (xplane) capture that
    start/stop also drives — the public form of the
    ``MXNET_PROFILER_XLA_LOGDIR`` env var.  None leaves the current
    setting untouched; the empty string "" CLEARS it (device capture
    off).  Merge both outputs with tools/trace_merge.py.
    """
    if mode not in (_MODE_SYMBOLIC, _MODE_ALL):
        raise MXNetError(f"invalid profiler mode {mode!r}")
    if kwargs:
        import warnings
        warnings.warn("profiler_set_config: ignoring unknown options %r"
                      % sorted(kwargs), stacklevel=2)
    _profiler.mode = mode
    _profiler.filename = filename
    _profiler.continuous_dump = continuous_dump
    if xla_logdir is not None:
        _profiler._xla_logdir = xla_logdir or None  # "" clears


set_config = profiler_set_config


def profiler_set_state(state="stop"):
    """reference: profiler.py:40 / MXSetProfilerState."""
    s = {"stop": PROFILER_STATE_STOP, "run": PROFILER_STATE_RUN}
    if state not in s:
        raise MXNetError(f"invalid profiler state {state!r}")
    _profiler.set_state(s[state])


set_state = profiler_set_state


def dump_profile():
    """reference: profiler.py:52 dump_profile / MXDumpProfile."""
    _profiler.dump()


dump = dump_profile


def is_running():
    return _profiler.state == PROFILER_STATE_RUN


# -- span tracing (mxnet_tpu.tracing; docs/OBSERVABILITY.md) -----------------
# The profiler's cross-process face: span_begin/span_end with a
# thread-local current span, monotonic clocks, a bounded ring and the
# MXNET_TRACE master switch all live in mxnet_tpu.tracing — re-exported
# here so instrumentation sites (and the reference-shaped public
# surface) reach them as profiler.span_begin(...) without a second
# import.
span = tracing.span
span_begin = tracing.span_begin
span_end = tracing.span_end
trace_instant = tracing.instant
trace_enabled = tracing.enabled
# set-up phases (bind, init_params, the first update's compile), always
# on: {name: [seconds of each occurrence, in order]}
phase_seconds = tracing.phase_seconds


# -- host-dispatch counters --------------------------------------------------
# One counter per dispatch KIND (fused step launch, K-step scan launch,
# host readback, eager forward, ...).  This is the test hook behind the
# multi-step driver's contract — "run_steps(k) is ONE device dispatch and
# ONE host readback" is asserted by tests/test_run_steps.py against these
# counts, so a regression that silently reintroduces per-step host
# round-trips fails loudly instead of only showing up on a chip.
_dispatch_counts: dict = {}
_dispatch_lock = threading.Lock()


def record_dispatch(kind: str):
    """Count one host-side dispatch event of ``kind`` (always on — a
    dict increment is noise next to the device round-trip it marks)."""
    with _dispatch_lock:
        _dispatch_counts[kind] = _dispatch_counts.get(kind, 0) + 1


def dispatch_counts() -> dict:
    with _dispatch_lock:
        return dict(_dispatch_counts)


def reset_dispatch_counts():
    with _dispatch_lock:
        _dispatch_counts.clear()


# -- host-sync counters ------------------------------------------------------
# One counter per host-READBACK site (ndarray.asnumpy, metric.sync,
# predict.readback, ...).  This is the test hook behind the sync-free
# training loop: "the host touches the device once per LOG INTERVAL,
# not once per batch" is asserted by tests/test_sync_free.py and the
# ci/run_ci.sh sync-count gate against these counts, so a change that
# quietly reintroduces a per-batch device->host sync fails loudly on
# CPU instead of only showing up as step-time jitter on a chip.
# Separate from the dispatch counters: a dispatch LAUNCHES device work
# asynchronously; a sync BLOCKS the host on it — only the second one
# serializes the loop.
_host_sync_counts: dict = {}
_host_sync_lock = threading.Lock()


def record_host_sync(kind: str):
    """Count one host-blocking device readback of ``kind`` (always on —
    a dict increment is noise next to the device round-trip it marks)."""
    with _host_sync_lock:
        _host_sync_counts[kind] = _host_sync_counts.get(kind, 0) + 1


def host_syncs() -> dict:
    with _host_sync_lock:
        return dict(_host_sync_counts)


def host_sync_total() -> int:
    """Total host syncs across all sites (the gate's one number)."""
    with _host_sync_lock:
        return sum(_host_sync_counts.values())


def reset_host_syncs():
    with _host_sync_lock:
        _host_sync_counts.clear()


# -- kvstore channel counters ------------------------------------------------
# One counter per transport-resilience event on the dist kvstore channel
# (retry, reconnect, replay, replay_acked, hard_fail, heartbeat,
# heartbeat_miss; the elastic layer adds roster_bump, the eviction/
# handoff family, coordinator_failover / coordinator_failover_observed
# and the coordinator_slot + failover_rebuild_s gauges — a coordinator
# succession is a first-class counter, not a log line).  Separate from
# the dispatch counters on purpose: the
# multi-step-driver tests assert dispatch_counts() by EXACT equality, and
# a channel retry must never be able to fail a dispatch-contract test.
# tests/test_faultinject.py asserts recovery paths against these.
_channel_counts: dict = {}
_channel_lock = threading.Lock()


def record_channel_event(kind: str):
    """Count one kvstore transport event of ``kind`` (always on — a dict
    increment is noise next to the socket round-trip it marks)."""
    with _channel_lock:
        _channel_counts[kind] = _channel_counts.get(kind, 0) + 1


def record_channel_count(kind: str, n: int):
    """Add ``n`` to the transport counter ``kind`` — the bulk form of
    :func:`record_channel_event` for per-row accounting (e.g.
    ``kvstore.sparse_rows``: one sparse push moves thousands of rows;
    counting them one event at a time would put a lock round-trip per
    row on the push path).  Lives in _channel_counts, NOT the byte
    counters, so row counts never pollute wire_bytes_total."""
    with _channel_lock:
        _channel_counts[kind] = _channel_counts.get(kind, 0) + int(n)


def record_channel_gauge(kind: str, value):
    """SET a transport gauge (last-value, not a count): the elastic
    roster generation is the canonical one — ``kvstore.roster_generation``
    must read as "which membership epoch am I on", where an increment
    per observer would be meaningless."""
    with _channel_lock:
        _channel_counts[kind] = value


def channel_counts() -> dict:
    with _channel_lock:
        return dict(_channel_counts)


def reset_channel_counts():
    with _channel_lock:
        _channel_counts.clear()


def fleet_route_counts() -> dict:
    """Per-replica routing counters for a serving fleet: {uri: attempts
    routed there}, stripped of the ``fleet.route:`` prefix.  The chaos
    gate asserts on a DELTA of this map — after a kill/blackhole, the
    dead replicas' counts must stop moving while the survivors' climb."""
    with _channel_lock:
        return {k[len("fleet.route:"):]: v
                for k, v in _channel_counts.items()
                if k.startswith("fleet.route:")}


# -- kvstore channel byte counters -------------------------------------------
# Bytes moved per transport DIRECTION ("sent"/"recv" for the socket wire,
# "allgather" for host collectives).  Separate from the event counters:
# events prove a recovery path RAN, bytes prove a wire optimization is
# real — the 2-bit compression acceptance asserts its >=8x push-byte
# reduction against these.
_channel_bytes: dict = {}


def record_channel_bytes(kind: str, n: int):
    """Add ``n`` bytes to the transport byte counter ``kind`` (always on
    — two dict ops are noise next to the socket write they measure)."""
    with _channel_lock:
        _channel_bytes[kind] = _channel_bytes.get(kind, 0) + int(n)


def channel_bytes() -> dict:
    with _channel_lock:
        return dict(_channel_bytes)


# The hierarchical kvstore tier's in-host mesh traffic counts under
# "ici_*" kinds (kvstore_server._send_msg byte_kind) — a separate
# counter FAMILY from the TCP wire, because the whole point of the tier
# is moving bytes from the wire onto the mesh, and the two families side
# by side show the shift.
ICI_BYTE_PREFIX = "ici_"

# Control-plane traffic (heartbeats, roster beats/leaves, codec hellos)
# counts under "control"/"control_recv" kinds — a third family next to
# the data wire and the mesh, so wire_bytes_per_step measures GRADIENTS
# only: a heartbeat cadence change must never move a banked wire-byte
# number.  Mesh-side control rides "ici_control*" and stays inside the
# ici_ family (the mesh totals already exclude the wire).
CONTROL_BYTE_PREFIX = "control"


def is_control_byte_kind(kind: str) -> bool:
    """True for control-plane byte kinds on either transport."""
    return (kind.startswith(CONTROL_BYTE_PREFIX)
            or kind.startswith(ICI_BYTE_PREFIX + CONTROL_BYTE_PREFIX))


# Same-host shared-memory lane traffic (mxnet_tpu/shmlane.py) counts
# under "shm_sent"/"shm_recv" — a fourth family next to the socket mesh
# kinds, because the lane's whole point is that these bytes never cross
# a socket: when MXNET_KVSTORE_SHM is on, follower<->leader payload
# moves from ici_* to shm_* and the socket's ici_* drops to control
# traffic (hellos, heartbeats).
SHM_BYTE_PREFIX = "shm_"


def ici_bytes_total() -> int:
    """Total in-mesh (hierarchy-tier) bytes moved over SOCKETS so far;
    the shm lane's share counts under shm_bytes_total."""
    with _channel_lock:
        return sum(v for k, v in _channel_bytes.items()
                   if k.startswith(ICI_BYTE_PREFIX))


def ici_payload_bytes_total() -> int:
    """The mesh sockets' DATA share: ici_* minus ici_control* — with
    the shm lane active this is ≈0 (payload rides the ring), which is
    exactly what the CI shm gate pins."""
    with _channel_lock:
        return sum(v for k, v in _channel_bytes.items()
                   if k.startswith(ICI_BYTE_PREFIX)
                   and not k.startswith(ICI_BYTE_PREFIX
                                        + CONTROL_BYTE_PREFIX))


def shm_bytes_total() -> int:
    """Total same-host shared-memory lane bytes moved so far (both
    directions; zero socket syscalls behind any of them)."""
    with _channel_lock:
        return sum(v for k, v in _channel_bytes.items()
                   if k.startswith(SHM_BYTE_PREFIX))


def wire_bytes_total() -> int:
    """Total non-mesh DATA bytes (TCP wire + host collectives);
    control-plane traffic is excluded so the banked per-step number
    measures gradients, not heartbeat cadence — and the in-host
    families (ici_*, shm_*) are excluded so it measures the WIRE."""
    with _channel_lock:
        return sum(v for k, v in _channel_bytes.items()
                   if not k.startswith(ICI_BYTE_PREFIX)
                   and not k.startswith(CONTROL_BYTE_PREFIX)
                   and not k.startswith(SHM_BYTE_PREFIX))


def control_bytes_total() -> int:
    """Total wire-side control-plane bytes (heartbeats, roster beats,
    codec hellos); mesh-side control counts into ici_bytes_total."""
    with _channel_lock:
        return sum(v for k, v in _channel_bytes.items()
                   if k.startswith(CONTROL_BYTE_PREFIX))


def reset_channel_bytes():
    with _channel_lock:
        _channel_bytes.clear()


# -- kvstore serialization counters -------------------------------------------
# What the frame layer COSTS, separate from what it MOVES: codec_bytes
# (descriptor bytes emitted by the generated binary codec), pickle_bytes
# (skeleton bytes emitted by the legacy pickle path), send_syscalls
# (socket writes per frame — 1 with vectored sendmsg, 2+N without).
# Deliberately its own dict, not more _channel_bytes kinds: the
# fault-injection tests assert channel counters by exact equality, and
# the hot-path acceptance pin is pickle_bytes == 0 over a measured
# window.
_serialization: dict = {}
_serialization_lock = threading.Lock()


def record_serialization(kind: str, n: int):
    """Add ``n`` to the serialization counter ``kind`` (always on — a
    dict increment is noise next to the encode it measures)."""
    with _serialization_lock:
        _serialization[kind] = _serialization.get(kind, 0) + int(n)


def serialization_counts() -> dict:
    with _serialization_lock:
        return dict(_serialization)


def codec_bytes_total() -> int:
    """Descriptor bytes emitted by the binary wire codec so far."""
    with _serialization_lock:
        return _serialization.get("codec_bytes", 0)


def pickle_bytes_total() -> int:
    """Skeleton bytes pickled by the legacy frame path so far — the
    steady-state acceptance pin is 0 with the codec negotiated on."""
    with _serialization_lock:
        return _serialization.get("pickle_bytes", 0)


def send_syscalls_total() -> int:
    """Socket write syscalls issued by the frame layer so far."""
    with _serialization_lock:
        return _serialization.get("send_syscalls", 0)


def reset_serialization():
    with _serialization_lock:
        _serialization.clear()


# -- kvstore wire-overlap counters -------------------------------------------
# The fused-dist K-step driver overlaps the push/pull wire round of chunk
# j-1 behind chunk j's scanned compute.
# Two clocks make the overlap CPU-testable the way host_syncs made the
# sync-free loop testable:
#   * wire_wait  — host time actually BLOCKED on a pull future (the
#     exposed, un-overlapped part of the wire),
#   * wire_round — full enqueue->resolved time of the same rounds (what
#     the wire costs with no overlap at all).
# overlap_pct = 100*(1 - wait/round) is the regression gate: staleness 0
# (barrier'd chunk boundary) pins it near 0, staleness >= 1 must keep it
# strictly positive whenever compute overlaps any of the round trip —
# ci/run_ci.sh asserts wire_wait_ms strictly below the unoverlapped
# baseline on CPU.
_wire_lock = threading.Lock()
_wire = {"wait_s": 0.0, "round_s": 0.0, "rounds": 0}


def record_wire_wait(dur_s: float):
    """Add host-blocked seconds spent waiting on an in-flight kvstore
    pull (the exposed wire).  Also emitted as a chrome-trace event
    (category "wire") when the profiler is running, so a single-process
    trace shows the wire stall next to the dispatches it blocked —
    these clocks used to feed only the counters and never reached the
    trace export."""
    with _wire_lock:
        _wire["wait_s"] += float(dur_s)
    _profiler.record_ended("kvstore.wire_wait", "wire", dur_s)


def record_wire_round(dur_s: float):
    """Add one completed wire round's full enqueue->resolved seconds
    (chrome-trace event "wire" category when the profiler runs — see
    record_wire_wait)."""
    with _wire_lock:
        _wire["round_s"] += float(dur_s)
        _wire["rounds"] += 1
    _profiler.record_ended("kvstore.wire_round", "wire", dur_s)


def wire_wait_ms() -> float:
    with _wire_lock:
        return _wire["wait_s"] * 1e3


def wire_round_ms() -> float:
    with _wire_lock:
        return _wire["round_s"] * 1e3


def wire_rounds() -> int:
    with _wire_lock:
        return _wire["rounds"]


def wire_overlap_pct() -> float:
    """Fraction of the wire hidden behind compute, as a percentage:
    100*(1 - wait/round) over every recorded round, 0.0 before the
    first round (and never negative — scheduling jitter can make a
    single wait marginally exceed its round)."""
    with _wire_lock:
        if _wire["rounds"] == 0 or _wire["round_s"] <= 0.0:
            return 0.0
        return max(0.0, 100.0 * (1.0 - _wire["wait_s"] / _wire["round_s"]))


def reset_wire_counters():
    with _wire_lock:
        _wire["wait_s"] = 0.0
        _wire["round_s"] = 0.0
        _wire["rounds"] = 0


# -- mesh fan-in clock --------------------------------------------------------
# Host time the hierarchy-tier LEADER spends blocked in collect_push
# waiting for every follower's round to arrive — the serialization the
# parallel acceptor pool + shm lane exist to shrink.
_fanin_lock = threading.Lock()
_fanin = {"wait_s": 0.0, "rounds": 0}


def record_mesh_fanin_wait(dur_s: float):
    """Add one collect_push round's blocked seconds (chrome-trace event
    "wire" category when the profiler runs, like the wire clocks)."""
    with _fanin_lock:
        _fanin["wait_s"] += float(dur_s)
        _fanin["rounds"] += 1
    _profiler.record_ended("kvstore.mesh_fanin", "wire", dur_s)


def mesh_fanin_wait_ms() -> float:
    with _fanin_lock:
        return _fanin["wait_s"] * 1e3


def mesh_fanin_rounds() -> int:
    with _fanin_lock:
        return _fanin["rounds"]


def reset_mesh_fanin():
    with _fanin_lock:
        _fanin["wait_s"] = 0.0
        _fanin["rounds"] = 0


# -- serving latency / QPS counters ------------------------------------------
# Request-latency distributions for the serving tier (mxnet_tpu.serving):
# per KIND (e.g. "serving.request", "serving.batch") a bounded ring of
# duration samples plus completion timestamps.  p50/p99 sit next to
# wire_bytes_per_step on purpose: the serving SLO numbers are first-class
# profiler outputs, not log lines — tests/test_serving.py pins the
# percentile and QPS arithmetic, and ServingReplica's "serving_stats"
# envelope serves these dicts to clients.  Bounded (ring, not full
# history): a replica serving millions of requests must not grow host
# memory with uptime; MXNET_SERVING_LATENCY_WINDOW sizes the ring.
_latency_lock = threading.Lock()
_latency: dict = {}   # kind -> {"durs": deque, "ts": deque, "count", "total"}


def _latency_window() -> int:
    return max(2, int(env("MXNET_SERVING_LATENCY_WINDOW", 2048)))


def record_latency(kind: str, dur_s: float, ts: Optional[float] = None):
    """Record one completed request of ``kind`` taking ``dur_s`` seconds.
    ``ts`` is the completion time (``time.monotonic()`` when omitted —
    injectable so the QPS arithmetic is testable without sleeping)."""
    if ts is None:
        ts = time.monotonic()
    # each completed request is also a chrome-trace event, so a
    # single-process serving trace shows queue-wait + forward time
    _profiler.record_ended(kind, "latency", dur_s)
    with _latency_lock:
        st = _latency.get(kind)
        if st is None:
            from collections import deque
            w = _latency_window()
            st = _latency[kind] = {"durs": deque(maxlen=w),
                                   "ts": deque(maxlen=w),
                                   "count": 0, "total": 0.0}
        st["durs"].append(float(dur_s))
        st["ts"].append(float(ts))
        st["count"] += 1
        st["total"] += float(dur_s)


def percentile(samples, q) -> float:
    """Nearest-rank percentile (q in [0, 100]) over ``samples``.  The
    deterministic textbook definition — sorted sample at rank
    ``ceil(q/100 * n)`` — so the p50/p99 numbers tests pin are exact,
    not interpolation-scheme-dependent."""
    xs = sorted(samples)
    if not xs:
        raise MXNetError("percentile of an empty sample set")
    import math
    rank = max(1, math.ceil((float(q) / 100.0) * len(xs)))
    return xs[min(rank, len(xs)) - 1]


def latency_stats(kind: str) -> Optional[dict]:
    """{count, window, p50_ms, p99_ms, mean_ms, max_ms, qps} for ``kind``
    or None before the first sample.  Percentiles/mean/max are over the
    ring window; ``count``/``total`` are lifetime.  QPS is completions
    over the window's timespan — (len-1)/(last-first), the unbiased
    inter-arrival estimate; 0.0 until two samples exist."""
    with _latency_lock:
        st = _latency.get(kind)
        if st is None:
            return None
        durs = list(st["durs"])
        ts = list(st["ts"])
        count, total = st["count"], st["total"]
    qps = 0.0
    if len(ts) >= 2 and ts[-1] > ts[0]:
        qps = (len(ts) - 1) / (ts[-1] - ts[0])
    return {
        "count": count,
        "window": len(durs),
        "p50_ms": percentile(durs, 50) * 1e3,
        "p99_ms": percentile(durs, 99) * 1e3,
        "mean_ms": (sum(durs) / len(durs)) * 1e3,
        "max_ms": max(durs) * 1e3,
        "qps": qps,
    }


def latency_kinds() -> list:
    with _latency_lock:
        return sorted(_latency)


def reset_latency():
    with _latency_lock:
        _latency.clear()


_NULL = __import__("contextlib").nullcontext()


def scope(name, category="operator", require_mode=None):
    """API-parity alias of ``tracing.span(name, category)``, the one span
    entry point.  ``require_mode="all"`` (the eager per-operator site)
    keeps its meaning: nothing unless the profiler's mode is "all"."""
    if require_mode is not None and _profiler.mode != require_mode:
        return _NULL
    return tracing.span(name, category)


# -- the universal snapshot ---------------------------------------------------
def snapshot(compact: bool = False) -> dict:
    """EVERY counter family in one plain-builtin dict — the single
    source behind the kvstore ``("stats",)`` envelope
    (kvstore_server._stats_payload), ``distributed.cluster_stats()``,
    the elastic beat piggyback and ``python -m mxnet_tpu.profiler
    --dump``, so no consumer can drift from another.

    ``compact=True`` returns only the transport families (channel
    counts/gauges, bytes, wire clocks) — the per-beat piggyback the
    elastic stats bank accumulates; full counters since process start,
    so a lost beat costs freshness, never correctness."""
    out = {
        "channel": channel_counts(),
        "channel_bytes": channel_bytes(),
        "wire": {
            "wait_ms": wire_wait_ms(),
            "round_ms": wire_round_ms(),
            "rounds": wire_rounds(),
            "overlap_pct": wire_overlap_pct(),
        },
    }
    if compact:
        # the health status rides the compact form too: beats piggyback
        # it, so every peer's stats bank holds each member's last-known
        # OK/DEGRADED/CRITICAL verdict next to its counters
        # (docs/OBSERVABILITY.md health section)
        out["health"] = _health.snapshot_section(compact=True)
        return out
    role, rank = tracing.role_rank()
    out.update({
        "pid": os.getpid(),
        "role": role,
        "rank": int(rank or 0),
        "dispatch": dispatch_counts(),
        "host_syncs": host_syncs(),
        "host_sync_total": host_sync_total(),
        "latency": {k: latency_stats(k) for k in latency_kinds()},
        "trace": tracing.stats(),
        "health": _health.snapshot_section(),
    })
    return out


def reset_all():
    """Zero every counter family (the --reset CLI and test isolation;
    the span FILE journal is append-only evidence and stays)."""
    reset_dispatch_counts()
    reset_host_syncs()
    reset_channel_counts()
    reset_channel_bytes()
    reset_wire_counters()
    reset_latency()
    tracing.reset()


def _main(argv=None) -> int:
    """``python -m mxnet_tpu.profiler [--dump] [--reset] [--watch S]``
    — the shell face of :func:`snapshot` for scripts and chip runbooks:
    ``--dump`` (the default) prints the full snapshot as ONE JSON line
    (what scripts parse); ``--reset`` zeroes the counters first (combine
    both for a read-and-rearm); ``--watch S`` repeats the dump every S seconds —
    one JSON line per tick, same contract — so a chip runbook can tail
    live counters (``| jq .wire``) without writing a loop.  ``--ticks
    N`` bounds the watch (0 = until interrupted)."""
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m mxnet_tpu.profiler",
        description="dump/reset/watch the mxnet_tpu profiler counter "
                    "snapshot (docs/OBSERVABILITY.md)")
    ap.add_argument("--dump", action="store_true",
                    help="print the snapshot as one JSON line (default "
                         "when --reset is not given)")
    ap.add_argument("--reset", action="store_true",
                    help="zero every counter family")
    ap.add_argument("--watch", type=float, default=None, metavar="S",
                    help="interval mode: print one snapshot JSON line "
                         "every S seconds (ctrl-C to stop)")
    ap.add_argument("--ticks", type=int, default=0, metavar="N",
                    help="with --watch: stop after N lines (0 = run "
                         "until interrupted)")
    args = ap.parse_args(argv)
    if args.watch is not None:
        if args.watch <= 0:
            ap.error("--watch interval must be > 0 seconds")
        if args.reset:
            reset_all()
        tick = 0
        try:
            while True:
                print(json.dumps(snapshot(), sort_keys=True,
                                 default=str), flush=True)
                tick += 1
                if args.ticks and tick >= args.ticks:
                    break
                time.sleep(args.watch)
        except KeyboardInterrupt:
            pass
        return 0
    # dump BEFORE reset: the --dump --reset combination is
    # read-and-rearm — print the accumulated counters, THEN zero them
    # (the other order would print an empty snapshot and lose the data)
    if args.dump or not args.reset:
        print(json.dumps(snapshot(), sort_keys=True, default=str))
    if args.reset:
        reset_all()
    return 0


if env("MXNET_PROFILER_AUTOSTART", 0):
    profiler_set_state("run")


if __name__ == "__main__":
    import sys
    sys.exit(_main())
