"""Controlled concurrency scheduler: PCT exploration + replayable journals.

The happens-before sanitizer (:mod:`mxnet_tpu.analysis.hb`) reports
races that happen to fire under the ONE schedule the OS picked.  This
module makes the schedule an input: it serializes the process to one
runnable thread at a time, choosing who runs next at the yield points
the hb shim already intercepts — lock acquire/release, Condition
wait/notify, ``queue.Queue`` put/get (their mutex and condvars are
born instrumented under the shim), ``Thread`` start/join, ``time.sleep``
and every :func:`hb.track` container access — using PCT-style random
priority scheduling (Burckhardt et al., "A Randomized Scheduler with
Probabilistic Guarantees of Finding Bugs"): each thread gets a random
priority, the highest-priority runnable thread always runs, and
``depth`` − 1 seeded priority-change points demote the running thread
mid-schedule.  ``(seed, scenario)`` therefore names a schedule, and a
failing schedule serializes to an fsync'd JSONL journal that
:func:`replay` re-executes decision for decision.

Mechanics — cooperative baton passing:

* every controlled thread parks on a private raw ``_thread`` gate;
  exactly one holds the TOKEN and executes;
* blocking primitives are MODELED: a lock acquire that would block
  parks the thread in the scheduler (the real inner acquire only ever
  happens after the model granted the lock, so it cannot block);
  Condition waits release/reacquire through the model the same way;
  ``Thread.join`` waits on the model's thread-exit signal; ``sleep``
  and every timed wait park with a real-clock deadline the monitor
  fires — so poll loops keep their real-time semantics;
* a thread that blocks OUTSIDE the model (socket IO, foreign locks)
  is detected by a lease watchdog, marked EXTERNAL, and scheduling
  continues without it; it rejoins at its next yield point.  Pure
  thread scenarios (no sockets, no sleeps) are bit-deterministic;
  socket scenarios are explored best-effort.

On top of the scheduler:

* **deadlock detector** — every live controlled thread blocked on an
  UNTIMED modeled primitive with no external threads outstanding is a
  cycle by construction; the finding names every thread's held and
  waited-for locks with live stacks, then aborts the schedule;
* **starvation budget** — a thread runnable for
  ``MXNET_SCHED_STARVE_OPS`` consecutive decisions without being
  scheduled is a finding (the lost-fairness shape PCT priorities can
  legitimately produce is reset whenever the thread blocks or runs);
* **op budget** — a schedule that makes no progress past
  ``max_ops`` decisions is reported as a livelock and aborted;
* **FastTrack integration** — every schedule runs under a fresh
  :class:`hb.Sanitizer`, so each explored interleaving is also
  race-checked; violations are findings.
"""
from __future__ import annotations

import _thread
import contextlib
import json
import os
import random
import sys
import threading
import time
import traceback
from typing import Dict, List, Optional

__all__ = [
    "SchedAbort", "Scheduler", "ScheduleResult", "ExploreResult",
    "run_schedule", "explore", "replay", "read_journal",
]

_mono = time.monotonic
_real_sleep = time.sleep

# How long a replay waits for the journal's expected thread to arrive
# at a yield before declaring the run divergent (module-level so tests
# can tighten it).
_REPLAY_STALL_S = 30.0


class SchedAbort(BaseException):
    """Raised inside controlled threads to unwind an aborted schedule.

    A ``BaseException`` so the bare-thread capture patterns
    (``except Exception``) in scenario code don't swallow the unwind.
    """


# thread states
_NEW, _RUNNABLE, _RUNNING, _BLOCKED, _EXTERNAL, _DONE = "NRGBXD"


class _TS:
    """Per-thread scheduler state."""

    __slots__ = ("thread", "lid", "idx", "tid", "state", "gate",
                 "wake_action", "wake_reason", "wait_kind", "wait_key",
                 "wait_name", "deadline", "prio", "starve",
                 "starve_reported", "held", "external")

    def __init__(self, thread, lid, idx, prio):
        self.thread = thread
        self.lid = lid            # logical id ("T0", "T1", ...) by
        self.idx = idx            # registration order — replay-stable
        self.tid = None           # real ident, filled at thread begin
        self.state = _NEW
        self.gate = _thread.allocate_lock()
        self.gate.acquire()       # parked = gate.acquire() blocks
        self.wake_action = "go"
        self.wake_reason = None
        self.wait_kind = None
        self.wait_key = None
        self.wait_name = None
        self.deadline = None
        self.prio = prio
        self.starve = 0
        self.starve_reported = False
        self.held = []            # _LockModel list, acquisition order
        self.external = False


class _LockModel:
    __slots__ = ("key", "name", "owner", "count", "waiters")

    def __init__(self, key, name):
        self.key = key
        self.name = name
        self.owner = None         # _TS
        self.count = 0
        self.waiters = []         # _TS


class _Journal:
    """Append-only JSONL schedule journal (one object per line, fsync at
    the records that must survive a crash, torn trailing lines tolerated
    by the reader)."""

    def __init__(self, path: Optional[str]):
        self.path = path
        self._f = open(path, "w") if path else None
        self._n = 0

    def write(self, obj, sync=False) -> None:
        if self._f is None:
            return
        self._f.write(json.dumps(obj) + "\n")
        self._n += 1
        if sync or self._n % 256 == 0:
            self._f.flush()
            os.fsync(self._f.fileno())

    def close(self, keep: bool) -> None:
        if self._f is None:
            return
        self._f.flush()
        os.fsync(self._f.fileno())
        self._f.close()
        self._f = None
        if not keep and self.path:
            try:
                os.unlink(self.path)
            except OSError:
                pass


def _res_name(lock) -> str:
    return (getattr(lock, "name", None)
            or getattr(lock, "_name", None)
            or "lock:%x" % id(lock))


class Scheduler:
    """One schedule's controller.  Installed into the hb/runtime shims
    via :func:`hb.set_scheduler`; every shim interception point calls
    back into it.  All state lives under one raw ``_thread`` meta lock
    so the scheduler can never appear in the graphs it drives."""

    # monitor tick: deadline firing + lease granularity
    _TICK = 0.002

    def __init__(self, seed_key, depth=3, starve_ops=20000,
                 est_ops=256, journal: Optional[_Journal] = None,
                 replay_decisions: Optional[List[str]] = None,
                 lease_s=0.5, max_ops=300000):
        self._meta = _thread.allocate_lock()
        self._rng = random.Random(str(seed_key))
        self._depth = max(1, int(depth))
        self._starve_ops = int(starve_ops)
        self._max_ops = int(max_ops)
        self._lease = float(lease_s)
        self.closed = False
        self.aborting = False
        self._all: List[_TS] = []
        self._suppress: set = set()   # tids temporarily passthrough
        self._by_tid: Dict[int, _TS] = {}
        self._by_thread: Dict[int, _TS] = {}
        self._by_lid: Dict[str, _TS] = {}
        self._token: Optional[_TS] = None
        self._grant_t = 0.0
        self._last: Optional[_TS] = None
        self._di = 0              # decision index
        self._demote = -1.0       # next demotion priority (PCT)
        self._locks: Dict[int, _LockModel] = {}
        self._cvs: Dict[int, List[_TS]] = {}
        self._joiners: Dict[int, List[_TS]] = {}
        self._external_n = 0
        self.findings: List[tuple] = []
        self.decisions: List[tuple] = []   # (lid, op, res) in order
        self._journal = journal or _Journal(None)
        self._replay = replay_decisions
        self._ri = 0
        self._replay_stall_t = None
        # PCT: depth-1 priority change points over the estimated
        # schedule length (the explorer feeds each schedule the
        # previous one's measured length, so the points land inside
        # the actual run)
        hi = max(int(est_ops), self._depth + 1)
        self._change_points = (
            set(self._rng.sample(range(1, hi), self._depth - 1))
            if self._depth > 1 else set())
        self._mon_stop = False

    # -- registration -----------------------------------------------------
    def attach_main(self) -> None:
        """Register the calling thread as T0 and hand it the token."""
        th = threading.current_thread()
        with self._meta:
            ts = self._new_ts_locked(th)
            ts.tid = _thread.get_ident()
            ts.state = _RUNNING
            self._by_tid[ts.tid] = ts
            self._token = ts
            self._grant_t = _mono()
            self._last = ts
        _thread.start_new_thread(self._monitor, ())

    def _new_ts_locked(self, th) -> _TS:
        lid = "T%d" % len(self._all)
        ts = _TS(th, lid, len(self._all), self._rng.random())
        self._all.append(ts)
        self._by_thread[id(th)] = ts
        self._by_lid[lid] = ts
        self._journal.write({"kind": "thread", "lid": lid,
                             "name": th.name})
        return ts

    def thread_spawn(self, th) -> None:
        """Called from the hb shim's patched ``Thread.start`` BEFORE
        the real start — registration order is creation order, which
        is deterministic under the token."""
        if self.closed:
            return
        with self._meta:
            if id(th) not in self._by_thread:
                self._new_ts_locked(th)

    def thread_start(self, th, orig_start) -> None:
        """Deterministic ``Thread.start``: the CPython ``_started``
        Event handshake inside ``orig_start`` races the child's
        uncontrolled bootstrap against the spawner's modeled cv wait —
        whether the flag beats the wait would vary run to run and
        leak into the decision stream.  So the spawner goes
        PASSTHROUGH (real primitives, no decisions journaled) for the
        handshake, then rendezvouses until the child parked at its
        first yield point, then takes one explicit scheduling point:
        every schedule sees the same stream, and PCT gets the classic
        preempt-at-start window."""
        me = self._current()
        if me is None or self.closed:
            orig_start(th)
            return
        tid = _thread.get_ident()
        with self._meta:
            self._suppress.add(tid)
        try:
            orig_start(th)
        finally:
            with self._meta:
                self._suppress.discard(tid)
        ts = self._by_thread.get(id(th))
        if ts is None:
            return
        deadline = _mono() + 10.0
        while _mono() < deadline:
            with self._meta:
                if self.closed or ts.state != _NEW:
                    break
            _real_sleep(0.0002)
        self.yield_point("start", ts.lid)

    def thread_begin(self, th) -> None:
        """First thing a controlled child runs: park until scheduled."""
        ts = self._by_thread.get(id(th))
        if ts is None or self.closed:
            return
        with self._meta:
            ts.tid = _thread.get_ident()
            self._by_tid[ts.tid] = ts
        self._pass_baton(ts, _RUNNABLE, ("begin", ts.lid))

    def thread_end(self, th) -> None:
        ts = self._by_thread.get(id(th))
        if ts is None:
            return
        with self._meta:
            if ts.state == _DONE:
                return
            if ts.external:
                ts.external = False
                self._external_n -= 1
            was_token = self._token is ts
            ts.state = _DONE
            for w in self._joiners.pop(id(th), []):
                if w.state == _BLOCKED and w.wait_kind == "join" \
                        and w.wait_key == id(th):
                    w.state = _RUNNABLE
                    w.wake_reason = "done"
            if self.closed:
                return
            if was_token:
                self._token = None
            if self._token is None:
                chosen = self._pick(("end", ts.lid))
                if chosen is not None:
                    self._dispatch_locked(chosen)
                else:
                    self._check_deadlock_locked()

    def thread_join(self, th, timeout):
        """Modeled join.  Returns 'done', 'timeout', or None
        (uncontrolled caller / unknown thread / closed → real join)."""
        me = self._current()
        if me is None or self.closed:
            return None
        ts = self._by_thread.get(id(th))
        if ts is None:
            return None
        self._pass_baton(me, _RUNNABLE, ("join", ts.lid))
        deadline = _mono() + timeout if timeout is not None else None
        while True:
            with self._meta:
                if self.closed:
                    return None
                if ts.state == _DONE:
                    return "done"
                if deadline is not None and _mono() >= deadline:
                    return "timeout"
                lst = self._joiners.setdefault(id(th), [])
                if me not in lst:
                    lst.append(me)
            r = self._pass_baton(
                me, _BLOCKED, ("wait-join", ts.lid),
                wait=("join", id(th), "join:" + ts.lid, deadline))
            if r == "closed":
                return None

    # -- identity ---------------------------------------------------------
    def _current(self) -> Optional[_TS]:
        tid = _thread.get_ident()
        if tid in self._suppress:
            return None
        ts = self._by_tid.get(tid)
        if ts is None or ts.state == _DONE:
            return None
        return ts

    def is_controlled(self) -> bool:
        return self._current() is not None

    # -- the baton --------------------------------------------------------
    def _pass_baton(self, me, state, op, wait=None) -> str:
        """Move ``me`` to ``state`` (_RUNNABLE or _BLOCKED + wait
        info), pick who runs next, and park until this thread holds
        the token again.  Returns the wake reason; raises
        :class:`SchedAbort` when the schedule is aborting."""
        deadlocked = False
        with self._meta:
            if self.closed:
                return "closed"
            had = self._token is me
            if had:
                self._token = None
            if me.external:
                me.external = False
                self._external_n -= 1
            me.state = state
            me.wake_reason = None
            if state == _BLOCKED:
                me.wait_kind, me.wait_key, me.wait_name, me.deadline = wait
            else:
                me.wait_kind = me.wait_key = me.wait_name = None
                me.deadline = None
                me.starve = 0
            if had or self._token is None:
                chosen = self._pick(op)
                if chosen is me:
                    me.state = _RUNNING
                    self._token = me
                    self._grant_t = _mono()
                    return "go"
                if chosen is not None:
                    self._dispatch_locked(chosen)
                elif state == _BLOCKED:
                    deadlocked = self._check_deadlock_locked()
        if deadlocked:
            raise SchedAbort()
        me.gate.acquire()
        if me.wake_action == "abort":
            raise SchedAbort()
        return me.wake_reason or "go"

    def _dispatch_locked(self, chosen) -> None:
        chosen.state = _RUNNING
        chosen.starve = 0
        chosen.wake_action = "abort" if self.aborting else "go"
        self._token = chosen
        self._grant_t = _mono()
        chosen.gate.release()

    def _pick(self, op) -> Optional[_TS]:
        """Choose the next thread (caller holds meta).  PCT in record
        mode, journal-following in replay mode."""
        runnable = [t for t in self._all if t.state == _RUNNABLE]
        if not runnable:
            return None
        self._di += 1
        if self._di in self._change_points and self._last is not None:
            # PCT priority-change point: demote whoever ran last
            self._last.prio = self._demote
            self._demote -= 1.0
        if self._replay is not None:
            chosen = self._replay_pick_locked(runnable)
            if chosen is None:
                self._di -= 1     # nothing consumed — not a decision
                return None
        else:
            chosen = max(runnable, key=lambda t: (t.prio, -t.idx))
        for t in runnable:
            if t is chosen:
                continue
            t.starve += 1
            if self._starve_ops and t.starve >= self._starve_ops \
                    and not t.starve_reported:
                t.starve_reported = True
                self._finding_locked(
                    "starvation",
                    "%s (%s) stayed runnable for %d consecutive "
                    "scheduling decisions without running (budget "
                    "MXNET_SCHED_STARVE_OPS=%d)"
                    % (t.lid, t.thread.name, t.starve, self._starve_ops))
        self._last = chosen
        res = op[1] if len(op) > 1 else None
        self.decisions.append((chosen.lid, op[0], res))
        self._journal.write({"kind": "d", "i": self._di,
                             "t": chosen.lid, "op": op[0], "r": res})
        if self._di >= self._max_ops and not self.aborting:
            self._finding_locked(
                "op-budget",
                "schedule exceeded %d decisions without finishing — "
                "livelock (or raise max_ops)" % self._max_ops)
            self._abort_locked()
        return chosen

    def _replay_pick_locked(self, runnable) -> Optional[_TS]:
        if self._ri >= len(self._replay):
            # recorded run ended here (abort point); free-run the tail
            return max(runnable, key=lambda t: (t.prio, -t.idx))
        lid = self._replay[self._ri]
        ts = self._by_lid.get(lid)
        if ts is None or ts.state in (_NEW, _EXTERNAL, _RUNNING):
            return None           # not arrived at a yield yet — wait
        if ts.state == _BLOCKED:
            if ts.deadline is not None:
                ts.state = _RUNNABLE     # the recorded timeout firing
                ts.wake_reason = "timeout"
                ts.prio = self._demote   # same demotion as the monitor
                self._demote -= 1.0
            else:
                self._finding_locked(
                    "replay-divergence",
                    "journal expects %s at decision %d but it is "
                    "blocked on %s %s" % (lid, self._ri, ts.wait_kind,
                                          ts.wait_name))
                self._abort_locked()
                return None
        if ts.state != _RUNNABLE:
            return None
        self._ri += 1
        self._replay_stall_t = None
        return ts

    # -- findings / abort -------------------------------------------------
    def _finding_locked(self, kind, detail) -> None:
        self.findings.append((kind, detail))
        self._journal.write({"kind": "finding", "type": kind,
                             "detail": detail}, sync=True)

    def add_finding(self, kind, detail) -> None:
        with self._meta:
            self._finding_locked(kind, detail)

    def _abort_locked(self) -> None:
        """Wake every parked thread with the abort action and go
        passthrough — modeled ops fall back to real primitives so the
        scenario can tear itself down."""
        if self.aborting:
            return
        self.aborting = True
        self.closed = True
        self._mon_stop = True
        me = _thread.get_ident()
        for ts in self._all:
            if ts.state in (_RUNNABLE, _BLOCKED) and ts.tid != me:
                ts.wake_action = "abort"
                ts.state = _RUNNING
                ts.gate.release()
        self._token = None

    def _check_deadlock_locked(self) -> bool:
        """All live controlled threads blocked on UNTIMED modeled
        primitives, none external, none still starting → a wait cycle
        by construction.  Build the who-holds-what report with live
        stacks, record the finding, abort.  Caller holds meta; returns
        True when a deadlock was declared (caller must raise)."""
        if self.closed or self.aborting or self._external_n > 0:
            return False
        live = [t for t in self._all if t.state != _DONE]
        if not live:
            return False
        for t in live:
            if t.state != _BLOCKED or t.deadline is not None:
                return False
        frames = sys._current_frames()
        lines = ["deadlock: all %d live threads blocked on shim "
                 "primitives" % len(live)]
        for t in live:
            held = ", ".join(m.name for m in t.held) or "nothing"
            lines.append(
                "  %s (%s): waiting on %s %s; holding %s"
                % (t.lid, t.thread.name, t.wait_kind, t.wait_name, held))
            f = frames.get(t.tid)
            if f is not None:
                stack = [s for s in traceback.format_stack(f)
                         if "analysis/sched.py" not in s
                         and "analysis/hb.py" not in s]
                lines.append("".join("    " + ln for s in stack[-6:]
                                     for ln in s.splitlines(True)))
        self._finding_locked("deadlock", "\n".join(lines))
        self._abort_locked()
        return True

    # -- yield points -----------------------------------------------------
    def yield_point(self, kind, name) -> None:
        """A pure scheduling point: tracked container accesses, SPSC
        ring probes, notifies."""
        me = self._current()
        if me is None or self.closed:
            return
        self._pass_baton(me, _RUNNABLE, (kind, name))

    def sleep_yield(self, secs) -> bool:
        """Modeled ``time.sleep``: park with a real-clock deadline the
        monitor fires — the sleeper stops holding the token, and poll
        loops keep real-time semantics.  False → caller really sleeps."""
        me = self._current()
        if me is None or self.closed:
            return False
        if secs is None or secs <= 0:
            self._pass_baton(me, _RUNNABLE, ("sleep0", None))
            return True
        r = self._pass_baton(me, _BLOCKED, ("sleep", None),
                             wait=("sleep", None, "sleep(%g)" % secs,
                                   _mono() + secs))
        return r != "closed"

    # -- lock modeling ----------------------------------------------------
    def lock_acquire(self, lock, blocking, timeout):
        """Modeled acquire.  True = granted (the caller's real inner
        acquire is then uncontended), False = nonblocking/timed
        failure, None = uncontrolled caller or closed (caller uses the
        real path)."""
        me = self._current()
        if me is None or self.closed:
            return None
        key = id(lock)
        name = _res_name(lock)
        if timeout is not None and timeout > 0:
            deadline = _mono() + timeout
        else:
            deadline = None
        # the pre-acquire scheduling point: the PCT preemption window
        self._pass_baton(me, _RUNNABLE, ("acquire", name))
        while True:
            with self._meta:
                if self.closed:
                    return None
                m = self._locks.get(key)
                if m is None:
                    m = self._locks[key] = _LockModel(key, name)
                if m.owner is None:
                    m.owner = me
                    m.count = 1
                    me.held.append(m)
                    self._unwait_locked(m, me)
                    return True
                if m.owner is me:
                    m.count += 1
                    return True
                if not blocking:
                    self._unwait_locked(m, me)
                    return False
                if deadline is not None and _mono() >= deadline:
                    self._unwait_locked(m, me)
                    return False
                if me not in m.waiters:
                    m.waiters.append(me)
            r = self._pass_baton(me, _BLOCKED, ("wait-lock", name),
                                 wait=("lock", key, name, deadline))
            if r == "closed":
                return None

    @staticmethod
    def _unwait_locked(m, me) -> None:
        try:
            m.waiters.remove(me)
        except ValueError:
            pass

    def lock_release(self, lock) -> bool:
        """Modeled release bookkeeping (True = modeled; the caller
        performs the real release then calls :meth:`after_release`)."""
        me = self._current()
        if me is None or self.closed:
            return False
        with self._meta:
            m = self._locks.get(id(lock))
            if m is None or m.owner is not me:
                return False      # not modeled-owned → real path
            m.count -= 1
            if m.count > 0:
                return True
            m.owner = None
            try:
                me.held.remove(m)
            except ValueError:
                pass
            self._wake_lock_waiters_locked(m)
        return True

    def _wake_lock_waiters_locked(self, m) -> None:
        for w in m.waiters:
            if w.state == _BLOCKED and w.wait_kind == "lock" \
                    and w.wait_key == m.key:
                w.state = _RUNNABLE
                w.wake_reason = "granted"
        m.waiters = []

    def after_release(self, lock) -> None:
        """The post-release scheduling point (the real lock is free;
        freshly woken waiters are schedulable)."""
        me = self._current()
        if me is None or self.closed:
            return
        self._pass_baton(me, _RUNNABLE, ("release", _res_name(lock)))

    # -- condition modeling ----------------------------------------------
    def cv_wait(self, cv, timeout):
        """Modeled Condition wait: model-release the lock, park on the
        cv, reacquire on wake.  Returns 'notified'/'timeout', or None
        when closed before parking (caller does the real wait)."""
        me = self._current()
        if me is None or self.closed:
            return None
        lock = cv._lock
        key = id(lock)
        name = "cv@" + _res_name(lock)
        saved_count = 0
        with self._meta:
            if self.closed:
                return None
            m = self._locks.get(key)
            if m is not None and m.owner is me:
                saved_count = m.count
                m.count = 0
                m.owner = None
                try:
                    me.held.remove(m)
                except ValueError:
                    pass
                self._wake_lock_waiters_locked(m)
            self._cvs.setdefault(id(cv), []).append(me)
        saved = cv._release_save()      # the real full release
        deadline = _mono() + timeout if timeout is not None else None
        try:
            r = self._pass_baton(me, _BLOCKED, ("wait-cv", name),
                                 wait=("cv", id(cv), name, deadline))
        except SchedAbort:
            self._cv_unwait(cv, me)
            try:
                cv._acquire_restore(saved)
            except Exception:  # noqa: BLE001 — unwinding anyway
                pass
            raise
        self._cv_unwait(cv, me)
        self._lock_reacquire(me, key, name, max(1, saved_count))
        cv._acquire_restore(saved)      # real reacquire — uncontended
        return "notified" if r in ("go", "closed", "granted") else r

    def _cv_unwait(self, cv, me) -> None:
        with self._meta:
            lst = self._cvs.get(id(cv))
            if lst is not None:
                try:
                    lst.remove(me)
                except ValueError:
                    pass

    def _lock_reacquire(self, me, key, name, count) -> None:
        """Blocking modeled reacquire after a cv wait (no timeout: the
        real Condition protocol reacquires unconditionally)."""
        while True:
            with self._meta:
                if self.closed:
                    return
                m = self._locks.get(key)
                if m is None:
                    m = self._locks[key] = _LockModel(key, name)
                if m.owner is None:
                    m.owner = me
                    m.count = count
                    me.held.append(m)
                    self._unwait_locked(m, me)
                    return
                if m.owner is me:
                    m.count += count
                    return
                if me not in m.waiters:
                    m.waiters.append(me)
            r = self._pass_baton(me, _BLOCKED, ("wait-lock", name),
                                 wait=("lock", key, name, None))
            if r == "closed":
                return

    def cv_notify(self, cv, n) -> int:
        """Wake up to ``n`` modeled waiters; returns how many of the
        ``n`` are left for the caller's REAL notify (waiters parked in
        the real cv: uncontrolled threads, post-close stragglers)."""
        if self.closed:
            return n
        woken = 0
        with self._meta:
            lst = self._cvs.get(id(cv))
            while lst and woken < n:
                w = lst.pop(0)
                if w.state == _BLOCKED and w.wait_kind == "cv" \
                        and w.wait_key == id(cv):
                    w.state = _RUNNABLE
                    w.wake_reason = "notified"
                    woken += 1
            if woken and self._token is None and not self.closed:
                chosen = self._pick(("notify-dispatch", None))
                if chosen is not None:
                    self._dispatch_locked(chosen)
        return n - woken

    # -- the monitor ------------------------------------------------------
    def _monitor(self) -> None:
        """Raw background thread: fires real-clock deadlines (timed
        waits, sleeps), leases the token away from threads blocked
        outside the model, and watches replay for stalls."""
        while True:
            _real_sleep(self._TICK)
            with self._meta:
                if self.closed or self._mon_stop:
                    return
                now = _mono()
                for ts in self._all:
                    if ts.state == _BLOCKED and ts.deadline is not None \
                            and now >= ts.deadline:
                        ts.state = _RUNNABLE
                        ts.wake_reason = "timeout"
                        # Timer wakeups go to the BACK of the priority
                        # order: PCT's static priorities assume
                        # bounded-length threads, and a periodic loop
                        # (heartbeat, poller) that kept a high priority
                        # across every firing would starve the threads
                        # doing the actual work forever.
                        ts.prio = self._demote
                        self._demote -= 1.0
                tok = self._token
                if tok is not None and now - self._grant_t > self._lease:
                    # the token holder is blocked outside the model
                    # (socket, foreign lock, long compute): free the
                    # token; the thread rejoins at its next yield
                    tok.state = _EXTERNAL
                    tok.external = True
                    self._external_n += 1
                    self._token = None
                if self._token is None:
                    chosen = self._pick(("monitor", None))
                    if chosen is not None:
                        self._dispatch_locked(chosen)
                    elif self._replay is not None:
                        # replay stall: the expected thread never
                        # arrives (timing-dependent divergence)
                        if self._replay_stall_t is None:
                            self._replay_stall_t = now
                        elif now - self._replay_stall_t > \
                                _REPLAY_STALL_S:
                            self._finding_locked(
                                "replay-divergence",
                                "replay stalled %.0fs waiting for %s "
                                "at decision %d" % (
                                    _REPLAY_STALL_S,
                                    self._replay[self._ri]
                                    if self._ri < len(self._replay)
                                    else "<end>", self._ri))
                            self._abort_locked()

    # -- shutdown ---------------------------------------------------------
    def close(self) -> None:
        """Normal end of schedule: go passthrough, wake every parked
        thread (they resume on real primitives for teardown)."""
        with self._meta:
            if self.closed:
                return
            self.closed = True
            self._mon_stop = True
            me = _thread.get_ident()
            for ts in self._all:
                if ts.state in (_RUNNABLE, _BLOCKED) and ts.tid != me:
                    ts.wake_action = "go"
                    ts.wake_reason = "closed"
                    ts.state = _RUNNING
                    ts.gate.release()
            self._token = None


# -- the Condition / sleep patches -------------------------------------------
class SchedCondition(threading.Condition):
    """Drop-in ``threading.Condition`` whose waits and notifies route
    through the installed scheduler for controlled threads, and behave
    exactly like the stock class otherwise (uncontrolled threads,
    after close).  CPython's ``queue.Queue`` and ``threading.Event``
    look ``threading.Condition`` up at call time, so patching the
    module attribute covers queue put/get blocking and Event waits."""

    def wait(self, timeout=None):
        from . import hb as _hb
        sch = _hb.scheduler()
        if sch is not None and not sch.closed and sch.is_controlled():
            r = sch.cv_wait(self, timeout)
            if r is not None:
                return r != "timeout"
        return super().wait(timeout)

    def notify(self, n=1):
        from . import hb as _hb
        sch = _hb.scheduler()
        if sch is not None and not sch.closed:
            left = sch.cv_notify(self, n)
            if left > 0 and getattr(self, "_waiters", None):
                super().notify(min(left, len(self._waiters)))
            sch.yield_point("notify", "cv@" + _res_name(self._lock))
            return
        super().notify(n)

    def notify_all(self):
        self.notify(1 << 30)

    notifyAll = notify_all


_hook_installed = False


def _ensure_excepthook() -> None:
    """Filter SchedAbort out of ``threading.excepthook`` PERMANENTLY
    (installed at first schedule, idempotent): an aborted controlled
    thread can still be unwinding after ``_patched`` exits, so a
    scoped save/restore races the teardown and leaks tracebacks."""
    global _hook_installed
    if _hook_installed:
        return
    orig_hook = threading.excepthook

    def hook(args):
        # SchedAbort unwinding a controlled thread is the scheduler's
        # own teardown, not a scenario failure — keep stderr clean
        if args.exc_type is not SchedAbort:
            orig_hook(args)

    threading.excepthook = hook
    _hook_installed = True


@contextlib.contextmanager
def _patched(sch):
    """Install the scheduler: hb hook + threading.Condition +
    time.sleep.  Must nest INSIDE ``hb.shim`` so locks are HBLocks."""
    import select as _select_mod
    from . import hb as _hb
    orig_cond = threading.Condition
    orig_sleep = time.sleep
    orig_select = _select_mod.select

    def sched_sleep(secs):
        s = _hb.scheduler()
        if s is not None and s.sleep_yield(secs):
            return
        orig_sleep(secs)

    def sched_select(rlist, wlist, xlist, timeout=None):
        # A TIMED select from a controlled thread is a poll sweep:
        # model the wait as a deadline sleep (so the poller yields the
        # token and gets the timer demotion like any periodic loop)
        # then probe readiness without blocking.  An untimed select is
        # real blocking IO — leave it to the lease watchdog.
        s = _hb.scheduler()
        if (s is not None and timeout is not None
                and s.is_controlled() and not s.closed):
            if timeout > 0:
                s.sleep_yield(timeout)
            else:
                s.yield_point("select", None)
            return orig_select(rlist, wlist, xlist, 0)
        return orig_select(rlist, wlist, xlist, timeout)

    threading.Condition = SchedCondition
    time.sleep = sched_sleep
    _ensure_excepthook()
    _select_mod.select = sched_select
    _hb.set_scheduler(sch)
    try:
        yield
    finally:
        _hb.set_scheduler(None)
        threading.Condition = orig_cond
        time.sleep = orig_sleep
        _select_mod.select = orig_select


@contextlib.contextmanager
def _env_overlay(env: Dict[str, str]):
    saved = {}
    try:
        for k, v in (env or {}).items():
            saved[k] = os.environ.get(k)
            os.environ[k] = str(v)
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


# -- schedule results ---------------------------------------------------------
class ScheduleResult:
    def __init__(self, scenario, index, seed, findings, decisions,
                 ops, journal_path, race_count):
        self.scenario = scenario
        self.index = index
        self.seed = seed
        self.findings = findings          # [(kind, detail), ...]
        self.decisions = decisions        # [(lid, op, res), ...]
        self.ops = ops
        self.journal_path = journal_path  # None when clean (deleted)
        self.race_count = race_count

    @property
    def clean(self) -> bool:
        return not self.findings


class ExploreResult:
    def __init__(self, scenario, seed, schedules):
        self.scenario = scenario
        self.seed = seed
        self.schedules: List[ScheduleResult] = schedules

    @property
    def findings(self):
        return [f for r in self.schedules for f in r.findings]

    @property
    def failing(self) -> Optional[ScheduleResult]:
        for r in self.schedules:
            if r.findings:
                return r
        return None


def _default_journal_dir() -> str:
    from ..base import env as _env
    return str(_env("MXNET_SCHED_JOURNAL_DIR", "_sched_journals"))


def run_schedule(scenario, index=0, seed=0, depth=3, starve_ops=None,
                 journal_dir=None, est_ops=256,
                 replay_decisions=None, keep_journal=False,
                 max_ops=300000, lease_s=None) -> ScheduleResult:
    """Run ``scenario`` (a :class:`scenarios.Scenario`) under ONE
    controlled schedule.  The journal is written as the schedule runs
    and kept iff the schedule produced findings (or ``keep_journal``)."""
    from . import hb as _hb
    from ..base import env as _env
    if starve_ops is None:
        starve_ops = int(_env("MXNET_SCHED_STARVE_OPS", 20000))
    if lease_s is None:
        lease_s = getattr(scenario, "lease_s", 0.5)
    journal_dir = journal_dir or _default_journal_dir()
    os.makedirs(journal_dir, exist_ok=True)
    tag = "replay-" if replay_decisions is not None else ""
    path = os.path.join(journal_dir, "%s%s-seed%s-i%d.jsonl"
                        % (tag, scenario.name, seed, index))
    jr = _Journal(path)
    jr.write({"kind": "header", "v": 1, "scenario": scenario.name,
              "seed": seed, "index": index, "depth": depth,
              "starve_ops": starve_ops, "est_ops": est_ops,
              "lease_s": lease_s}, sync=True)
    sch = Scheduler("%s:%s:%s" % (scenario.name, seed, index),
                    depth=depth, starve_ops=starve_ops, est_ops=est_ops,
                    journal=jr, replay_decisions=replay_decisions,
                    max_ops=max_ops, lease_s=lease_s)
    san = _hb.Sanitizer(strict=False)
    with _env_overlay(scenario.env):
        with _hb.shim(san=san):
            with _patched(sch):
                sch.attach_main()
                try:
                    scenario.fn()
                except SchedAbort:
                    pass
                except (KeyboardInterrupt, SystemExit):
                    raise
                except BaseException as exc:  # noqa: BLE001 — finding
                    sch.add_finding(
                        "scenario-error",
                        "%s: %s\n%s" % (type(exc).__name__, exc,
                                        traceback.format_exc()))
                finally:
                    sch.close()
    for v in san.violations():
        sch.findings.append(("race", v))
        jr.write({"kind": "finding", "type": "race", "detail": v},
                 sync=True)
    findings = list(sch.findings)
    jr.write({"kind": "end", "decisions": sch._di,
              "findings": len(findings),
              "status": "findings" if findings else "clean"}, sync=True)
    keep = bool(findings) or keep_journal
    jr.close(keep=keep)
    return ScheduleResult(scenario.name, index, seed, findings,
                          list(sch.decisions), sch._di,
                          path if keep else None,
                          len(san.violations()))


def explore(scenario_name, schedules=20, seed=0, depth=None,
            starve_ops=None, journal_dir=None,
            stop_on_finding=True, max_ops=300000) -> ExploreResult:
    """Drive ``scenario_name`` through N seeded schedules.  Each
    schedule feeds the next one's PCT change-point range with its
    measured length, so the priority changes land inside the run."""
    from ..base import env as _env
    from .scenarios import get as _get_scenario
    if depth is None:
        depth = int(_env("MXNET_SCHED_DEPTH", 3))
    sc = _get_scenario(scenario_name)
    est = 256
    results = []
    for i in range(int(schedules)):
        r = run_schedule(sc, index=i, seed=seed, depth=depth,
                         starve_ops=starve_ops, journal_dir=journal_dir,
                         est_ops=est, max_ops=max_ops)
        results.append(r)
        if r.ops > 0:
            est = max(64, r.ops)
        if r.findings and stop_on_finding:
            break
    return ExploreResult(scenario_name, seed, results)


# -- journals -----------------------------------------------------------------
def read_journal(path):
    """Parse a schedule journal: (header, decisions, findings).
    Torn trailing lines (a crash mid-write) are tolerated."""
    header = None
    decisions = []
    findings = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                obj = json.loads(line)
            except ValueError:
                continue          # torn line — fsync'd records precede it
            kind = obj.get("kind")
            if kind == "header":
                header = obj
            elif kind == "d":
                decisions.append(obj)
            elif kind == "finding":
                findings.append(obj)
    if header is None:
        raise ValueError("no journal header in %s" % path)
    return header, decisions, findings


def replay(journal_path, journal_dir=None) -> ScheduleResult:
    """Re-execute a recorded schedule decision for decision.  The
    scenario, seed, and depth come from the journal header; the seeded
    RNG re-derives identical priorities, and the pick loop follows the
    journal's thread choices instead of the priorities — so a pure
    thread scenario reproduces bit-identically (same decisions, same
    findings), and a divergence is itself reported as a finding."""
    from .scenarios import get as _get_scenario
    header, decisions, _ = read_journal(journal_path)
    sc = _get_scenario(header["scenario"])
    lids = [d["t"] for d in decisions]
    return run_schedule(
        sc, index=header.get("index", 0), seed=header.get("seed", 0),
        depth=header.get("depth", 3),
        starve_ops=header.get("starve_ops"),
        journal_dir=journal_dir, est_ops=header.get("est_ops", 256),
        replay_decisions=lids, keep_journal=True,
        lease_s=header.get("lease_s"))
