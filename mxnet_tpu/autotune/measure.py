"""Measurement executors: run one config in a fresh subprocess.

Every measurement runs in its OWN child process with a hard deadline —
a config that hangs, OOMs, or crashes is killed/recorded and the sweep
moves on; nothing a trial does can wedge the harness.  The child's
whole process GROUP is SIGKILLed on timeout because targets like the
launcher-driven smokes spawn their own children.

One process per chip: the children run ONE AT A TIME and the parent
(``python -m mxnet_tpu.autotune``) never initialises a JAX backend — it
learns the device from the child's JSON row — so each child finds the
chip free (tests/test_import_hermetic.py pins the import side of that).

Contract with targets: the child prints ONE JSON object line on stdout
(the bench.py output contract); stderr/progress marks are free-form.
The LAST parseable JSON-object line wins, matching bench.py's
single-line guarantee while tolerating chatty targets.
"""
from __future__ import annotations

import dataclasses
import json
import os
import signal
import subprocess
import sys
import time
from typing import Dict, List, Optional


@dataclasses.dataclass
class MeasureResult:
    status: str                    # ok | timeout | crash | error
    payload: Optional[dict]        # the parsed JSON line (None unless found)
    duration_s: float
    error: Optional[str] = None


def _last_json_line(text: str) -> Optional[dict]:
    out = None
    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            d = json.loads(line)
        except ValueError:
            continue
        if isinstance(d, dict):
            out = d
    return out


class SubprocessExecutor:
    """Run target commands with per-trial env overrides and a deadline."""

    def __init__(self, timeout_s: float, mark=None):
        self.timeout_s = max(1.0, float(timeout_s))
        self._mark = mark or (lambda msg: None)

    def run(self, argv: List[str], env_overrides: Dict[str, object],
            cwd: Optional[str] = None) -> MeasureResult:
        env = dict(os.environ)
        for k, v in env_overrides.items():
            env[k] = str(v)
        t0 = time.perf_counter()
        try:
            proc = subprocess.Popen(
                argv, cwd=cwd, env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                start_new_session=True)   # own group: killpg reaps children
        except OSError as e:
            return MeasureResult(status="crash", payload=None,
                                 duration_s=0.0,
                                 error="spawn failed: %s" % e)
        try:
            out, _ = proc.communicate(timeout=self.timeout_s)
        except subprocess.TimeoutExpired:
            self._kill_group(proc)
            try:
                out, _ = proc.communicate(timeout=10)
            except Exception:  # noqa: BLE001 — already SIGKILLed; best effort
                out = b""
            dt = time.perf_counter() - t0
            return MeasureResult(
                status="timeout", payload=_last_json_line(
                    (out or b"").decode(errors="replace")),
                duration_s=dt,
                error="trial deadline %.0fs exceeded — process group "
                      "SIGKILLed" % self.timeout_s)
        dt = time.perf_counter() - t0
        text = (out or b"").decode(errors="replace")
        payload = _last_json_line(text)
        if proc.returncode != 0:
            return MeasureResult(
                status="crash", payload=payload, duration_s=dt,
                error="rc=%s: %s" % (proc.returncode,
                                     text.strip()[-400:] or "<no output>"))
        if payload is None:
            return MeasureResult(
                status="error", payload=None, duration_s=dt,
                error="no JSON line on stdout (output contract): %s"
                      % (text.strip()[-400:] or "<no output>"))
        if payload.get("error"):
            return MeasureResult(status="error", payload=payload,
                                 duration_s=dt,
                                 error=str(payload["error"])[:400])
        return MeasureResult(status="ok", payload=payload, duration_s=dt)

    @staticmethod
    def _kill_group(proc) -> None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError, OSError):
            proc.kill()


def python_argv(*tail: str) -> List[str]:
    """argv prefix for a child running THIS interpreter."""
    return [sys.executable, *tail]
