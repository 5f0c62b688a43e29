"""Device time of the traced slice under a loop node's scope, shared by
``loop_body_ms_per_step`` and ``loop_recompute_ms_per_step`` (a file whose
name starts with ``_`` is no metric: the harness skips it).

A loop node (op ``_foreach``, mxnet_tpu/ops/control_flow.py) runs under
``jax.named_scope(<node>)`` like every symbol node, and its body's nodes
under theirs inside it, so an ``XLA Ops`` event of the loop carries
``jit(mx_fused_step)/jvp(<loop>)/while/body/closed_call/<node>/<primitive>``
forward and ``.../transpose(jvp(<loop>))/while/body/closed_call/checkpoint/
<node>/..`` backward; what the backward recomputes of the forward (the
node's ``remat=True``: ``jax.checkpoint`` of the scanned body) has the
component ``rematted_computation`` after ``checkpoint`` (PERF.md section 6,
PR 34, shows sample paths from the chip).  The loop nodes' names are asked
of the cell's symbol, as ``program_trace.node_ops`` asks.  Nothing, and no
error, where the cell's symbol has no loop node, where the program has no
such op (the parent of PR 34) or where the run was not traced."""
import sys

from chipbench import program_trace

LOOP_OP = "_foreach"
RECOMPUTED = "rematted_computation"
_KEY = "_loop_events"


def _read(record):
    path = program_trace.find_xplane()
    ops = program_trace.node_ops(program_trace._cell_of(path)) or {}
    loops = {name for name, op in ops.items() if op == LOOP_OP}
    if not loops:
        return None
    planes = [p for p in program_trace.read_planes(path)
              if program_trace.DEVICE_PLANE.match(p["name"])]
    total = recomputed = 0.0
    samples = {}
    for plane in planes:
        for line in plane["lines"]:
            for _, _, dur, scope in line["events"]:
                parts = scope.split("/") if scope else ()
                if not any(program_trace._inside(p) in loops for p in parts):
                    continue
                total += dur
                again = RECOMPUTED in parts
                recomputed += dur if again else 0.0
                kind = "recomputed" if again else \
                    "backward" if "transpose(" in scope else "forward"
                if dur > samples.get(kind, (0.0, ""))[0]:
                    samples[kind] = (dur, scope)    # the longest of its kind
    n = max(1, len(planes))
    for kind, (dur, scope) in sorted(samples.items()):
        print("[loop_events] the longest %s event, %.1f us: %s"
              % (kind, dur / 1e3, scope), file=sys.stderr, flush=True)
    return {"total_s": total / n / 1e9, "recomputed_s": recomputed / n / 1e9}


def ms_per_step(record, key):
    """ms a step of ``total_s`` or ``recomputed_s``; None where there is
    nothing to read."""
    trace = record.get("trace")
    if not trace or not trace.get("steps"):
        return None
    if _KEY not in record:
        record[_KEY] = None
        try:
            record[_KEY] = _read(record)
        except Exception as e:  # noqa: BLE001 -- a reader never fails a run
            print("[loop_events] nothing read: %r" % (e,), file=sys.stderr,
                  flush=True)
    got = record[_KEY]
    return None if got is None else got[key] / trace["steps"] * 1e3
