"""Shared by the set-up ledger's readers (not a metric: its name starts
with ``_``).  The system's own Module is the first built, so its set-up is
the first occurrence of each Module phase in ``mxnet_tpu.tracing``'s
always-on phase clock, plus every ``mx.module.recompile`` (a compile of
the fused step found after the first update).  The compile ledger
(``tracing.compile_records()``) files each top-level trace, lowering and
compile under the innermost phase open as it ran; ``mx.module.build_step``
nests in ``mx.module.first_update``.  A later Module (rn50's float32 one)
and the reference programs are in none of these.  A program without the
ledger (the parent of PR 39) gives nothing."""
PHASES = ("mx.module.bind", "mx.module.init_params",
          "mx.module.init_optimizer", "mx.module.first_update")
RECOMPILE = "mx.module.recompile"


def tracing():
    """``mxnet_tpu.tracing`` where it keeps the compile ledger, else None."""
    from mxnet_tpu import tracing as t
    return t if hasattr(t, "compile_records") else None


def own(record):
    """A ledger record filed under the system's own Module's set-up."""
    return (record["occurrence"] == 0 and record["phase"] in PHASES
            + ("mx.module.build_step",)) or record["phase"] == RECOMPILE


def recompile_seconds(t):
    return sum(t.phase_seconds().get(RECOMPILE, []))


def stage_seconds(stage):
    """Seconds of the union of the system's own ``stage`` records."""
    t = tracing()
    if t is None:
        return None
    return t.union_seconds([r for r in t.compile_records()
                            if r["stage"] == stage and own(r)])
