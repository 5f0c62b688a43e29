"""Seconds the fused step spent compiling again after its first update:
the sum of ``mxnet_tpu.tracing.phase_seconds()["mx.module.recompile"]``,
each the union of the compile ledger's records inside the ``update()``
whose jit cache grew past one entry (``Module._note_step_compiled``).
0.0 where the program keeps the ledger and nothing compiled again; nothing
on a program without it.  What ROADMAP S4(a)'s fix would save."""
from chipbench.layer_metrics import _setup_ledger as ledger

UNIT = "s"
LAYER = "executor"
MOVES = "setup_s"
SOURCE = "program_span"


def read(record):
    t = ledger.tracing()
    return None if t is None else ledger.recompile_seconds(t)
