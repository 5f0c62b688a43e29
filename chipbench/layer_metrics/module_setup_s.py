"""Seconds of the system's own Module's set-up, as the program times it:
the first ``mx.module.bind``, ``init_params``, ``init_optimizer`` and
``first_update`` of ``mxnet_tpu.tracing.phase_seconds()``, plus every
``mx.module.recompile``.  The part of ``setup_s`` a change to the program
can move; what is left of ``setup_s`` is the benchmark's own (batches,
reference check, warm-up steps).  Nothing on a program without the compile
ledger."""
from chipbench.layer_metrics import _setup_ledger as ledger

UNIT = "s"
LAYER = "training driver"
MOVES = "setup_s"
SOURCE = "program_span"


def read(record):
    t = ledger.tracing()
    if t is None:
        return None
    phases = t.phase_seconds()
    if not all(phases.get(p) for p in ledger.PHASES):
        return None
    return sum(phases[p][0] for p in ledger.PHASES) \
        + ledger.recompile_seconds(t)
