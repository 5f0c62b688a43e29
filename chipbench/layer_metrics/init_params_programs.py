"""Programs compiled or loaded from the cache while the system's own
Module drew its parameters: the compile ledger's ``compile`` records filed
under the first ``mx.module.init_params``.  jax compiles a program once a
process, so each record is a distinct program (a name and its shapes).
Chip-only like the phase clocks: the CPU compiles another set.  Nothing on
a program without the ledger."""
from chipbench.layer_metrics import _setup_ledger as ledger

UNIT = "count"
LAYER = "training driver"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(record):
    t = ledger.tracing()
    if t is None:
        return None
    return sum(1 for r in t.compile_records()
               if r["stage"] == "compile" and r["occurrence"] == 0
               and r["phase"] == "mx.module.init_params")
