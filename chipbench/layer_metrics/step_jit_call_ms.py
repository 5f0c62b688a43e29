"""Host time a step inside ``mx.module.update.call``: the jitted fused
step's own dispatch."""
from chipbench import program_trace

UNIT = "ms"
LAYER = "executor"
MOVES = "train_items_per_s"
SOURCE = "program_span"


def read(record):
    return program_trace.span_ms_per_step(record, "mx.module.update.call")
