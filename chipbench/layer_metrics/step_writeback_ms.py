"""Host time a step inside ``mx.module.update.writeback``: handing the
step's results to the Module's arrays and poisoning what was donated."""
from chipbench import program_trace

UNIT = "ms"
LAYER = "training driver"
MOVES = "train_items_per_s"
SOURCE = "program_span"


def read(record):
    return program_trace.span_ms_per_step(record, "mx.module.update.writeback")
