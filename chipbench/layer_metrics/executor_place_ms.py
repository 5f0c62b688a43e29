"""Host time a step inside ``mx.executor.place``: ``Executor.forward``
walking every argument and auxiliary state to where the program reads it."""
from chipbench import program_trace

UNIT = "ms"
LAYER = "executor"
MOVES = "train_items_per_s"
SOURCE = "program_span"


def read(record):
    return program_trace.span_ms_per_step(record, "mx.executor.place")
