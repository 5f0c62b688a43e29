"""How many values a step actually moves to the device while placing its
arguments: entries of ``mx.executor.device_put`` in the traced slice over
its steps.  0 for a resident batch."""
from chipbench import program_trace

UNIT = "count"
LAYER = "executor"
MOVES = "step_ms_p90"
SOURCE = "program_counter"


def read(record):
    return program_trace.span_count_per_step(record, "mx.executor.device_put")
