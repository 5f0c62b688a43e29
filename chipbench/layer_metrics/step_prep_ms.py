"""Host time a step inside ``mx.module.update.prep``: update counts, lr and
wd scalars, state tuples: everything ``Module.update`` does before the
jitted call."""
from chipbench import program_trace

UNIT = "ms"
LAYER = "training driver"
MOVES = "train_items_per_s"
SOURCE = "program_span"


def read(record):
    return program_trace.span_ms_per_step(record, "mx.module.update.prep")
