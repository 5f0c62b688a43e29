"""Share of the traced slice's device time whose event carries a scope the
program wrote (a symbol node's, or the optimizer's): what is left is what
the split cannot place by name."""
from chipbench import program_trace

UNIT = "%"
LAYER = "ops"
MOVES = "train_items_per_s"
SOURCE = "device_trace"


def read(record):
    got = program_trace.of(record)
    if got is None or not got["scoped"]:
        return None
    return got["scoped_s"] / sum(got["phase_s"].values()) * 100.0
