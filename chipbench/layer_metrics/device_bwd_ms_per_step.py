"""Device time of the traced slice's ``XLA Ops`` events whose scope path
has ``transpose(``: the backward pass, per whole step."""
from chipbench import program_trace

UNIT = "ms"
LAYER = "ops"
MOVES = "train_items_per_s"
SOURCE = "device_trace"


def read(record):
    return program_trace.device_ms_per_step(record, "backward")
