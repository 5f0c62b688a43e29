"""Device time of the traced slice's ``XLA Ops`` events whose scope path
names a symbol node under ``jvp(..)`` and no ``transpose(``: the forward
pass (chipbench/program_trace.py ``classify``), per whole step."""
from chipbench import program_trace

UNIT = "ms"
LAYER = "ops"
MOVES = "train_items_per_s"
SOURCE = "device_trace"


def read(record):
    return program_trace.device_ms_per_step(record, "forward")
