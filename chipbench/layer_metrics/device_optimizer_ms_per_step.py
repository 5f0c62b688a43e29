"""Device time of the traced slice's ``XLA Ops`` events under the fused
step's ``optimizer`` scope (``opt.apply_fused``; ``param_constraint``, the
sharding constraints after it, counts with it), per whole step."""
from chipbench import program_trace

UNIT = "ms"
LAYER = "ops"
MOVES = "train_items_per_s"
SOURCE = "device_trace"


def read(record):
    return program_trace.device_ms_per_step(record, "optimizer")
