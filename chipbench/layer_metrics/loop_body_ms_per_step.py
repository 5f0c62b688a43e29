"""Device time of the traced slice's ``XLA Ops`` events whose scope path
passes through a loop node's scope (forward, backward and the backward's
recomputation of the forward alike), per whole step: what the looped stack
costs, beside the heads and the optimizer outside it
(chipbench/layer_metrics/_loop_events.py)."""
from chipbench.layer_metrics import _loop_events

UNIT = "ms"
LAYER = "ops"
MOVES = "train_items_per_s"
SOURCE = "device_trace"


def read(record):
    return _loop_events.ms_per_step(record, "total_s")
