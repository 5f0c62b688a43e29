"""The part of ``loop_body_ms_per_step`` that is the backward pass's
recomputation of the forward: events under a loop node's scope with the
component ``rematted_computation`` in their path (the node's
``remat=True``; chipbench/layer_metrics/_loop_events.py), per whole step.
By design about a quarter of the loop's matmul time."""
from chipbench.layer_metrics import _loop_events

UNIT = "ms"
LAYER = "ops"
MOVES = "train_items_per_s"
SOURCE = "device_trace"


def read(record):
    return _loop_events.ms_per_step(record, "recomputed_s")
