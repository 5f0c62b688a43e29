"""Seconds of the system's own Module's first ``update()``: build, trace,
lower, and compile or cache load of the fused step: the first
``mx.module.first_update`` of ``mxnet_tpu.profiler.phase_seconds()``."""
from chipbench import program_trace

UNIT = "s"
LAYER = "executor"
MOVES = "setup_s"
SOURCE = "program_span"


def read(record):
    return program_trace.phase_seconds("mx.module.first_update")
