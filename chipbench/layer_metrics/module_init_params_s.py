"""Seconds the system's own Module (the first built) spent in
``init_params``: the first ``mx.module.init_params`` of
``mxnet_tpu.profiler.phase_seconds()``."""
from chipbench import program_trace

UNIT = "s"
LAYER = "training driver"
MOVES = "setup_s"
SOURCE = "program_span"


def read(record):
    return program_trace.phase_seconds("mx.module.init_params")
