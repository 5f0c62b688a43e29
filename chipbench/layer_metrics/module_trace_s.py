"""Seconds of Python tracing in the system's own Module's set-up: the
union of the compile ledger's top-level ``trace`` records
(jax ``jaxpr_trace_duration``, nested traces folded into the one that
encloses them) filed under the phases ``module_setup_s`` sums.  Unlike
``trace_lower_s`` it leaves out the yardstick's programs and counts a
nested trace once."""
from chipbench.layer_metrics import _setup_ledger as ledger

UNIT = "s"
LAYER = "executor"
MOVES = "setup_s"
SOURCE = "program_span"


def read(record):
    return ledger.stage_seconds("trace")
