"""Seconds of Python tracing and lowering during set-up (the executor
turning the symbol into one jitted program): jax.monitoring duration events
``jaxpr_trace_duration`` and ``jaxpr_to_mlir_module_duration``.  The part
of set-up that no compile cache removes."""
UNIT = "s"
LAYER = "executor"
MOVES = "setup_s"
SOURCE = "program_span"


def read(record):
    return record["compile"]["setup"]["trace_lower_s"]
