"""Seconds of lowering to StableHLO in the system's own Module's set-up,
Mosaic kernels included: the union of the compile ledger's ``lower``
records (jax ``jaxpr_to_mlir_module_duration``) filed under the phases
``module_setup_s`` sums."""
from chipbench.layer_metrics import _setup_ledger as ledger

UNIT = "s"
LAYER = "compile"
MOVES = "setup_s"
SOURCE = "program_span"


def read(record):
    return ledger.stage_seconds("lower")
