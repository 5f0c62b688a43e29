"""Seconds inside XLA:TPU and Mosaic compilation (or loading the compiled
program from the persistent cache) during set-up: jax.monitoring duration
events ending ``backend_compile_duration``."""
UNIT = "s"
LAYER = "compile"
MOVES = "setup_s"
SOURCE = "program_span"


def read(record):
    return record["compile"]["setup"]["backend_compile_s"]
