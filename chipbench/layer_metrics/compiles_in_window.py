"""Backend-compile events between the window's first dispatch and its last
stamp; ``correct`` needs 0.  A count: true on any platform."""
UNIT = "count"
LAYER = "executor"
MOVES = "step_ms_p90"
SOURCE = "program_counter"
CHIP_ONLY = False


def read(record):
    return record["compile"]["window"]["backend_compiles"]
