"""Framework host syncs per step over the window
(``mxnet_tpu.profiler.host_sync_total``); chipbench's own stamp waits are
not framework syncs and are not counted.  A count: true on any platform."""
UNIT = "count"
LAYER = "training driver"
MOVES = "train_items_per_s"
SOURCE = "program_counter"
CHIP_ONLY = False


def read(record):
    return record["host_syncs"] / record["steps"]
