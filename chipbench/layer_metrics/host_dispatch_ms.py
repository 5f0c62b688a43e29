"""Mean host time inside ``Module.forward`` + ``Module.update`` per step,
over the window (chipbench's own clock around the two calls)."""
UNIT = "ms"
LAYER = "training driver"
MOVES = "train_items_per_s"
SOURCE = "host_clock"


def read(record):
    return record["host_dispatch_s"] / record["steps"] * 1e3
