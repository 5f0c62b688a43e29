"""Union of the device plane's ``XLA Ops`` intervals over the traced
slice, per whole step in it."""
UNIT = "ms"
LAYER = "ops"
MOVES = "train_items_per_s"
SOURCE = "device_trace"


def read(record):
    trace = record.get("trace")
    if not trace or not trace["steps"]:
        return None
    return trace["busy_s"] / trace["steps"] * 1e3
