"""Share of the traced slice in which no operation ran on the device."""
UNIT = "%"
LAYER = "device"
MOVES = "train_items_per_s"
SOURCE = "device_trace"


def read(record):
    trace = record.get("trace")
    if not trace:
        return None
    return (1.0 - trace["busy_s"] / trace["window_s"]) * 100.0
