"""Device time of the Pallas flash-attention kernels per step: events of
the traced slice whose name starts ``flash_fwd``, ``flash_bwd_dq`` or
``flash_bwd_dkv`` (the prefixes are the keys of the family's
``kernel_costs``).  Nothing where no such kernel ran."""
UNIT = "ms"
LAYER = "kernels"
MOVES = "train_items_per_s"
SOURCE = "device_trace"
PREFIX = "flash_"


def read(record):
    trace = record.get("trace")
    if not trace or not trace["steps"]:
        return None
    calls = sum(n for k, n in trace["kernel_calls"].items()
                if k.startswith(PREFIX))
    if not calls:
        return None
    total = sum(s for k, s in trace["kernel_s"].items()
                if k.startswith(PREFIX))
    return total / trace["steps"] * 1e3
