"""The flash kernels' share of their roofline: for every call in the traced
slice the least time the chip could take (the larger of its FLOPs over the
published peak FLOP/s and its bytes over the published peak bytes/s, from
the family's ``kernel_costs``), summed, over the kernels' measured time.
At 4x16 heads of 1024x64 all three are compute-bound (254, 303 and 338
FLOP/byte against a ridge of 197e12 / 819e9 = 240.5)."""
UNIT = "%"
LAYER = "kernels"
MOVES = "train_items_per_s"
SOURCE = "device_trace"
PREFIX = "flash_"


def read(record):
    trace, peaks = record.get("trace"), record.get("peaks")
    if not trace or not peaks:
        return None
    least = measured = 0.0
    for name, cost in record["kernel_costs"].items():
        calls = trace["kernel_calls"].get(name, 0)
        if not name.startswith(PREFIX) or not calls:
            continue
        least += calls * max(cost["flops"] / peaks["bf16_flops_per_s"],
                             cost["bytes"] / peaks["hbm_bytes_per_s"])
        measured += trace["kernel_s"][name]
    if not measured:
        return None
    return least / measured * 100.0
