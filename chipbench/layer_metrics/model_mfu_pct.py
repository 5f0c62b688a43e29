"""Model FLOP/s utilisation: the operations the model requires for a step
(forward + backward, the family file's shape function, nothing recomputed)
times the window's steps per second, over chips times the published bf16
peak of this exact device kind (chipbench/peaks.json)."""
UNIT = "%"
LAYER = "device"
MOVES = "train_items_per_s"
SOURCE = "host_clock"


def read(record):
    if not record.get("peaks"):
        return None
    rate = record["model_flops_per_step"] * record["steps"] \
        / record["window_s"]
    return rate / (record["chips"]
                   * record["peaks"]["bf16_flops_per_s"]) * 100.0
