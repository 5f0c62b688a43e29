"""How often a loop node's body was interpreted under a jax trace in this
process: ``mxnet_tpu.profiler.dispatch_counts()["loop.body_trace"]``,
bumped inside the scanned body (mxnet_tpu/ops/control_flow.py).  A body is
traced once for each program that holds its node (shape inference, the
executor's shape-only trace of the forward, the fused step, and the same
again for a float32 Module the check builds), never once per loop step or
per layer application: a fixed few, all in set-up (no program is traced in
the window: ``compiles_in_window``).  A program without the counter (no
loop node ran, or the parent of PR 34) gives nothing."""
UNIT = "count"
LAYER = "executor"
MOVES = "setup_s"
SOURCE = "program_counter"
CHIP_ONLY = False


def read(record):
    from mxnet_tpu import profiler
    return profiler.dispatch_counts().get("loop.body_trace")
