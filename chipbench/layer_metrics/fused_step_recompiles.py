"""How often a fused step that was already compiled compiled again in this
process: ``mxnet_tpu.profiler.dispatch_counts()["fused_step.recompile"]``,
counted where the jit cache grows (``Module._note_step_compiled``).  A
program that has no set-up clock (``profiler.phase_seconds``) is older
than the counter: nothing."""
UNIT = "count"
LAYER = "executor"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(record):
    from mxnet_tpu import profiler
    if not hasattr(profiler, "phase_seconds"):
        return None
    return profiler.dispatch_counts().get("fused_step.recompile", 0)
