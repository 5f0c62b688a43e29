"""Shared plumbing for the on-chip entry points (chip_smoke.py, bench.py,
benchmark/*.py): the per-chip peak table, the compile-cache placement,
the readback barrier and the progress marker.  One copy, so a new device
kind or a fix to the sync discipline lands everywhere at once.

A chip belongs to one process: these helpers start no child, retry
nothing and watch nothing.  An entry point calls ``jax.devices()``
itself; if that fails, the run fails."""
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# where the persistent XLA compile cache lives unless the environment
# names another place; fixed (the path is part of what makes a cache
# directory findable by the next run) and git-ignored
COMPILE_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_compile_cache")


def env_int(name, default):
    """Shared int-env knob parser for the bench scripts."""
    return int(os.environ.get(name, str(default)))


def make_mark(tag):
    t0 = time.perf_counter()

    def _mark(msg):
        print("[%s +%.1fs] %s" % (tag, time.perf_counter() - t0, msg),
              file=sys.stderr, flush=True)
    return _mark


def place_compile_cache():
    """Give this process a persistent compile cache; return its path.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, so nothing
    is touched.  Unset: ``jax_compilation_cache_dir`` becomes the fixed
    in-checkout COMPILE_CACHE_DIR.  Call before the first compile; every
    chip entry point does, and nothing else in the tree sets the
    option."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


# peak dense bf16 FLOP/s per chip, keyed by the exact jax ``device_kind``.
# Source: Google Cloud TPU documentation, system architecture pages
# ("TPU v5e": 197 TFLOP/s bf16 per chip; v4: 275; v5p: 459; v6e: 918).
PEAK_BF16 = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,      # v5e
    "TPU v5": 459e12,           # v5p
    "TPU v6 lite": 918e12,      # v6e (Trillium)
}


def peak_flops(device_kind):
    """Peak bf16 FLOP/s of ``device_kind``; a kind not in the table is an
    error, never a default (a utilization over a guessed peak is worse
    than none)."""
    try:
        return PEAK_BF16[device_kind]
    except KeyError:
        raise KeyError(
            "no peak FLOP/s recorded for device kind %r; add it to "
            "benchmark/_bench_common.PEAK_BF16 with its source"
            % (device_kind,)) from None


def make_hard_sync(mod):
    """Synchronization barrier for a fused-step Module: a jitted scalar
    reduction over ALL updated params, fetched to host.  A host readback
    of a value that data-depends on every param cannot complete before
    the final step's compute ran, whatever the runtime does with donated
    buffers."""
    import jax
    import jax.numpy as jnp
    upd_names = mod._update_names()

    @jax.jit
    def _psum_all(vals):
        return sum(jnp.sum(jnp.abs(v.astype(jnp.float32))) for v in vals)

    def hard_sync():
        vals = tuple(mod._exec.arg_dict[n]._data for n in upd_names)
        return float(_psum_all(vals))

    return hard_sync


def bench_log_path():
    """The shared banked-measurements file (repo root BENCH_LOG.jsonl)."""
    return os.path.join(REPO_ROOT, "BENCH_LOG.jsonl")


def is_cpu_device(device) -> bool:
    """True when a measurement's device field names a CPU backend.
    THE predicate for "not chip evidence" — shared by bench.py's banking
    gate and the defaults promoter, so the definition can't drift between
    the writers and the reader."""
    return "cpu" in str(device or "").lower()
