"""Small shared pieces of the yardstick.

``make_mark`` is a copy of the function in ``benchmark/_bench_common.py``
and ``place_compile_cache`` does that one's job through the environment
(the program may change; the yardstick may not, so nothing here imports
from there, and tests/test_chip_path.py lets only that file set jax's
cache option).  The peak table is
``peaks.json``; ``sgd_momentum_delta`` is the plain one-step update every
family's reference ends with."""
import json
import os
import sys
import time

PKG_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(PKG_DIR)
# fixed and inside the checkout: the path is part of the cache's key
COMPILE_CACHE_DIR = os.path.join(CHECKOUT, ".jax_compile_cache")
OUT_DIR = os.path.join(CHECKOUT, ".chipbench_out")


def make_mark(tag, t0=None):
    t0 = time.perf_counter() if t0 is None else t0

    def _mark(msg):
        print("[%s +%6.1fs] %s" % (tag, time.perf_counter() - t0, msg),
              file=sys.stderr, flush=True)
    return _mark


def place_compile_cache():
    """Give this process a persistent compile cache; return its path, or
    None where none could be placed.  ``JAX_COMPILATION_CACHE_DIR`` set:
    jax reads it, nothing is touched.  Unset: it is set to the fixed
    in-checkout COMPILE_CACHE_DIR, which jax reads once, as it is imported
    -- so this runs before the first ``import jax`` (python3 -m chipbench
    sees to that) and does nothing in a process that has jax already.
    Every program is kept, however quickly it compiled: a cell builds some
    fifty small ones, and recompiling them was 10 s of every warm run."""
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        if "jax" in sys.modules:
            return None
        os.environ["JAX_COMPILATION_CACHE_DIR"] = COMPILE_CACHE_DIR
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return os.environ["JAX_COMPILATION_CACHE_DIR"]


def load_peaks(device_kind):
    """Published peaks of ``device_kind`` (exact jax string) from
    peaks.json; a kind not in the table is an error, never a default."""
    with open(os.path.join(PKG_DIR, "peaks.json")) as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise KeyError("no peaks recorded for device kind %r; add it to "
                       "chipbench/peaks.json with its source"
                       % (device_kind,))
    return table[device_kind]


def seed_key(seed):
    """A jax PRNG key from any non-negative whole ``--seed`` (the driver's
    are above 2**31): high and low 32 bits folded separately, so nothing
    depends on 64-bit mode."""
    import jax
    import numpy as np
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(np.uint32((seed >> 32) & 0xFFFFFFFF)),
        np.uint32(seed & 0xFFFFFFFF))


def sgd_momentum_delta(params, grads, opt):
    """First step of SGD with momentum from a zero momentum state, on
    float32 masters: ``mom = -lr * (g + wd_t * w)``, ``w += mom``; the
    returned dict is that ``mom`` (the delta of each tensor).  ``wd_t``
    is ``opt['wd']`` for names ending in one of ``opt['wd_suffixes']``
    and 0 for the rest (biases, betas)."""
    import jax.numpy as jnp
    lr = jnp.float32(opt["learning_rate"])
    out = {}
    for name, w in params.items():
        decays = name.endswith(tuple(opt.get("wd_suffixes", ())))
        wd = jnp.float32(opt.get("wd", 0.0) if decays else 0.0)
        g = grads[name].astype(jnp.float32)
        out[name] = -lr * (g + wd * w.astype(jnp.float32))
    return out
