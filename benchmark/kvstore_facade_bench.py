"""KVStore-facade overhead vs the fused GSPMD step (VERDICT r3 weak #5).

``kvstore type='tpu'`` is a compatibility facade: update-on-kvstore
semantics (per-parameter push/pull, server-side-style optimizer) over
jitted reductions.  The documented perf path is the fused Module step —
one XLA program for forward+backward+update.  This bench MEASURES the
facade's cost instead of leaving the docstring claim untested: the same
model/batch trained both ways, ms/step each, overhead ratio reported.

Prints one JSON line {"metric": "kvstore_facade_overhead_ratio", ...}
and appends it to BENCH_LOG.jsonl on real hardware.

Knobs: KVF_LAYERS=18 KVF_BATCH=64 KVF_ITERS=12 KVF_CPU=1 (smoke).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from benchmark._bench_common import (  # noqa: E402
    env_int as _env_int, make_hard_sync, make_mark, is_cpu_device,
    bench_log_path, place_compile_cache)

_mark = make_mark("kvf")

_ERR_BASE = {"metric": "kvstore_facade_overhead_ratio", "value": None,
             "unit": "x", "vs_baseline": None}


def main():
    cpu_smoke = os.environ.get("KVF_CPU", "") not in ("", "0")
    if cpu_smoke:
        from cpu_pin import pin_cpu
        pin_cpu(1)
    else:
        place_compile_cache()
    import jax
    import jax.numpy as jnp
    dev = jax.devices()[0]
    _mark("backend up: %s" % dev.device_kind)
    import mxnet_tpu as mx
    from mxnet_tpu import models

    layers = _env_int("KVF_LAYERS", 18)
    batch = _env_int("KVF_BATCH", 4 if cpu_smoke else 64)
    iters = _env_int("KVF_ITERS", 3 if cpu_smoke else 12)
    size = 32 if cpu_smoke else 224
    net = models.resnet(num_classes=100, num_layers=layers,
                        image_shape=(3, size, size))

    key = jax.random.PRNGKey(0)
    kx, ky = jax.random.split(key)
    bx = mx.nd.NDArray(jax.random.uniform(kx, (batch, 3, size, size),
                                          jnp.float32))
    by = mx.nd.NDArray(jax.random.randint(ky, (batch,), 0, 100)
                       .astype(jnp.float32))
    bx.wait_to_read()
    by.wait_to_read()
    db = mx.io.DataBatch(data=[bx], label=[by])

    def build(kvstore):
        mod = mx.mod.Module(net, context=mx.tpu(0) if not cpu_smoke
                            else mx.cpu(),
                            compute_dtype=jnp.bfloat16)
        mod.bind(data_shapes=[("data", (batch, 3, size, size))],
                 label_shapes=[("softmax_label", (batch,))])
        mx.random.seed(0)
        mod.init_params(mx.initializer.Xavier(rnd_type="gaussian",
                                              magnitude=2.0))
        mod.init_optimizer(kvstore=kvstore, optimizer="sgd",
                           optimizer_params={"learning_rate": 0.01,
                                             "momentum": 0.9})
        return mod

    def time_path(mod, n_iters):
        hard_sync = make_hard_sync(mod)

        def step():
            mod.forward(db, is_train=True)
            mod.backward()
            mod.update()

        step()
        hard_sync()
        _mark("first step done (compile)")
        t0 = time.perf_counter()
        for _ in range(n_iters):
            step()
        hard_sync()
        return (time.perf_counter() - t0) / n_iters * 1e3  # ms

    # fused: the documented perf path (no kvstore, one XLA program)
    _mark("fused path")
    fused_ms = time_path(build(kvstore=None), iters)
    _mark("fused %.2f ms/step" % fused_ms)

    # facade: update-on-kvstore through the 'tpu' compatibility store —
    # pass the OBJECT so a single-process run keeps the facade instead of
    # _create_kvstore optimizing it away
    _mark("facade path")
    facade_ms = time_path(build(kvstore=mx.kv.create("tpu")), iters)
    _mark("facade %.2f ms/step" % facade_ms)

    out = dict(_ERR_BASE)
    out["value"] = round(facade_ms / fused_ms, 3)
    out.update({
        "fused_ms_per_step": round(fused_ms, 2),
        "facade_ms_per_step": round(facade_ms, 2),
        "model": "resnet-%d" % layers, "batch": batch,
        "image_size": size, "device": dev.device_kind, "iters": iters,
    })
    if not is_cpu_device(dev.device_kind):
        try:
            with open(bench_log_path(), "a") as f:
                f.write(json.dumps(dict(out, ts=time.time())) + "\n")
        except OSError:
            pass
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
