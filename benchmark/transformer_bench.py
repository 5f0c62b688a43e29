"""Transformer-LM training throughput on the real chip (tokens/sec + MFU).

End-to-end companion to benchmark/attention_bench.py: the long-context
flagship (models/transformer.py — Pallas flash attention fwd+bwd, GQA,
pre-norm GPT-style blocks) driven through the SAME fused Module train
step the ResNet bench uses (forward + backward + SGD-momentum as one XLA
program, donated buffers, bf16 compute / fp32 master).

No analog exists in the reference (MXNet 0.12 predates the transformer);
the bar is architectural: a demonstrably-fast end-to-end training number
for the new-capability track, reported with MFU so it is comparable
across chips.

Prints one JSON line: {"metric": "transformer_lm_tokens_per_sec", ...}
and appends it (timestamped) to BENCH_LOG.jsonl.

Config knobs (GPT-2-small-shaped defaults):
    TFB_LAYERS=12 TFB_DMODEL=768 TFB_HEADS=12 TFB_KV_HEADS= TFB_SEQ=1024
    TFB_BATCH=8 TFB_VOCAB=50304 TFB_ITERS=20 TFB_WARMUP=3
    TFB_LOSS=softmax|chunked_ce TFB_CE_CHUNKS=8   (chunked head: the
    (B*S, V) logits never materialize — ops/chunked_loss.py)
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from benchmark._bench_common import (  # noqa: E402
    env_int as _env_int, make_mark, peak_flops, make_hard_sync,
    place_compile_cache)

_mark = make_mark("tfb")


LAYERS = _env_int("TFB_LAYERS", 12)
DMODEL = _env_int("TFB_DMODEL", 768)
HEADS = _env_int("TFB_HEADS", 12)
KV_HEADS = os.environ.get("TFB_KV_HEADS", "")
LOSS = os.environ.get("TFB_LOSS", "softmax")
CE_CHUNKS = _env_int("TFB_CE_CHUNKS", 8)
SEQ = _env_int("TFB_SEQ", 1024)
BATCH = _env_int("TFB_BATCH", 8)
VOCAB = _env_int("TFB_VOCAB", 50304)   # 50257 rounded up to a lane multiple
ITERS = _env_int("TFB_ITERS", 20)
WARMUP = _env_int("TFB_WARMUP", 3)


def main():
    # same truthiness as chip_convergence_run's DIGITS_CPU: "0" = chip run
    cpu_smoke = os.environ.get("TFB_CPU", "") not in ("", "0")
    if cpu_smoke:                     # CPU smoke mode (tests/dev boxes)
        from cpu_pin import pin_cpu
        pin_cpu(1)
    else:
        place_compile_cache()
    import jax
    import jax.numpy as jnp
    dev = jax.devices()[0]
    _mark("backend up: %s" % dev.device_kind)

    import mxnet_tpu as mx
    from mxnet_tpu.models.transformer import transformer_lm

    kv = int(KV_HEADS) if KV_HEADS else None
    net = transformer_lm(VOCAB, SEQ, num_layers=LAYERS, d_model=DMODEL,
                         num_heads=HEADS, num_kv_heads=kv,
                         loss_type=LOSS, ce_chunks=CE_CHUNKS)
    mod = mx.mod.Module(net, context=mx.tpu(0),
                        compute_dtype=jnp.bfloat16)
    it = mx.io.NDArrayIter(
        data=np.zeros((BATCH, SEQ), np.float32),
        label=np.zeros((BATCH, SEQ), np.float32), batch_size=BATCH)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.initializer.Xavier(rnd_type="gaussian",
                                          magnitude=2.0))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 1e-3,
                                         "momentum": 0.9})
    n_params = sum(int(np.prod(mod._exec.arg_dict[n].shape))
                   for n in mod._update_names())
    _mark("module bound + params initialized")

    # device-resident token batches, rotated per step
    batches = []
    for seed in (0, 1):
        key = jax.random.PRNGKey(seed)
        kx, ky = jax.random.split(key)
        bx = mx.nd.NDArray(jax.random.randint(
            kx, (BATCH, SEQ), 0, VOCAB).astype(jnp.float32))
        by = mx.nd.NDArray(jax.random.randint(
            ky, (BATCH, SEQ), 0, VOCAB).astype(jnp.float32))
        bx.wait_to_read()
        by.wait_to_read()
        batches.append(mx.io.DataBatch(data=[bx], label=[by]))

    def step(i):
        mod.forward(batches[i % 2], is_train=True)
        mod.update()

    hard_sync = make_hard_sync(mod)

    for i in range(WARMUP):
        step(i)
        if i == 0:
            hard_sync()
            _mark("first step done (compile)")
    hard_sync()
    _mark("warmup done")

    mod.forward(batches[0], is_train=True)
    flops_per_step = mod.fused_step_flops()
    flops_source = "xla_cost_analysis"
    _mark("flops per step: %.3e (%s)" % (flops_per_step, flops_source))
    iters = ITERS

    t0 = time.perf_counter()
    for i in range(iters):
        step(i)
    hard_sync()
    dt = time.perf_counter() - t0

    step_s = dt / iters
    tokens_per_sec = BATCH * SEQ / step_s
    peak = None if cpu_smoke else peak_flops(dev.device_kind)
    mfu = (flops_per_step / step_s / peak) if peak else None
    out = {
        "metric": "transformer_lm_tokens_per_sec",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec",
        "vs_baseline": None,   # no reference analog (pre-transformer era)
        "step_ms": round(step_s * 1e3, 2),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "config": {"layers": LAYERS, "d_model": DMODEL, "heads": HEADS,
                   "kv_heads": kv, "seq": SEQ, "batch": BATCH,
                   "vocab": VOCAB, "loss": LOSS,
                   "ce_chunks": CE_CHUNKS if LOSS == "chunked_ce"
                   else None},
        "n_params": n_params,
        "flops_per_step": flops_per_step,
        "flops_source": flops_source,
        "device": dev.device_kind,
        "iters": iters,
    }
    try:
        stats = dev.memory_stats() or {}
        if stats.get("peak_bytes_in_use"):
            out["peak_hbm_gb"] = round(stats["peak_bytes_in_use"] / 2**30, 2)
    except Exception:  # noqa: BLE001
        pass
    if not cpu_smoke:  # don't log CPU smoke runs
        try:
            with open(os.path.join(os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))), "BENCH_LOG.jsonl"),
                    "a") as f:
                f.write(json.dumps(dict(out, ts=time.time())) + "\n")
        except OSError:
            pass
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
