"""PTB-LSTM training throughput on the real chip (tokens/sec).

The fused ``RNN`` op (ops/rnn.py — lax.scan over time with gates batched
into one matmul per step) replaces the reference's cuDNN fused RNN
(/root/reference/src/operator/cudnn_rnn-inl.h:57-72); its numerics are
pinned by tests/test_rnn.py, but SURVEY §7 lists "fused scan kernels with
equivalent perf" as a hard part — this bench produces the TPU number.

PTB-medium shape (reference example/rnn lstm_bucketing, BASELINE config
4): 2x650 LSTM over seq 35, vocab 10k, driven through the same fused
Module train step as the ResNet/transformer benches (forward + backward
+ SGD-momentum as one XLA program, donated buffers).

Prints one JSON line: {"metric": "lstm_ptb_tokens_per_sec", ...} and
appends it (timestamped) to BENCH_LOG.jsonl.

Config knobs:
    RNB_LAYERS=2 RNB_HIDDEN=650 RNB_EMBED=650 RNB_SEQ=35 RNB_BATCH=64
    RNB_VOCAB=10000 RNB_ITERS=20 RNB_WARMUP=3   RNB_CPU=1 (smoke mode)
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

_mark = make_mark("rnb")


LAYERS = _env_int("RNB_LAYERS", 2)
HIDDEN = _env_int("RNB_HIDDEN", 650)
EMBED = _env_int("RNB_EMBED", 650)
SEQ = _env_int("RNB_SEQ", 35)
BATCH = _env_int("RNB_BATCH", 64)
VOCAB = _env_int("RNB_VOCAB", 10000)
ITERS = _env_int("RNB_ITERS", 20)
WARMUP = _env_int("RNB_WARMUP", 3)


def build_sym():
    import mxnet_tpu as mx
    data = mx.sym.Variable("data")            # (N, T) token ids
    label = mx.sym.Variable("softmax_label")
    embed = mx.sym.Embedding(data, input_dim=VOCAB, output_dim=EMBED,
                             name="embed")
    cell = mx.rnn.FusedRNNCell(HIDDEN, num_layers=LAYERS, mode="lstm",
                               prefix="lstm_")
    out, _ = cell.unroll(SEQ, inputs=embed, merge_outputs=True,
                         layout="NTC")
    pred = mx.sym.Reshape(out, shape=(-1, HIDDEN))
    pred = mx.sym.FullyConnected(pred, num_hidden=VOCAB, name="pred")
    lab = mx.sym.Reshape(label, shape=(-1,))
    return mx.sym.SoftmaxOutput(pred, lab, name="softmax")


def main():
    cpu_smoke = os.environ.get("RNB_CPU", "") not in ("", "0")
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

    net = build_sym()
    mod = mx.mod.Module(net, context=mx.tpu(0),
                        compute_dtype=jnp.bfloat16)
    it = mx.io.NDArrayIter(
        data=np.zeros((BATCH, SEQ), np.float32),
        label=np.zeros((BATCH, SEQ), np.float32), batch_size=BATCH)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 1.0,
                                         "momentum": 0.9})
    n_params = sum(int(np.prod(mod._exec.arg_dict[n].shape))
                   for n in mod._update_names())
    _mark("module bound + params initialized (%d params)" % n_params)

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
        "metric": "lstm_ptb_tokens_per_sec",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec",
        "vs_baseline": None,   # BASELINE.json published{} has no PTB row
        "step_ms": round(step_s * 1e3, 2),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "config": {"layers": LAYERS, "hidden": HIDDEN, "embed": EMBED,
                   "seq": SEQ, "batch": BATCH, "vocab": VOCAB},
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
