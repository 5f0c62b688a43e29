"""KV-cache decode throughput on the real chip (tokens/sec per stream).

The inference side of the transformer track: one autoregressive step of
``models.transformer_decode_step`` (rolled KV cache riding Module
state_names, one jitted program per step — models/transformer.py:190)
measured at serving-shaped batch sizes.  No reference analog (its
inference story is the RNN example); the numbers quantify the decode
path the KV-cache + beam-search capability ships.

Per config it reports per-step latency and tokens/sec:
  batch=1   — interactive single-stream latency
  batch=32  — small serving batch

Prints one JSON line: {"metric": "decode_tokens_per_sec", ...} and
appends it (timestamped) to BENCH_LOG.jsonl.

Config knobs (GPT-2-small-shaped defaults):
    DEC_LAYERS=12 DEC_DMODEL=768 DEC_HEADS=12 DEC_KV_HEADS= DEC_MAXLEN=1024
    DEC_VOCAB=50304 DEC_STEPS=64 DEC_BATCHES=1,32   DEC_CPU=1 (smoke)
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from benchmark._bench_common import (  # noqa: E402
    env_int as _env_int, make_mark, place_compile_cache)

_mark = make_mark("dec")

LAYERS = _env_int("DEC_LAYERS", 12)
DMODEL = _env_int("DEC_DMODEL", 768)
HEADS = _env_int("DEC_HEADS", 12)
KV_HEADS = os.environ.get("DEC_KV_HEADS", "")
MAXLEN = _env_int("DEC_MAXLEN", 1024)
VOCAB = _env_int("DEC_VOCAB", 50304)
STEPS = _env_int("DEC_STEPS", 64)
BATCHES = [int(b) for b in
           os.environ.get("DEC_BATCHES", "1,32").split(",")]


def _bench_batch(B, kw):
    import mxnet_tpu as mx
    from mxnet_tpu import models
    from mxnet_tpu.io import DataBatch

    dec = models.transformer_decode_step(VOCAB, MAXLEN, B, **kw)
    state_names = []
    for i in range(LAYERS):
        state_names += [f"layer{i}_k_cache", f"layer{i}_v_cache"]
    state_names.append("cur_pos")
    dmod = mx.mod.Module(dec, context=mx.tpu(0), data_names=("data",),
                         label_names=None, state_names=state_names)
    dmod.bind(data_shapes=[("data", (B,))], for_training=False)
    dmod.init_params(mx.initializer.Xavier())
    dmod.set_states(value=0)

    tok = mx.nd.NDArray(np.zeros((B,), np.float32))

    def step():
        dmod.forward(DataBatch(data=[tok]), is_train=False)
        outs = dmod.get_outputs()
        dmod.set_states(states=dmod.get_outputs()[1:])
        return outs[0]

    # warmup/compile, then a synced timing loop: one host readback of the
    # final logits data-depends on every step in the chain
    import jax
    jax.block_until_ready(step()._data)
    _mark("batch %d: compiled" % B)
    dmod.set_states(value=0)
    t0 = time.perf_counter()
    out = None
    for _ in range(STEPS):
        out = step()
    _ = out.asnumpy()
    dt = time.perf_counter() - t0
    step_ms = dt / STEPS * 1e3
    return {"batch": B, "step_ms": round(step_ms, 3),
            "tokens_per_sec": round(B * STEPS / dt, 1),
            "tokens_per_sec_per_stream": round(STEPS / dt, 1)}


def main():
    cpu_smoke = os.environ.get("DEC_CPU", "") not in ("", "0")
    if cpu_smoke:
        from cpu_pin import pin_cpu
        pin_cpu(1)
    else:
        place_compile_cache()
    import jax
    dev = jax.devices()[0]
    _mark("backend up: %s" % dev.device_kind)

    kv = int(KV_HEADS) if KV_HEADS else None
    kw = dict(num_layers=LAYERS, d_model=DMODEL, num_heads=HEADS,
              num_kv_heads=kv)
    rows = []
    for B in BATCHES:
        _mark("decode bench batch %d" % B)
        rows.append(_bench_batch(B, kw))
        print(json.dumps(dict(rows[-1], device=dev.device_kind)),
              flush=True)
    # headline value: largest-batch aggregate throughput
    best = rows[-1]
    out = {
        "metric": "decode_tokens_per_sec",
        "value": best["tokens_per_sec"],
        "unit": "tokens/sec",
        "vs_baseline": None,  # no reference analog (pre-LLM era)
        "config": {"layers": LAYERS, "d_model": DMODEL, "heads": HEADS,
                   "kv_heads": kv, "max_len": MAXLEN, "vocab": VOCAB,
                   "steps": STEPS},
        "per_batch": rows,
        "device": dev.device_kind,
    }
    if not cpu_smoke:
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
