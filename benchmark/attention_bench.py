"""Flash-attention microbench on the real chip (VERDICT r2 item 5).

Compares the Pallas flash kernels (fwd and fwd+bwd) against the naive XLA
attention oracle (softmax(QK^T)V materialized) at S in {1k, 4k, 16k}, bf16,
GQA on/off.  Prints one JSON line per config plus a markdown table.  Run
in the one process that holds the chip:

    python benchmark/attention_bench.py            # full sweep
    ATTN_SEQS=1024,4096 python benchmark/attention_bench.py

The naive oracle is O(S^2) memory; configs where it OOMs are reported as
``naive_ms: null`` (the flash kernel still runs — that IS the capability
gap being demonstrated).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _sync(x):
    import jax
    jax.block_until_ready(x)


ITERS = max(1, int(os.environ.get("ATTN_ITERS", "10")))
REPEATS = max(1, int(os.environ.get("ATTN_REPEATS", "3")))


def _time(fn, *args, iters=None, warmup=2):
    iters = ITERS if iters is None else iters
    t_best = None
    for _ in range(warmup):
        _sync(fn(*args))
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        _sync(out)
        dt = (time.perf_counter() - t0) / iters
        t_best = dt if t_best is None else min(t_best, dt)
    return t_best * 1e3  # ms


def main():
    from benchmark._bench_common import make_mark, place_compile_cache
    mark = make_mark("attn")
    place_compile_cache()
    import jax
    import jax.numpy as jnp
    dev = jax.devices()[0]
    mark("backend up: %s" % dev.device_kind)
    from mxnet_tpu.ops.attention import flash_attention, _attn_reference

    seqs = [int(s) for s in
            os.environ.get("ATTN_SEQS", "1024,4096,16384").split(",")]
    # kernel tile sweep, e.g. ATTN_BLOCKS=derived,128x128,512x512; the
    # default is what the op runs: tiles derived from the shapes
    blocks = [(None, None) if spec == "derived"
              else tuple(int(x) for x in spec.split("x")) for spec in
              os.environ.get("ATTN_BLOCKS", "derived").split(",")]
    B, H, D = 4, 16, 128
    rows = []
    for S in seqs:
        for gqa in (False, True):
            Hk = H // 8 if gqa else H
            key = jax.random.PRNGKey(0)
            kq, kk, kv = jax.random.split(key, 3)
            q = jax.random.normal(kq, (B, H, S, D), jnp.bfloat16)
            k = jax.random.normal(kk, (B, Hk, S, D), jnp.bfloat16)
            v = jax.random.normal(kv, (B, Hk, S, D), jnp.bfloat16)

            # the naive oracle is block-independent: time it ONCE per
            # (S, gqa) — it is the O(S^2), OOM-prone, slowest leg
            naive_f = jax.jit(lambda q, k, v: _attn_reference(
                q, k, v, True, None))

            def loss_naive(q, k, v):
                return jnp.sum(_attn_reference(q, k, v, True, None)
                               .astype(jnp.float32))

            naive_b = jax.jit(jax.grad(loss_naive, argnums=(0, 1, 2)))
            naive = {}
            mark("naive S=%d gqa=%s" % (S, gqa))
            try:
                naive["fwd"] = round(_time(naive_f, q, k, v), 3)
                naive["bwd"] = round(_time(naive_b, q, k, v), 3)
            except Exception as e:  # noqa: BLE001 — OOM at long S expected
                naive["error"] = str(e)[:120]

            for bq, bk in blocks:
                try:
                    mark("flash S=%d gqa=%s %s" % (S, gqa,
                                                   _blocks_label(bq, bk)))
                    _bench_flash(rows, dev, S, gqa, bq, bk, B, H, Hk, D,
                                 q, k, v, naive)
                except Exception as e:  # noqa: BLE001 — keep sweeping
                    print(json.dumps({"S": S, "gqa": gqa,
                                      "blocks": _blocks_label(bq, bk),
                                      "error": str(e)[:200]}), flush=True)
    print("\n| S | GQA | blocks | flash fwd ms | naive fwd ms | "
          "flash f+b ms | naive f+b ms | fwd speedup | f+b speedup |")
    print("|---|-----|-----|-----------|-----------|-----------|"
          "-----------|------|------|")
    for r in rows:
        print("| {S} | {gqa} | {blocks} | {flash_fwd_ms} | "
              "{naive_fwd_ms} | {flash_bwd_ms} | {naive_bwd_ms} | "
              "{fs} | {bs} |".format(
                  fs=r.get("fwd_speedup", "—"), bs=r.get("bwd_speedup", "—"),
                  **{k: r.get(k) for k in
                     ("S", "gqa", "blocks", "flash_fwd_ms", "naive_fwd_ms",
                      "flash_bwd_ms", "naive_bwd_ms")}))
    _write_dispatch_table(rows, dev)
    return 0


def _blocks_label(bq, bk):
    return "derived" if bq is None else "%dx%d" % (bq, bk)


def _write_dispatch_table(rows, dev):
    """Measured per-shape winner table for ops.attention dispatch
    (VERDICT r3 item 5: where the Pallas kernel loses to XLA, the op
    must pick XLA — by measurement, not belief).  Chip results only;
    a CPU smoke must never overwrite hardware evidence."""
    from benchmark._bench_common import is_cpu_device
    if is_cpu_device(getattr(dev, "device_kind", "cpu")):
        return
    best = {}  # (S, gqa) -> (rank, blocks, speedup)
    for r in rows:
        if "flash_fwd_ms" not in r:
            continue
        key = (r["S"], bool(r["gqa"]))
        if r.get("naive_bwd_ms") is None:
            # the XLA reference cannot run BACKWARD at this shape (its
            # O(S^2) scores OOMed): flash is the only trainable impl —
            # never let a fwd-only comparison hand the win to xla here
            tier, sp = 2, float("inf")
        elif r.get("bwd_speedup") is not None:
            tier, sp = 1, r["bwd_speedup"]
        else:
            tier, sp = 0, r.get("fwd_speedup") or 0.0
        # rank: measurement tier FIRST so bwd-timed rows are never
        # compared against fwd-only fallback rows (like-for-like within
        # a key); then speedup; then RAW flash time (negated) so that
        # inf-speedup rows (naive OOMed everywhere) still pick the
        # FASTEST flash tile config, not the first swept
        flash_ms = r.get("flash_bwd_ms") or r.get("flash_fwd_ms") or 1e9
        rank = (tier, sp, -flash_ms)
        if key not in best or rank > best[key][0]:
            best[key] = (rank, r.get("blocks", "derived"), sp)
    # each measured S speaks for its neighborhood: ranges split at the
    # geometric midpoint between adjacent measured lengths.  The row's
    # `blocks` is a record of what was timed; dispatch reads `winner`
    # only and the kernels derive their tiles from the shapes, so a
    # table that decides anything is one swept at ATTN_BLOCKS=derived.
    table_rows = []
    for gqa in (False, True):
        seqs = sorted(s for (s, g) in best if g == gqa)
        for i, s in enumerate(seqs):
            lo = 0 if i == 0 else int((seqs[i - 1] * s) ** 0.5) + 1
            hi = (1 << 62) if i == len(seqs) - 1 \
                else int((s * seqs[i + 1]) ** 0.5)
            _, blocks, sp = best[(s, gqa)]
            table_rows.append(
                {"min_seq": lo, "max_seq": hi, "gqa": gqa,
                 "measured_seq": s, "blocks": blocks,
                 "winner": "flash" if sp >= 1.0 else "xla",
                 "measured_speedup": None if sp == float("inf") else sp})
    table = {"device": dev.device_kind, "rows": table_rows}
    # one canonical artifact path, owned by the READER
    from mxnet_tpu.ops.attention import _DISPATCH_PATH as path
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(table, f, indent=1)
    print("dispatch table -> %s" % path, flush=True)


def _bench_flash(rows, dev, S, gqa, bq, bk, B, H, Hk, D, q, k, v, naive):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.attention import flash_attention

    flash_f = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, True, None, bq, bk))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, None, bq, bk)
                       .astype(jnp.float32))

    flash_b = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))

    row = {"S": S, "gqa": gqa, "blocks": _blocks_label(bq, bk),
           "B": B, "H": H, "Hk": Hk, "D": D, "device": dev.device_kind}
    row["flash_fwd_ms"] = round(_time(flash_f, q, k, v), 3)
    row["flash_bwd_ms"] = round(_time(flash_b, q, k, v), 3)
    row["naive_fwd_ms"] = naive.get("fwd")
    row["naive_bwd_ms"] = naive.get("bwd")
    if "error" in naive:
        row["naive_error"] = naive["error"]
    if row["naive_fwd_ms"]:
        row["fwd_speedup"] = round(
            row["naive_fwd_ms"] / row["flash_fwd_ms"], 2)
    if row["naive_bwd_ms"]:  # naive bwd can OOM even when fwd fit
        row["bwd_speedup"] = round(
            row["naive_bwd_ms"] / row["flash_bwd_ms"], 2)
    rows.append(row)
    print(json.dumps(row), flush=True)


if __name__ == "__main__":
    sys.exit(main())
