"""Benchmark: ResNet-50 fused training-step throughput on one real chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

TPU-shaped config: bfloat16 compute with fp32 master weights (the
framework's compute_dtype mixed precision), batch 256, donated
param/aux/optimizer buffers (in-place HBM updates), device-resident input
batches rotated per step (the steady state an overlapped host input
pipeline delivers).  The measured step is forward + backward + SGD-momentum
update driven through the framework's own Module API
(bind/init/forward/update), compiled by XLA into ONE program per step.

Reported: imgs/sec, step_ms, and MFU (XLA cost-analysis FLOPs of the fused
step divided by the chip's peak bf16 FLOP rate).  The run happens in this
one process on whatever ``jax.devices()`` returns; any failure — no
backend, out of memory, an unknown device kind — ends it non-zero.

Baseline for vs_baseline: the reference's published ResNet-50 training
speed — 109 images/sec on 1× K80 at batch 32 (BASELINE.md,
example/image-classification/README.md:147-157).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark._bench_common import (   # noqa: E402
    make_mark, peak_flops as _peak_flops, make_hard_sync,
    place_compile_cache)

_mark = make_mark("bench")

import numpy as np

BASELINE_IMGS_PER_SEC = 109.0   # ResNet-50, 1x K80, batch 32


def _promote_mod():
    """mxnet_tpu.autotune.promote loaded BY PATH — the module is
    stdlib-only on purpose: the promoted env knobs must be in place
    before the mxnet_tpu package is imported and reads them."""
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "mxnet_tpu", "autotune", "promote.py")
    spec = importlib.util.spec_from_file_location("_bench_promote", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _defaults_path():
    return os.environ.get("BENCH_DEFAULTS_PATH") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_DEFAULTS.json")


def _topology_key(device_kind, hosts=1):
    """THE topology this run measures: device kind x host count x
    worker/server count (promoted defaults are keyed by it, so a
    b256-TPU winner can never leak into a CPU or MULTICHIP run)."""
    return _promote_mod().topology_key(
        device_kind, hosts=hosts,
        workers=int(os.environ.get("DMLC_NUM_WORKER", "1") or 1),
        servers=int(os.environ.get("DMLC_NUM_SERVER", "0") or 0))


def _resolve_config(device_kind, hosts=1):
    """Resolution order per knob: env var > the PER-TOPOLOGY promoted
    entry in BENCH_DEFAULTS.json (autotune winners; legacy
    flat files apply only to the topology their provenance names) >
    built-in defaults.  Resolved only AFTER backend init because the
    topology is unknowable before the device kind is.  Promoted ``env``
    knobs (e.g. a measured-best MXNET_KVSTORE_WINDOW) are setdefault-ed
    into the environment — an explicit env var always wins."""
    prom = _promote_mod()
    topo = _topology_key(device_kind, hosts)
    entry = prom.lookup_defaults(_defaults_path(), topo)
    applied_env = prom.apply_env_defaults(entry)
    cfg = {
        "topology": topo,
        "applied_env": applied_env,
        "batch": int(os.environ.get("BENCH_BATCH",
                                    entry.get("batch", 256))),
        "dtype": os.environ.get("BENCH_DTYPE",
                                entry.get("dtype", "bfloat16")),
        "opt": os.environ.get("BENCH_OPT", entry.get("opt", "sgd")),
        # Steps fused into ONE dispatch via Module.run_steps (lax.scan
        # over the fused step).  K>1 amortizes the per-step host
        # dispatch to 1/K per step — 1 = classic per-step dispatch.
        "steps_per_call": int(os.environ.get(
            "BENCH_STEPS_PER_CALL", entry.get("steps_per_call", 1))),
        # TPU-native stem variant (space-to-depth, mathematically
        # equivalent — models/resnet.py space_to_depth_stem_weight)
        "stem": os.environ.get("BENCH_STEM", entry.get("stem", "conv7")),
        # activation layout: nchw (MXNet default) or nhwc (channels-
        # last, the MLPerf-TPU ResNet convention; weights stay OIHW)
        "layout": os.environ.get(
            "BENCH_LAYOUT", str(entry.get("layout", "nchw"))).upper(),
        # BENCH_REMAT: 0 (off), 1/full (whole-step recompute),
        # save_matmuls (keep conv/FC outputs)
        "remat": os.environ.get("BENCH_REMAT",
                                str(entry.get("remat", "0"))),
    }
    if cfg["remat"] not in ("0", "", "False", "false"):
        # must be set before the Module traces the step
        # (executor.maybe_mirror); "False" guards the promoted path:
        # sweep records log remat=False for the off case
        os.environ["MXNET_BACKWARD_DO_MIRROR"] = "1"
        if cfg["remat"] not in ("1", "full", "True", "true"):
            os.environ["MXNET_REMAT_POLICY"] = cfg["remat"]
    return cfg


WARMUP = int(os.environ.get("BENCH_WARMUP", "5"))
ITERS = int(os.environ.get("BENCH_ITERS", "30"))

def _make_record_iter(batch):
    """Raw-uint8 record dataset for real-data mode (built once, cached).

    BENCH_DATA_REC can point at a real --pack-raw .rec; otherwise a
    synthetic 512-image 256x256 raw rec is packed on first use.  The
    uint8 payloads exercise the exact pipeline ImageNet-through-
    ImageRecordUInt8Iter uses: read, crop, mirror, NCHW, all native.
    """
    import mxnet_tpu as mx
    from mxnet_tpu import recordio
    path = os.environ.get("BENCH_DATA_REC")
    if not path:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            ".bench_raw_512.rec")
        if not os.path.exists(path):
            _mark("packing synthetic raw rec (512 x 256x256x3) ...")
            rs = np.random.RandomState(0)
            rec = recordio.MXRecordIO(path, "w")
            for i in range(512):
                rec.write(recordio.pack(
                    recordio.IRHeader(0, float(i % 1000), i, 0),
                    rs.randint(0, 256, (256, 256, 3),
                               np.uint8).tobytes()))
            rec.close()
    # NHWC host layout: unflipped rows are single memcpys (~10x the NCHW
    # gather on one core); the HWC->CHW transpose happens on DEVICE where
    # it fuses into the uint8->fp32 cast.  BENCH_RECORD_LAYOUT=nchw
    # re-measures the old host-transpose path.
    layout = os.environ.get("BENCH_RECORD_LAYOUT", "nhwc").upper()
    return mx.io.ImageRecordUInt8Iter(
        path_imgrec=path, data_shape=(3, 224, 224), batch_size=batch,
        rand_crop=True, rand_mirror=True, shuffle=True,
        output_layout=layout)


def _iter_rate(it, max_batches=20):
    """Host-pipeline-only throughput (genuinely no device in the loop:
    next_raw returns host numpy, no NDArray wrap/device_put)."""
    it.reset()
    n = 0
    t0 = time.perf_counter()
    for _ in range(max_batches):
        try:
            data, _label, _pad = it.next_raw()
        except StopIteration:
            break
        n += data.shape[0]
    dt = time.perf_counter() - t0
    it.reset()
    return n / dt


def main():
    place_compile_cache()
    import jax
    dev = jax.devices()[0]
    _mark("backend up: %s" % dev.device_kind)
    if os.environ.get("BENCH_SPARSE", "0") == "1":
        # row-sparse kvstore wire mode: no model, the table IS the
        # workload (two-tower scenario's wire cost, isolated)
        return _run_sparse(dev)
    return _run(dev)


def _run_sparse(dev):
    """BENCH_SPARSE=1: row-sparse kvstore wire bench — an embedding
    table push loop at BENCH_SPARSE_DENSITY touch density through the
    dist_async store, sparse wire vs the dense baseline on the SAME
    rounds.  Banks sparse_rows_per_step next to wire_bytes_per_step
    (the regression gate: wire_bytes_per_step ~ density x dense at low
    density, rows x (8 + 4*dim) + frame overhead).  Self-contained:
    spins up in-process servers when MXT_SERVER_URIS is unset, so a
    smoke run needs no launcher."""
    import mxnet_tpu as mx
    from mxnet_tpu import profiler as _mx_prof
    from mxnet_tpu.ndarray import sparse as _sp

    vocab = int(os.environ.get("BENCH_SPARSE_VOCAB", "65536"))
    dim = int(os.environ.get("BENCH_SPARSE_DIM", "64"))
    density = float(os.environ.get("BENCH_SPARSE_DENSITY", "0.01"))
    iters = int(os.environ.get("BENCH_SPARSE_ITERS", "20"))
    touch = max(1, int(vocab * density))

    own_servers = []
    if not os.environ.get("MXT_SERVER_URIS"):
        from mxnet_tpu.kvstore_server import KVStoreServer
        n = int(os.environ.get("BENCH_SPARSE_SERVERS", "2"))
        own_servers = [KVStoreServer(server_id=i, num_workers=1)
                       for i in range(n)]
        for s in own_servers:
            s.start_background()
        os.environ["MXT_SERVER_URIS"] = ",".join(
            "127.0.0.1:%d" % s.port for s in own_servers)
        os.environ.setdefault("DMLC_NUM_WORKER", "1")
        os.environ.setdefault("DMLC_WORKER_ID", "0")
        # stripe the table across the in-process roster
        os.environ.setdefault("MXNET_KVSTORE_BIGARRAY_BOUND",
                              str(max(dim, vocab * dim // (2 * n))))
    _mark("sparse bench: %dx%d table, %d rows/step, %d iters"
          % (vocab, dim, touch, iters))

    rng = np.random.RandomState(0)
    rounds = []
    for _ in range(iters):
        ids = np.sort(rng.choice(vocab, size=touch,
                                 replace=False)).astype(np.int64)
        rounds.append((ids, rng.randn(touch, dim).astype(np.float32)))

    def one_pass(sparse_wire):
        os.environ["MXNET_KVSTORE_SPARSE"] = "1" if sparse_wire else "0"
        kv = mx.kv.create("dist_async")
        kv.init("emb", mx.nd.zeros((vocab, dim)))
        kv.set_optimizer(mx.optimizer.SGD(
            learning_rate=0.1, momentum=0.0, wd=0.0, rescale_grad=1.0))
        kv._flush_all()
        b0 = _mx_prof.wire_bytes_total()
        r0 = _mx_prof.channel_counts().get("kvstore.sparse_rows", 0)
        t0 = time.perf_counter()
        for ids, vals in rounds:
            kv.push("emb", _sp.row_sparse_array((vals, ids),
                                                shape=(vocab, dim)))
        kv._flush_all()          # every push acked: bytes are banked
        dt = time.perf_counter() - t0
        wire = _mx_prof.wire_bytes_total() - b0
        rows = _mx_prof.channel_counts().get("kvstore.sparse_rows",
                                             0) - r0
        kv.close(stop_servers=False)
        return wire, rows, dt

    try:
        dense_wire, _, dense_dt = one_pass(sparse_wire=False)
        wire, rows, dt = one_pass(sparse_wire=True)
    finally:
        for s in own_servers:
            s.stop()

    out = {
        "metric": "sparse_embed_push_rows_per_sec",
        "value": round(rows / dt, 1) if dt else None,
        "unit": "rows/sec",
        "device": dev.device_kind,
        "vocab": vocab,
        "dim": dim,
        "density": density,
        "iters": iters,
        "step_ms": round(dt / iters * 1e3, 2),
        "sparse_rows_per_step": round(rows / iters, 1),
        "wire_bytes_per_step": round(wire / iters, 1),
        # the dense equivalent IS the baseline: same rounds, sparse
        # wire off (worker densifies before push)
        "dense_wire_bytes_per_step": round(dense_wire / iters, 1),
        "dense_step_ms": round(dense_dt / iters * 1e3, 2),
        "wire_reduction_x": (round(dense_wire / wire, 1)
                             if wire else None),
    }
    from benchmark._bench_common import is_cpu_device
    if out.get("device") and not is_cpu_device(out["device"]):
        try:
            with open(os.path.join(
                    os.path.dirname(os.path.abspath(__file__)),
                    "BENCH_LOG.jsonl"), "a") as f:
                f.write(json.dumps(dict(out, ts=time.time())) + "\n")
        except OSError:
            pass
    print(json.dumps(out))
    return 0


def _run(dev):
    import threading
    import jax
    import jax.numpy as jnp
    # topology known only now (device kind + process count): resolve the
    # promoted per-topology defaults BEFORE the framework import so any
    # promoted env knobs are in place for every later read
    cfg = _resolve_config(dev.device_kind, hosts=jax.process_count())
    if cfg["applied_env"]:
        _mark("promoted env defaults for %s: %s"
              % (cfg["topology"], cfg["applied_env"]))
    batch = cfg["batch"]
    steps_per_call = cfg["steps_per_call"]
    import mxnet_tpu as mx
    from mxnet_tpu import models

    sym = models.resnet(num_classes=1000, num_layers=50,
                        image_shape=(3, 224, 224), stem=cfg["stem"],
                        layout=cfg["layout"])
    compute_dtype = None if cfg["dtype"] in ("float32", "fp32") \
        else jnp.dtype(cfg["dtype"])
    mod = mx.mod.Module(sym, context=mx.tpu(0),
                        compute_dtype=compute_dtype)

    rng = np.random.RandomState(0)
    x = rng.uniform(-1, 1, (batch, 3, 224, 224)).astype(np.float32)
    y = rng.randint(0, 1000, (batch,)).astype(np.float32)
    it = mx.io.NDArrayIter(data=x, label=y, batch_size=batch)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.initializer.Xavier(rnd_type="gaussian", magnitude=2.0))
    # BENCH_OPT=lars exercises the large-batch trust-ratio recipe (same
    # lr/momentum/wd knobs; LARS adds per-layer rate adaptation)
    mod.init_optimizer(optimizer=cfg["opt"],
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9, "wd": 1e-4})
    _mark("module bound + params initialized")

    # two device-resident batches, rotated per step — generated ON device
    # (a 256x3x224x224 fp32 batch is 154 MB; feeding it from the host
    # every step would measure the host link, not the chip)
    batches = []
    super_batches = []   # (k, batch, ...) stacks for steps_per_call > 1
    if os.environ.get("BENCH_DATA", "synthetic") != "record":
        for seed in (0, 1):
            k = jax.random.PRNGKey(seed)
            kx, ky = jax.random.split(k)
            bx = mx.nd.NDArray(jax.random.uniform(
                kx, (batch, 3, 224, 224), jnp.float32, -1.0, 1.0))
            by = mx.nd.NDArray(jax.random.randint(
                ky, (batch,), 0, 1000).astype(jnp.float32))
            bx.wait_to_read()
            by.wait_to_read()
            batches.append(mx.io.DataBatch(data=[bx], label=[by]))
        if steps_per_call > 1:
            # K distinct per-step batches stacked on device (tiling the
            # two base batches — rotation inside the scan, like the
            # K=1 loop rotates across calls)
            for s in (0, 1):
                bx = jnp.stack([batches[(s + j) % 2].data[0]._data
                                for j in range(steps_per_call)])
                by = jnp.stack([batches[(s + j) % 2].label[0]._data
                                for j in range(steps_per_call)])
                bx.block_until_ready()
                super_batches.append((bx, by))

    # real-data mode (BENCH_DATA=record): batches come from a raw-uint8
    # ImageRecordUInt8Iter on disk through the full host pipeline — read,
    # crop, mirror, uint8 NCHW — then are device_put as uint8 (4x fewer
    # bytes than fp32 through the host->device link) and cast on device.
    # A background thread keeps one prepared batch in flight (the
    # double-buffered prefetch the reference gets from iter_prefetcher.h).
    real_iter = None
    if os.environ.get("BENCH_DATA", "synthetic") == "record":
        real_iter = _make_record_iter(batch)
        host_rate = _iter_rate(real_iter, max_batches=20)
        _mark("host pipeline alone: %.0f imgs/sec" % host_rate)

        import queue as _q
        feed_q = _q.Queue(maxsize=2)

        def _feeder():
            # host numpy only — the single uint8 device_put happens in
            # step(), so each batch crosses the host->device link ONCE
            while True:
                real_iter.reset()
                while True:
                    try:
                        data, label, _pad = real_iter.next_raw()
                    except StopIteration:
                        break
                    feed_q.put((data, label))

        threading.Thread(target=_feeder, daemon=True).start()

        nhwc_feed = real_iter.provide_data[0].shape[-1] == 3

        if steps_per_call > 1:
            def step(i):
                # K host batches -> ONE stacked uint8 transfer -> device
                # layout/cast -> ONE scanned dispatch for all K steps
                datas, labels = zip(*[feed_q.get()
                                      for _ in range(steps_per_call)])
                dx = jnp.asarray(np.stack(datas))    # uint8, one transfer
                if nhwc_feed:                        # (k,n,H,W,C)->(k,n,C,H,W)
                    dx = jnp.transpose(dx, (0, 1, 4, 2, 3))
                mod.run_steps(dx.astype(jnp.float32),
                              jnp.asarray(np.stack(labels)),
                              k=steps_per_call)
        else:
            def step(i):
                data, label = feed_q.get()
                dx = jnp.asarray(data)           # uint8, one transfer
                if nhwc_feed:                    # device-side NHWC->NCHW
                    dx = jnp.transpose(dx, (0, 3, 1, 2))
                bx = mx.nd.NDArray(dx.astype(jnp.float32))  # cast on device
                by = mx.nd.NDArray(jnp.asarray(label))
                mod.forward(mx.io.DataBatch(data=[bx], label=[by]),
                            is_train=True)
                mod.update()
    elif steps_per_call > 1:
        def step(i):
            bx, by = super_batches[i % len(super_batches)]
            mod.run_steps(bx, by, k=steps_per_call)
    else:
        def step(i):
            b = batches[i % len(batches)]
            mod.forward(b, is_train=True)
            mod.update()

    # Synchronization barrier (make_hard_sync: jitted reduction over ALL
    # updated params fetched to host)
    hard_sync = make_hard_sync(mod)

    _mark("device batches ready")
    for i in range(WARMUP):
        step(i)
        if i == 0:
            hard_sync()
            _mark("first step done (compile)")
    hard_sync()
    _mark("warmup done")

    # FLOPs of one fused step from XLA cost analysis (fwd + bwd + update)
    if batches:
        cost_batch = batches[0]
    else:  # record mode: any fp32 device batch of the right shape works
        cost_batch = mx.io.DataBatch(
            data=[mx.nd.NDArray(jnp.zeros((batch, 3, 224, 224),
                                          jnp.float32))],
            label=[mx.nd.NDArray(jnp.zeros((batch,), jnp.float32))])
    mod.forward(cost_batch, is_train=True)
    flops_per_step = mod.fused_step_flops()
    flops_source = "xla_cost_analysis"
    mod.update()  # consume the snapshot taken for cost analysis
    _mark("cost analysis done: %s" % flops_per_step)
    iters = ITERS

    # BENCH_PROFILE=1: capture an xplane trace of a few steady-state
    # steps (AFTER warmup/compile so the capture is pure execution);
    # summarize offline with tools/xplane_summary.py — this is the
    # data source for the MFU gap analysis.
    profile_dir = None
    if os.environ.get("BENCH_PROFILE", "0") == "1":
        import jax as _jax
        profile_dir = os.environ.get(
            "BENCH_PROFILE_DIR",
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "docs", "artifacts", "xplane_resnet50"))
        os.makedirs(profile_dir, exist_ok=True)
        _jax.profiler.start_trace(profile_dir)
        for i in range(3):
            step(i)
        hard_sync()
        _jax.profiler.stop_trace()
        _mark("profile captured to %s" % profile_dir)

    # transport byte counters around the measured loop: with a dist
    # kvstore in the step this is the per-step wire cost (and the direct
    # evidence for the gradient-compression win); 0 in single-process
    # configs.  See profiler.channel_bytes.
    from mxnet_tpu import profiler as _mx_prof
    from mxnet_tpu import health as _mx_health
    wire0 = _mx_prof.wire_bytes_total()
    ici0 = _mx_prof.ici_bytes_total()
    sync0 = _mx_prof.host_sync_total()
    wait0 = _mx_prof.wire_wait_ms()
    round0 = _mx_prof.wire_round_ms()
    pickle0 = _mx_prof.pickle_bytes_total()
    syscalls0 = _mx_prof.send_syscalls_total()
    shm0 = _mx_prof.shm_bytes_total()
    fanin_ms0 = _mx_prof.mesh_fanin_wait_ms()
    srows0 = _mx_prof.channel_counts().get("kvstore.sparse_rows", 0)
    t0 = time.perf_counter()
    for i in range(iters):
        step(i)
    # snapshot host syncs BEFORE the barrier: hard_sync's own readback is
    # measurement plumbing, not part of the training loop being scored
    host_syncs = _mx_prof.host_sync_total() - sync0
    hard_sync()
    dt = time.perf_counter() - t0
    wire_bytes = _mx_prof.wire_bytes_total() - wire0
    ici_bytes = _mx_prof.ici_bytes_total() - ici0
    pickle_bytes = _mx_prof.pickle_bytes_total() - pickle0
    send_syscalls = _mx_prof.send_syscalls_total() - syscalls0
    shm_bytes = _mx_prof.shm_bytes_total() - shm0
    fanin_ms = _mx_prof.mesh_fanin_wait_ms() - fanin_ms0
    sparse_rows = _mx_prof.channel_counts().get(
        "kvstore.sparse_rows", 0) - srows0
    # overlap over THIS timed region only (wait/round deltas), so
    # warmup and earlier configs can't dilute the reported fraction
    wire_wait_d = _mx_prof.wire_wait_ms() - wait0
    wire_round_d = _mx_prof.wire_round_ms() - round0
    overlap_pct = (max(0.0, 100.0 * (1.0 - wire_wait_d / wire_round_d))
                   if wire_round_d > 0 else 0.0)

    # one step() call runs steps_per_call training steps; report per
    # TRAINING step so K=1 and K=8 rows compare directly
    step_s = dt / iters / steps_per_call
    imgs_per_sec = batch / step_s
    # a CPU run (CI contract check) has no peak and reports no MFU
    peak = None if dev.platform == "cpu" else _peak_flops(dev.device_kind)
    mfu = (flops_per_step / step_s / peak) if peak else None
    out = {
        "metric": "resnet50_train_imgs_per_sec",
        "value": round(imgs_per_sec, 2),
        "unit": "imgs/sec",
        "vs_baseline": round(imgs_per_sec / BASELINE_IMGS_PER_SEC, 2),
        "step_ms": round(step_s * 1e3, 2),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "batch": batch,
        "dtype": str(cfg["dtype"]),
        "device": dev.device_kind,
        "flops_per_step": flops_per_step,
        "flops_source": flops_source,
        "peak_flops": peak,
        "stem": cfg["stem"],
        "layout": cfg["layout"].lower(),
        "opt": cfg["opt"],
        "iters": iters,
        "steps_per_call": steps_per_call,
        "wire_bytes_per_step": round(
            wire_bytes / iters / steps_per_call, 1),
        # row-sparse wire rows per TRAINING step (ISSUE 19): 0 for the
        # dense resnet grads; nonzero means some param rode the sparse
        # path — next to wire_bytes_per_step so a density regression
        # (sparse rows up, bytes up) is one-row-visible.  BENCH_SPARSE=1
        # runs the dedicated embedding-table wire bench instead.
        "sparse_rows_per_step": round(
            sparse_rows / iters / steps_per_call, 1),
        # in-host mesh bytes of the hierarchical kvstore tier
        # (MXNET_KVSTORE_HIERARCHY): the bytes the tier moved OFF the
        # wire and onto ICI — 0 when the tier is off.  Its companion
        # regression gate is wire_bytes_per_step dropping by ~the
        # workers-per-host factor
        "ici_bytes_per_step": round(
            ici_bytes / iters / steps_per_call, 1),
        # host-blocking readbacks per TRAINING step (profiler.host_syncs)
        # — 0.0 in the steady state: the sync-free loop's one number.
        # Nonzero means something in the step path re-grew a per-step
        # device->host sync.
        "host_syncs_per_step": round(
            host_syncs / iters / steps_per_call, 3),
        # exposed (host-blocked) kvstore wire per TRAINING step and the
        # fraction of the wire hidden behind the scanned compute — 0.0
        # off the dist path; under fused dist_async training the
        # overlap_pct is the headline number
        # (profiler.wire_wait_ms/wire_overlap_pct)
        "wire_wait_ms_per_step": round(
            wire_wait_d / iters / steps_per_call, 3),
        "overlap_pct": round(overlap_pct, 1),
        # frame-layer cost counters:
        # pickle_bytes_per_step must be 0 steady-state with the binary
        # codec negotiated (MXNET_KVSTORE_CODEC auto/binary — the
        # regression gate for pickle creeping back onto the hot path);
        # send_syscalls_per_step tracks the vectored sendmsg win (one
        # syscall per frame vs 2+N sendalls)
        "pickle_bytes_per_step": round(
            pickle_bytes / iters / steps_per_call, 1),
        "send_syscalls_per_step": round(
            send_syscalls / iters / steps_per_call, 2),
        # same-host transport counters:
        # shm_bytes_per_step = mesh frames that rode the shared-memory
        # lane instead of loopback TCP (MXNET_KVSTORE_SHM; 0 flat or
        # with the lane off — paired with send_syscalls_per_step
        # dropping to the control-plane floor); mesh_fanin_ms_per_step
        # = leader wall-clock blocked collecting the followers' round
        # (the number MXNET_KVSTORE_MESH_ACCEPTORS parallelism shrinks)
        "shm_bytes_per_step": round(
            shm_bytes / iters / steps_per_call, 1),
        "mesh_fanin_ms_per_step": round(
            fanin_ms / iters / steps_per_call, 3),
        # report from the env the executor actually reads, so an
        # externally-set MXNET_BACKWARD_DO_MIRROR is labeled correctly
        "remat": (os.environ.get("MXNET_REMAT_POLICY", "full")
                  if os.environ.get("MXNET_BACKWARD_DO_MIRROR") == "1"
                  else False),
        "data_mode": os.environ.get("BENCH_DATA", "synthetic"),
        # end-of-run health digest next to the perf numbers: watchdog
        # trip counts and the worst SLO verdict the run saw — an
        # UNHEALTHY run (stalled barrier, BUSY storm, dead node) is
        # visible in BENCH_LOG.jsonl, not just slow
        # (docs/OBSERVABILITY.md health section)
        "health": _mx_health.summary(),
        # the topology this measurement belongs to — promotion keys
        # BENCH_DEFAULTS.json entries by it (autotune/promote.py)
        "topology": cfg["topology"],
        "hosts": jax.process_count(),
    }
    if real_iter is not None:
        out["host_pipeline_imgs_per_sec"] = round(host_rate, 1)
    # cluster counters next to wire_bytes_per_step (when a dist kvstore
    # is live): every server's ("stats",) reply — channel counts/gauges,
    # byte counters, wire clocks — rides the one-line JSON row, so
    # autotune trials and chip sessions bank cluster evidence for free
    # (docs/OBSERVABILITY.md).  Compact form; absent in single-process
    # configs so the CI bench-contract row stays lean.
    try:
        from mxnet_tpu import distributed as _mx_dist
        cstats = _mx_dist.cluster_stats(compact=True)
        if cstats.get("servers"):
            out["cluster_stats"] = cstats
    except Exception:  # noqa: BLE001 — stats must never fail the bench
        pass
    try:
        stats = dev.memory_stats() or {}
        peak_bytes = stats.get("peak_bytes_in_use")
        if peak_bytes:
            out["peak_hbm_gb"] = round(peak_bytes / 2**30, 2)
    except Exception:  # noqa: BLE001 — not all backends expose stats
        pass
    # persist every successful CHIP measurement (BENCH_LOG.jsonl is
    # append-only, timestamped).  CPU smoke runs (CI) never bank: the
    # log is chip
    # evidence, and a cpu row as the "latest device" once tricked the
    # defaults promotion into batch-8 CPU settings.
    from benchmark._bench_common import is_cpu_device
    if out.get("device") and not is_cpu_device(out["device"]):
        try:
            with open(os.path.join(
                    os.path.dirname(os.path.abspath(__file__)),
                    "BENCH_LOG.jsonl"), "a") as f:
                f.write(json.dumps(dict(out, ts=time.time())) + "\n")
        except OSError:
            pass
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
