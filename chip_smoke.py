#!/usr/bin/env python
"""Does the main path still start on the chip?

One process, one plain ``jax.devices()``: no probe child, no retry, no
watchdog (a chip belongs to one process).  It drives the system through
the entry points a user calls and checks each result; the first failed
check or raised error ends the run non-zero — there is no degraded mode.

Phase A  trainer, full width.  ResNet-50 (1000 classes, 3x224x224, batch
         256, bf16 compute, SGD-momentum) through ``mx.mod.Module`` on
         ``mx.tpu(0)``: (a) ``fit`` over host-fed ``NDArrayIter`` batches,
         (b) ``forward``+``update`` on device-resident batches, (c) one
         ``run_steps(k=4)`` scan; after each, outputs finite, parameters
         moved, every parameter/aux/optimizer state on a TPU device.
         ``fused_step_flops()`` must return a number.
Phase B  the kernels compile.  ``transformer_lm`` (12 layers, d 768, 12
         heads, seq 1024, batch 8, vocab 50304, bf16) through the same
         Module step, with the two Pallas flash kernels (forward, merged
         backward) present in the lowered step as Mosaic custom calls; then ``flash_attention``
         value and gradients against the float32 XLA reference at head 64
         and 128, MHA and GQA, causal and not, S 1024 and one short block.
Phase C  four chips (only when JAX reports >= 4 devices): the Phase A
         step on ``[mx.tpu(i) for i in range(4)]`` — batch shards on four
         distinct devices, parameters addressable on all four.  With
         fewer devices it says that it did not run; it is never reported
         as passed.

Depth is not cut; weights and data are random from fixed seeds.  The last
stdout line is ``{"ok": true, "device": {...}}`` with the device as JAX
reports it; the line before it is a report (phases run, wall seconds,
the compile ledger's trace, lower and compile seconds and cache outcomes)
for the records.
"""
import gc
import json
import sys
import time

T0 = time.perf_counter()

from chipbench.common import place_compile_cache  # noqa: E402

# before the first ``import jax``: jax reads the cache's variable once, as
# it is imported
CACHE_DIR = place_compile_cache()

import jax  # noqa: E402

DEVICES = jax.devices()
DEV = DEVICES[0]
if DEV.platform != "tpu":
    sys.exit("chip_smoke: needs a TPU, but jax.devices()[0] is platform "
             "%r (%s); nothing was run" % (DEV.platform, DEV.device_kind))

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import models, tracing  # noqa: E402
from mxnet_tpu.ops import attention  # noqa: E402

def say(msg):
    print("[chip_smoke +%6.1fs] %s" % (time.perf_counter() - T0, msg),
          flush=True)


def check(cond, what):
    if not cond:
        raise AssertionError("chip_smoke: " + what)


# -- Phase A / C: the ResNet-50 trainer -------------------------------------
BATCH = 256


def resnet_module(context, kvstore="local"):
    sym = models.resnet(num_classes=1000, num_layers=50,
                        image_shape=(3, 224, 224))
    mod = mx.mod.Module(sym, context=context, compute_dtype=jnp.bfloat16)
    mod.bind(data_shapes=[("data", (BATCH, 3, 224, 224))],
             label_shapes=[("softmax_label", (BATCH,))])
    mod.init_params(mx.initializer.Xavier(rnd_type="gaussian",
                                          magnitude=2.0))
    mod.init_optimizer(kvstore=kvstore, optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9, "wd": 1e-4})
    return mod


def device_batch(seed, lead=()):
    kx, ky = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.uniform(kx, lead + (BATCH, 3, 224, 224), jnp.float32,
                           -1.0, 1.0)
    y = jax.random.randint(ky, lead + (BATCH,), 0, 1000).astype(jnp.float32)
    return x, y


@jax.jit
def _checksums(vals):
    return jnp.stack([jnp.sum(jnp.abs(v.astype(jnp.float32))) for v in vals])


def param_checksums(mod):
    """Host vector of per-parameter |.| sums; reading it also waits for
    every step that produced those parameters."""
    names = mod._update_names()
    return np.asarray(_checksums(
        tuple(mod._exec.arg_dict[n]._data for n in names)))


def check_step(mod, before, what, n_devices=1):
    """After a training call: outputs finite, parameters moved, and all
    training state resident on TPU devices."""
    after = param_checksums(mod)
    check(np.all(np.isfinite(after)), what + ": non-finite parameters")
    out = mod.get_outputs()[0].asnumpy()
    check(out.shape == (BATCH, 1000), what + ": output shape %r" % (out.shape,))
    check(np.all(np.isfinite(out)), what + ": non-finite outputs")
    check(np.allclose(out.sum(axis=1), 1.0, atol=2e-2),
          what + ": softmax rows do not sum to 1")
    names = mod._update_names()
    moved = after != before
    # a gradient can be structurally zero (bn_data's fixed gamma); both
    # ends of the network moving shows it flowed through the whole depth
    check(moved.mean() > 0.95, what + ": only %d of %d parameters changed"
          % (moved.sum(), moved.size))
    for n in ("conv0_weight", "fc1_weight"):
        check(moved[names.index(n)], what + ": %s did not change" % n)
    state = {n: mod._exec.arg_dict[n] for n in names}
    state.update(("aux:" + n, a) for n, a in mod._exec.aux_dict.items())
    for n in names:
        for i, s in enumerate(mod._opt_states[n]):
            state["opt:%s[%d]" % (n, i)] = s
    for n, arr in state.items():
        devs = arr._data.devices()
        check(all(d.platform == "tpu" for d in devs) and len(devs) == n_devices,
              what + ": %s lives on %s" % (n, sorted(map(str, devs))))
    return after


def phase_a():
    mod = resnet_module(mx.tpu(0))
    sums = param_checksums(mod)

    # (a) the path every example takes: fit() over host-fed batches.  The
    # iterator's arrays sit on cpu(0); the step must still run on the chip.
    rng = np.random.RandomState(0)
    n = 3 * BATCH
    it = mx.io.NDArrayIter(
        data=rng.uniform(-1, 1, (n, 3, 224, 224)).astype(np.float32),
        label=rng.randint(0, 1000, (n,)).astype(np.float32),
        batch_size=BATCH)
    check(all(d.platform == "cpu" for d in it.data[0][1]._data.devices()),
          "A(a): NDArrayIter data was expected on the host backend")
    t = time.perf_counter()
    mod.fit(it, num_epoch=1, eval_metric="acc")
    sums = check_step(mod, sums, "A(a) fit, 3 host-fed batches")
    say("A(a) fit over 3 host-fed batches ok (%.1fs)"
        % (time.perf_counter() - t))
    del it

    # (b) forward + update on device-resident batches
    t = time.perf_counter()
    for seed in (1, 2, 3):
        x, y = device_batch(seed)
        mod.forward(mx.io.DataBatch(data=[mx.nd.NDArray(x)],
                                    label=[mx.nd.NDArray(y)]),
                    is_train=True)
        mod.update()
    sums = check_step(mod, sums, "A(b) forward+update")
    say("A(b) 3 forward+update steps ok (%.1fs)" % (time.perf_counter() - t))

    # fused_step_flops needs a fresh forward snapshot; un-guarded
    x, y = device_batch(4)
    mod.forward(mx.io.DataBatch(data=[mx.nd.NDArray(x)],
                                label=[mx.nd.NDArray(y)]), is_train=True)
    flops = mod.fused_step_flops()
    check(isinstance(flops, float) and flops > 1e12,
          "A: fused_step_flops() returned %r" % (flops,))
    mod.update()
    say("A fused_step_flops() = %.4g" % flops)

    # (c) K steps as one scanned program
    t = time.perf_counter()
    xs, ys = device_batch(5, lead=(4,))
    outs = mod.run_steps(xs, ys, k=4)
    check(outs[0].shape == (4, BATCH, 1000),
          "A(c): stacked outputs %r" % (outs[0].shape,))
    check(bool(jnp.all(jnp.isfinite(outs[0]._data))),
          "A(c): non-finite stacked outputs")
    check_step(mod, sums, "A(c) run_steps(k=4)")
    say("A(c) run_steps(k=4) ok (%.1fs)" % (time.perf_counter() - t))
    return {"fused_step_flops": flops}


def phase_c():
    ctxs = [mx.tpu(i) for i in range(4)]
    # kvstore=None keeps the Phase A step: with a context list the default
    # 'local' store updates parameter by parameter through the kvstore
    # instead of inside the one fused program
    mod = resnet_module(ctxs, kvstore=None)
    sums = param_checksums(mod)
    for seed in (1, 2):
        x, y = device_batch(seed)
        mod.forward(mx.io.DataBatch(data=[mx.nd.NDArray(x)],
                                    label=[mx.nd.NDArray(y)]),
                    is_train=True)
        mod.update()
    check_step(mod, sums, "C dp=4 forward+update", n_devices=4)
    want = {c.jax_device() for c in ctxs}
    check(len(want) == 4, "C: contexts resolve to %d devices" % len(want))
    # the batch as the step consumed it: four shards, one per device
    data = mod._exec._arg_vals()[mod._exec._arg_names.index("data")]
    shards = data.addressable_shards
    placement = sorted((str(s.device), tuple(s.data.shape)) for s in shards)
    check({s.device for s in shards} == want
          and all(s.data.shape[0] == BATCH // 4 for s in shards),
          "C: batch shards are %r" % (placement,))
    w = mod._exec.arg_dict["fc1_weight"]._data
    check(w.is_fully_addressable and set(w.devices()) == want,
          "C: fc1_weight is on %s" % sorted(map(str, w.devices())))
    say("C batch shards: %s" % placement)
    return {"batch_shards": placement}


# -- Phase B: the flash kernels ---------------------------------------------
# bf16 inputs, f32 reference: error as a share of the reference's largest
# magnitude.  bf16 carries 8 significand bits (2^-8 = 0.4%); the kernel
# rounds p (and ds) to bf16 once more before the second matmul of each
# tile, and the sums run over up to 1024 keys.
TOL_VALUE = 2e-2
TOL_GRAD = 4e-2


def rel_err(got, ref):
    got = np.asarray(got.astype(jnp.float32))
    ref = np.asarray(ref)
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


def phase_b():
    L, D, H, S, B, V = 12, 768, 12, 1024, 8, 50304
    net = models.transformer_lm(V, S, num_layers=L, d_model=D, num_heads=H)
    mod = mx.mod.Module(net, context=mx.tpu(0), compute_dtype=jnp.bfloat16)
    mod.bind(data_shapes=[("data", (B, S))],
             label_shapes=[("softmax_label", (B, S))])
    mod.init_params(mx.initializer.Xavier(rnd_type="gaussian",
                                          magnitude=2.0))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 1e-3,
                                         "momentum": 0.9})
    before = param_checksums(mod)
    t = time.perf_counter()
    for seed in (0, 1, 2):
        kx, ky = jax.random.split(jax.random.PRNGKey(seed))
        x = jax.random.randint(kx, (B, S), 0, V).astype(jnp.float32)
        y = jax.random.randint(ky, (B, S), 0, V).astype(jnp.float32)
        mod.forward(mx.io.DataBatch(data=[mx.nd.NDArray(x)],
                                    label=[mx.nd.NDArray(y)]),
                    is_train=True)
        if seed == 0:
            hlo = mod.fused_step_hlo()
        mod.update()
    after = param_checksums(mod)
    out = mod.get_outputs()[0]._data
    check(bool(jnp.all(jnp.isfinite(out))), "B: non-finite LM outputs")
    check(np.all(np.isfinite(after)) and (after != before).mean() > 0.95,
          "B: LM parameters did not move")
    # neither interpret mode nor the XLA reference answered: the lowered
    # step defines the forward and the merged backward kernel (12 heads of
    # 64 at S 1024: dQ's accumulator fits VMEM) as Mosaic custom calls
    # (jit outlines _flash_fwd/_flash_bwd once) and every layer calls them
    calls = {k: hlo.count('kernel_name = "%s"' % k)
             for k in ("flash_fwd", "flash_bwd_dkv_dq")}
    sites = {f: hlo.count("call @%s(" % f)
             for f in ("_flash_fwd", "_flash_bwd")}
    check(hlo.count("tpu_custom_call") == 2
          and all(c == 1 for c in calls.values())
          and all(n == L for n in sites.values()),
          "B: lowered LM step has %d tpu_custom_call, kernels %r, call "
          "sites %r (want 2 kernels, %d sites each)"
          % (hlo.count("tpu_custom_call"), calls, sites, L))
    say("B transformer_lm 3 steps ok (%.1fs), Mosaic kernels %r called at "
        "%r" % (time.perf_counter() - t, calls, sites))
    del mod

    errs = {}
    for D_, Hk, S_, causal in [(64, 8, 1024, True), (64, 8, 1024, False),
                               (64, 2, 1024, True), (128, 8, 1024, True),
                               (128, 2, 1024, True), (128, 2, 1024, False),
                               (64, 2, 100, True)]:  # one short block
        Bq, Hq = 2, 8
        ks = jax.random.split(jax.random.PRNGKey(D_ + Hk + S_), 4)
        q = jax.random.normal(ks[0], (Bq, Hq, S_, D_), jnp.bfloat16)
        k = jax.random.normal(ks[1], (Bq, Hk, S_, D_), jnp.bfloat16)
        v = jax.random.normal(ks[2], (Bq, Hk, S_, D_), jnp.bfloat16)
        g = jax.random.normal(ks[3], (Bq, Hq, S_, D_), jnp.bfloat16)

        def flash(q, k, v):
            return attention.flash_attention(q, k, v, causal, None)

        def ref(q, k, v):
            return attention._attn_reference(q, k, v, causal, None)

        out, vjp = jax.vjp(flash, q, k, v)
        grads = vjp(g)
        f32 = [a.astype(jnp.float32) for a in (q, k, v)]
        with jax.default_matmul_precision("float32"):
            out_r, vjp_r = jax.vjp(ref, *f32)
            grads_r = vjp_r(g.astype(jnp.float32))
        tag = "D%d H%d/%d S%d %s" % (D_, Hq, Hk, S_,
                                     "causal" if causal else "full")
        e = [rel_err(out, out_r)] + [rel_err(a, b)
                                     for a, b in zip(grads, grads_r)]
        errs[tag] = [round(x, 5) for x in e]
        check(e[0] < TOL_VALUE, "B %s: value error %.4f" % (tag, e[0]))
        check(max(e[1:]) < TOL_GRAD,
              "B %s: dq/dk/dv errors %r" % (tag, e[1:]))
    say("B flash vs f32 reference [value, dq, dk, dv]: %s" % errs)
    return {"mosaic_kernels": calls, "call_sites": sites,
            "flash_rel_err": errs}


def main():
    say("device: %s x%d (%s); compile cache: %s"
        % (DEV.device_kind, len(DEVICES), DEV.platform, CACHE_DIR))
    report = {"phases": {}}
    phases = [("A", phase_a), ("B", phase_b)]
    if len(DEVICES) >= 4:
        phases.append(("C", phase_c))
    for name, fn in phases:
        t = time.perf_counter()
        report["phases"][name] = dict(fn(), wall_s=round(
            time.perf_counter() - t, 1))
        gc.collect()
    if len(DEVICES) < 4:
        say("C did NOT run: it needs 4 devices, JAX reports %d"
            % len(DEVICES))
        report["phases"]["C"] = "not run: %d device(s)" % len(DEVICES)
    report["wall_s"] = round(time.perf_counter() - T0, 1)
    # what the run spent tracing, lowering and compiling: the program's
    # compile ledger, each stage's union of top-level records
    compiles = tracing.stats()["compiles"]
    report.update({"%s_s" % k: round(v, 1)
                   for k, v in compiles["seconds"].items()})
    report.update({"cache_%s" % k: n for k, n in compiles["cache"].items()})
    report["jax"] = jax.__version__
    print(json.dumps({"report": report}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": DEV.platform, "kind": DEV.device_kind,
        "count": len(DEVICES)}}), flush=True)


if __name__ == "__main__":
    main()
