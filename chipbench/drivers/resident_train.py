"""Closed-loop training on device-resident batches, through ``Module``.

Set-up: build the Module from the configuration, initialise it on the
device from ``--seed``, make the traffic's batches on the device, take the
Module's first step (what it started from and where it landed are kept on
the host), warm the one step program up, read the device's memory peak
(the training's own: no program of the yardstick's has been loaded yet),
then hold the kept first step to the family's plain reference
(chipbench/correct.py).  Window: ``forward`` + ``update`` on rotating
batches for ``--seconds``, dispatching DISPATCH_AHEAD steps before waiting
for a step's loss, so the device stays fed and every step leaves a
completion stamp.  A traced run then spends TRACE_SLICE_S on a profiled
slice of whole steps."""
import collections
import gc
import json
import math
import time

# steps dispatched before the loop waits for a step's loss
DISPATCH_AHEAD = 2
# steps run before the window, after the first one
WARMUP_STEPS = 3
# a traced run's profiled slice: this long, and at least so many whole steps
TRACE_SLICE_S = 1.5
TRACE_MIN_STEPS = 10


def _jax_array(nd):
    """The jax array behind an NDArray: the one place chipbench reaches
    under the program's public surface (PERF.md, Open questions)."""
    return nd._data


def build_module(r, built, cd, start=None):
    """The configuration's Module at compute dtype ``cd``, initialised
    from ``--seed`` or, where ``start`` is given, from those arrays."""
    import jax.numpy as jnp
    import mxnet_tpu as mx
    cfg = r.config
    init, opt = cfg["initializer"], cfg["optimizer"]
    if init["name"] != "xavier" or opt["name"] != "sgd":
        raise ValueError("resident_train: configuration %r asks for %s/%s; "
                         "this driver binds xavier/sgd"
                         % (cfg["name"], init["name"], opt["name"]))
    mx.random.seed(int(r.seed) & 0x7FFFFFFF)
    ctx = r.contexts[0] if len(r.contexts) == 1 else r.contexts
    mod = mx.mod.Module(
        built["symbol"], context=ctx,
        compute_dtype=None if cd == "float32" else jnp.dtype(cd))
    mod.bind(data_shapes=built["data_shapes"],
             label_shapes=built["label_shapes"])
    mod.init_params(
        mx.initializer.Xavier(
            rnd_type=init["rnd_type"], factor_type=init["factor_type"],
            magnitude=init["magnitude"]),
        arg_params=start and {n: mx.nd.NDArray(v) for n, v in start.items()})
    mod.init_optimizer(
        optimizer="sgd",
        optimizer_params={"learning_rate": opt["learning_rate"],
                          "momentum": opt["momentum"], "wd": opt["wd"]})
    return mod


def make_loss_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def loss_of(probs, label):
        """Mean cross-entropy of the Module's probabilities, one scalar."""
        lab = label.astype(jnp.int32).reshape(-1)
        p = probs.reshape(lab.shape[0], -1).astype(jnp.float32)
        return -jnp.mean(jnp.log(
            jnp.take_along_axis(p, lab[:, None], axis=1)[:, 0]))
    return loss_of


class Loop:
    """The training loop, the same code for warm-up, window and traced
    slice.  ``run`` dispatches steps until ``stop(n_dispatched, now)`` and
    returns (start time, steps dispatched, completion stamps, losses,
    seconds spent inside forward+update)."""

    def __init__(self, mod, batches, loss_of):
        import mxnet_tpu as mx
        self.mod, self.loss_of = mod, loss_of
        self.labels = [y for _, y in batches]
        self.batches = [mx.io.DataBatch(data=[mx.nd.NDArray(x)],
                                        label=[mx.nd.NDArray(y)])
                        for x, y in batches]
        self.i = 0

    def run(self, stop):
        from jax.profiler import TraceAnnotation
        mod, pending = self.mod, collections.deque()
        stamps, losses, dispatch_s, n = [], [], 0.0, 0
        t_start = time.perf_counter()
        while not stop(n, time.perf_counter() - t_start):
            k = self.i % len(self.batches)
            with TraceAnnotation("chipbench.step_dispatch"):
                t = time.perf_counter()
                mod.forward(self.batches[k], is_train=True)
                mod.update()
                dispatch_s += time.perf_counter() - t
            with TraceAnnotation("chipbench.loss_dispatch"):
                pending.append(self.loss_of(
                    _jax_array(mod.get_outputs()[0]), self.labels[k]))
            self.i += 1
            n += 1
            if len(pending) > DISPATCH_AHEAD:
                with TraceAnnotation("chipbench.stamp_wait"):
                    losses.append(float(pending.popleft()))
                stamps.append(time.perf_counter())
        with TraceAnnotation("chipbench.hard_sync"):
            # the last loss depends on the last step's program: when it
            # is on the host, that step has run
            while pending:
                losses.append(float(pending.popleft()))
                stamps.append(time.perf_counter())
        return t_start, n, stamps, losses, dispatch_s


def _params(mod):
    got = mod.get_params()[0]
    return {n: _jax_array(got[n]) for n in sorted(got)}


def one_step(mod, batch):
    import mxnet_tpu as mx
    data, label = batch
    mod.forward(mx.io.DataBatch(data=[mx.nd.NDArray(data)],
                                label=[mx.nd.NDArray(label)]),
                is_train=True)
    mod.update()


def first_step(r, mod, batch, loss_of):
    """The Module's first step on ``batch``, also the step program's first
    run.  Where it started and where it landed go to the host (the step
    donates the Module's buffers, and the device has no room to spare in
    the window); the loss and the compared output rows are small and stay."""
    import jax
    sample = r.family.output_sample(r.config, r.traffic, r.seed)
    p0 = jax.device_get(_params(mod))
    one_step(mod, batch)
    probs = _jax_array(mod.get_outputs()[0])
    loss = float(loss_of(probs, batch[1]))
    probs = probs.reshape(-1, probs.shape[-1])
    # the rows are an argument: as a constant they would make a program
    # of every seed
    out = probs if sample is None \
        else jax.jit(lambda p, rows: p[rows])(probs, sample)
    p1 = jax.device_get(_params(mod))
    return {"p0": p0, "delta": {n: p1[n] - p0[n] for n in p0},
            "loss": loss, "out": out, "sample": sample}


def float32_deltas(r, built, batch, p0):
    """The same Module code at float32 compute with float32 products
    ("highest"), one step from ``p0`` on ``batch``: the deltas that decide
    the tensors the stated precision cannot."""
    import jax
    import jax.numpy as jnp
    # a copy: the step donates what the Module holds
    mod = build_module(r, built, "float32",
                       start=jax.jit(lambda t: jax.tree.map(jnp.copy, t))(p0))
    with jax.default_matmul_precision("highest"):
        one_step(mod, batch)
    return jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))(
        _params(mod), p0)


def reference_check(r, built, batch, first):
    """Rules 1 and 2 of chipbench/correct.py on the kept first step.  The
    whole table goes to stderr; returns (ok, summary).  At most three
    parameter-sized trees are on the device at once."""
    import jax
    from chipbench import correct
    cfg, fam = r.config, r.family
    data, label = batch
    p0 = jax.device_put(first["p0"], r.devices[0])
    names = sorted(p0)

    loss32, out32, d32 = fam.reference(cfg, r.traffic, p0, data, label,
                                       "float32", first["sample"])
    loss32 = float(loss32)
    r.mark("ref32 done: loss %.6f" % loss32)
    e_out = correct.rel_l2(correct.pair_stats({"o": first["out"]},
                                              {"o": out32})["o"])
    if cfg["compute_dtype"] == "float32":
        loss16, c_out, d16 = loss32, 0.0, d32
    else:
        loss16, out16, d16 = fam.reference(
            cfg, r.traffic, p0, data, label, cfg["compute_dtype"],
            first["sample"])
        loss16 = float(loss16)
        c_out = correct.rel_l2(correct.pair_stats({"o": out16},
                                                  {"o": out32})["o"])
        del out16
    c_stats = correct.pair_stats(d16, d32)
    r.mark("ref16 (%s) done: loss %.6f" % (cfg["compute_dtype"], loss16))
    s32 = None
    if any(correct.rel_l2(c) > correct.C_DECIDABLE for c in c_stats.values()):
        # what the stated precision cannot decide, float32 compute does
        s32 = correct.pair_stats(float32_deltas(r, built, batch, p0), d32)
        r.mark("float32-compute Module step done")
    del p0
    dm = jax.device_put(first["delta"], r.devices[0])
    e_stats = correct.pair_stats(dm, d32)
    # the Module against ref16 itself: printed, decides nothing
    m_stats = correct.pair_stats(dm, d16)
    del dm, d16, d32, out32

    table = {}
    for n in names:
        table[n] = {"e": correct.rel_l2(e_stats[n]),
                    "c": correct.rel_l2(c_stats[n]),
                    "cos": correct.cosine(e_stats[n]),
                    "cos16": correct.cosine(c_stats[n]),
                    "e_m16": correct.rel_l2(m_stats[n]),
                    "cos_m16": correct.cosine(m_stats[n]),
                    "norm32": math.sqrt(e_stats[n][1])}
        if s32:
            table[n]["e32"] = correct.rel_l2(s32[n])
            table[n]["cos32"] = correct.cosine(s32[n])

    ok_f, fwd = correct.judge_forward(first["loss"], loss32, e_out, c_out)
    ok_d, deltas = correct.judge_deltas(table, built["output_weight"])
    for n in names:
        t = table[n]
        verdict = ("undecidable" if n in deltas["undecidable"] else
                   "FAILS" if n in deltas["failing"] else
                   "passes in float32" if n in deltas["decided_in_float32"]
                   else "passes")
        r.mark("delta %-34s e=%.4f c=%.4f cos=%.4f cos16=%.4f e_m16=%.4f "
               "cos_m16=%.4f%s |d32|=%.3e %s"
               % (n, t["e"], t["c"], t["cos"], t["cos16"], t["e_m16"],
                  t["cos_m16"],
                  " e32=%.4f cos32=%.4f" % (t["e32"], t["cos32"])
                  if "e32" in t else "", t["norm32"], verdict))
    fwd["loss_ref16"] = loss16
    r.mark("reference check, forward %s: %s"
           % ("ok" if ok_f else "FAILED", json.dumps(fwd)))
    r.mark("reference check, deltas %s: %s"
           % ("ok" if ok_d else "FAILED", json.dumps(deltas)))
    decided = [n for n in names if table[n]["c"] <= correct.C_DECIDABLE]
    own = [table[n]["e"] for n in decided]
    in32 = [table[n]["e32"] for n in deltas["decided_in_float32"]]
    # every decided tensor's reading over its own limit: the worst, limit 1
    over = [table[n]["e"] / max(correct.DELTA_FACTOR * table[n]["c"],
                                correct.DELTA_FLOOR) for n in decided] \
        + [e / correct.DELTA_FLOOR for e in in32]
    summary = {"loss_rel": fwd["loss_rel"], "loss_tol": fwd["loss_tol"],
               "out_rel_l2": fwd["out_rel_l2"],
               "out_tol": fwd["out_tol"], "tensors": deltas["tensors"],
               "decidable": deltas["decidable"],
               "decided_in_float32": len(in32),
               "undecidable": len(deltas["undecidable"]),
               "failing": deltas["failing"],
               "max_e_decidable": max(own, default=None),
               "max_e32": max(in32, default=None),
               "max_e_over_tol": max(over, default=None),
               "max_e_over_tol_limit": 1.0,
               "output_weight_e": table[built["output_weight"]]["e"],
               "output_weight_c": table[built["output_weight"]]["c"]}
    return ok_f and ok_d, summary


def p90_ms_per_step(stamps):
    """90th percentile of the time between consecutive completion stamps,
    every step one sample, in ms; with the number of intervals."""
    import numpy as np
    gaps = [(b - a) * 1e3 for a, b in zip(stamps, stamps[1:])]
    if not gaps:
        return math.nan, 0
    return float(np.percentile(gaps, 90)), len(gaps)


def longest_interval(stamps):
    """(ms, index of the step it ends on, the three intervals after it in
    ms).  After a stall of the host the steps that finished meanwhile are
    stamped at once, so short intervals follow; after a stall of the
    device the next intervals are a step long."""
    gaps = [(b - a) * 1e3 for a, b in zip(stamps, stamps[1:])]
    if not gaps:
        return math.nan, 0, []
    k = max(range(len(gaps)), key=gaps.__getitem__)
    return gaps[k], k + 1, [round(g, 1) for g in gaps[k + 1:k + 4]]


def printable(details):
    """A verdict's details as the result line can carry them: a loss that
    is not finite goes as its name ("inf", "nan"), which is no JSON number."""
    return {k: repr(v) if isinstance(v, float) and not math.isfinite(v)
            else v for k, v in details.items()}


def run(r):
    import jax
    from mxnet_tpu import profiler as prof
    from chipbench import correct, trace_reduce
    from chipbench.common import seed_key
    cfg, traffic, fam = r.config, r.traffic, r.family
    built = fam.build(cfg, traffic)
    mod = build_module(r, built, cfg["compute_dtype"])
    n_params = sum(int(v.size) for v in mod.get_params()[0].values())
    r.mark("module built: %d parameters in %d arrays, %d items a step"
           % (n_params, len(mod.get_params()[0]), built["items_per_step"]))

    keys = jax.random.split(seed_key(r.seed), int(traffic["resident_batches"]))
    batches = [fam.make_batch(cfg, traffic, k) for k in keys]
    loss_of = make_loss_fn()
    first = first_step(r, mod, batches[0], loss_of)
    r.mark("first step done, kept on the host: loss %.6f" % first["loss"])

    loop = Loop(mod, batches, loss_of)
    loop.i = 1      # batch 0 has had its step
    loop.run(lambda n, t: n >= WARMUP_STEPS)
    # the training's own peak: no reference program has been loaded yet
    memory_peak = r.memory_peak()
    ok_ref, ref_report = reference_check(r, built, batches[0], first)

    slice_s = TRACE_SLICE_S if r.trace_dir else 0.0
    seconds = max(1.0, r.seconds - slice_s)
    syncs0 = prof.host_sync_total()
    dispatches0 = dict(prof.dispatch_counts())
    # tracing and compiling leave a quarter of a million long-lived objects
    # behind.  A full collection over them inside the window would hold
    # the host for as long as the one timed here (65-213 ms on the chip's
    # machine, PERF.md section 2) and make a stamp that late: they are
    # collected now and kept out of later collections
    t = time.perf_counter()
    gc.collect()
    gc.freeze()
    r.mark("set-up done: a full gc took %.0f ms, %d objects frozen; compile "
           "clock %s" % ((time.perf_counter() - t) * 1e3,
                         gc.get_freeze_count(),
                         json.dumps(r.clock.t["setup"])))
    r.clock.phase = "window"
    setup_s = time.perf_counter() - r.t0
    t_start, steps, stamps, losses, dispatch_s = loop.run(
        lambda n, t: t >= seconds)
    r.clock.phase = "after"
    gc.unfreeze()
    window_s = stamps[-1] - t_start
    syncs = prof.host_sync_total() - syncs0
    compiles = r.clock.t["window"]["backend_compiles"]
    p90, n_gaps = p90_ms_per_step(stamps)
    ok_win, win = correct.judge_window(losses, compiles, steps, len(stamps))
    dispatches = {k: v - dispatches0.get(k, 0)
                  for k, v in prof.dispatch_counts().items()}
    # beside the p90: the longest single interval with what followed it
    longest = longest_interval(stamps)
    # every step's loss: how near the recipe came to rule 3's edge is in
    # the curve, and no side file keeps it
    r.mark("window losses, step 0 on: %s" % " ".join("%.4g" % x
                                                     for x in losses))
    r.mark("window on %s: %d steps in %.4f s; step_ms_p90 %.4f over %d "
           "intervals of one step; longest interval %.1f ms ending on step "
           "%d, then %s; loss first %.6f last %.6f; %s; host syncs %d; "
           "dispatch counts %s"
           % (r.devices[0].device_kind, steps, window_s, p90, n_gaps,
              longest[0], longest[1], longest[2], losses[0], losses[-1],
              json.dumps(win), syncs, json.dumps(dispatches)))

    record = {
        "correct": ok_ref and ok_win, "attempted": steps,
        "failed": steps - len(stamps),
        "end_to_end": {
            "train_items_per_s": (steps * built["items_per_step"] / window_s,
                                  "items/s"),
            "step_ms_p90": (p90, "ms"),
            "setup_s": (setup_s, "s")},
        "memory_peak_bytes": memory_peak,
        "steps": steps, "window_s": window_s,
        "items_per_step": built["items_per_step"], "chips": len(r.devices),
        "compile": r.clock.t, "host_dispatch_s": dispatch_s,
        "host_syncs": syncs,
        # the line's last key: each number compared, beside its limit
        "reference": dict(ref_report, window=printable(win)),
        "model_flops_per_step": fam.model_flops(cfg, traffic),
        "kernel_costs": (fam.kernel_costs(cfg, traffic)
                         if hasattr(fam, "kernel_costs") else {}),
        "trace": None}

    if r.trace_dir:
        k = max(TRACE_MIN_STEPS, math.ceil(slice_s * steps / window_s))
        jax.profiler.start_trace(r.trace_dir)
        try:
            loop.run(lambda n, t: n >= k)
        finally:
            jax.profiler.stop_trace()
        record["trace"] = trace_reduce.reduce_file(
            trace_reduce.find_xplane(r.trace_dir), steps=k,
            kernel_prefixes=sorted(record["kernel_costs"]))
        tr = record["trace"]
        r.mark("trace on %s: %d steps, window %.4f s, busy %.4f s on %d "
               "device plane(s); plane says %s"
               % (r.devices[0].device_kind, k, tr["window_s"], tr["busy_s"],
                  tr["device_planes"], json.dumps(tr["plane_peaks"])))
        # XLA's count of what it executes, beside the model's own count
        mod.forward(loop.batches[0], is_train=True)
        xla_flops = mod.fused_step_flops()
        mod.update()
        r.mark("flops a step: model %.4g (family file), XLA cost analysis "
               "of the compiled step %.4g"
               % (record["model_flops_per_step"], xla_flops))

    return record
