"""Executor: jit-compiled forward/backward over a Symbol graph.

TPU-native equivalent of the reference's GraphExecutor
(src/executor/graph_executor.cc:507 Init → memory planning → cached engine
ops) and the Python wrapper (python/mxnet/executor.py).  The entire
reference pipeline — gradient-graph construction (InitFullGraph :253),
memory planning (PlanMemory :868), op bulking (InitOpSegs :1302) — is
replaced by ONE idea: the symbol graph is interpreted as a pure jax function
and jit-compiled; XLA performs buffer assignment, fusion and scheduling.

The fused forward+backward program is differentiated with ``jax.vjp`` (the
XLA-native Gradient pass).  ``forward`` is *lazy*: outputs materialize on
first read, and a training step that calls forward→backward executes as a
single XLA program — the analog (and superset) of the reference's bulked
segment execution.
"""
from __future__ import annotations

import functools
from contextlib import contextmanager, nullcontext as _nullcontext
from typing import Dict, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from .base import MXNetError, env as _base_env
from .context import Context, current_context
from . import random as _rnd
from . import tracing as _tracing
from .ndarray import NDArray
from .ndarray.ndarray import zeros as nd_zeros
from .ops import registry as _reg
from .symbol.symbol import Symbol, node_num_outputs, _topo_sort


# Ops kept in float32 under mixed precision: normalization statistics and
# loss heads.  This is the TPU-native analog of the reference's fp16
# training recipe (example train scripts cast data to fp16 but cuDNN
# BatchNorm keeps fp32 statistics, and SoftmaxOutput runs on an fp32 cast).
AMP_FP32_OPS = frozenset({
    "InstanceNorm", "L2Normalization", "LRN", "norm",
    "SoftmaxOutput", "SoftmaxActivation", "softmax", "log_softmax",
    "log_softmax_mx", "LinearRegressionOutput", "LogisticRegressionOutput",
    "MAERegressionOutput", "MakeLoss", "SVMOutput", "CTCLoss",
    "softmax_cross_entropy", "_contrib_ExpectedExitLoss",
})

# Ops with a SPLIT precision contract: the listed input indices are cast to
# the compute dtype (the big activation tensors), everything else keeps its
# master precision (small per-channel params / statistics).  BatchNorm
# accumulates its stats in fp32 internally (ops/nn.py _batch_norm), so the
# (N,C,H,W) activation never round-trips HBM in fp32 — the TPU equivalent of
# the reference's fused cuDNN BN (cudnn_batch_norm-inl.h keeps fp32 stats
# over an fp16 data path).
AMP_SPLIT_OPS = {"BatchNorm": (0,), "RMSNorm": (0,)}

# Inputs that are not activations, by the role of the argument: an op's
# label or index input holds whole numbers (MXNet iterators give labels as
# float32), and bfloat16 keeps eight bits of them: id 49151 would become
# 49152 and 257 would become 256.  They are never cast, and neither is a
# value on its way to one through ops that only move elements around
# (AMP_MOVES_ONLY).  Keyed by the op's registered name.
AMP_INDEX_INPUTS = {
    "Embedding": (0,), "take": (1,), "batch_take": (1,), "one_hot": (0,),
    "gather_nd": (1,), "scatter_nd": (1,), "_scatter_set_nd": (2,),
    "_contrib_ChunkedLMLoss": (3,), "SoftmaxOutput": (1,),
    "softmax_cross_entropy": (1,),
}
AMP_MOVES_ONLY = frozenset({
    "Reshape", "Flatten", "transpose", "expand_dims", "slice_axis", "slice",
    "squeeze", "SwapAxis", "tile", "repeat", "Concat", "BlockGrad", "_copy",
    "broadcast_to", "broadcast_axis",
})


def _amp_uncast_inputs(nodes):
    """{(id(node), input position)} of the inputs mixed precision leaves in
    their own dtype: index and label inputs with what only feeds them
    (above), and the inputs of every node no Variable reaches: a table of
    constants (rotary angles, a position range, a mask) is computed in the
    dtype it was declared in and rounded once, where an activation meets
    it."""
    consumers = {}
    for n in nodes:
        for pos, (src, i) in enumerate(n.inputs):
            consumers.setdefault((id(src), i), []).append((id(n), pos))
    uncast = set()
    for n in reversed(nodes):
        if n.is_variable:
            continue
        name = _reg.get(n.op).name
        roles = AMP_INDEX_INPUTS.get(name, ())
        if name in AMP_MOVES_ONLY:
            read = [c for i in range(node_num_outputs(n))
                    for c in consumers.get((id(n), i), ())]
            if read and all(c in uncast for c in read):
                roles = range(len(n.inputs))
        uncast.update((id(n), pos) for pos in roles)
    constant = set()
    for n in nodes:
        if n.is_variable or _reg.get(n.op).needs_rng:
            continue
        if all(id(src) in constant for src, _ in n.inputs):
            constant.add(id(n))
            uncast.update((id(n), pos) for pos in range(len(n.inputs)))
    return frozenset(uncast)


def body_interpreter(subgraph, compute_dtype, node):
    """The interpreter of the sub-Symbol a loop node holds
    (ops/control_flow.py), or an MXNetError naming what a loop cannot
    carry: auxiliary states and randomness."""
    run, _, aux_names = build_interpreter(subgraph, compute_dtype)
    if aux_names:
        owners = sorted({f"{n.name} ({n.op})" for n in subgraph.nodes()
                         if not n.is_variable and any(
                             src.name in aux_names for src, _ in n.inputs)})
        raise MXNetError(
            f"loop node {node!r}: its body holds auxiliary states "
            f"{aux_names} of {owners}; a state a node updates in place has "
            f"no meaning across the iterations of one step, so a loop's "
            f"body may not have one (ops/control_flow.py)")
    if run.needs_rng:
        rng = sorted(f"{n.name} ({n.op})" for n in subgraph.nodes()
                     if not n.is_variable and _reg.get(n.op).needs_rng)
        raise MXNetError(
            f"loop node {node!r}: its body holds the RNG ops {rng}; every "
            f"iteration would draw the same numbers from the node's one "
            f"key, so a loop's body may not have one (ops/control_flow.py)")
    return run


def maybe_mirror(run):
    """Wrap an interpreter in jax.checkpoint when
    MXNET_BACKWARD_DO_MIRROR is set (reference: graph_executor.cc:281
    mirror-recompute): activations are rematerialized in backward, trading
    FLOPs for HBM.  Returns a function with the same
    (args, aux, key, is_train) signature; remat always traces train mode
    (the only mode with a backward).

    MXNET_REMAT_POLICY selects what backward may keep:
      * "full" (default) — keep nothing: recompute the whole forward
        (~33% extra FLOPs, maximum memory relief).
      * "save_matmuls" — keep conv/FC outputs (tagged with
        checkpoint_name in ops/nn.py) and recompute only the cheap
        elementwise/normalization chains between them: most of the
        memory relief for a few percent of FLOPs — the right trade for
        batch-512 ResNet on a 16 GB chip.
    """
    from .base import env as _env
    if not _env("MXNET_BACKWARD_DO_MIRROR", False):
        return run
    policy_name = _env("MXNET_REMAT_POLICY", "full")
    kw = {}
    if policy_name == "save_matmuls":
        kw["policy"] = jax.checkpoint_policies.save_only_these_names(
            "conv_out", "matmul_out")
    elif policy_name != "full":
        raise MXNetError(
            f"MXNET_REMAT_POLICY={policy_name!r}: expected 'full' or "
            f"'save_matmuls'")
    remat = jax.checkpoint(lambda av, aux, k: run(av, aux, k, True), **kw)
    return lambda av, aux, k, _t: remat(av, aux, k)


def build_interpreter(sym: Symbol, compute_dtype=None):
    """Build ``run(arg_vals, aux_vals, key, is_train) -> (outs, new_aux)``.

    The returned function is pure — jit/vjp/vmap-compatible.  RNG ops get
    per-node subkeys split from ``key`` (replacement for the reference's
    per-device PRNG resource, src/resource.cc kRandom).

    ``compute_dtype`` (e.g. ``jnp.bfloat16``) enables mixed precision: all
    floating-point op inputs are cast to it except ops in ``AMP_FP32_OPS``,
    which run in float32.  Master parameters stay float32 in HBM; the casts
    are inserted per-use and fused by XLA into the surrounding ops, so the
    MXU sees bf16 operands while optimizer state and normalization
    statistics keep full precision.
    """
    nodes = _topo_sort(sym.heads)
    arg_names = sym.list_arguments()
    aux_names = sym.list_auxiliary_states()
    arg_pos = {n: i for i, n in enumerate(arg_names)}
    aux_pos = {n: i for i, n in enumerate(aux_names)}
    heads = sym.heads
    rng_ids = [id(n) for n in nodes
               if not n.is_variable and _reg.get(n.op).needs_rng]
    rng_index = {nid: i for i, nid in enumerate(rng_ids)}
    cd = jnp.dtype(compute_dtype) if compute_dtype is not None else None
    uncast = _amp_uncast_inputs(nodes) if cd is not None else frozenset()
    # a loop node's body: its own interpreter, at the same compute dtype,
    # built (and refused, where it cannot be a loop's) as the graph is bound
    bodies = {}
    for n in nodes:
        sub = None if n.is_variable else _reg.get(n.op).subgraph_attr
        if sub:
            bodies[id(n)] = body_interpreter(n.attrs[sub], compute_dtype,
                                             n.name)

    def _amp_cast(ins, n):
        if id(n) in bodies:
            # the body's nodes cast what they read, use by use: a weight
            # enters the loop in its master precision, and its gradient is
            # summed over the iterations in it
            return ins
        split = AMP_SPLIT_OPS.get(n.op)
        want = jnp.float32 if n.op in AMP_FP32_OPS else cd
        return [v.astype(want)
                if ((split is None or i in split)
                    and (id(n), i) not in uncast and hasattr(v, "dtype")
                    and jnp.issubdtype(v.dtype, jnp.floating)
                    and v.dtype != want) else v
                for i, v in enumerate(ins)]

    def run(arg_vals, aux_vals, key, is_train, _collect=None):
        env = {}
        new_aux = list(aux_vals)
        if rng_ids:
            keys = jax.random.split(key, len(rng_ids))
        for n in nodes:
            if n.is_variable:
                if n.name in arg_pos:
                    env[(id(n), 0)] = arg_vals[arg_pos[n.name]]
                else:
                    env[(id(n), 0)] = aux_vals[aux_pos[n.name]]
                continue
            opdef = _reg.get(n.op)
            _reg.record_execution(n.op)
            ins = [env[(id(src), i)] for src, i in n.inputs]
            kwargs = dict(n.attrs)
            kwargs.pop("name", None)
            if opdef.takes_is_train:
                kwargs["is_train"] = is_train
            if id(n) in bodies:
                kwargs["_interpret"] = bodies[id(n)]
            # a location, not an operation: the node's name reaches each
            # device event's op_name as jvp(<node>) / transpose(jvp(<node>))
            with jax.named_scope(n.name):
                if cd is not None:
                    ins = _amp_cast(ins, n)
                if opdef.needs_rng:
                    outs = opdef.fn(keys[rng_index[id(n)]], *ins, **kwargs)
                else:
                    outs = opdef.fn(*ins, **kwargs)
            if not isinstance(outs, (tuple, list)):
                outs = (outs,)
            if opdef.num_aux and opdef.takes_is_train and is_train:
                updates = outs[-opdef.num_aux:]
                outs = outs[:-opdef.num_aux]
                aux_inputs = n.inputs[-opdef.num_aux:]
                for (src, _), u in zip(aux_inputs, updates):
                    if src.is_variable and src.name in aux_pos:
                        new_aux[aux_pos[src.name]] = u
            for i, o in enumerate(outs):
                env[(id(n), i)] = o
            if _collect is not None:
                _collect(n, outs[:node_num_outputs(n)])
        out_vals = tuple(env[(id(h), i)] for h, i in heads)
        return out_vals, tuple(new_aux)

    # whether the program actually consumes the PRNG key: dispatch uses
    # this to skip the per-step eager fold_in (a device op — through a
    # remote-attached chip that is a per-step round-trip for nothing)
    run.needs_rng = bool(rng_ids)
    return run, arg_names, aux_names


def build_multi_step(step_body, donate=True):
    """Compile a single fused training step into a K-step ``lax.scan``
    program — the multi-step driver shared by ``Module.run_steps`` and
    ``gluon.Trainer.step_k`` (whole-program TPU execution à la Fischer &
    Saba, arXiv:1810.09868: the host leaves the training loop entirely,
    amortizing the per-dispatch host cost over K steps).

    ``step_body(carry, x, const) -> (carry, y)`` is the pure single-step
    function: ``carry`` holds everything that flows step-to-step (params,
    aux/BN statistics, optimizer state — and, for callers that fold a
    device-resident metric, the metric's ``(sum, count)`` state, so K
    steps of metric accumulation ride the same one dispatch with zero
    readbacks; see metric.EvalMetric.device_update), ``x`` holds the
    per-step inputs scanned over their leading K axis (data, labels,
    per-step lr/wd/t, RNG keys), and ``const`` holds step-invariant
    inputs (fixed params, state inputs).  Returns a jitted ``fn(carry, xs, const) -> (carry,
    ys)``; K is the leading dim of ``xs``, so the jit cache is keyed by
    (K, shapes, carry structure) for free.  With ``donate`` the carry
    buffers (params/aux/optimizer state) are donated — XLA updates them
    in place in HBM across all K steps, exactly like the single fused
    step does for one.
    """
    # a stable name: the compiled module is jit_mx_run_steps
    def mx_run_steps(carry, xs, const):
        def body(c, x):
            return step_body(c, x, const)
        return jax.lax.scan(body, carry, xs)

    return jax.jit(mx_run_steps, donate_argnums=(0,) if donate else ())


def fused_dist_knobs(k):
    """``(chunk_size, staleness)`` for the fused-dist drivers — one
    reader for the knob pair so Module and Trainer can never parse the
    envs differently.  Note a ``k`` that is not a multiple of the chunk
    produces one tail chunk with its own leading dimension, which
    compiles as its own XLA program (the jit cache keys on shape):
    size K-step calls as multiples of MXNET_KVSTORE_FUSED_CHUNK to pay
    exactly one compile."""
    from .base import env
    chunk = max(1, min(k, int(env("MXNET_KVSTORE_FUSED_CHUNK", 8))))
    staleness = max(0, int(env("MXNET_KVSTORE_FUSED_STALENESS", 1)))
    return chunk, staleness


def drive_chunked_dist(num_steps, chunk_size, staleness, dispatch_chunk,
                       ship_chunk):
    """The chunked-scan dist_async driver: overlap the kvstore wire
    behind the scanned compute (the MXNet dependency-engine thesis —
    overlap communication with computation, arXiv:1512.01274 — rebuilt
    on XLA async dispatch; PipeDream-shaped pipelining, arXiv:1806.03377).

    ``num_steps`` splits into ceil(num_steps/chunk_size) chunks.  Per
    chunk ``j``:

    1. if chunk ``j-1-staleness`` has a wire round in flight, BLOCK on
       it and hand its pulled weights to ``dispatch_chunk`` for
       adoption — with staleness 0 this is a barrier'd chunk boundary
       (the wire fully exposed, every chunk starts from the server's
       post-previous-chunk weights); with staleness S>=1 the round has
       had S chunks of compute to resolve, so the block is only the
       un-overlapped residue (profiler.record_wire_wait counts it),
    2. ``dispatch_chunk(j, lo, hi, adopted) -> grads_host`` dispatches
       the scanned compute for steps [lo, hi) and reads the chunk's
       per-step gradients back (blocking on the chunk's COMPUTE, never
       on the wire),
    3. ``ship_chunk(j, grads_host) -> handle`` pushes the gradients
       (fire-and-forget through the pipelined window) and enqueues the
       next pull; ``handle.wait() -> {name: host array}`` resolves it.

    The lag is EXACT, not just bounded: chunk ``j`` always adopts the
    round issued after chunk ``j-1-staleness``'s pushes, even when a
    fresher round happens to have resolved — determinism is what makes
    the staleness-1 analytic golden simulable and therefore testable
    (tests/test_fused_dist.py).

    Fault composition: ``handle.wait()`` owns its own recovery — under
    MXNET_KVSTORE_ELASTIC an in-flight round whose server died mid-pull
    repairs the roster and REPLANS its unserved stripes from inside the
    wait (kvstore._PullHandle._replan), so this driver needs no
    elastic-specific control flow and elastic jobs run chunked instead
    of falling back to the eager per-step loop.

    Returns the FINAL round's pulled values — the server-authoritative
    weights at the sync point — or None when num_steps == 0."""
    import math

    from . import health as _health
    n_chunks = math.ceil(num_steps / chunk_size)
    pending = {}
    for j in range(n_chunks):
        # liveness breadcrumb per chunk: the health snapshot's
        # progress_age_s separates a stalled driver from a slow one
        _health.note_progress("fused.chunk")
        # one span per chunk: its children separate the scanned COMPUTE
        # from the exposed wire (the _PullHandle's kv.wire_wait span
        # lands under fused.adopt_wait, its kv.wire_round sibling shows
        # the full overlapped round) — the overlap the driver buys
        # becomes VISIBLE on the merged timeline, not just a percentage
        # (docs/OBSERVABILITY.md)
        with _tracing.span("fused.chunk", cat="fused", args={"chunk": j}):
            due = j - 1 - staleness
            if due in pending:
                with _tracing.span("fused.adopt_wait", cat="fused",
                              args={"due": due}):
                    adopted = pending.pop(due).wait()
            else:
                adopted = None
            lo = j * chunk_size
            hi = min(num_steps, lo + chunk_size)
            with _tracing.span("fused.chunk_compute", cat="fused",
                          args={"lo": lo, "hi": hi}):
                grads = dispatch_chunk(j, lo, hi, adopted)
            pending[j] = ship_chunk(j, grads)
    final = None
    for j in sorted(pending):
        with _tracing.span("fused.drain_wait", cat="fused", args={"chunk": j}):
            final = pending[j].wait()
    return final


def scan_cache_lookup(cache, key):
    """Bounded-LRU lookup for compiled multi-step programs (the one
    cache policy shared by Module.run_steps and Trainer.step_k): a hit
    is re-inserted so eviction pops the least-recently-used entry —
    plain FIFO would evict the hot long-lived program, which is always
    the FIRST one inserted."""
    entry = cache.get(key)
    if entry is not None:
        cache[key] = cache.pop(key)
    return entry


def scan_cache_store(cache, key, entry):
    """Insert + bound (``MXNET_SCAN_CACHE_MAX``, default 32): a metric
    with non-primitive hyperparameters keys by object identity
    (metric._device_sig), so recreating one per epoch would otherwise
    retain a compiled scan program per instance for the process
    lifetime."""
    from .base import env
    cache[key] = entry
    while len(cache) > int(env("MXNET_SCAN_CACHE_MAX", 32)):
        cache.pop(next(iter(cache)))
    return entry


# device buffers of the last schedule per optimizer (weak-keyed so a
# dropped optimizer frees them): constant-lr training re-sends NOTHING
# per dispatch — the K-step analog of Module._lrwd_cache's discipline
# ("per-step host→device scalar transfers would dominate step latency
# on a remote-attached chip")
_SCHED_DEV_CACHE: "weakref.WeakKeyDictionary" = None  # lazy-inited


def precompute_step_schedules(opt, keys, k):
    """Advance an optimizer's HOST-side schedule state by K steps and
    return the per-step hyperparameters as scan inputs — the shared
    schedule leg of the multi-step driver (one implementation for
    Module.run_steps and Trainer.step_k, so the two can never
    de-synchronize).

    For each of the K steps, ``opt._update_count`` advances for every
    key (exactly as K eager updates would), then lr/wd are sampled —
    cheap host float math, no device sync.  Returns ``(lrs, wds, ts)``,
    each a tuple over ``keys`` of ``(k,)`` device arrays (``ts`` is the
    per-key update count for needs_t optimizers, zeros otherwise).
    Device buffers are cached per optimizer while the host values are
    unchanged, so a constant schedule costs zero transfers per call."""
    global _SCHED_DEV_CACHE
    needs_t = getattr(opt, "needs_t", False)
    n = len(keys)
    lr = np.empty((k, n), np.float32)
    wd = np.empty((k, n), np.float32)
    ts = np.zeros((k, n), np.int32)
    for j in range(k):
        for col, key in enumerate(keys):
            opt._update_count(key)
            if needs_t:
                ts[j, col] = opt._index_update_count[key]
        lr[j] = [opt._get_lr(key) for key in keys]
        wd[j] = [opt._get_wd(key) for key in keys]

    if _SCHED_DEV_CACHE is None:
        import weakref
        _SCHED_DEV_CACHE = weakref.WeakKeyDictionary()
    hkey = (tuple(keys), k, lr.tobytes(), wd.tobytes(), ts.tobytes())
    cached = _SCHED_DEV_CACHE.get(opt)
    if cached is not None and cached[0] == hkey:
        return cached[1]

    def cols(m):
        return tuple(jnp.asarray(m[:, c]) for c in range(n))

    result = (cols(lr), cols(wd), cols(ts))
    _SCHED_DEV_CACHE[opt] = (hkey, result)
    return result


@contextmanager
def schedule_rollback(opt):
    """Undo an optimizer's host-side schedule advance if the guarded
    block fails.  precompute_step_schedules moves update counts (and any
    stateful lr scheduler) K steps ahead BEFORE the scan dispatch runs;
    if the dispatch then raises (compile OOM, backend loss), the
    schedules would be K steps ahead of the actual parameter state — and
    drift further on every retry.  Wrap precompute+dispatch in this to
    keep host schedule state transactional with the device step."""
    counts = dict(opt._index_update_count)
    num_update = opt.num_update
    sched = opt.lr_scheduler
    sched_state = dict(vars(sched)) if sched is not None else None
    try:
        yield
    except BaseException:
        opt._index_update_count = counts
        opt.num_update = num_update
        if sched is not None:
            vars(sched).clear()
            vars(sched).update(sched_state)
        raise


def make_lazy_outputs(avals, make_thunk):
    """Allocate lazy output NDArrays fulfilled by ONE shared thunk.

    ``make_thunk(outs)`` receives the fresh (uninitialized) arrays and
    returns the thunk that will ``_set_data`` all of them on first read.
    Single home for the NDArray internal-construction sequence shared by
    Executor.forward and Module.run_steps' last-step outputs."""
    from .ndarray import NDArray as _ND
    outs = [_ND.__new__(_ND) for _ in avals]
    thunk = make_thunk(outs)
    for oa, av in zip(outs, avals):
        oa._handle = object()
        oa._ctx = None
        oa._grad = None
        oa._grad_req = "null"
        oa._payload = None
        oa._set_lazy(thunk, aval=av)
    return outs


def poison_stale(arr, what):
    """Permanently mark a lazy NDArray as unavailable with a clear error.

    Used after a donated fused training step consumes the buffers a pending
    thunk would need.  The poison thunk re-arms itself before raising, so
    every read fails loudly instead of only the first (NDArray._data pops
    the thunk before invoking it)."""
    def thunk():
        arr._set_lazy(thunk)  # re-arm: stay poisoned across reads
        raise MXNetError(
            f"{what} buffers were fused into the donated training step and "
            "are not materialized after update(); read them before "
            "update(), or set MXNET_FUSED_DONATE=0 / "
            "MXNET_EXEC_BULK_EXEC_TRAIN=0 to keep them live")
    arr._set_lazy(thunk)


class Executor:
    """reference: include/mxnet/executor.h:52; python/mxnet/executor.py."""

    def __init__(self, symbol: Symbol, ctx=None, args=None, args_grad=None,
                 grad_req="write", aux_states=None, group2ctx=None,
                 shared_exec=None, compute_dtype=None):
        self._symbol = symbol
        self._ctx = ctx or current_context()
        self._compute_dtype = compute_dtype
        run, arg_names, aux_names = build_interpreter(symbol, compute_dtype)
        self._run = run
        self._arg_names = arg_names
        self._aux_names = aux_names
        self.arg_arrays = self._canon_arrays(args, arg_names, "args")
        self.aux_arrays = self._canon_arrays(aux_states, aux_names,
                                             "aux_states", allow_empty=True)
        self.grad_req = self._canon_grad_req(grad_req)
        self.grad_arrays = self._canon_grads(args_grad)
        self._monitor_callback = None
        self._monitor_all = False
        self._mesh = None
        # name -> where the program reads that value.  Until a mesh is
        # set (set_shardings) that is the bound context's device: host-fed
        # batches (NDArrayIter arrays sit on cpu(0)) and loaded
        # checkpoints are moved TO the chip instead of dragging the whole
        # step onto the host backend
        from jax.sharding import SingleDeviceSharding
        on_ctx = SingleDeviceSharding(self._ctx.jax_device())
        self._arg_shardings = dict.fromkeys(arg_names, on_ctx)
        self._aux_shardings = dict.fromkeys(aux_names, on_ctx)

        self._out_arrays: Optional[List[NDArray]] = None
        self._snapshot = None
        self._is_train = False
        self._last_key = None
        # output handles issued by forward() whose thunks still reference a
        # live snapshot — must be poisoned if a donated step consumes the
        # snapshot's buffers.  Weak refs: the executor must not keep
        # dropped outputs (and their snapshots) alive.
        self._issued_outs: List = []

        # MXNET_EXEC_BULK_EXEC_INFERENCE=0 restores per-op dispatch for
        # forward-only graphs (the reference's bulk-exec toggle): the
        # interpreter runs un-jitted, so every op is its own XLA call —
        # slower, but each intermediate is individually inspectable.
        if _base_env("MXNET_EXEC_BULK_EXEC_INFERENCE", True):
            self._jit_fwd = jax.jit(
                lambda a, x, k, t: run(a, x, k, t), static_argnums=(3,))
        else:
            self._jit_fwd = lambda a, x, k, t: run(a, x, k, t)
        self._jit_fwd_bwd = jax.jit(self._fused_fwd_bwd)

    # ------------------------------------------------------------------
    def _canon_arrays(self, arrays, names, what, allow_empty=False):
        if arrays is None:
            if allow_empty and not names:
                return []
            raise MXNetError(f"bind: {what} must be provided (or use "
                             f"simple_bind)")
        if isinstance(arrays, dict):
            missing = [n for n in names if n not in arrays]
            if missing:
                raise MXNetError(f"bind: missing {what}: {missing}")
            return [arrays[n] for n in names]
        arrays = list(arrays)
        if len(arrays) != len(names):
            raise MXNetError(f"bind: expected {len(names)} {what}, "
                             f"got {len(arrays)}")
        return arrays

    def _canon_grad_req(self, grad_req):
        names = self._arg_names
        if isinstance(grad_req, str):
            return {n: grad_req for n in names}
        if isinstance(grad_req, (list, tuple)):
            return dict(zip(names, grad_req))
        if isinstance(grad_req, dict):
            return {n: grad_req.get(n, "null") for n in names}
        raise TypeError(type(grad_req))

    def _canon_grads(self, args_grad):
        names = self._arg_names
        if args_grad is None:
            return [None] * len(names)
        if isinstance(args_grad, dict):
            return [args_grad.get(n) for n in names]
        args_grad = list(args_grad)
        if len(args_grad) != len(names):
            raise MXNetError("bind: args_grad length mismatch")
        return args_grad

    # -- dict views (reference: executor.py arg_dict etc.) --------------
    @property
    def arg_dict(self) -> Dict[str, NDArray]:
        return dict(zip(self._arg_names, self.arg_arrays))

    @property
    def grad_dict(self) -> Dict[str, NDArray]:
        return dict(zip(self._arg_names, self.grad_arrays))

    @property
    def aux_dict(self) -> Dict[str, NDArray]:
        return dict(zip(self._aux_names, self.aux_arrays))

    @property
    def output_dict(self) -> Dict[str, NDArray]:
        return dict(zip(self._symbol.list_outputs(), self.outputs))

    @property
    def symbol(self):
        return self._symbol

    # ------------------------------------------------------------------
    @classmethod
    def simple_bind(cls, symbol: Symbol, ctx=None, grad_req="write",
                    type_dict=None, shared_exec=None, shapes=None,
                    compute_dtype=None):
        """reference: MXExecutorSimpleBind (c_api_executor.cc:219) —
        infer all shapes from the provided input shapes, allocate arg/grad/aux
        arrays, return a bound executor."""
        shapes = shapes or {}
        arg_shapes, out_shapes, aux_shapes = symbol.infer_shape(**shapes)
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        type_dict = type_dict or {}
        args = [nd_zeros(s, dtype=type_dict.get(n, "float32"))
                for n, s in zip(arg_names, arg_shapes)]
        aux = [nd_zeros(s, dtype=type_dict.get(n, "float32"))
               for n, s in zip(aux_names, aux_shapes)]
        ex = cls(symbol, ctx, args=args, grad_req=grad_req, aux_states=aux,
                 compute_dtype=compute_dtype)
        ex.grad_arrays = [
            nd_zeros(s, dtype=type_dict.get(n, "float32"))
            if ex.grad_req[n] != "null" else None
            for n, s in zip(arg_names, arg_shapes)]
        return ex

    def _next_key(self):
        return _rnd.key_for(self._run)

    # ------------------------------------------------------------------
    def forward(self, is_train=False, **kwargs):
        """Lazy forward: argument *values* are captured now; outputs
        materialize on first read — and if ``backward`` runs first,
        forward+backward fuse into ONE XLA program (replacing the
        reference's op bulking, graph_executor.cc:1302)."""
        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError(f"forward: unknown argument {k!r}")
            pos = self._arg_names.index(k)
            if isinstance(v, NDArray):
                self.arg_arrays[pos]._set_data(v._data)
            else:
                self.arg_arrays[pos]._set_data(jnp.asarray(v))
        self._is_train = is_train
        self._last_key = self._next_key()
        # snapshot the input values: later arg mutation (or a second
        # forward) must not change what THIS forward's outputs resolve to
        with _tracing.span("mx.executor.place", "executor"):
            snapshot = (self._arg_vals(), self._aux_vals(), self._last_key,
                        is_train)
        self._snapshot = snapshot
        out_avals = self._out_aval_list(is_train)
        out_arrays = make_lazy_outputs(
            out_avals,
            lambda outs: lambda: self._materialize(snapshot, outs))
        self._out_arrays = out_arrays
        import weakref
        self._issued_outs = [r for r in self._issued_outs
                             if (a := r()) is not None
                             and a._thunk is not None]
        self._issued_outs.extend(weakref.ref(a) for a in out_arrays)
        if self._monitor_callback is not None:
            self._materialize(snapshot, out_arrays, monitor=True)
        return self._out_arrays

    @property
    def outputs(self) -> List[NDArray]:
        if self._out_arrays is None:
            self.forward(self._is_train)
        return self._out_arrays

    def set_shardings(self, mesh, arg_pspecs, aux_pspecs=None):
        """Annotate arguments with mesh shardings (mxnet_tpu.parallel).

        Every subsequent forward/backward/fused step runs as ONE SPMD
        program over ``mesh`` — GSPMD inserts the gradient psum that the
        reference implemented as kvstore push/pull (comm.h:462) and the
        activation collectives that `group2ctx` placement implemented as
        _CrossDeviceCopy nodes (graph_executor.cc:403)."""
        from jax.sharding import NamedSharding, PartitionSpec
        self._mesh = mesh
        self._arg_shardings = {
            n: NamedSharding(mesh, arg_pspecs.get(n, PartitionSpec()))
            for n in self._arg_names}
        self._aux_shardings = {
            n: NamedSharding(mesh, (aux_pspecs or {}).get(n, PartitionSpec()))
            for n in self._aux_names}

    def _sharded(self, val, sh):
        if sh is None or isinstance(val, jax.core.Tracer):
            return val
        cur = getattr(val, "sharding", None)
        if cur is not None:
            # is_equivalent_to, not ==: XLA normalizes trailing-None
            # specs (P('tp', None) comes back as P('tp')), and a false
            # mismatch here would force the host round-trip below, which
            # cannot work for process-spanning arrays
            try:
                same = cur == sh or cur.is_equivalent_to(sh, np.ndim(val))
            except Exception:  # noqa: BLE001 — foreign sharding types
                same = False
            # an uncommitted value (fresh jnp output) on the right device
            # is still committed below — no copy — because jit keys its
            # programs on it: the same step fed resident and host-fed
            # batches would otherwise compile twice
            if same and val.committed:
                return val
        if sh.is_fully_addressable:
            with _tracing.span("mx.executor.device_put", "executor"):
                return jax.device_put(val, sh)
        # mesh spans processes (multi-host SPMD): device_put cannot target
        # non-addressable shardings.  Every process feeds the same global
        # host value (the SPMD data contract — dist scripts use identical
        # seeds/batches), so build the global array from the shards THIS
        # process addresses.
        with _tracing.span("mx.executor.device_put", "executor"):
            # analysis: allow(host-sync): multi-host staging — val is the HOST feed value every process supplies (SPMD data contract); the copy builds the global array, it does not read a device buffer back
            arr = np.asarray(val)
            return jax.make_array_from_callback(
                arr.shape, sh, lambda idx: arr[idx])

    def _placed(self, arrays, names, shardings):
        """Values of ``arrays`` where the program reads them.  Off-mesh a
        value that had to move is written back (same version handle — the
        value did not change), so a checkpoint loaded on the host moves
        once, not once per step."""
        vals = []
        for n, a in zip(names, arrays):
            val = a._data
            placed = self._sharded(val, shardings[n])
            if placed is not val and self._mesh is None:
                a._payload = placed
            vals.append(placed)
        return tuple(vals)

    def _arg_vals(self):
        return self._placed(self.arg_arrays, self._arg_names,
                            self._arg_shardings)

    def _aux_vals(self):
        return self._placed(self.aux_arrays, self._aux_names,
                            self._aux_shardings)

    def _placed_like(self, name, val):
        """``val`` where the program keeps argument ``name`` — for values
        that meet the program's outputs in a later jit (a batch's labels
        in a device-resident metric) or stack its inputs (run_steps)."""
        return self._sharded(val, self._arg_shardings[name])

    def _out_aval_list(self, is_train):
        cache = getattr(self, "_aval_cache", None)
        if cache is None:
            cache = self._aval_cache = {}
        sig = (tuple((a.shape, str(a.dtype)) for a in self.arg_arrays),
               is_train)
        if sig not in cache:
            dummy = jax.random.PRNGKey(0)
            cache[sig] = list(jax.eval_shape(
                lambda a, x, k: self._run(a, x, k, is_train),
                self._arg_vals(), self._aux_vals(), dummy)[0])
        return cache[sig]

    def _materialize(self, snapshot, out_arrays, monitor=False):
        arg_vals, aux_vals, key, is_train = snapshot
        if monitor:
            collected = []
            with _tracing.span("mx.executor.forward.monitored", "executor"):
                outs, new_aux = self._run(
                    arg_vals, aux_vals, key, is_train,
                    _collect=lambda n, os: collected.append((n, os)))
            cb = self._monitor_callback
            for n, os in collected:
                for i, o in enumerate(os):
                    nm = (n.name + "_output" if len(os) == 1
                          else f"{n.name}_output{i}")
                    cb(nm, NDArray(o))
        else:
            from . import profiler as _prof
            _prof.record_dispatch("executor.forward")
            with _tracing.span("mx.executor.forward.call", "executor"):
                outs, new_aux = self._jit_fwd(arg_vals, aux_vals, key,
                                              is_train)
        for oa, v in zip(out_arrays, outs):
            oa._set_data(v)
        if is_train and snapshot is self._snapshot:
            for a, v in zip(self.aux_arrays, new_aux):
                a._set_data(v)

    # ------------------------------------------------------------------
    def _fused_fwd_bwd(self, arg_vals, aux_vals, key, cotangents,
                       grad_mask=None):
        """One XLA program: forward + vjp backward (+ aux updates)."""
        run = maybe_mirror(self._run)

        def f(av):
            outs, new_aux = run(av, aux_vals, key, True)
            diff = tuple(o for o in outs
                         if jnp.issubdtype(o.dtype, jnp.inexact))
            return diff, (outs, new_aux)

        diff, vjp_fn, (outs, new_aux) = jax.vjp(f, arg_vals, has_aux=True)
        grads = vjp_fn(tuple(cotangents))[0]
        need = tuple(g if self.grad_req[n] != "null" else None
                     for n, g in zip(self._arg_names, grads))
        return outs, new_aux, need

    def backward(self, out_grads=None, is_train=True):
        """Run the fused fwd+bwd program; write gradients per grad_req
        (reference: GraphExecutor::Backward, graph_executor.cc:93)."""
        if not any(r != "null" for r in self.grad_req.values()):
            raise MXNetError("backward: no gradients required "
                             "(all grad_req are null)")
        snapshot = getattr(self, "_snapshot", None)
        if snapshot is not None:
            arg_vals, aux_vals, key, _ = snapshot
        else:
            arg_vals, aux_vals = self._arg_vals(), self._aux_vals()
            key = self._last_key if self._last_key is not None \
                else self._next_key()
        out_avals = self._out_aval_list(True)
        diff_avals = [o for o in out_avals
                      if jnp.issubdtype(o.dtype, jnp.inexact)]
        if out_grads is None:
            cts = tuple(jnp.ones(o.shape, o.dtype) for o in diff_avals)
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            vals = [g._data if isinstance(g, NDArray) else jnp.asarray(g)
                    for g in out_grads]
            diff_idx = [i for i, o in enumerate(out_avals)
                        if jnp.issubdtype(o.dtype, jnp.inexact)]
            cts = tuple(vals[i] for i in diff_idx)
        from . import profiler as _prof
        _prof.record_dispatch("executor.fwd_bwd")
        with _tracing.span("mx.executor.fwd_bwd.call", "executor"):
            outs, new_aux, grads = self._jit_fwd_bwd(arg_vals, aux_vals,
                                                     key, cts)
        if self._out_arrays is None:
            self._out_arrays = [NDArray(o) for o in outs]
        else:
            for oa, v in zip(self._out_arrays, outs):
                oa._set_data(v)
        for a, v in zip(self.aux_arrays, new_aux):
            a._set_data(v)
        for name, garr, g in zip(self._arg_names, self.grad_arrays, grads):
            req = self.grad_req[name]
            if req == "null" or g is None:
                continue
            if garr is None:
                continue
            if req == "add":
                garr._set_data(garr._data + g)
            else:
                garr._set_data(g)

    # ------------------------------------------------------------------
    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        """reference: executor.py copy_params_from."""
        for name, arr in arg_params.items():
            if name in self.arg_dict:
                self.arg_dict[name]._set_data(
                    arr._data if isinstance(arr, NDArray)
                    else jnp.asarray(arr))
            elif not allow_extra_params:
                raise MXNetError(f"unknown argument {name!r}")
        if aux_params:
            for name, arr in aux_params.items():
                if name in self.aux_dict:
                    self.aux_dict[name]._set_data(
                        arr._data if isinstance(arr, NDArray)
                        else jnp.asarray(arr))
                elif not allow_extra_params:
                    raise MXNetError(f"unknown aux state {name!r}")

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Re-bind with new input shapes (jit recompiles per shape —
        reference: executor.py reshape)."""
        shapes = {n: tuple(a.shape) for n, a in self.arg_dict.items()}
        shapes.update({k: tuple(v) for k, v in kwargs.items()})
        new = Executor.simple_bind(self._symbol, self._ctx,
                                   grad_req=self.grad_req, shapes=shapes,
                                   compute_dtype=self._compute_dtype)
        for n, a in self.arg_dict.items():
            if n not in kwargs and n in new.arg_dict:
                if new.arg_dict[n].shape == a.shape:
                    new.arg_dict[n]._set_data(a._data)
        for n, a in self.aux_dict.items():
            if n in new.aux_dict and new.aux_dict[n].shape == a.shape:
                new.aux_dict[n]._set_data(a._data)
        if self._mesh is not None:
            # carry the sharding annotations over (pspecs are rank-generic,
            # so the same specs apply to the reshaped arrays)
            new.set_shardings(
                self._mesh,
                {n: s.spec for n, s in self._arg_shardings.items()},
                {n: s.spec for n, s in self._aux_shardings.items()})
        return new

    def set_monitor_callback(self, callback, monitor_all=False):
        """reference: GraphExecutor::SetMonitorCallback
        (graph_executor.cc:120) — per-output stats for mx.mon.Monitor."""
        self._monitor_callback = callback
        self._monitor_all = monitor_all

    def debug_str(self):
        lines = [f"Symbol outputs: {self._symbol.list_outputs()}"]
        for n in self._symbol.nodes():
            if n.is_variable:
                lines.append(f"Variable:{n.name}")
            else:
                lines.append(f"Op:{n.op}, Name={n.name}")
        return "\n".join(lines)
