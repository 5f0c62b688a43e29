"""Gluon Trainer (reference: python/mxnet/gluon/trainer.py:27).

The reference's step() pushes gradients to a kvstore (allreduce across
devices) and pulls updated weights (trainer.py:148).  Here a parameter is
ONE logical array (possibly mesh-sharded), so `step` = run the optimizer
update on each param's gradient; cross-chip gradient reduction already
happened inside the backward program (GSPMD psum).  The kvstore argument is
accepted for API parity and drives update_on_kvstore semantics.
"""
from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import numpy as np

from ..base import MXNetError, env
from .. import optimizer as opt_mod
from .. import kvstore as kvs_mod
from .parameter import ParameterDict, Parameter


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None,
                 kvstore='device', compression_params=None,
                 mesh=None, zero_stage=None):
        if isinstance(params, (dict, ParameterDict)):
            params = list(params.values())
        if not isinstance(params, (list, tuple)):
            raise ValueError(
                "First argument must be a list or dict of Parameters, "
                f"got {type(params)}")
        self._params = []
        for param in params:
            if not isinstance(param, Parameter):
                raise ValueError(f"not a Parameter: {param!r}")
            param._trainer = self
            self._params.append(param)
        self._scale = 1.0
        # ZeRO-1 over the dp mesh axis — same contract as
        # Module(zero_stage=1) (docs/design/kvstore.md): optimizer states
        # (+ fp32 masters) live dp-sharded; GSPMD schedules the
        # reduce-scatter/all-gather inside the fused update.
        from .. import parallel as _par
        if mesh is None:
            mesh = _par.current_mesh()
        self._mesh = mesh
        explicit_zero = zero_stage is not None
        if zero_stage is None:
            zero_stage = env("MXNET_ZERO_STAGE", 0)
        if zero_stage not in (0, 1):
            raise ValueError("zero_stage must be 0 or 1")
        if explicit_zero and zero_stage >= 1 and mesh is None:
            raise MXNetError(
                "zero_stage=1 needs a device mesh with dp>1 — pass "
                "mesh= (parallel.make_mesh) or enter a use_mesh scope")
        self._zero_stage = int(zero_stage)
        self._zero_dp = (_par.mesh_shape(mesh).get("dp", 1)
                         if mesh is not None else 1)
        if not explicit_zero and zero_stage >= 1 and self._zero_dp <= 1:
            # mirror Module's warning: env-enabled ZeRO without a dp>1
            # mesh silently leaves optimizer states replicated
            logging.warning(
                "MXNET_ZERO_STAGE=1 ignored: no device mesh with dp>1 "
                "on this Trainer — optimizer states will be fully "
                "replicated")
        optimizer_params = dict(optimizer_params or {})
        self._init_optimizer(optimizer, optimizer_params)
        self._kv_type = kvstore
        self._kvstore = None
        self._kv_initialized = False
        # on-the-wire gradient compression (reference: Trainer
        # compression_params -> kvstore.set_gradient_compression).
        # Validated eagerly so a typo'd config fails at construction,
        # then shipped to the store at the lazy kvstore init.
        self._compression_params = None
        if compression_params:
            from ..compression import GradientCompression
            GradientCompression(compression_params)   # validate now
            self._compression_params = dict(compression_params)

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: param for i, param in enumerate(self._params)}
        if isinstance(optimizer, opt_mod.Optimizer):
            assert not optimizer_params, \
                "optimizer_params must be None if optimizer is an " \
                "Optimizer instance"
            self._optimizer = optimizer
        else:
            self._optimizer = opt_mod.create(optimizer, **optimizer_params)
        self._optimizer.param_dict = param_dict
        self._updaters = [opt_mod.get_updater(self._optimizer)]

    def _init_kvstore(self):
        """reference: trainer.py:102 — create the store lazily at first
        step; on TPU it is a facade over in-program collectives, EXCEPT
        dist_async where the kvstore path IS the mechanism: the optimizer
        runs server-side and step() becomes push-grad/pull-weight
        (reference trainer.py:148 update-on-kvstore)."""
        if self._kv_type:
            self._kvstore = kvs_mod.create(self._kv_type) \
                if isinstance(self._kv_type, str) else self._kv_type
        if self._kvstore is not None and self._compression_params:
            # before any push: the first gradient must already ride the
            # compressed wire (dist_async; a no-wire store records the
            # setting and compresses nothing)
            self._kvstore.set_gradient_compression(
                self._compression_params)
        self._update_on_kvstore = (
            self._kvstore is not None
            and getattr(self._kvstore, "type", "") == "dist_async")
        if self._update_on_kvstore:
            # the optimizer is NOT shipped here: the server applies
            # updates with the optimizer AS PICKLED, so sending it from a
            # pre-first-step path (save_states/load_states resume flow)
            # would freeze the DEFAULT rescale_grad=1.0 into the servers
            # and every update would land ~batch_size× too large.
            # _ensure_kv_optimizer ships it from the first step(), after
            # rescale_grad is set (ADVICE r5: trainer.py resume path).
            self._kv_opt_sent = False
            self._kv_deferred_states = None
            self._kv_replay_states = None
            self._kv_param_inited = set()
            # ALL materialized params — including frozen (grad_req
            # 'null') ones — sync to the server-authoritative value, so
            # every worker trains against the same frozen weights
            # (reference: _initialize_kvstore registers every param)
            inited = [p for p in self._params if p._data is not None]
            for param in inited:
                self._kvstore.init(param.name, param.data())
                self._kv_param_inited.add(param.name)
            # pull the AUTHORITATIVE weights back: the server kept the
            # first-arriving worker's init, and every worker must start
            # from that same point (reference: model.py:96
            # _initialize_kvstore pulls after init)
            if inited:
                self._kvstore.pull([p.name for p in inited],
                                   out=[p.data() for p in inited])
        self._kv_initialized = True

    def _ensure_kv_optimizer(self):
        """Ship the optimizer to the dist_async servers once, from the
        first step() — AFTER rescale_grad is set — then replay any
        buffered load_states blob.  A pre-first-step save_states/
        load_states no longer bakes rescale_grad=1.0 into the servers'
        pickle-time snapshot."""
        if self._kv_opt_sent:
            return
        self._kvstore.set_optimizer(self._optimizer)
        self._kv_opt_snapshot = (self._optimizer.lr,
                                 self._optimizer.rescale_grad)
        self._kv_opt_sent = True
        # replay loaded states AFTER the ship: set_optimizer replaced the
        # server-side updater, which discarded any states a pre-first-
        # step load_states applied — without the replay, a resume
        # against live servers silently restarts the optimizer fresh
        blob = self._kv_deferred_states or self._kv_replay_states
        self._kv_deferred_states = self._kv_replay_states = None
        if blob is not None:
            self._kvstore.load_optimizer_states_blob(blob)

    @property
    def learning_rate(self):
        return self._optimizer.learning_rate

    def set_learning_rate(self, lr):
        self._optimizer.lr = lr

    def step(self, batch_size, ignore_stale_grad=False):
        """reference: trainer.py:148.

        Dense-gradient params with a pure-jax optimizer go through ONE
        jitted update over all of them (the gluon analog of Module's fused
        step — N per-param eager dispatches per step would each be a
        device round-trip on a remote-attached chip).  Sparse-gradient
        params and non-pure optimizers keep the per-param eager path.
        """
        # rescale BEFORE the lazy kvstore init: dist_async pickles the
        # optimizer to the servers at init and applies THERE
        self._optimizer.rescale_grad = self._scale / batch_size
        if not self._kv_initialized:
            self._init_kvstore()
        if getattr(self, "_update_on_kvstore", False):
            self._ensure_kv_optimizer()
            return self._step_on_kvstore(ignore_stale_grad)
        updater = self._updaters[0]
        from ..ndarray.sparse import RowSparseNDArray
        fuse = (env("MXNET_EXEC_BULK_EXEC_TRAIN", True)
                and getattr(self._optimizer, "pure_update", False))
        fused_batch = []
        for i, param in enumerate(self._params):
            if param.grad_req == 'null':
                continue
            if param._data is None:
                if not ignore_stale_grad:
                    raise MXNetError(
                        f"Parameter {param.name!r} was not initialized")
                continue
            grad = param.grad()
            if fuse and not isinstance(grad, RowSparseNDArray):
                fused_batch.append((i, param, grad))
            else:
                if self._zero_stage >= 1 and self._zero_dp > 1 \
                        and not getattr(self, "_zero_eager_warned", False):
                    self._zero_eager_warned = True
                    import warnings
                    warnings.warn(
                        "zero_stage=1 requested but parameter "
                        f"{param.name!r} updates on the eager path "
                        "(sparse grad, non-pure optimizer, or bulk-exec "
                        "disabled) — its optimizer state is NOT sharded",
                        stacklevel=2)
                updater(i, grad, param.data())
        if fused_batch:
            self._fused_update(fused_batch, updater)

    def _step_on_kvstore(self, ignore_stale_grad):
        """Async-PS step: ONE list-form push of every grad (small
        same-server keys coalesce into a single ``push_multi`` envelope
        under ``MXNET_KVSTORE_COALESCE_BYTES`` — per-param pushes used
        to bypass the coalescing path entirely and pay a frame+ack per
        tiny tensor), then ONE batched pull of the server's current
        weights back (reference: trainer.py:148 _update
        update-on-kvstore branch; pipelined pull = ~max-RTT, not N
        round trips).  Per-server FIFO guarantees each pull observes
        this worker's own pushes."""
        snap = (self._optimizer.lr, self._optimizer.rescale_grad)
        if snap != self._kv_opt_snapshot \
                and not getattr(self, "_kv_opt_drift_warned", False):
            self._kv_opt_drift_warned = True
            import warnings
            warnings.warn(
                "optimizer hyperparameters changed after the first "
                "dist_async step (lr/rescale_grad %s -> %s) — the SERVER "
                "keeps applying its pickle-time snapshot (re-sending the "
                "optimizer would reset server-side momentum state); "
                "restart training to change hyperparameters, as with the "
                "reference's server-side optimizer" %
                (self._kv_opt_snapshot, snap), stacklevel=3)
        live = []
        for param in self._params:
            if param.grad_req == 'null':
                continue
            if param._data is None:
                if not ignore_stale_grad:
                    raise MXNetError(
                        f"Parameter {param.name!r} was not initialized")
                continue
            if param.name not in self._kv_param_inited:
                # deferred-init param materialized after the first step:
                # register it before its first push (first-init-wins
                # makes a late init safe under concurrent workers)
                self._kvstore.init(param.name, param.data())
                self._kv_param_inited.add(param.name)
            live.append(param)
        if live:
            self._kvstore.push([p.name for p in live],
                               [p.grad() for p in live])
            self._kvstore.pull([p.name for p in live],
                               out=[p.data() for p in live])

    def _zero_pspec(self, arr):
        """Delegates to the shared rule in parallel.sharding (one source
        of truth with Module)."""
        from .. import parallel as _par
        return _par.zero_pspec(arr, self._zero_dp)

    def _zero_shard_state(self, state):
        import jax
        from jax.sharding import NamedSharding
        for s in self._optimizer._state_tuple(state):
            if s is None:
                continue
            s._set_data(jax.device_put(
                s._data, NamedSharding(self._mesh, self._zero_pspec(s))))

    def _zero_check_placed(self, batch, ws):
        """ZeRO-1 shards states onto the mesh, so the params must already
        live there (net.collect_params().place(mesh)) — otherwise jit
        fails with an opaque 'incompatible devices' error; fail clearly
        instead."""
        for (_i, p, _g), w in zip(batch, ws):
            if getattr(getattr(w, "sharding", None), "mesh", None) \
                    != self._mesh:
                raise MXNetError(
                    f"zero_stage=1: parameter {p.name!r} is not placed "
                    "on the trainer's mesh — call "
                    "net.collect_params().place(mesh) (and dp-shard the "
                    "input batch) before training")

    def _fused_update(self, batch, updater):
        """Apply the optimizer to every (dense) param in ONE jit call
        (the per-param dispatch lives in Optimizer.apply_fused, shared
        with Module's fused step).

        Shares per-param state with the eager Updater (same dict), so
        save_states/load_states and mixing eager/sparse updates stay
        coherent.  The jit cache is a dict keyed by (param set, mp
        layout, optimizer hyperparameter signature): changing e.g.
        momentum or rescale_grad mid-run retraces, and alternating keys
        (a smaller final batch) each compile once.
        """
        opt = self._optimizer
        zero1 = self._zero_stage >= 1 and self._zero_dp > 1
        for i, param, _g in batch:
            if i not in updater.states:
                updater.states[i] = \
                    opt.create_state_multi_precision(i, param.data())
                updater.states_synced[i] = True
                if zero1:
                    self._zero_shard_state(updater.states[i])
            opt._update_count(i)
        needs_t = getattr(opt, "needs_t", False)
        states = [opt._state_tuple(updater.states[i]) for i, _p, _g in batch]
        use_mp = tuple(opt.mp_states_active(p.data(), st)
                       for (_i, p, _g), st in zip(batch, states))
        ws = tuple(p._data._data for _i, p, _g in batch)
        gs = tuple(g._data for _i, _p, g in batch)
        sts = tuple(tuple(s._data for s in st) for st in states)
        if zero1:
            self._zero_check_placed(batch, ws)
            # params keep their CURRENT sharding (captured from the live
            # arrays — gluon has no rules engine; replicated unless the
            # user sharded them), states stay dp-sharded.  The specs join
            # the cache key so a placement change retraces the constraint.
            from jax.sharding import PartitionSpec as _P
            param_specs = tuple(
                getattr(w.sharding, "spec", _P()) for w in ws)
        else:
            param_specs = None
        key = (tuple(i for i, _p, _g in batch), use_mp, needs_t,
               opt.hyperparam_signature(), zero1, param_specs)
        cache = getattr(self, "_fused_cache", None)
        if cache is None:
            cache = self._fused_cache = {}
        fn = cache.get(key)
        if fn is None:
            def fused(ws, gs, sts, lrs, wds, ts):
                new_ws, new_sts = opt.apply_fused(
                    ws, gs, sts, lrs, wds, use_mp,
                    ts=ts if needs_t else None)
                if zero1:
                    from jax.sharding import NamedSharding
                    from .. import parallel as _par
                    mesh = self._mesh
                    new_ws = tuple(
                        jax.lax.with_sharding_constraint(
                            w, NamedSharding(mesh, ps))
                        for w, ps in zip(new_ws, param_specs))
                    new_sts = _par.constrain_zero_states(
                        new_sts, mesh, self._zero_dp)
                return new_ws, new_sts

            fn = cache[key] = jax.jit(fused)
        # cache lr/wd device scalars while unchanged (per-step host→device
        # scalar transfers would reintroduce the round-trips this path
        # removes — same discipline as Module._lrwd_cache)
        lrs = tuple(np.float32(opt._get_lr(i)) for i, _p, _g in batch)
        wds = tuple(np.float32(opt._get_wd(i)) for i, _p, _g in batch)
        lw_cache = getattr(self, "_lrwd_cache", None)
        if lw_cache is not None and lw_cache[0] == (lrs, wds):
            lrs, wds = lw_cache[1]
        else:
            key_ = (lrs, wds)
            lrs = tuple(jnp.asarray(v) for v in lrs)
            wds = tuple(jnp.asarray(v) for v in wds)
            self._lrwd_cache = (key_, (lrs, wds))
        if needs_t:
            # per-param bias-correction counts (a frozen/unfrozen param's
            # count differs — matching the eager path exactly)
            ts = tuple(jnp.asarray(opt._index_update_count[i], jnp.int32)
                       for i, _p, _g in batch)
        else:
            ts = getattr(self, "_t_zeros", None)
            if ts is None or len(ts) != len(batch):
                ts = self._t_zeros = tuple(
                    jnp.asarray(0, jnp.int32) for _ in batch)
        new_ws, new_sts = fn(ws, gs, sts, lrs, wds, ts)
        for (_i, p, _g), w, st_old, st_new in zip(batch, new_ws, states,
                                                  new_sts):
            p._data._set_data(w)
            for s, v in zip(st_old, st_new):
                s._set_data(v)

    def step_k(self, loss_fn, data, label=None, k=None, batch_size=None,
               eval_metric=None):
        """Run K training steps (forward + backward + update) as ONE
        scanned XLA program — the gluon analog of ``Module.run_steps``,
        built on the same ``executor.build_multi_step`` driver: a single
        host dispatch launches all K steps, amortizing the per-dispatch
        host cost to 1/K per step.

        ``loss_fn(data, label) -> loss NDArray`` is the user's forward
        (net + loss); it is traced ONCE into the scan body, with this
        trainer's parameters functionalized into the scan carry:
        trainable parameters update via the optimizer each step,
        non-trainable parameters the forward mutates (BatchNorm
        running stats) ride the carry too, so their K-step evolution
        matches K eager steps exactly.  ``data``/``label`` stack the K
        batches on a leading step axis (a single array or a tuple of
        arrays, mirrored into loss_fn per step).  Returns the per-step
        loss values stacked on a leading K axis (ONE host readback reads
        them all).

        ``eval_metric`` (the gluon leg of the sync-free training loop):
        each step's ``(labels, loss)`` pair folds into the metric.  A
        device-capable metric (metric.EvalMetric.device_update — e.g.
        ``Loss``, ``MAE``) rides the scan carry: K steps of metric
        accumulation cost ZERO extra dispatches and readbacks, and the
        host only syncs when the metric is read (get_name_value) — on
        the fused AND eager drivers alike.  Metrics without a device
        form fold host-side: on the fused path from ONE stacked
        readback of the K losses; on the eager fallback per step (the
        eager driver is per-step in every respect).

        Per-step lr/wd schedules and update counts are precomputed
        host-side, exactly as K ``step()`` calls would advance them.
        dist_async update-on-kvstore runs the CHUNKED variant of the
        same scan — one dispatch per ``MXNET_KVSTORE_FUSED_CHUNK``
        steps, a local worker-side replica of the server update keeping
        the in-chunk trajectory fresh, and the grad-push/weight-pull
        wire overlapped behind the next chunk's compute
        (``MXNET_KVSTORE_FUSED_STALENESS``; the Module.run_steps dist
        driver's gluon twin — see its docstring for the staleness
        contract).  Falls back to the eager loop (autograd
        record/backward + step) for K=1, non-pure optimizers,
        ``MXNET_KVSTORE_FUSED=0`` (dist), or
        ``MXNET_EXEC_BULK_EXEC_TRAIN=0``.  Caveat: ops drawing from the
        global RNG (Dropout) freeze their trace-time draw — use the
        eager path (or Module.run_steps, whose interpreter threads keys
        explicitly) for stochastic-regularization training.
        """
        import jax.numpy as jnp
        data_t = tuple(d._data if hasattr(d, "_data") else jnp.asarray(d)
                       for d in (data if isinstance(data, (list, tuple))
                                 else (data,)))
        label_t = None
        if label is not None:
            label_t = tuple(
                l._data if hasattr(l, "_data") else jnp.asarray(l)
                for l in (label if isinstance(label, (list, tuple))
                          else (label,)))
        ks = {int(a.shape[0]) for a in data_t + (label_t or ())}
        if len(ks) != 1:
            raise MXNetError(f"step_k: inconsistent leading (step) dims "
                             f"{sorted(ks)}")
        inferred = ks.pop()
        if inferred == 0:
            raise MXNetError("step_k: inputs stack ZERO steps (empty "
                             "leading axis)")
        if k is None:
            k = inferred
        elif k != inferred:
            raise MXNetError(f"step_k: k={k} but inputs stack {inferred} "
                             "steps (leading dim)")
        if batch_size is None:
            batch_size = int(data_t[0].shape[1]) if data_t[0].ndim > 1 \
                else 1
        # rescale BEFORE the lazy kvstore init (same contract as step)
        self._optimizer.rescale_grad = self._scale / batch_size
        if not self._kv_initialized:
            self._init_kvstore()
        fusable = (k > 1
                   and env("MXNET_EXEC_BULK_EXEC_TRAIN", True)
                   and getattr(self._optimizer, "pure_update", False))
        if getattr(self, "_update_on_kvstore", False):
            # dist_async no longer falls back to eager: the chunked
            # driver scans fwd+bwd+local-update and overlaps the
            # grad-push/weight-pull wire behind the next chunk's
            # compute (the Module.run_steps dist driver's gluon twin).
            # Elastic jobs ride it too — an in-flight pull_async
            # handle replans against the post-bump stripe layout from
            # inside wait() (docs/ROBUSTNESS.md replan contract).
            if fusable and env("MXNET_KVSTORE_FUSED", True):
                self._ensure_kv_optimizer()
                return self._step_k_fused(loss_fn, data_t, label_t, k,
                                          eval_metric, dist=True)
            return self._step_k_eager(loss_fn, data_t, label_t, k,
                                      batch_size, eval_metric)
        if not fusable:
            return self._step_k_eager(loss_fn, data_t, label_t, k,
                                      batch_size, eval_metric)
        return self._step_k_fused(loss_fn, data_t, label_t, k, eval_metric)

    def _step_k_eager(self, loss_fn, data_t, label_t, k, batch_size,
                      eval_metric=None):
        """K eager steps: record → backward → step, one dispatch each
        (the universal fallback; same math as the scanned path)."""
        from .. import autograd as _ag
        from ..ndarray import NDArray
        import jax.numpy as jnp
        def _wrap(vals):
            nds = tuple(NDArray(v) for v in vals)
            return nds[0] if len(nds) == 1 else nds

        losses = []
        for j in range(k):
            args = [_wrap([a[j] for a in data_t])]
            if label_t is not None:
                args.append(_wrap([a[j] for a in label_t]))
            with _ag.record():
                loss = loss_fn(*args)
            loss.backward()
            self.step(batch_size)
            if eval_metric is not None:
                # device-resident when the metric supports it (no sync)
                labs = [NDArray(a[j]) for a in label_t] \
                    if label_t is not None else []
                eval_metric.accumulate(labs, [loss])
            losses.append(loss._data)
        return NDArray(jnp.stack(losses))

    def _step_k_fused(self, loss_fn, data_t, label_t, k,
                      eval_metric=None, dist=False):
        """``dist=True`` is the update-on-kvstore variant: the SAME
        scanned body (the local update doubles as the worker-side
        replica of the server's updater — both run
        ``Optimizer._update_impl``) additionally scans out the raw
        per-step gradients, and the dispatch runs chunked through
        ``executor.drive_chunked_dist`` with the push/pull wire
        overlapped behind the next chunk's compute.  Staleness
        semantics and the exactness contract are documented on
        ``Module._run_steps_fused_dist``."""
        from .. import autograd as _ag
        from .. import profiler as _prof
        from ..ndarray import NDArray
        import jax
        import jax.numpy as jnp
        opt = self._optimizer
        updater = self._updaters[0]
        # ZeRO-1 state sharding composes with the LOCAL fused driver
        # only — under update-on-kvstore the authoritative states live
        # on the servers and the local replica states stay replicated
        zero1 = self._zero_stage >= 1 and self._zero_dp > 1 and not dist
        deferred = [p.name for p in self._params
                    if p._deferred_init is not None]
        if deferred:
            # a deferred-init param materializing INSIDE the jit trace
            # would silently train nothing (it never joins the carry)
            # and leak tracers into the live Parameter — fail clearly
            raise MXNetError(
                "step_k: parameters pending deferred init "
                f"({deferred[:3]}...) — run one eager forward (e.g. "
                "net(first_batch)) to materialize shapes before step_k")
        trainable, idxs = [], []
        aux_params = []
        for i, param in enumerate(self._params):
            if param._data is None:
                continue
            if param.grad_req == 'null':
                # non-trainable but possibly MUTATED by the forward
                # (BatchNorm running stats): carried through the scan
                aux_params.append(param)
            else:
                trainable.append(param)
                idxs.append(i)
        for i, param in zip(idxs, trainable):
            if i not in updater.states:
                updater.states[i] = \
                    opt.create_state_multi_precision(i, param.data())
                updater.states_synced[i] = True
                if zero1:
                    self._zero_shard_state(updater.states[i])
        needs_t = getattr(opt, "needs_t", False)
        states = [opt._state_tuple(updater.states[i]) for i in idxs]
        use_mp = tuple(opt.mp_states_active(p.data(), st)
                       for p, st in zip(trainable, states))
        ws = tuple(p._data._data for p in trainable)
        auxs = tuple(p._data._data for p in aux_params)
        sts = tuple(tuple(s._data for s in st) for st in states)
        if zero1:
            self._zero_check_placed(
                [(i, p, None) for i, p in zip(idxs, trainable)], ws)
            from jax.sharding import PartitionSpec as _P
            param_specs = tuple(
                getattr(w.sharding, "spec", _P()) for w in ws)
        else:
            param_specs = None
        donate = bool(env("MXNET_FUSED_DONATE", True))
        # device-capable metrics ride the scan carry (zero extra
        # dispatches/readbacks for K steps of metric accumulation);
        # others fold host-side from the stacked losses below
        use_dev_metric = (eval_metric is not None
                          and getattr(eval_metric, "device_enabled",
                                      lambda: False)())
        # cache key: loss_fn by CODE + bound instance + closure-cell
        # identities, not object identity — the natural per-iteration
        # lambda (`tr.step_k(lambda x, y: loss(net(x), y), ...)`) is a
        # fresh object every call but shares its code and closes over
        # the same net/loss objects, so it must HIT (identity keying
        # would retrace + recompile the whole K-step program per call
        # and pin every stale closure).  __self__ joins the key because
        # bound methods of two instances share __code__ with an empty
        # closure; callables without __code__ fall back to identity.
        pins = (getattr(loss_fn, "__self__", None),) + tuple(
            c.cell_contents
            for c in (getattr(loss_fn, "__closure__", None) or ()))
        fn_key = (getattr(loss_fn, "__code__", loss_fn),
                  tuple(id(p) for p in pins))
        key = (fn_key, tuple(idxs), len(aux_params), use_mp, needs_t,
               opt.hyperparam_signature(), zero1, param_specs,
               label_t is None, donate, dist,
               eval_metric._device_sig() if use_dev_metric else None)
        cache = getattr(self, "_step_k_cache", None)
        if cache is None:
            cache = self._step_k_cache = {}
        from ..executor import scan_cache_lookup, scan_cache_store
        entry = scan_cache_lookup(cache, key)
        # the entry PINS the id()'d objects: without the strong refs, a
        # GC'd closure object's address could be reused by a NEW object
        # and false-hit a program traced against the old one
        fn = entry[0] if entry is not None else None
        if fn is None:
            all_params = trainable + aux_params
            metric = eval_metric if use_dev_metric else None

            def f_loss(ws_, auxs_, data_j, label_j):
                """Functionalized forward: park traced values in the
                live Parameters, run the user's loss_fn, harvest the
                (possibly updated) aux payloads, restore."""
                old = [(p._data._payload, p._data._thunk)
                       for p in all_params]
                try:
                    for p, w in zip(trainable, ws_):
                        p._data._set_data(w)
                    for p, a in zip(aux_params, auxs_):
                        p._data._set_data(a)
                    args = [NDArray(data_j[0]) if len(data_j) == 1
                            else tuple(NDArray(d) for d in data_j)]
                    if label_j is not None:
                        args.append(NDArray(label_j[0])
                                    if len(label_j) == 1 else
                                    tuple(NDArray(l) for l in label_j))
                    with _ag.train_mode():
                        loss = loss_fn(*args)
                    new_auxs = tuple(p._data._data for p in aux_params)
                    return loss._data, new_auxs
                finally:
                    for p, (payload, thunk) in zip(all_params, old):
                        p._data._payload = payload
                        p._data._thunk = thunk

            def scan_body(carry, x, const):
                ws_, auxs_, sts_, mstate = carry
                data_j, label_j, lrs, wds, ts = x

                loss_val, vjp_fn, new_auxs = jax.vjp(
                    lambda w: f_loss(w, auxs_, data_j, label_j),
                    ws_, has_aux=True)
                grads = vjp_fn(jnp.ones_like(loss_val))[0]
                new_ws, new_sts = opt.apply_fused(
                    ws_, grads, sts_, lrs, wds, use_mp,
                    ts=ts if needs_t else None)
                if zero1:
                    from jax.sharding import NamedSharding
                    from .. import parallel as _par
                    mesh = self._mesh
                    new_ws = tuple(
                        jax.lax.with_sharding_constraint(
                            w, NamedSharding(mesh, ps))
                        for w, ps in zip(new_ws, param_specs))
                    new_sts = _par.constrain_zero_states(
                        new_sts, mesh, self._zero_dp)
                if metric is not None:
                    # (labels, loss) fold into the device metric state —
                    # accumulation stays in the one scanned program
                    mstate = metric.device_update(
                        mstate,
                        list(label_j) if label_j is not None else [],
                        [loss_val])
                ys = (loss_val, grads) if dist else loss_val
                return (new_ws, new_auxs, new_sts, mstate), ys

            from ..executor import build_multi_step
            fn = build_multi_step(scan_body, donate=donate)
            scan_cache_store(cache, key, (fn, pins))

        # per-step lr/wd/t advance exactly as K step() calls would
        # (shared helper with Module.run_steps); rollback keeps the host
        # schedule transactional with the dispatch — a failed compile
        # must not leave counts K steps ahead of the params.  The dist
        # driver keys schedules by param NAME — the wire key the
        # SERVER's updater advances counts under — so the local replica
        # samples the same lr sequence the server does
        from ..executor import precompute_step_schedules, schedule_rollback
        sched_keys = [p.name for p in trainable] if dist else idxs
        with schedule_rollback(opt):
            lrs, wds, ts = precompute_step_schedules(opt, sched_keys, k)
            # _take (not peek), and only now that every pre-dispatch
            # step that can fail (the schedule precompute above) is
            # done: the carry is donated, so a failed DISPATCH must
            # leave the metric empty rather than holding deleted
            # buffers — but a failed precompute rolls back and must
            # not cost the pending interval
            init_m = eval_metric._take_device_state() if use_dev_metric \
                else ()

            def _writeback(ws_, auxs_, sts_):
                for p, w in zip(trainable, ws_):
                    p._data._set_data(w)
                for p, a in zip(aux_params, auxs_):
                    p._data._set_data(a)
                for st_old, st_new in zip(states, sts_):
                    for s, v in zip(st_old, st_new):
                        s._set_data(v)

            if dist:
                new_ws, new_auxs, new_sts, new_m, losses = \
                    self._drive_step_k_dist(fn, trainable, use_mp, ws,
                                            auxs, sts, init_m, data_t,
                                            label_t, lrs, wds, ts, k,
                                            _writeback)
            else:
                _prof.record_dispatch("step_k.dispatch")
                with _prof.span("mx.trainer.step_k.call", "trainer"):
                    (new_ws, new_auxs, new_sts, new_m), losses = fn(
                        (ws, auxs, sts, init_m),
                        (data_t, label_t, lrs, wds, ts), ())
        _writeback(new_ws, new_auxs, new_sts)
        if use_dev_metric:
            eval_metric._absorb_device_state(new_m)
        elif eval_metric is not None:
            # host fallback: ONE stacked readback for all K losses (and
            # labels), folded per step.  NDArray-wrapped like the eager
            # path — the same user metric must work on both drivers
            eval_metric._warn_host_fallback()
            # ONE blocking device_get for losses AND labels together —
            # two sequential gets would pay the host round trip twice
            # while the sync counter reported one
            host_losses, host_labels = jax.device_get(
                (losses, label_t if label_t is not None else ()))
            if label_t is None:
                host_labels = None
            _prof.record_host_sync("step_k.metric_fold")
            for j in range(k):
                eval_metric.update(
                    [NDArray(a[j]) for a in host_labels]
                    if host_labels is not None else [],
                    [NDArray(host_losses[j])])
        return NDArray(losses)

    def _drive_step_k_dist(self, fn, trainable, use_mp, ws, auxs, sts,
                           init_m, data_t, label_t, lrs, wds, ts, k,
                           on_failure):
        """Chunked dispatch of the dist step_k scan: one compiled-scan
        launch and one grad-push/weight-pull wire round per chunk, the
        round overlapped behind the NEXT chunk's compute
        (executor.drive_chunked_dist; profiler.wire_wait_ms /
        wire_overlap_pct count the exposed vs hidden wire).  Returns
        ``(new_ws, new_auxs, new_sts, new_m, stacked_losses)`` with
        ``new_ws`` the FINAL pull's server-authoritative weights."""
        import jax
        import jax.numpy as jnp
        from .. import profiler as _prof
        from ..executor import drive_chunked_dist, fused_dist_knobs
        kv = self._kvstore
        names = [p.name for p in trainable]
        shapes = [tuple(p._data.shape) for p in trainable]
        dtypes = [p._data._data.dtype for p in trainable]
        # a deferred-init param that materialized after _init_kvstore
        # must register before its first push — same first-init-wins
        # late registration the eager _step_on_kvstore performs
        for p in trainable:
            if p.name not in self._kv_param_inited:
                kv.init(p.name, p.data())
                self._kv_param_inited.add(p.name)
        chunk, staleness = fused_dist_knobs(k)
        carry = {"ws": ws, "auxs": auxs, "sts": sts, "m": init_m,
                 "losses": []}

        def adopt(adopted):
            # chunk-boundary re-sync: weights adopt the pulled server
            # values — for a multi-precision param the fp32 MASTER in
            # states[0] adopts too (the update runs on it and recasts
            # the weight); the rest of the replica optimizer state and
            # aux stay local (the async-SGD-grade part of the contract)
            new_ws, new_sts = [], list(carry["sts"])
            for i, (n, dt) in enumerate(zip(names, dtypes)):
                w = jnp.asarray(adopted[n])
                if use_mp[i]:
                    master = w.astype(jnp.float32)
                    new_sts[i] = (master,) + tuple(new_sts[i][1:])
                    w = master.astype(dt)
                else:
                    w = w.astype(dt)
                new_ws.append(w)
            carry["ws"] = tuple(new_ws)
            carry["sts"] = tuple(new_sts)

        def dispatch_chunk(j, lo, hi, adopted):
            if adopted is not None:
                adopt(adopted)
            xs = (tuple(a[lo:hi] for a in data_t),
                  tuple(a[lo:hi] for a in label_t)
                  if label_t is not None else None,
                  tuple(v[lo:hi] for v in lrs),
                  tuple(v[lo:hi] for v in wds),
                  tuple(v[lo:hi] for v in ts))
            _prof.record_dispatch("step_k.dist_chunk")
            with _prof.span("mx.trainer.step_k.dist_chunk.call", "trainer"):
                (nws, nauxs, nsts, nm), (losses, grads) = fn(
                    (carry["ws"], carry["auxs"], carry["sts"],
                     carry["m"]), xs, ())
            carry.update(ws=nws, auxs=nauxs, sts=nsts, m=nm)
            carry["losses"].append(losses)
            # ONE stacked readback of the chunk's raw per-step grads —
            # blocks on the chunk's COMPUTE; the wire round itself is
            # what the driver overlaps behind the next chunk
            grads_np = jax.device_get(grads)
            _prof.record_host_sync("step_k.dist_grad_readback")
            return grads_np

        def ship_chunk(j, grads_np):
            return kv.ship_chunk_steps(names, grads_np, shapes)

        try:
            final = drive_chunked_dist(k, chunk, staleness,
                                       dispatch_chunk, ship_chunk)
        except BaseException:
            # a wire failure mid-drive lands AFTER earlier chunks
            # donated the original param/aux/state buffers — the carry
            # holds the latest chunk's OUTPUT arrays (alive): park them
            # so the trainer's params stay readable at the last
            # locally-completed step
            on_failure(carry["ws"], carry["auxs"], carry["sts"])
            raise
        # the final pull is the sync point: trainable weights adopt the
        # server-authoritative values, fp32 masters included (exactly
        # like step()'s pull)
        adopt(final)
        losses = (jnp.concatenate(carry["losses"])
                  if len(carry["losses"]) > 1 else carry["losses"][0])
        return (carry["ws"], carry["auxs"], carry["sts"], carry["m"],
                losses)

    def allreduce_grads(self):
        """No-op on TPU: gradient reduction is fused into backward
        (GSPMD psum) — kept for API parity (reference: trainer.py
        allreduce_grads)."""

    def update(self, batch_size, ignore_stale_grad=False):
        self.step(batch_size, ignore_stale_grad)

    def save_states(self, fname):
        """reference: trainer.py save_states.  Under dist_async the
        optimizer states LIVE on the servers — fetch them from there
        (worker-side updater states would be an empty dict).  The store
        is created here if needed so a pre-first-step call routes
        correctly (resume-from-checkpoint pattern)."""
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            if not self._kv_opt_sent:
                # THIS worker never stepped, but another worker may have
                # shipped the optimizer and trained — gather from the
                # servers if they answer; never ship the optimizer from
                # here (that would freeze rescale_grad=1.0 server-side)
                if self._kv_deferred_states is not None:
                    with open(fname, 'wb') as fout:
                        fout.write(self._kv_deferred_states)
                    return
                try:
                    self._kvstore.save_optimizer_states(fname)
                except MXNetError:
                    # fresh cluster, no optimizer anywhere: no states
                    # exist yet — write an empty state dict
                    with open(fname, 'wb') as fout:
                        fout.write(self._updaters[0].get_states())
                return
            self._kvstore.save_optimizer_states(fname)
            return
        with open(fname, 'wb') as fout:
            fout.write(self._updaters[0].get_states())

    def load_states(self, fname):
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            if not self._kv_opt_sent:
                # if another worker already installed the server-side
                # optimizer, apply NOW (deferring would rewind their
                # later progress at this worker's first step); on a
                # fresh cluster buffer until the first step() ships the
                # optimizer with the REAL rescale_grad
                try:
                    self._kvstore.load_optimizer_states(fname)
                    # rank 0's first step RE-SHIPS the optimizer, which
                    # replaces the server updater and wipes the states
                    # just applied — keep the blob so the ship replays
                    # it (tracked separately from _kv_deferred_states:
                    # a pre-step save_states must keep returning the
                    # LIVE server states, which other workers may have
                    # advanced past this blob)
                    with open(fname, 'rb') as fin:
                        self._kv_replay_states = fin.read()
                except MXNetError:
                    with open(fname, 'rb') as fin:
                        self._kv_deferred_states = fin.read()
                return
            self._kvstore.load_optimizer_states(fname)
            return
        with open(fname, 'rb') as fin:
            self._updaters[0].set_states(fin.read())
        if self._zero_stage >= 1 and self._zero_dp > 1:
            # restored buffers land unsharded — re-apply ZeRO placement
            # now, not at the first step, to avoid the O(P) peak
            for st in self._updaters[0].states.values():
                self._zero_shard_state(st)
