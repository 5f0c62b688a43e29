"""Module: symbol + executor + optimizer, the intermediate-level trainer
(reference: python/mxnet/module/module.py).

TPU-first design: the reference's DataParallelExecutorGroup (one executor
per GPU, batch split host-side, kvstore reduce — executor_group.py:99,233)
is replaced by ONE executor whose arrays may be sharded over a device mesh
(data-parallel = batch-axis sharding; see mxnet_tpu.parallel).  ``update``
runs a FUSED training step: forward + backward + optimizer update compile
into a single XLA program (the reference needed three engine passes plus a
kvstore round trip per step).
"""
from __future__ import annotations

import logging
import sys
from typing import Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..base import MXNetError, env
from ..context import Context, cpu, current_context
from ..executor import Executor
from ..initializer import Uniform, InitDesc
from ..model import (_create_kvstore, _initialize_kvstore, _update_params,
                     _update_params_on_kvstore, load_checkpoint,
                     save_checkpoint)
from ..ndarray import NDArray
from .. import optimizer as opt_mod
from .. import profiler as _prof
from .. import random as _rnd
from .. import tracing as _tracing
from .base_module import BaseModule, _check_input_names, _parse_data_desc


# what jit keys a compiled entry on, per argument leaf
_JIT_KEY_FIELDS = ("shape", "dtype", "weak_type", "sharding", "committed")


def _jit_leaf_key(v):
    """The fields of _JIT_KEY_FIELDS for one argument leaf.  A host value
    (numpy or Python scalar) has no sharding and is never committed; a
    donated, deleted array still answers all five."""
    aval = v.aval if isinstance(v, jax.Array) \
        else jax.api_util.shaped_abstractify(v)
    return {"shape": tuple(aval.shape), "dtype": str(aval.dtype),
            "weak_type": bool(aval.weak_type),
            "sharding": str(getattr(v, "sharding", None)),
            "committed": bool(getattr(v, "committed", False))}


def _report_step_compile(instant, what, moved, **extra):
    """One stderr line and one trace instant for a fused step that was
    compiled or built again: ``moved`` maps (field, before, after) to the
    labels it moved on; the line names at most six labels a field, the
    instant's args carry all of them, and ``extra`` too."""
    parts = ["%s %s -> %s on %d: %s%s"
             % (field, was, now, len(labels), ", ".join(labels[:6]),
                " (+%d more)" % (len(labels) - 6) if len(labels) > 6 else "")
             for (field, was, now), labels in moved.items()]
    line = "%s: %s" % (what, "; ".join(parts) or
                       "nothing jit keys on differs (the cache was "
                       "cleared, or a transform's context changed)")
    if extra:
        line += " [%s]" % ", ".join("%s %s" % kv for kv in extra.items())
    _tracing.instant(instant, "module", args=dict(extra, moved=[
        {"field": f, "before": str(a), "after": str(b), "leaves": labels}
        for (f, a, b), labels in moved.items()]))
    print("mxnet_tpu: " + line, file=sys.stderr, flush=True)


class Module(BaseModule):
    """reference: module.py:39 Module."""

    def __init__(self, symbol, data_names=('data',),
                 label_names=('softmax_label',), logger=logging,
                 context=None, work_load_list=None, fixed_param_names=None,
                 state_names=None, mesh=None, sharding_rules=None,
                 compute_dtype=None, zero_stage=None):
        super().__init__(logger=logger)
        if context is None:
            context = [current_context()]
        if isinstance(context, Context):
            context = [context]
        self._context = context
        self._work_load_list = work_load_list
        # -- mesh parallelism (mxnet_tpu.parallel) -------------------------
        # The reference replicated one executor per context and split the
        # batch host-side (executor_group.py:99,233).  Here a context list
        # becomes a dp mesh over those devices, and an explicit `mesh`
        # (or an ambient parallel.use_mesh scope) enables arbitrary
        # dp/tp/pp/sp/ep layouts on the SAME Module code path.
        from .. import parallel as _par
        if mesh is None:
            mesh = _par.current_mesh()
        if mesh is None and len(context) > 1:
            mesh = _par.make_mesh(
                dp=len(context),
                devices=[c.jax_device() for c in context])
        self._mesh = mesh
        self._sharding_rules = sharding_rules
        # Mixed precision: master weights stay fp32; the executor casts
        # per-op inputs to this dtype (see executor.AMP_FP32_OPS).  The
        # TPU-native analog of the reference's --dtype float16 training
        # recipe (example/image-classification/common/fit.py).
        self._compute_dtype = compute_dtype
        # ZeRO-1 optimizer-state sharding over the dp axis.  The modern
        # answer to the reference's update-on-kvstore mode (SURVEY §2.5
        # "gradient aggregation modes" → optimizer-state sharding
        # decision): instead of an optimizer living in a parameter
        # server, each dp rank owns a 1/dp shard of every optimizer
        # state (and fp32 master weight); GSPMD then materializes the
        # reduce-scatter(grads) → sharded update → all-gather(params)
        # schedule inside the one fused step.  Opt-in: zero_stage=1 or
        # MXNET_ZERO_STAGE=1.
        explicit_zero = zero_stage is not None
        if zero_stage is None:
            zero_stage = env("MXNET_ZERO_STAGE", 0)
        if zero_stage not in (0, 1):
            raise ValueError("zero_stage must be 0 or 1 (ZeRO-2/3 shard "
                             "gradients/params too — not implemented; "
                             "ZeRO-1 covers the optimizer-state memory, "
                             "which dominates for Adam-family training)")
        if explicit_zero and zero_stage >= 1 and mesh is None:
            raise MXNetError(
                "zero_stage=1 needs a device mesh with dp>1 — pass "
                "mesh= (parallel.make_mesh) or enter a use_mesh scope")
        if not explicit_zero and zero_stage >= 1:
            from .. import parallel as _par
            dp = (_par.mesh_shape(mesh).get("dp", 1)
                  if mesh is not None else 1)
            if dp <= 1:
                # env-enabled ZeRO silently no-ops without a dp>1 mesh —
                # the user who exported MXNET_ZERO_STAGE=1 must learn the
                # states are replicated, not sharded (the explicit-kwarg
                # path raises instead)
                logging.warning(
                    "MXNET_ZERO_STAGE=1 ignored: no device mesh with "
                    "dp>1 on this Module — optimizer states will be "
                    "fully replicated")
        self._zero_stage = int(zero_stage)

        self._symbol = symbol
        data_names = list(data_names) if data_names is not None else []
        label_names = list(label_names) if label_names is not None else []
        state_names = list(state_names) if state_names is not None else []
        fixed_param_names = list(fixed_param_names) \
            if fixed_param_names is not None else []
        _check_input_names(symbol, data_names, "data", True)
        _check_input_names(symbol, label_names, "label", False)
        _check_input_names(symbol, state_names, "state", True)
        _check_input_names(symbol, fixed_param_names, "fixed_param", True)

        arg_names = symbol.list_arguments()
        input_names = data_names + label_names + state_names
        self._param_names = [x for x in arg_names if x not in input_names]
        self._fixed_param_names = fixed_param_names
        self._aux_names = symbol.list_auxiliary_states()
        self._data_names = data_names
        self._label_names = label_names
        self._state_names = state_names
        self._output_names = symbol.list_outputs()

        self._arg_params = None
        self._aux_params = None
        self._params_dirty = False

        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = None
        self._updater = None
        self._preload_opt_states = None
        self._grad_req = None
        self._exec: Optional[Executor] = None
        self._fused_step = None
        self._fused_hparam_sig = None
        # compiled entries of _fused_step at its last call, and what jit
        # keyed the newest of them on (_note_step_compiled)
        self._fused_cache_size = 0
        self._fused_jit_key = None
        self._updated_once = False
        self._run_steps_cache: Dict[tuple, object] = {}
        self._opt_states: Dict[str, tuple] = {}
        self._pending_backward = False

    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        """reference: module.py load."""
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = '%s-%04d.states' % (prefix, epoch)
        return mod

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        """reference: module.py save_checkpoint."""
        self._symbol.save('%s-symbol.json' % prefix)
        param_name = '%s-%04d.params' % (prefix, epoch)
        self.save_params(param_name)
        logging.info('Saved checkpoint to "%s"', param_name)
        if save_optimizer_states:
            state_name = '%s-%04d.states' % (prefix, epoch)
            self.save_optimizer_states(state_name)
            logging.info('Saved optimizer state to "%s"', state_name)

    # -- properties -----------------------------------------------------------
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        return [(n, o.shape) for n, o in
                zip(self._output_names, self._exec.outputs)]

    # -- params ---------------------------------------------------------------
    def get_params(self):
        assert self.binded and self.params_initialized
        return ({n: self._exec.arg_dict[n] for n in self._param_names},
                dict(self._exec.aux_dict))

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False,
                    allow_extra=False):
        """reference: module.py:460 init_params."""
        if self.params_initialized and not force_init:
            return
        assert self.binded, 'call bind before initializing the parameters'
        with _tracing.phase("mx.module.init_params"):
            self._init_params(initializer, arg_params, aux_params,
                              allow_missing, allow_extra)

    def _init_params(self, initializer, arg_params, aux_params,
                     allow_missing, allow_extra):
        def _impl(name, arr, cache):
            if cache is not None and name in cache:
                cache_arr = cache[name]
                if cache_arr is not arr:
                    arr._set_data(cache_arr._data)
            else:
                if not allow_missing and cache is not None:
                    raise RuntimeError(f"{name} is not presented")
                if initializer is not None:
                    init = initializer
                    attrs = self._symbol.attr_dict()
                    if name in attrs and '__init__' in attrs[name]:
                        from .. import initializer as init_mod
                        import json as _json
                        klass, kw = _json.loads(attrs[name]['__init__'])
                        init = init_mod.create(klass, **kw)
                    # global_init lets composite inits (FusedRNN) fall
                    # back to the caller's initializer per weight piece
                    init(InitDesc(name, global_init=initializer), arr)

        cache_arg = arg_params if arg_params is not None else \
            (self._arg_params if self._arg_params else None)
        cache_aux = aux_params if aux_params is not None else \
            (self._aux_params if self._aux_params else None)
        if not allow_extra:
            # the reference rejects unknown names unless allow_extra=True
            # (module.py set_params) — silently dropping a typo'd weight
            # is how a checkpoint loads "successfully" untrained.  Every
            # symbol argument (params, inputs, labels, STATES) is known.
            known = set(self._symbol.list_arguments()) \
                | set(self._aux_names)
            for cache in (cache_arg, cache_aux):
                unknown = [n for n in (cache or {}) if n not in known]
                if unknown:
                    raise ValueError(
                        "extra parameters not in the symbol (pass "
                        "allow_extra=True to ignore): %r" % sorted(unknown))
        for name in self._param_names:
            _impl(name, self._exec.arg_dict[name], cache_arg)
        for name in self._aux_names:
            _impl(name, self._exec.aux_dict[name], cache_aux)
        self.params_initialized = True
        self._params_dirty = False

    # -- bind -----------------------------------------------------------------
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req='write'):
        """reference: module.py bind → DataParallelExecutorGroup; here: one
        simple_bind'ed jit executor (sharding covers multi-device)."""
        if force_rebind:
            self._reset_bind()
        if self.binded:
            self.logger.warning('Already bound, ignoring bind()')
            return
        with _tracing.phase("mx.module.bind"):
            self._bind(data_shapes, label_shapes, for_training,
                       inputs_need_grad, shared_module, grad_req)

    def _bind(self, data_shapes, label_shapes, for_training,
              inputs_need_grad, shared_module, grad_req):
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.binded = True

        self._data_shapes, self._label_shapes = _parse_data_desc(
            self.data_names, self.label_names, data_shapes, label_shapes)

        shapes = {d.name: d.shape for d in self._data_shapes}
        type_dict = {d.name: getattr(d, 'dtype', np.float32)
                     for d in self._data_shapes}
        if self._label_shapes:
            shapes.update({l.name: l.shape for l in self._label_shapes})
            type_dict.update({l.name: getattr(l, 'dtype', np.float32)
                              for l in self._label_shapes})

        req = {}
        for name in self._symbol.list_arguments():
            if name in self._data_names:
                req[name] = 'write' if inputs_need_grad else 'null'
            elif name in self._label_names or name in self._state_names:
                req[name] = 'null'
            elif name in self._fixed_param_names:
                req[name] = 'null'
            else:
                req[name] = grad_req if for_training else 'null'
        self._grad_req = req

        self._exec = Executor.simple_bind(
            self._symbol, self._context[0], grad_req=req,
            type_dict=type_dict, shapes=shapes,
            compute_dtype=self._compute_dtype)
        self._apply_shardings()
        self._fused_step = None
        self._run_steps_cache = {}
        if self.params_initialized:
            # params loaded before bind (Module.load) — copy into executor
            # (reference: module.py bind → exec_group.set_params)
            if self._arg_params:
                self._exec.copy_params_from(self._arg_params,
                                            self._aux_params,
                                            allow_extra_params=True)
        if shared_module is not None and shared_module.params_initialized:
            arg, aux = shared_module.get_params()
            self._exec.copy_params_from(arg, aux, allow_extra_params=True)
            self.params_initialized = True

    def reshape(self, data_shapes, label_shapes=None):
        """Re-bind for new input shapes, keeping parameters, grad_req and
        optimizer state (reference: module.py:444 Module.reshape —
        batch-size or image-size switch without re-initialization).

        Delegates to Executor.reshape — the same path forward() uses for
        implicit shape changes — which carries params/aux/grad_req/
        shardings over; each shape gets its own jit program and
        re-reshaping to a previous shape reuses XLA's compile cache."""
        assert self.binded and self.params_initialized
        had_labels = bool(self._label_shapes)
        new_data, new_labels = _parse_data_desc(
            self.data_names, self.label_names, data_shapes, label_shapes)
        if had_labels and not new_labels:
            # the executor would keep the label at the OLD batch size and
            # the next training step would fail deep inside the jit
            # (checked BEFORE mutating module metadata, so a caught error
            # leaves the module consistent)
            raise MXNetError(
                "reshape: this module was bound with label_shapes — pass "
                "matching label_shapes (the label batch must move with "
                "the data batch)")
        self._data_shapes, self._label_shapes = new_data, new_labels
        new = {d.name: tuple(d.shape) for d in self._data_shapes}
        if self._label_shapes:
            new.update({l.name: tuple(l.shape)
                        for l in self._label_shapes})
        self._exec = self._exec.reshape(**new)
        self._apply_shardings()
        self._fused_step = None
        self._run_steps_cache = {}

    def _reset_bind(self):
        self.binded = False
        self._exec = None
        self._fused_step = None
        self._run_steps_cache = {}

    def _apply_shardings(self):
        """Annotate the executor's args with mesh shardings: inputs batch-
        sharded over dp, params per the rules (default replicated)."""
        if self._mesh is None or self._exec is None:
            return
        from .. import parallel as _par
        mesh = self._mesh
        dp = _par.mesh_shape(mesh).get("dp", 1)
        pspecs = {}
        io_names = set(self._data_names) | set(self._label_names)
        for n, arr in self._exec.arg_dict.items():
            if n in io_names:
                if dp > 1 and arr.ndim and arr.shape[0] % dp:
                    raise MXNetError(
                        f"batch dim of {n!r} ({arr.shape[0]}) not divisible "
                        f"by dp={dp}; pad the batch (NDArrayIter pads the "
                        f"final partial batch)")
                pspecs[n] = _par.data_pspec(arr.ndim)
            else:
                pspecs[n] = _par.infer_pspec(n, arr.shape, mesh,
                                             self._sharding_rules)
        aux_pspecs = {
            n: _par.infer_pspec(n, a.shape, mesh, self._sharding_rules)
            for n, a in self._exec.aux_dict.items()}
        self._exec.set_shardings(mesh, pspecs, aux_pspecs)

    # -- optimizer ------------------------------------------------------------
    def init_optimizer(self, kvstore='local', optimizer='sgd',
                       optimizer_params=(('learning_rate', 0.01),),
                       force_init=False):
        """reference: module.py:556 init_optimizer."""
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self.logger.warning('optimizer already initialized, ignoring...')
            return
        with _tracing.phase("mx.module.init_optimizer"):
            self._init_optimizer(kvstore, optimizer, optimizer_params)

    def _init_optimizer(self, kvstore, optimizer, optimizer_params):
        if self._params_dirty:
            self._sync_params_from_devices()

        arg_params = {n: self._exec.arg_dict[n] for n in self._param_names}
        (kvstore_obj, update_on_kvstore) = _create_kvstore(
            kvstore, len(self._context), arg_params)
        batch_size = self._data_shapes[0].shape[0]
        if kvstore_obj and 'dist' in kvstore_obj.type:
            batch_size *= kvstore_obj.num_workers
        if isinstance(optimizer, str):
            idx2name = {n: n for n in self._param_names}
            optimizer_params = dict(optimizer_params)
            if 'rescale_grad' not in optimizer_params:
                # reference: module.py:486 — grads are per-batch sums
                optimizer_params['rescale_grad'] = 1.0 / batch_size
            optimizer = opt_mod.create(
                optimizer, sym=self.symbol, param_idx2name=idx2name,
                **optimizer_params)
        else:
            assert isinstance(optimizer, opt_mod.Optimizer)

        self._optimizer = optimizer
        self._kvstore = kvstore_obj
        self._update_on_kvstore = update_on_kvstore
        self._updater = None

        if kvstore_obj:
            # copy initialized params into the store
            _initialize_kvstore(kvstore=kvstore_obj,
                                param_arrays=[[arg_params[n]] for n in
                                              self._param_names],
                                arg_params=arg_params,
                                param_names=self._param_names,
                                update_on_kvstore=update_on_kvstore)
        if update_on_kvstore:
            kvstore_obj.set_optimizer(self._optimizer)
        else:
            self._updater = opt_mod.get_updater(optimizer)

        # per-param optimizer state for the fused step (multi-precision
        # prepends an fp32 master copy for fp16/bf16 weights — reference:
        # optimizer.py Updater master-weight cast).  States are created
        # beside their weight, so weights loaded on the host move to the
        # context's device first.
        if self._mesh is None:
            self._exec._arg_vals()
        self._opt_states = {
            n: optimizer.create_state_multi_precision(
                n, self._exec.arg_dict[n])
            for n in self._update_names()}
        self._shard_opt_states()

        self.optimizer_initialized = True
        if self._preload_opt_states is not None:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None

    def _update_names(self):
        return [n for n in self._param_names
                if self._grad_req.get(n, 'null') != 'null']

    def _zero_pspec(self, arr):
        """ZeRO-1 partition spec (delegates to the shared rule in
        parallel.sharding so Module and Trainer cannot diverge)."""
        from .. import parallel as _par
        return _par.zero_pspec(arr, self._zero_dp())

    def _zero_dp(self):
        from .. import parallel as _par
        if self._mesh is None:
            return 1
        return _par.mesh_shape(self._mesh).get("dp", 1)

    def _shard_opt_states(self):
        """Place every optimizer-state array (incl. fp32 master weights)
        with its ZeRO-1 sharding.  Placement here + GSPMD propagation in
        the fused jit is the whole mechanism — no collective is written
        by hand; XLA inserts reduce-scatter/all-gather over ICI."""
        if self._zero_stage < 1 or self._zero_dp() <= 1:
            return
        import jax
        from jax.sharding import NamedSharding
        mesh = self._mesh
        for n, states in self._opt_states.items():
            for s in states:
                if s is None:   # e.g. DCASGD momentum=0 slot
                    continue
                s._set_data(jax.device_put(
                    s._data, NamedSharding(mesh, self._zero_pspec(s))))

    # -- compute --------------------------------------------------------------
    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        if is_train is None:
            is_train = self.for_training
        with _tracing.span("mx.module.forward", "module"):
            kwargs = {}
            for name, arr in zip(self._data_names, data_batch.data):
                kwargs[name] = arr
            if data_batch.label is not None and self._label_names:
                for name, arr in zip(self._label_names, data_batch.label):
                    kwargs[name] = arr
            # shape change (e.g. final partial batch with pad) → jit
            # recompiles; data AND label shapes must move together
            # (reference: module.py reshape(data_shapes, label_shapes))
            io_names = self._data_names + self._label_names
            cur = {n: tuple(self._exec.arg_dict[n].shape)
                   for n in io_names if n in self._exec.arg_dict}
            new = {n: tuple(kwargs[n].shape)
                   for n in io_names if n in kwargs}
            if any(cur.get(n) != s for n, s in new.items()):
                self._exec = self._exec.reshape(**new)
                self._apply_shardings()
                self._fused_step = None
                self._run_steps_cache = {}
            self._exec.forward(is_train=is_train, **kwargs)
            self._pending_backward = False
            self._out_grads = None

    def backward(self, out_grads=None):
        """Mark backward pending; gradients materialize lazily (or fuse into
        update())."""
        assert self.binded and self.params_initialized
        self._pending_backward = True
        self._out_grads = out_grads
        exec_ = self._exec
        for name, garr in exec_.grad_dict.items():
            if garr is not None:
                garr._set_lazy(
                    lambda og=out_grads: exec_.backward(out_grads=og))

    def update(self):
        """One fused XLA program: forward + backward + optimizer update
        (reference: module.py:615 update → kvstore push/pull + updater)."""
        assert self.binded and self.params_initialized \
            and self.optimizer_initialized
        self._params_dirty = True
        opt = self._optimizer
        use_fused = (env("MXNET_EXEC_BULK_EXEC_TRAIN", True)
                     and getattr(opt, "pure_update", False)
                     and not self._update_on_kvstore
                     and getattr(self, '_out_grads', None) is None)
        if not use_fused:
            names = self._update_names()
            self._exec.backward(out_grads=getattr(self, '_out_grads', None))
            if self._update_on_kvstore:
                _update_params_on_kvstore(
                    [[self._exec.arg_dict[n]] for n in names],
                    [[self._exec.grad_dict[n]] for n in names],
                    self._kvstore, names)
            else:
                _update_params(
                    [self._exec.arg_dict[n] for n in names],
                    [self._exec.grad_dict[n] for n in names],
                    updater=self._updater, num_device=1,
                    kvstore=self._kvstore, param_names=names)
            self._pending_backward = False
            return
        if self._updated_once:
            self._update_fused(opt)
        else:
            # the first update of a Module: trace, lower, compile or
            # cache load
            with _tracing.phase("mx.module.first_update"):
                self._update_fused(opt)
            self._updated_once = True

    def _update_fused(self, opt):
        # the span closes after _fused_step_call's frame is gone: freeing
        # its ~5 references a parameter is part of what an update costs
        with _tracing.span("mx.module.update", "module") as sp:
            self._fused_step_call(opt, sp)

    def _fused_step_call(self, opt, sp):
        """One jitted call, between the host spans that split what
        dispatching it costs (docs/OBSERVABILITY.md); ``sp`` is the
        enclosing ``mx.module.update`` span's ring record, or None."""
        with _tracing.span("mx.module.update.prep", "module"):
            names = self._update_names()
            sig = opt.hyperparam_signature()
            if self._fused_step is None or \
                    self._fused_hparam_sig != sig:
                # hyperparameters (momentum, betas, rescale_grad...)
                # are baked into the trace — rebuild if they were
                # mutated mid-run
                if self._fused_step is not None:
                    self._report_rebuild(self._fused_hparam_sig, sig)
                self._fused_step = self._build_fused_step(names)
            for n in names:
                opt._update_count(n)
            t = opt._index_update_count[names[0]] if names else 1
            lrs = tuple(np.float32(opt._get_lr(n)) for n in names)
            wds = tuple(np.float32(opt._get_wd(n)) for n in names)
            # cache lr/wd device buffers while unchanged: per-step
            # host→device scalar transfers (2 per param) would
            # dominate step latency on a remote-attached chip
            cache = getattr(self, "_lrwd_cache", None)
            if cache is not None and cache[0] == (lrs, wds):
                lrs, wds = cache[1]
            else:
                key_ = (lrs, wds)
                lrs = tuple(jnp.asarray(v) for v in lrs)
                wds = tuple(jnp.asarray(v) for v in wds)
                self._lrwd_cache = (key_, (lrs, wds))
            snapshot = self._exec._snapshot
            if snapshot is None:
                raise MXNetError("update() called before forward()")
            arg_vals, aux_vals, key, _ = snapshot
            pvals = tuple(arg_vals[i] for i in self._fused_upd_idx)
            io_vals = tuple(arg_vals[i] for i in self._fused_io_idx)
            states = tuple(tuple(s._data for s in self._opt_states[n])
                           for n in names)
            # t is only read by needs_t optimizers (Adam bias
            # correction); otherwise reuse one cached device scalar
            # instead of a per-step host→device transfer
            if getattr(opt, "needs_t", False):
                t_dev = jnp.asarray(t, jnp.int32)
            else:
                t_dev = getattr(self, "_t_const", None)
                if t_dev is None:
                    t_dev = self._t_const = jnp.asarray(0, jnp.int32)
            args = (pvals, io_vals, aux_vals, key, states, lrs, wds,
                    t_dev)
        if sp is not None:
            sp.args = {"step": t}
        _prof.record_dispatch("fused_step.dispatch")
        # one integer read a step: where the compile ledger stood, so a
        # compile inside this call can be filed afterwards
        since = _tracing.compile_count()
        with _tracing.span("mx.module.update.call", "module"):
            outs, new_aux, new_params, new_states = \
                self._fused_step(*args)
        # one integer compare a step: a second entry means jit keyed
        # this call's arguments apart from the last compile's
        entries = self._fused_step._cache_size()
        if entries != self._fused_cache_size:
            self._note_step_compiled(names, args, entries, since, t)
        with _tracing.span("mx.module.update.writeback", "module"):
            exec_ = self._exec
            if exec_._out_arrays is not None:
                for oa, v in zip(exec_._out_arrays, outs):
                    oa._set_data(v)
            for a, v in zip(exec_.aux_arrays, new_aux):
                a._set_data(v)
            for n, w in zip(names, new_params):
                exec_.arg_dict[n]._set_data(w)
            for n, st in zip(names, new_states):
                for s, v in zip(self._opt_states[n], st):
                    s._set_data(v)
            if self._fused_donate:
                self._poison_after_donate()
            self._pending_backward = False

    def _step_arg_leaves(self, names, args):
        """(label, value) for every leaf of the fused step's arguments,
        labelled by what the Module calls it."""
        pvals, io_vals, aux_vals, key, states, lrs, wds, t = args
        arg_names = self._exec._arg_names
        leaves = [("param %s" % n, v) for n, v in zip(names, pvals)]
        leaves += [("input %s" % arg_names[i], v)
                   for i, v in zip(self._fused_io_idx, io_vals)]
        leaves += [("aux %s" % n, v)
                   for n, v in zip(self._exec._aux_names, aux_vals)]
        leaves.append(("rng key", key))
        for n, st in zip(names, states):
            leaves += [("optimizer state %d of %s" % (j, n), v)
                       for j, v in enumerate(st)]
        leaves += [("lr of %s" % n, v) for n, v in zip(names, lrs)]
        leaves += [("wd of %s" % n, v) for n, v in zip(names, wds)]
        leaves.append(("t", t))
        return leaves

    def _note_step_compiled(self, names, args, entries, since, step):
        """The fused step's jit cache grew at this call.  The first entry
        is the expected compile; a later one is a recompile: counted
        (``fused_step.recompile``), timed (the compile ledger's records
        since ``since``, filed as phase ``mx.module.recompile``), marked
        in the trace, and explained on stderr by what differs, leaf by
        leaf, between this call's arguments and those of the last compile
        in what ``jit`` keys on.  Runs only when the cache grows."""
        key = {label: _jit_leaf_key(v)
               for label, v in self._step_arg_leaves(names, args)}
        before, self._fused_jit_key = self._fused_jit_key, key
        self._fused_cache_size = entries
        if entries <= 1 or before is None:
            return
        _prof.record_dispatch("fused_step.recompile")
        seconds, cache = _tracing.file_compiles("mx.module.recompile", since)
        moved = {}      # (field, before, after) -> [leaf labels]
        for label, now in key.items():
            was = before.get(label, {})
            for field in _JIT_KEY_FIELDS:
                if was.get(field) != now[field]:
                    moved.setdefault((field, was.get(field), now[field]),
                                     []).append(label)
        _report_step_compile(
            "mx.module.update.recompile",
            "the fused step compiled again (jit cache entry %d): between "
            "the last compile's arguments and this call's" % entries,
            moved, seconds=round(seconds, 6), step=step,
            cache="/".join(cache) or "none")

    def _report_rebuild(self, was, now):
        """The optimizer's hyperparameter signature moved between two
        updates: the step is built, traced and compiled anew."""
        was, now = dict(was or ()), dict(now)
        moved = {(k, was.get(k), now.get(k)): ["optimizer"]
                 for k in sorted(set(was) | set(now))
                 if was.get(k) != now.get(k)}
        _prof.record_dispatch("fused_step.rebuild")
        _report_step_compile(
            "mx.module.update.rebuild",
            "the fused step is rebuilt: the optimizer's hyperparameter "
            "signature moved", moved)

    def _poison_after_donate(self):
        """A donated step consumed the old param/aux/state buffers; the
        pre-step snapshots and any lazy thunks referencing them
        (gradients, outputs from earlier forwards) are no longer
        executable — poison them with a clear error."""
        from ..executor import poison_stale
        exec_ = self._exec
        exec_._snapshot = None
        for name, garr in exec_.grad_dict.items():
            if garr is not None and garr._thunk is not None:
                poison_stale(garr, "gradient")
        for ref in exec_._issued_outs:
            oarr = ref()
            if oarr is not None and oarr._thunk is not None:
                poison_stale(oarr, "output")
        exec_._issued_outs = []

    def _split_arg_idx(self, names):
        """Partition executor arg positions into (updated params, io) —
        the ONE source of truth for the index layout shared by the step
        body (_make_step_body) and the scan driver's io scatter
        (_run_steps_fused)."""
        arg_names = self._exec._arg_names
        upd_idx = [arg_names.index(n) for n in names]
        upd_set = set(upd_idx)
        io_idx = [i for i in range(len(arg_names)) if i not in upd_set]
        return upd_idx, io_idx

    def _make_step_body(self, names, with_grads=False):
        """Build the PURE single fused-step function
        ``step(pvals, io_vals, aux_vals, key, states, lrs, wds, t) ->
        (outs, new_aux, new_params, new_states)`` shared by the per-step
        jit (update) and the K-step scan (run_steps): both drivers trace
        the SAME body, so scanned training is bit-equivalent to eager
        fused steps by construction.

        ``with_grads`` appends the raw (pre-rescale) per-param gradients
        to the return — the fused-dist driver ships exactly these over
        the kvstore wire, the same quantity the eager dist loop reads
        from grad_dict, while the LOCAL update the body already applied
        keeps the in-chunk weight trajectory fresh (the worker-side
        replica of the server's update)."""
        exec_ = self._exec
        run = exec_._run
        arg_names = exec_._arg_names
        upd_idx, io_idx = self._split_arg_idx(names)
        self._fused_upd_idx = upd_idx
        self._fused_io_idx = io_idx
        opt = self._optimizer
        needs_t = getattr(opt, "needs_t", False)
        # static per-param decision: multi-precision iff a master fp32 copy
        # was prepended by create_state_multi_precision
        use_mp = [opt.mp_states_active(exec_.arg_dict[n],
                                       self._opt_states[n])
                  for n in names]

        from ..executor import maybe_mirror
        run_fwd = maybe_mirror(run)
        zero1 = self._zero_stage >= 1 and self._zero_dp() > 1
        constrain = self._mesh is not None
        if constrain:
            from .. import parallel as _par
            # params leave the step in their RULE sharding (tp weights
            # stay tp-sharded; replicated params replicated) — an
            # unconditional P() here would all-gather tensor-parallel
            # weights onto every chip.  Pinning is REQUIRED on any mesh,
            # not just under ZeRO: free GSPMD propagation may emit a
            # param with a different sharding than the next forward's
            # declared in_sharding, and on a process-spanning mesh the
            # executor cannot fall back to a host round-trip to fix it.
            param_pspecs = [
                _par.infer_pspec(n, self._exec.arg_dict[n].shape,
                                 self._mesh, self._sharding_rules)
                for n in names]

        # the name is the compiled module's (jit_mx_fused_step) and part
        # of its key in the persistent compile cache
        def mx_fused_step(pvals, io_vals, aux_vals, key, states, lrs, wds,
                          t):
            def f(pv):
                av = [None] * len(arg_names)
                for i, v in zip(upd_idx, pv):
                    av[i] = v
                for i, v in zip(io_idx, io_vals):
                    av[i] = v
                outs, new_aux = run_fwd(tuple(av), aux_vals, key, True)
                diff = tuple(o for o in outs
                             if jnp.issubdtype(o.dtype, jnp.inexact))
                return diff, (outs, new_aux)

            diff, vjp_fn, (outs, new_aux) = jax.vjp(f, pvals, has_aux=True)
            cts = tuple(jnp.ones(o.shape, o.dtype) for o in diff)
            grads = vjp_fn(cts)[0]
            # per-param dispatch shared with Trainer (optimizer.apply_fused
            # owns the multi-precision contract)
            with jax.named_scope("optimizer"):
                new_params, new_states = opt.apply_fused(
                    pvals, grads, states, lrs, wds, use_mp,
                    ts=(t,) * len(names) if needs_t else None)
            with jax.named_scope("param_constraint"):
                if constrain:
                    # pin the schedule: params leave the step in their
                    # rule sharding (under ZeRO-1 the dp all-gather
                    # happens HERE, inside the fused program, overlapped
                    # by XLA)
                    from jax.sharding import NamedSharding
                    mesh_ = self._mesh
                    new_params = tuple(
                        jax.lax.with_sharding_constraint(
                            w, NamedSharding(mesh_, ps))
                        for w, ps in zip(new_params, param_pspecs))
                if zero1:
                    # state math stays dp-sharded (GSPMD reduce-scatters
                    # the grads feeding it)
                    new_states = _par.constrain_zero_states(
                        new_states, self._mesh, self._zero_dp())
            if with_grads:
                return (outs, new_aux, tuple(new_params),
                        tuple(new_states), tuple(grads))
            return outs, new_aux, tuple(new_params), tuple(new_states)

        return mx_fused_step

    def _build_fused_step(self, names):
        # Donate the buffers the step replaces — params, aux (BN stats),
        # optimizer state — so XLA updates them in place in HBM (the analog
        # of the reference's in-place engine writes; halves peak param
        # memory and removes copy traffic).
        with _tracing.phase("mx.module.build_step"):
            self._fused_donate = bool(env("MXNET_FUSED_DONATE", True))
            donate = (0, 2, 4) if self._fused_donate else ()
            self._fused_cache_size = 0
            self._fused_hparam_sig = self._optimizer.hyperparam_signature()
            return jax.jit(self._make_step_body(names),
                           donate_argnums=donate)

    # -- multi-step driver --------------------------------------------------
    def run_steps(self, data, label=None, k=None, eval_metric=None):
        """Run K fused training steps as ONE XLA program (`jax.lax.scan`
        over the fused fwd+bwd+update body): one host dispatch launches
        all K steps, amortizing the per-dispatch host cost to 1/K per
        step — the whole-program TPU execution move of Fischer & Saba
        (arXiv:1810.09868), and the engine-level overlap idea of MXNet
        taken to its limit: the host leaves the training loop entirely.

        ``data``/``label`` carry the K batches stacked on a leading step
        axis (array ``(k, batch, ...)``, dict name->array, or a list of
        per-step batches for a single input).  Parameters, aux states
        (BatchNorm statistics) and optimizer state flow step-to-step in
        the scan carry, with their buffers donated (in-place HBM
        updates); per-step lr/wd schedules and update counts are
        precomputed host-side so schedules advance exactly as K eager
        ``update()`` calls would.  Host-visible values (the per-step
        outputs — loss heads included) accumulate as stacked scan
        outputs and are read back ONCE per call: pass ``eval_metric`` to
        fold them into a metric here (single readback), or read the
        returned stacked outputs yourself.

        The compiled program is cached per (K, shapes, param set,
        optimizer hyperparameters).  dist_async update-on-kvstore runs
        the CHUNKED variant of the same program — one dispatch per
        ``MXNET_KVSTORE_FUSED_CHUNK`` steps with the grad-push/weight-
        pull wire overlapped behind the next chunk's compute
        (:meth:`_run_steps_fused_dist`).  Falls back to the eager
        per-step driver (BaseModule.run_steps) for K=1, shape changes
        vs the bound shapes (bucketing / variable shapes), non-pure
        optimizers, non-dist_async update-on-kvstore,
        ``MXNET_KVSTORE_FUSED=0``, and
        ``MXNET_EXEC_BULK_EXEC_TRAIN=0`` — same math, K dispatches.

        Returns the per-step outputs stacked on a leading K axis, one
        NDArray per output; scanned training is bit-equivalent to K
        eager fused steps because both trace the SAME step body
        (tests/test_run_steps.py pins this).
        """
        assert self.binded and self.params_initialized \
            and self.optimizer_initialized
        from .base_module import _canon_step_inputs
        data_arrays, k = _canon_step_inputs(
            self._data_names, data, "data", k)
        label_arrays, k = _canon_step_inputs(
            self._label_names, label, "label", k)
        opt = self._optimizer
        names = self._update_names()
        shapes_ok = all(
            tuple(a.shape[1:]) == tuple(self._exec.arg_dict[n].shape)
            for n, a in zip(self._data_names + self._label_names,
                            data_arrays + label_arrays))
        fusable = (k > 1 and bool(names) and shapes_ok
                   and env("MXNET_EXEC_BULK_EXEC_TRAIN", True)
                   and getattr(opt, "pure_update", False))
        if self._update_on_kvstore:
            # dist_async update-on-kvstore no longer falls back to eager:
            # the chunked driver scans fwd+bwd+local-update per chunk and
            # overlaps the push/pull wire behind the next chunk's compute
            # (_run_steps_fused_dist).  Other update-on-kvstore stores
            # (local multi-device, dist_sync) keep the eager per-step
            # loop — they have no async wire to overlap.  Elastic jobs
            # ride the chunked driver too: an in-flight pull_async
            # handle now REPLANS itself against the post-bump stripe
            # layout from inside wait() (kvstore._PullHandle._replan;
            # docs/ROBUSTNESS.md replan contract), and the push leg
            # already repaired+rerouted.
            if (fusable and self._kvstore is not None
                    and getattr(self._kvstore, "type", "") == "dist_async"
                    and env("MXNET_KVSTORE_FUSED", True)):
                return self._run_steps_fused_dist(
                    data_arrays, label_arrays, k, names, eval_metric)
            return self._run_steps_eager(data_arrays, label_arrays, k,
                                         eval_metric)
        if not fusable:
            return self._run_steps_eager(data_arrays, label_arrays, k,
                                         eval_metric)
        return self._run_steps_fused(data_arrays, label_arrays, k, names,
                                     eval_metric)

    def _compile_run_steps_scan(self, names, eval_metric, use_dev_metric,
                                donate, with_grads=False):
        """Compiled K-step scan program over the fused step body, cached
        per (param set, optimizer hyperparameters, donation, metric
        device signature, grads-on-the-wire) — shared by the local
        fused driver (:meth:`_run_steps_fused`) and the dist_async
        chunked driver (:meth:`_run_steps_fused_dist`), which
        additionally scans the per-step raw gradients out for the
        kvstore wire.  Returns
        ``(fn, upd_idx, io_idx, step_pos, const_pos)``."""
        exec_ = self._exec
        arg_names = exec_._arg_names
        upd_idx, io_idx = self._split_arg_idx(names)
        step_names = set(self._data_names) | set(self._label_names)
        step_pos = [j for j, i in enumerate(io_idx)
                    if arg_names[i] in step_names]
        const_pos = [j for j, i in enumerate(io_idx)
                     if arg_names[i] not in step_names]
        cache = self._run_steps_cache
        cache_key = (tuple(names), self._optimizer.hyperparam_signature(),
                     donate, with_grads,
                     eval_metric._device_sig() if use_dev_metric else None)
        from ..executor import scan_cache_lookup, scan_cache_store
        fn = scan_cache_lookup(cache, cache_key)
        if fn is None:
            from ..executor import build_multi_step
            body = self._make_step_body(names, with_grads=with_grads)
            metric = eval_metric if use_dev_metric else None
            out_names = self._output_names
            # label name -> stacked-input slot, in LABEL_NAMES order:
            # the metric fold must see labels exactly as update_metric
            # presents them (dict insertion order feeds _select_dict)
            step_arg_names = [arg_names[io_idx[j]] for j in step_pos]
            label_slots = [(nm, step_arg_names.index(nm))
                           for nm in self._label_names
                           if nm in step_arg_names]

            def scan_body(carry, x, const):
                pvals, aux_vals, states, mstate = carry
                step_io, key, lrs, wds, t = x
                io_vals = [None] * len(io_idx)
                for j, v in zip(step_pos, step_io):
                    io_vals[j] = v
                for j, v in zip(const_pos, const):
                    io_vals[j] = v
                res = body(pvals, tuple(io_vals), aux_vals, key, states,
                           lrs, wds, t)
                outs, new_aux, new_params, new_states = res[:4]
                if metric is not None:
                    mstate = metric.device_update_dict(
                        mstate,
                        {nm: step_io[i] for nm, i in label_slots},
                        dict(zip(out_names, outs)))
                ys = (outs, res[4]) if with_grads else outs
                return (new_params, new_aux, new_states, mstate), ys

            fn = scan_cache_store(cache, cache_key,
                                  build_multi_step(scan_body,
                                                   donate=donate))
        return fn, upd_idx, io_idx, step_pos, const_pos

    def _run_steps_fused(self, data_arrays, label_arrays, k, names,
                         eval_metric):
        exec_ = self._exec
        opt = self._optimizer
        arg_names = exec_._arg_names
        donate = bool(env("MXNET_FUSED_DONATE", True))
        # metric accumulation rides the scan carry when the metric has a
        # device form: K steps of metrics cost ZERO extra dispatches and
        # ZERO readbacks — the state stays on device until a callback
        # syncs it (the tentpole of the sync-free loop; metrics without
        # a device form keep the old one-readback host fold below)
        use_dev_metric = (eval_metric is not None
                          and getattr(eval_metric, "device_enabled",
                                      lambda: False)())
        fn, upd_idx, io_idx, step_pos, const_pos = \
            self._compile_run_steps_scan(names, eval_metric,
                                         use_dev_metric, donate)
        self._fused_upd_idx = upd_idx
        self._fused_io_idx = io_idx
        self._fused_donate = donate

        # per-step lr/wd/t precomputed host-side (shared helper with
        # Trainer.step_k): schedules advance exactly as K eager update()
        # calls would, then travel as (k,)-arrays scanned with the data,
        # so mid-scan lr changes cost nothing.  The step body takes ONE
        # t per step (all names update together), so ts uses column 0.
        # schedule_rollback keeps the host schedule state transactional
        # with the dispatch: a failed compile/launch must not leave
        # counts K steps ahead of the params.
        from ..executor import precompute_step_schedules, schedule_rollback
        with schedule_rollback(opt):
            lrs, wds, tcols = precompute_step_schedules(opt, names, k)
            ts = tcols[0]

            # per-step RNG keys consume the global counter exactly like
            # K eager forwards; RNG-free programs share one constant key
            # (same discipline as random.key_for)
            run = exec_._run
            if getattr(run, "needs_rng", False):
                keys = jnp.stack([_rnd.next_key() for _ in range(k)])
            else:
                keys = jnp.stack([_rnd.key_for(run)] * k)

            arg_vals = exec_._arg_vals()
            aux_vals = exec_._aux_vals()
            pvals = tuple(arg_vals[i] for i in upd_idx)
            const = tuple(arg_vals[io_idx[j]] for j in const_pos)
            step_io = tuple(self._stacked_input(arg_names[io_idx[j]],
                                                data_arrays, label_arrays)
                            for j in step_pos)
            states = tuple(tuple(s._data for s in self._opt_states[n])
                           for n in names)
            # seed the metric carry from any pending device state, so a
            # log interval spanning eager batches AND run_steps calls
            # accumulates continuously.  _take (not peek): the carry is
            # DONATED — detaching first means a failed dispatch leaves
            # the metric empty, not pointing at deleted buffers
            init_m = eval_metric._take_device_state() \
                if use_dev_metric else ()

            _prof.record_dispatch("run_steps.dispatch")
            with _tracing.span("mx.module.run_steps.call", "module"):
                (new_pvals, new_aux, new_states, new_m), ys = fn(
                    (pvals, aux_vals, states, init_m),
                    (step_io, keys, lrs, wds, ts), const)
        self._params_dirty = True
        for n, w in zip(names, new_pvals):
            exec_.arg_dict[n]._set_data(w)
        for a, v in zip(exec_.aux_arrays, new_aux):
            a._set_data(v)
        for n, st in zip(names, new_states):
            for s, v in zip(self._opt_states[n], st):
                s._set_data(v)
        if donate:
            self._poison_after_donate()
        self._pending_backward = False

        # expose the LAST step's outputs through get_outputs() (lazy: the
        # slice dispatches only if actually read)
        from ..executor import make_lazy_outputs

        def last_thunk(outs):
            def thunk():
                for oa, y in zip(outs, ys):
                    oa._set_data(y[-1])
            return thunk

        exec_._out_arrays = make_lazy_outputs(
            exec_._out_aval_list(True), last_thunk)

        stacked = [NDArray(y) for y in ys]
        if use_dev_metric:
            # K steps of metrics came back as the scan carry — adopt it
            # as the metric's pending state; a later sync() (callback /
            # get_name_value) is the only readback
            eval_metric._absorb_device_state(new_m)
        elif eval_metric is not None:
            self._fold_metric(eval_metric, label_arrays, ys, k)
        return stacked

    def _run_steps_fused_dist(self, data_arrays, label_arrays, k, names,
                              eval_metric):
        """K update-on-kvstore steps as a CHUNKED scan with the wire
        overlapped behind compute — dispatch amortization and the
        pipelined dist_async wire finally compose (the MXNet
        dependency-engine thesis rebuilt on XLA async dispatch).

        The scanned body is the SAME fused step as the local driver —
        fwd+bwd plus a LOCAL optimizer update (the worker-side replica
        of the server's updater; both run ``Optimizer._update_impl``)
        — so the in-chunk weight trajectory stays fresh, and it
        additionally scans out the raw per-step gradients.  Per chunk
        of ``MXNET_KVSTORE_FUSED_CHUNK`` steps the host reads those
        gradients back in ONE stacked device_get, pushes them per step
        through the pipelined window (small keys coalesce per
        envelope) and enqueues a non-blocking ``pull_async``; the
        round resolves while the NEXT chunk computes
        (executor.drive_chunked_dist), and its server-authoritative
        weights replace the carry exactly
        ``MXNET_KVSTORE_FUSED_STALENESS`` chunk boundaries later.
        Staleness 0 degrades to a barrier'd boundary: single-worker it
        is bit-identical to the eager dist loop (the local replica and
        the server apply identical update sequences); multi-worker the
        contract is the elastic handoff one — bit-identical at
        quiescent sync points for commutative updates, async-SGD-grade
        in between.  Optimizer state and aux (BN stats) stay
        worker-local between sync points; the final pull is adopted as
        the authoritative weights (fp32 masters included for
        multi-precision params), exactly like the eager loop's last
        pull.  Under MXNET_KVSTORE_ELASTIC a roster bump mid-drive is
        survivable: the push leg repairs and re-routes through
        _submit_planned, and an in-flight pull handle replans its
        unserved stripes against the new layout from inside wait()
        (docs/ROBUSTNESS.md replan contract).  Transport kills still
        recover through the window replay underneath; a HARD failure
        mid-drive writes the carry's last chunk-output state back so
        the module stays readable, then raises."""
        exec_ = self._exec
        opt = self._optimizer
        kv = self._kvstore
        arg_names = exec_._arg_names
        donate = bool(env("MXNET_FUSED_DONATE", True))
        use_dev_metric = (eval_metric is not None
                          and getattr(eval_metric, "device_enabled",
                                      lambda: False)())
        fn, upd_idx, io_idx, step_pos, const_pos = \
            self._compile_run_steps_scan(names, eval_metric,
                                         use_dev_metric, donate,
                                         with_grads=True)
        self._fused_upd_idx = upd_idx
        self._fused_io_idx = io_idx
        self._fused_donate = donate

        from ..executor import (drive_chunked_dist, fused_dist_knobs,
                                precompute_step_schedules,
                                schedule_rollback)
        chunk, staleness = fused_dist_knobs(k)
        shapes = {n: tuple(exec_.arg_dict[n].shape) for n in names}
        # multi-precision params update on the fp32 master in states[0]
        # (apply_fused recasts the weight from it), so adopting pulled
        # server weights must ALSO overwrite the master — replacing only
        # pvals would be recomputed away on the very next step
        use_mp = [opt.mp_states_active(exec_.arg_dict[n],
                                       self._opt_states[n])
                  for n in names]
        with schedule_rollback(opt):
            # worker-side schedules advance per step exactly as the
            # server's per-push counts do (single worker: identical lr
            # sequence; multi-worker the server counts all ranks'
            # pushes — the same server-authoritative behavior the
            # eager dist loop has)
            lrs, wds, tcols = precompute_step_schedules(opt, names, k)
            ts = tcols[0]
            run = exec_._run
            if getattr(run, "needs_rng", False):
                keys = jnp.stack([_rnd.next_key() for _ in range(k)])
            else:
                keys = jnp.stack([_rnd.key_for(run)] * k)
            arg_vals = exec_._arg_vals()
            aux_vals = exec_._aux_vals()
            const = tuple(arg_vals[io_idx[j]] for j in const_pos)
            step_io = tuple(self._stacked_input(arg_names[io_idx[j]],
                                                data_arrays, label_arrays)
                            for j in step_pos)
            init_m = eval_metric._take_device_state() \
                if use_dev_metric else ()
            carry = {
                "pvals": tuple(arg_vals[i] for i in upd_idx),
                "aux": aux_vals,
                "states": tuple(
                    tuple(s._data for s in self._opt_states[n])
                    for n in names),
                "m": init_m,
                "outs": [],
            }

            def adopt(adopted):
                # chunk-boundary re-sync: the carry WEIGHTS adopt the
                # pulled server values (authoritative — they include
                # every worker's pushes through the due chunk); for a
                # multi-precision param the fp32 MASTER in states[0]
                # adopts too (the update runs on it and recasts the
                # weight, so it is the real carrier).  The rest of the
                # optimizer state and aux stay local — the
                # async-SGD-grade part of the contract.
                pvals, states = [], list(carry["states"])
                for i, n in enumerate(names):
                    w = jnp.asarray(adopted[n])
                    if use_mp[i]:
                        master = w.astype(jnp.float32)
                        states[i] = (master,) + tuple(states[i][1:])
                        w = master.astype(exec_.arg_dict[n].dtype)
                    else:
                        w = w.astype(exec_.arg_dict[n].dtype)
                    pvals.append(w)
                carry["pvals"] = tuple(pvals)
                carry["states"] = tuple(states)

            def dispatch_chunk(j, lo, hi, adopted):
                if adopted is not None:
                    adopt(adopted)
                xs = (tuple(a[lo:hi] for a in step_io), keys[lo:hi],
                      tuple(v[lo:hi] for v in lrs),
                      tuple(v[lo:hi] for v in wds), ts[lo:hi])
                _prof.record_dispatch("run_steps.dist_chunk")
                with _tracing.span("mx.module.run_steps.dist_chunk.call",
                                   "module"):
                    (new_p, new_aux, new_st, new_m), (outs, grads) = fn(
                        (carry["pvals"], carry["aux"], carry["states"],
                         carry["m"]), xs, const)
                carry.update(pvals=new_p, aux=new_aux, states=new_st,
                             m=new_m)
                carry["outs"].append(outs)
                # ONE stacked readback of the chunk's per-step raw
                # gradients — the wire needs host bytes; this blocks on
                # the chunk's COMPUTE only (the wire round itself is
                # what the driver overlaps behind the next chunk)
                grads_np = jax.device_get(grads)
                _prof.record_host_sync("run_steps.dist_grad_readback")
                return grads_np

            def ship_chunk(j, grads_np):
                return kv.ship_chunk_steps(names, grads_np,
                                           [shapes[n] for n in names])

            try:
                final = drive_chunked_dist(k, chunk, staleness,
                                           dispatch_chunk, ship_chunk)
            except BaseException:
                # a wire failure mid-drive lands AFTER earlier chunks
                # donated the original param/aux/state buffers — but the
                # carry holds the latest chunk's OUTPUT arrays (alive):
                # write them back so the module stays readable at the
                # last locally-completed step, and poison the stale lazy
                # handles exactly like the success path does
                self._writeback_dist_carry(names, carry)
                if donate:
                    self._poison_after_donate()
                raise

        self._params_dirty = True
        # the FINAL pull is the sync point: the local params adopt the
        # server-authoritative weights, exactly how the eager dist
        # loop's last per-step pull leaves them (fp32 masters included)
        adopt(final)
        self._writeback_dist_carry(names, carry)
        if donate:
            self._poison_after_donate()
        self._pending_backward = False

        ys = [jnp.concatenate([c[i] for c in carry["outs"]])
              if len(carry["outs"]) > 1 else carry["outs"][0][i]
              for i in range(len(self._output_names))]

        from ..executor import make_lazy_outputs

        def last_thunk(outs):
            def thunk():
                for oa, y in zip(outs, ys):
                    oa._set_data(y[-1])
            return thunk

        exec_._out_arrays = make_lazy_outputs(
            exec_._out_aval_list(True), last_thunk)

        stacked = [NDArray(y) for y in ys]
        if use_dev_metric:
            eval_metric._absorb_device_state(carry["m"])
        elif eval_metric is not None:
            self._fold_metric(eval_metric, label_arrays, ys, k)
        return stacked

    def _writeback_dist_carry(self, names, carry):
        """Write the dist driver's carry (latest chunk-output params,
        aux, optimizer states) back into the executor — the shared tail
        of the success path (after adopting the final pull) and the
        mid-drive failure path (where the carry is the last consistent
        local state the donated originals can be replaced with)."""
        exec_ = self._exec
        for n, w in zip(names, carry["pvals"]):
            exec_.arg_dict[n]._set_data(w)
        for a, v in zip(exec_.aux_arrays, carry["aux"]):
            a._set_data(v)
        for n, st in zip(names, carry["states"]):
            for s_arr, v in zip(self._opt_states[n], st):
                s_arr._set_data(v)

    def _stacked_input(self, name, data_arrays, label_arrays):
        """Device value for one stacked (k, batch, ...) input, with the
        batch axis (axis 1 of the stack) dp-sharded when a mesh is set."""
        io_names = self._data_names + self._label_names
        arr = (data_arrays + label_arrays)[io_names.index(name)]
        if self._mesh is None:
            return self._exec._placed_like(name, jnp.asarray(arr))
        from .. import parallel as _par
        from jax.sharding import NamedSharding, PartitionSpec
        per_step = _par.data_pspec(np.ndim(arr) - 1)
        sh = NamedSharding(self._mesh,
                           PartitionSpec(None, *tuple(per_step)))
        return self._exec._sharded(jnp.asarray(arr), sh)

    def _fold_metric(self, eval_metric, label_arrays, ys, k):
        """Host fallback for metrics without a device form: ONE host
        readback for all K steps' outputs, then fold them into the
        metric per step.  Values are NDArray-wrapped — the classic
        custom-metric contract (user update() may call .asnumpy()), at
        the price of the legacy path's per-value syncs."""
        host_outs = jax.device_get(ys)
        _prof.record_dispatch("run_steps.readback")
        _prof.record_host_sync("run_steps.metric_fold")
        labels_np = [np.asarray(a) for a in label_arrays]
        for j in range(k):
            eval_metric.update_dict(
                {n: NDArray(a[j]) for n, a in
                 zip(self._label_names, labels_np)},
                {n: NDArray(o[j]) for n, o in
                 zip(self._output_names, host_outs)})

    def _lower_fused_step(self):
        """Trace+lower one fused training step (no backend compile).
        Requires a bound, optimizer-initialized module with a fresh
        forward() snapshot (i.e. call right after forward())."""
        if not self.optimizer_initialized:
            raise MXNetError("fused step: call init_optimizer() first")
        names = self._update_names()
        if self._fused_step is None:
            self._fused_step = self._build_fused_step(names)
        snapshot = self._exec._snapshot
        if snapshot is None:
            raise MXNetError("fused step: call forward() first")
        arg_vals, aux_vals, key, _ = snapshot
        pvals = tuple(arg_vals[i] for i in self._fused_upd_idx)
        io_vals = tuple(arg_vals[i] for i in self._fused_io_idx)
        states = tuple(tuple(s._data for s in self._opt_states[n])
                       for n in names)
        lrs = tuple(np.float32(1e-3) for _ in names)
        wds = tuple(np.float32(0.0) for _ in names)
        return self._fused_step.lower(
            pvals, io_vals, aux_vals, key, states, lrs, wds,
            jnp.asarray(1, jnp.int32))

    def fused_step_flops(self):
        """XLA cost-analysis FLOPs of one fused training step (for MFU
        reporting), read from the COMPILED program: the TPU client only
        analyses what its compiler has scheduled (the un-compiled
        ``Lowered.cost_analysis()`` this used to call answers on the CPU
        backend only).  Returns a positive number or raises."""
        ca = self._lower_fused_step().compile().cost_analysis()
        flops = float((ca or {}).get("flops", 0.0))
        if not flops > 0.0:
            raise MXNetError(
                "fused step: XLA cost analysis reported no FLOPs (%r)"
                % (ca,))
        return flops

    def fused_step_hlo(self):
        """StableHLO text of the fused training step (pre-backend-opt) —
        the dtype contract is visible here: in bf16 compute_dtype mode
        every convolution/dot must consume bf16 operands (the AMP split
        keeps only statistics/loss in fp32).  Used by tests/test_amp_hlo.py
        to pin the MFU-critical precision layout without a chip."""
        return self._lower_fused_step().as_text()

    def get_outputs(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        return self._exec.outputs

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.params_initialized and \
            self.inputs_need_grad
        return [self._exec.grad_dict[n] for n in self._data_names]

    def update_metric(self, eval_metric, labels):
        """Device-resident when the metric supports it: accumulation
        stays on the async engine (metric.EvalMetric.accumulate_dict)
        and the host only syncs when a callback reads the metric — the
        training loop itself never blocks on a device->host readback
        (was: one asnumpy per output per batch through
        EvalMetric.update)."""
        # the iterator left the labels on cpu(0); the outputs live where
        # the step ran, and the metric reads both in one program
        labels = [NDArray(self._exec._placed_like(n, l._data))
                  if isinstance(l, NDArray) else l
                  for n, l in zip(self._label_names, labels or [])]
        eval_metric.accumulate_dict(
            dict(zip(self._label_names, labels)),
            dict(zip(self._output_names, self.get_outputs())))

    # -- state ---------------------------------------------------------------
    def _sync_params_from_devices(self):
        self._params_dirty = False

    def save_optimizer_states(self, fname):
        """reference: module.py save_optimizer_states."""
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
        else:
            import pickle
            import jax
            # ONE stacked readback for every state tensor (was one
            # np.asarray sync per state), recorded under the host-sync
            # contract like every other deliberate readback site
            states = jax.device_get(
                {n: tuple(s._data for s in st)
                 for n, st in self._opt_states.items()})
            _prof.record_host_sync("module.save_optimizer_states")
            with open(fname, 'wb') as fout:
                pickle.dump(states, fout)

    def load_optimizer_states(self, fname):
        """reference: module.py load_optimizer_states."""
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
        else:
            import pickle
            with open(fname, 'rb') as fin:
                # analysis: allow(unsafe-pickle): trusted LOCAL checkpoint file named by the caller — never bytes off the wire (those decode in kvstore_server through the allowlist)
                states = pickle.load(fin)
            for n, st in states.items():
                if n in self._opt_states:
                    for s, v in zip(self._opt_states[n], st):
                        if s is not None:
                            s._set_data(jnp.asarray(v))
            # restored buffers land unsharded; re-apply ZeRO-1 placement
            # immediately or the resume step would hold full O(P)
            # optimizer state per chip — the very peak ZeRO avoids
            self._shard_opt_states()

    def bump_serving_version(self, version=None):
        """Publish the CURRENT server-side weights to serving replicas
        watching this job's parameter servers (the train-and-serve
        topology, docs/SERVING.md).  Requires update-on-kvstore over a
        dist store — in that mode the servers' weights are the live
        weights by construction, so publication is just a version bump
        (:func:`mxnet_tpu.serving.publish_version`); replicas ``pull()``
        the refreshed parameters on their next refresh check."""
        assert self.optimizer_initialized
        if self._kvstore is None or not self._update_on_kvstore \
                or 'dist' not in self._kvstore.type:
            raise MXNetError(
                "bump_serving_version needs update-on-kvstore over a "
                "dist store (the servers must HOLD the live weights a "
                "replica can pull) — init_optimizer(kvstore='dist_async')")
        from ..serving import publish_version
        return publish_version(self._kvstore, version)

    def borrow_optimizer(self, shared_module):
        """Share optimizer/updater/state with another Module
        (reference: module.py borrow_optimizer — BucketingModule makes all
        buckets apply updates through one optimizer)."""
        assert shared_module.optimizer_initialized
        self._optimizer = shared_module._optimizer
        self._kvstore = shared_module._kvstore
        self._update_on_kvstore = shared_module._update_on_kvstore
        self._updater = shared_module._updater
        self._opt_states = shared_module._opt_states
        self.optimizer_initialized = True

    def get_states(self, merge_multi_context=True):
        """Current values of the state inputs, as immutable snapshots
        (reference: module.py get_states — a later set_states must not
        change what the caller saved, e.g. TBPTT save/restore).  The
        returned NDArrays alias the live executor buffers (jnp.asarray is
        zero-copy): the snapshot guarantee rests on jax.Array immutability
        plus set_states REBINDING rather than mutating.  If these buffers
        are ever fed to a donating computation, switch this to a real copy
        (jnp.array(..., copy=True))."""
        assert self.binded and self.params_initialized
        from ..ndarray import NDArray as _ND
        return [_ND(jnp.asarray(self._exec.arg_dict[n]._data))
                for n in self._state_names]

    def set_states(self, states=None, value=None):
        """Set state inputs from arrays or a scalar fill (reference:
        module.py set_states)."""
        assert self.binded and self.params_initialized
        assert (states is None) != (value is None), \
            "provide exactly one of states/value"
        if value is not None:
            for n in self._state_names:
                arr = self._exec.arg_dict[n]
                arr._set_data(jnp.full(arr.shape, value,
                                       np.dtype(arr.dtype)))
            return
        assert len(states) == len(self._state_names), \
            (len(states), self._state_names)
        for n, s in zip(self._state_names, states):
            src = s[0] if isinstance(s, (list, tuple)) else s
            self._exec.arg_dict[n]._set_data(jnp.asarray(src._data))

    def install_monitor(self, mon):
        assert self.binded
        mon.install(self._exec)

    def prepare(self, data_batch):
        pass
