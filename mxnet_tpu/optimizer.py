"""Optimizers (reference: python/mxnet/optimizer.py:434-1106).

Each optimizer implements a *pure* functional update
``_update_impl(weight, grad, states, lr, wd) -> (new_weight, new_states)``
on jax arrays.  The imperative :meth:`update` wraps it for NDArray handles
(the reference's engine-routed optimizer ops, src/operator/optimizer_op.cc);
the Module/Trainer fused training step calls ``_update_impl`` *inside* the
jitted step so weight updates fuse with the backward pass and donated
buffers update in place at the XLA level.
"""
from __future__ import annotations

import logging
import math
import pickle
from typing import Dict, List, Optional, Tuple

import numpy as np
import jax.numpy as jnp

from .base import MXNetError, Registry
from .ndarray import NDArray
from .ndarray.ndarray import zeros as nd_zeros

_OPT_REGISTRY = Registry("optimizer")


class Optimizer:
    """Base optimizer (reference: optimizer.py Optimizer)."""

    # True when _update_impl is a pure jax function safe to trace inside the
    # Module fused training step (stateless given lr/wd/t args)
    pure_update = False

    def __init__(self, rescale_grad=1.0, param_idx2name=None, wd=0.0,
                 clip_gradient=None, learning_rate=0.01, lr_scheduler=None,
                 sym=None, begin_num_update=0, multi_precision=False,
                 param_dict=None):
        self.rescale_grad = rescale_grad
        self.lr = learning_rate
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None:
            self.lr_scheduler.base_lr = learning_rate
        self.wd = wd
        self.lr_mult = {}
        self.wd_mult = {}
        self.begin_num_update = begin_num_update
        self.num_update = begin_num_update
        self._index_update_count = {}
        self.clip_gradient = clip_gradient
        self.multi_precision = multi_precision
        if param_idx2name is None:
            param_idx2name = {}
        if not isinstance(param_idx2name, dict):
            raise ValueError("param_idx2name should be a dict of param indexes to names.")
        self.idx2name = param_idx2name.copy()
        self.sym = sym
        self.param_dict = param_dict or {}
        self.set_lr_mult({})
        self.set_wd_mult({})

    def __getstate__(self):
        """Pickling (kvstore set_optimizer ships the optimizer to the
        dist_async servers) drops param_dict: it holds live gluon
        Parameter objects whose _trainer backref reaches the kvstore's
        sockets, and per-param lr/wd multipliers are a worker-side
        concern (the reference's __getstate__ does the same,
        python/mxnet/optimizer.py)."""
        state = self.__dict__.copy()
        state["param_dict"] = {}
        return state

    # -- registry (reference: Optimizer.register / create_optimizer) --------
    @staticmethod
    def register(klass):
        _OPT_REGISTRY.register(klass, name=klass.__name__)
        return klass

    @staticmethod
    def create_optimizer(name, **kwargs):
        return _OPT_REGISTRY.get(name)(**kwargs)

    # -- state ---------------------------------------------------------------
    def create_state(self, index, weight) -> Tuple:
        """Return the (possibly empty) tuple of state arrays for a weight."""
        return ()

    def create_state_multi_precision(self, index, weight):
        if self.multi_precision and weight.dtype in (np.float16, jnp.bfloat16):
            w32 = NDArray(weight._data.astype(jnp.float32))
            return (w32,) + self.create_state(index, w32)
        return self.create_state(index, weight)

    def mp_states_active(self, weight, states):
        """True when ``states`` carry an fp32 master copy for a
        low-precision ``weight`` (i.e. create_state_multi_precision
        prepended one).  Single source of truth for both the imperative
        update path and the fused-step builder."""
        return (self.multi_precision
                and weight.dtype in (np.float16, jnp.bfloat16)
                and bool(states) and states[0] is not None
                and tuple(states[0].shape) == tuple(weight.shape))

    # -- the pure update ------------------------------------------------------
    def _update_impl(self, weight, grad, states, lr, wd):
        raise NotImplementedError

    def apply_fused(self, ws, gs, states, lrs, wds, use_mp, ts=None):
        """Per-param _update_impl dispatch for a fused (traced) step —
        the single source of the multi-precision contract shared by
        Module._build_fused_step and Trainer._fused_update: when a param
        has an fp32 master copy (use_mp), the update runs on states[0]
        and the low-precision weight is recast from it.

        ``ts``: per-param update counts for needs_t optimizers (Adam bias
        correction); None when the optimizer ignores t.  Pure in all
        traced arguments; hyperparameters (betas, momentum, clip...) are
        read from self at trace time — callers must key their jit cache
        on them.
        """
        new_ws, new_sts = [], []
        for i, (w, g, st, lr, wd, mp) in enumerate(
                zip(ws, gs, states, lrs, wds, use_mp)):
            kw = {"t": ts[i]} if ts is not None else {}
            if mp:
                nw32, ns = self._update_impl(
                    st[0], g.astype(jnp.float32), st[1:], lr, wd, **kw)
                new_ws.append(nw32.astype(w.dtype))
                new_sts.append((nw32,) + tuple(ns))
            else:
                nw, ns = self._update_impl(w, g, st, lr, wd, **kw)
                new_ws.append(nw)
                new_sts.append(tuple(ns))
        return tuple(new_ws), tuple(new_sts)

    # attrs that advance every step and are NOT baked into traces (step
    # counts travel as traced args; lr/wd as runtime args).  Including
    # them in the signature would invalidate the fused-step jit cache on
    # EVERY update — a silent full-recompile-per-step regression (seen as
    # ~0.3 s/step for a toy MLP, ~50 s/step for ResNet-50).
    _SIG_EXCLUDE = frozenset(("num_update", "begin_num_update", "lr", "wd"))

    def hyperparam_signature(self):
        """Scalar hyperparameters baked into a fused-step trace — jit
        caches must include this so mutating e.g. momentum or
        rescale_grad mid-run retraces instead of silently using stale
        values.  Step counters and lr are excluded: they are passed as
        runtime arguments, never baked."""
        return tuple(sorted(
            (k, v) for k, v in vars(self).items()
            if k not in self._SIG_EXCLUDE
            and isinstance(v, (int, float, bool, str, type(None)))))

    # -- imperative API (reference: Optimizer.update) ------------------------
    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        states = self._state_tuple(state)
        # per-param update count for needs_t optimizers (Adam/LAMB bias
        # correction) — a state created at step N must start at t=1
        tkw = ({"t": self._index_update_count[index]}
               if getattr(self, "needs_t", False) else {})
        from .ndarray.sparse import RowSparseNDArray
        use_mp = self.mp_states_active(weight, states)
        if isinstance(grad, RowSparseNDArray):
            impl = getattr(self, "_update_impl_rsp", None)
            if impl is not None and grad.indices.shape[0] > 0:
                # touch only the gradient's rows (reference: sparse
                # sgd/adam updates, optimizer_op.cc lazy_update path).
                # Multi-precision: the sparse update applies to the fp32
                # master copy (states[0]); the low-precision weight is a
                # cast-down view of it.
                if use_mp:
                    w32 = states[0]._data
                    new_w32, new_sub = impl(
                        w32, grad.data._data.astype(jnp.float32),
                        grad.indices._data,
                        tuple(s._data for s in states[1:]), lr, wd, index)
                    states[0]._set_data(new_w32)
                    weight._set_data(new_w32.astype(weight._data.dtype))
                    for s, v in zip(states[1:], new_sub):
                        s._set_data(v)
                    return
                new_w, new_states = impl(
                    weight._data, grad.data._data, grad.indices._data,
                    tuple(s._data for s in states), lr, wd, index)
                weight._set_data(new_w)
                for s, v in zip(states, new_states):
                    s._set_data(v)
                return
            if grad.indices.shape[0] == 0:
                return  # nothing touched
            grad = NDArray(grad._data)  # dense fallback (densifies)
        if use_mp:
            w32 = states[0]._data
            new_w32, new_sub = self._update_impl(
                w32, grad._data.astype(jnp.float32),
                tuple(s._data for s in states[1:]), lr, wd, **tkw)
            states[0]._set_data(new_w32)
            weight._set_data(new_w32.astype(weight._data.dtype))
            for s, v in zip(states[1:], new_sub):
                s._set_data(v)
        else:
            new_w, new_states = self._update_impl(
                weight._data, grad._data, tuple(s._data for s in states),
                lr, wd, **tkw)
            weight._set_data(new_w)
            for s, v in zip(states, new_states):
                s._set_data(v)

    def update_multi_precision(self, index, weight, grad, state):
        self.update(index, weight, grad, state)

    @staticmethod
    def _state_tuple(state):
        if state is None:
            return ()
        if isinstance(state, (list, tuple)):
            return tuple(state)
        return (state,)

    # -- lr/wd plumbing (reference: optimizer.py:233-433) ---------------------
    def set_learning_rate(self, lr):
        if self.lr_scheduler is not None:
            raise UserWarning("LRScheduler of the optimizer has already been "
                              "defined.")
        self.lr = lr

    def set_lr_mult(self, args_lr_mult):
        self.lr_mult = {}
        if self.sym is not None:
            attr = self.sym.attr_dict()
            for name in self.sym.list_arguments():
                if name in attr and '__lr_mult__' in attr[name]:
                    self.lr_mult[name] = float(attr[name]['__lr_mult__'])
        self.lr_mult.update(args_lr_mult)

    def set_wd_mult(self, args_wd_mult):
        self.wd_mult = {}
        for n in self.idx2name.values():
            if not (n.endswith('_weight') or n.endswith('_gamma')):
                self.wd_mult[n] = 0.0
        if self.sym is not None:
            attr = self.sym.attr_dict()
            for name in self.sym.list_arguments():
                if name in attr and '__wd_mult__' in attr[name]:
                    self.wd_mult[name] = float(attr[name]['__wd_mult__'])
        self.wd_mult.update(args_wd_mult)

    def _update_count(self, index):
        if index not in self._index_update_count:
            self._index_update_count[index] = self.begin_num_update
        self._index_update_count[index] += 1
        self.num_update = max(self._index_update_count[index], self.num_update)

    @staticmethod
    def _mult_index(index):
        """Multiplier-lookup key for ``index``.  A kvstore dist_async
        big-array stripe arrives as ``<key>@s<i>`` (kvstore.py striping)
        — per-stripe STATE needs the full index, but lr/wd multipliers
        belong to the underlying parameter, so strip the transport
        suffix before the lookup."""
        if isinstance(index, str) and "@s" in index:
            base = index.rsplit("@s", 1)[0]
            try:
                return int(base)
            except ValueError:
                return base
        return index

    def _get_lr(self, index):
        if self.lr_scheduler is not None:
            lr = self.lr_scheduler(self.num_update)
        else:
            lr = self.lr
        index = self._mult_index(index)
        if index in self.param_dict:
            lr *= self.param_dict[index].lr_mult
        elif index in self.lr_mult:
            lr *= self.lr_mult[index]
        elif index in self.idx2name:
            lr *= self.lr_mult.get(self.idx2name[index], 1.0)
        return lr

    def _get_wd(self, index):
        wd = self.wd
        index = self._mult_index(index)
        if index in self.param_dict:
            wd *= self.param_dict[index].wd_mult
        elif index in self.wd_mult:
            wd *= self.wd_mult[index]
        elif index in self.idx2name:
            wd *= self.wd_mult.get(self.idx2name[index], 1.0)
        return wd


register = Optimizer.register
create = Optimizer.create_optimizer


def _l2norm(x):
    """fp32 L2 norm of a (possibly low-precision) tensor — the layer-wise
    trust-ratio norms in LARS/LAMB must not accumulate in bf16."""
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


def _clip(g, clip_gradient):
    if clip_gradient is not None and clip_gradient > 0:
        return jnp.clip(g, -clip_gradient, clip_gradient)
    return g


@register
class SGD(Optimizer):
    """SGD with momentum and optional multi-precision
    (reference: optimizer.py:434 SGD; op: src/operator/optimizer_op.cc
    sgd_update/sgd_mom_update/mp_sgd_*)."""

    pure_update = True

    def __init__(self, momentum=0.0, lazy_update=True, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return ()
        return (nd_zeros(weight.shape, dtype=weight.dtype),)

    def _update_impl(self, weight, grad, states, lr, wd):
        g = _clip(grad * self.rescale_grad, self.clip_gradient)
        if self.momentum == 0.0 or not states:
            return weight - lr * (g + wd * weight), ()
        mom = states[0]
        new_mom = self.momentum * mom - lr * (g + wd * weight)
        return weight + new_mom, (new_mom,)

    def _update_impl_rsp(self, weight, values, indices, states, lr, wd,
                         index=0):
        """Row-sparse update touching only the gradient's rows
        (reference: optimizer_op.cc SGDMomLazyUpdate — momentum/wd apply
        per TOUCHED row only; duplicates pre-aggregated like
        AddTakeGradRspKernel)."""
        from .ndarray.sparse import dedup_rows
        vals, idx = dedup_rows(values, indices.astype(jnp.int32),
                               weight.shape[0])
        g = _clip(vals * self.rescale_grad, self.clip_gradient)
        rows = jnp.take(weight, idx, axis=0, mode="fill", fill_value=0)
        if self.momentum == 0.0 or not states:
            return weight.at[idx].add(-lr * (g + wd * rows), mode="drop"), ()
        mom = states[0]
        mom_rows = jnp.take(mom, idx, axis=0, mode="fill", fill_value=0)
        new_mom_rows = self.momentum * mom_rows - lr * (g + wd * rows)
        new_mom = mom.at[idx].set(new_mom_rows, mode="drop")
        return weight.at[idx].add(new_mom_rows, mode="drop"), (new_mom,)


@register
class NAG(Optimizer):
    """Nesterov accelerated gradient (reference: optimizer.py NAG)."""

    pure_update = True

    def __init__(self, momentum=0.0, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return ()
        return (nd_zeros(weight.shape, dtype=weight.dtype),)

    def _update_impl(self, weight, grad, states, lr, wd):
        g = _clip(grad * self.rescale_grad, self.clip_gradient) + wd * weight
        if self.momentum == 0.0 or not states:
            return weight - lr * g, ()
        mom = states[0]
        new_mom = self.momentum * mom + g
        return weight - lr * (g + self.momentum * new_mom), (new_mom,)


@register
class SGLD(Optimizer):
    """Stochastic gradient Langevin dynamics (reference: optimizer.py SGLD)."""

    def _update_impl(self, weight, grad, states, lr, wd):
        from . import random as _rnd
        import jax
        g = _clip(grad * self.rescale_grad, self.clip_gradient)
        noise = jax.random.normal(_rnd.next_key(), weight.shape,
                                  weight.dtype) * math.sqrt(lr)
        return weight - lr / 2 * (g + wd * weight) + noise, ()


@register
class DCASGD(Optimizer):
    """Delay-compensated async SGD (reference: optimizer.py DCASGD)."""

    def __init__(self, momentum=0.0, lamda=0.04, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.weight_previous = {}
        self.lamda = lamda

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return (None, NDArray(weight._data))
        return (nd_zeros(weight.shape, dtype=weight.dtype),
                NDArray(weight._data))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        g = _clip(grad._data * self.rescale_grad, self.clip_gradient)
        mon, previous_weight = state
        pw = previous_weight._data
        comp = g + wd * weight._data + \
            self.lamda * g * g * (weight._data - pw)
        if mon is not None:
            new_mon = self.momentum * mon._data - lr * comp
            mon._set_data(new_mon)
            delta = new_mon
        else:
            delta = -lr * comp
        previous_weight._set_data(weight._data)
        weight._set_data(weight._data + delta)


@register
class Adam(Optimizer):
    """reference: optimizer.py Adam; op adam_update."""

    pure_update = True
    needs_t = True  # _update_impl takes the update count for bias correction

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (nd_zeros(weight.shape, dtype=weight.dtype),
                nd_zeros(weight.shape, dtype=weight.dtype))

    def _update_impl(self, weight, grad, states, lr, wd, t=None):
        # jnp ops throughout so ``t`` may be a traced scalar inside the
        # fused Module training step (no per-step recompilation)
        mean, var = states
        if t is None:
            t = self._index_update_count.get(0, self.num_update) or 1
        # f32 scalars: a bare jnp.asarray would be float64 under the
        # global x64 mode (base.py) and silently promote the whole update
        coef1 = 1. - jnp.float32(self.beta1) ** t
        coef2 = 1. - jnp.float32(self.beta2) ** t
        lr = lr * jnp.sqrt(coef2) / coef1
        g = _clip(grad * self.rescale_grad, self.clip_gradient) + wd * weight
        m = self.beta1 * mean + (1. - self.beta1) * g
        v = self.beta2 * var + (1. - self.beta2) * jnp.square(g)
        return weight - lr * m / (jnp.sqrt(v) + self.epsilon), (m, v)

    def _update_impl_rsp(self, weight, values, indices, states, lr, wd,
                         index=0):
        """Lazy Adam on touched rows only (reference: optimizer_op.cc
        AdamUpdateRspRspImpl — mean/var decay applied per touched row)."""
        from .ndarray.sparse import dedup_rows
        mean, var = states
        t = self._index_update_count.get(index, self.num_update) or 1
        coef1 = 1. - jnp.float32(self.beta1) ** t
        coef2 = 1. - jnp.float32(self.beta2) ** t
        lr = lr * jnp.sqrt(coef2) / coef1
        vals, idx = dedup_rows(values, indices.astype(jnp.int32),
                               weight.shape[0])
        rows = jnp.take(weight, idx, axis=0, mode="fill", fill_value=0)
        g = _clip(vals * self.rescale_grad, self.clip_gradient) + wd * rows
        m_rows = jnp.take(mean, idx, axis=0, mode="fill", fill_value=0)
        v_rows = jnp.take(var, idx, axis=0, mode="fill", fill_value=0)
        new_m = self.beta1 * m_rows + (1. - self.beta1) * g
        new_v = self.beta2 * v_rows + (1. - self.beta2) * jnp.square(g)
        upd = -lr * new_m / (jnp.sqrt(new_v) + self.epsilon)
        return (weight.at[idx].add(upd, mode="drop"),
                (mean.at[idx].set(new_m, mode="drop"),
                 var.at[idx].set(new_v, mode="drop")))


@register
class AdaGrad(Optimizer):
    """reference: optimizer.py AdaGrad."""

    pure_update = True

    def __init__(self, eps=1e-7, **kwargs):
        super().__init__(**kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return (nd_zeros(weight.shape, dtype=weight.dtype),)

    def _update_impl(self, weight, grad, states, lr, wd):
        hist = states[0]
        g = _clip(grad * self.rescale_grad, self.clip_gradient)
        new_hist = hist + jnp.square(g)
        w = weight - lr * (g / jnp.sqrt(new_hist + self.float_stable_eps)
                           + wd * weight)
        return w, (new_hist,)


@register
class RMSProp(Optimizer):
    """reference: optimizer.py RMSProp (centered=False → Tieleman&Hinton;
    True → Graves/'alex' variant rmspropalex_update)."""

    pure_update = True

    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9,
                 epsilon=1e-8, centered=False, clip_weights=None, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.gamma1 = gamma1
        self.gamma2 = gamma2
        self.centered = centered
        self.epsilon = epsilon
        self.clip_weights = clip_weights

    def create_state(self, index, weight):
        if self.centered:
            return (nd_zeros(weight.shape, dtype=weight.dtype),
                    nd_zeros(weight.shape, dtype=weight.dtype),
                    nd_zeros(weight.shape, dtype=weight.dtype))
        return (nd_zeros(weight.shape, dtype=weight.dtype),)

    def _update_impl(self, weight, grad, states, lr, wd):
        g = _clip(grad * self.rescale_grad, self.clip_gradient) + wd * weight
        if not self.centered:
            n = states[0]
            new_n = self.gamma1 * n + (1 - self.gamma1) * jnp.square(g)
            w = weight - lr * g / jnp.sqrt(new_n + self.epsilon)
            if self.clip_weights:
                w = jnp.clip(w, -self.clip_weights, self.clip_weights)
            return w, (new_n,)
        n, gm, delta = states
        new_n = self.gamma1 * n + (1 - self.gamma1) * jnp.square(g)
        new_g = self.gamma1 * gm + (1 - self.gamma1) * g
        new_delta = self.gamma2 * delta - lr * g / jnp.sqrt(
            new_n - jnp.square(new_g) + self.epsilon)
        w = weight + new_delta
        if self.clip_weights:
            w = jnp.clip(w, -self.clip_weights, self.clip_weights)
        return w, (new_n, new_g, new_delta)


@register
class AdaDelta(Optimizer):
    """reference: optimizer.py AdaDelta."""

    pure_update = True

    def __init__(self, rho=0.90, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho = rho
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (nd_zeros(weight.shape, dtype=weight.dtype),
                nd_zeros(weight.shape, dtype=weight.dtype))

    def _update_impl(self, weight, grad, states, lr, wd):
        acc_g, acc_delta = states
        g = _clip(grad * self.rescale_grad, self.clip_gradient)
        new_acc_g = self.rho * acc_g + (1. - self.rho) * jnp.square(g)
        delta = jnp.sqrt(acc_delta + self.epsilon) / \
            jnp.sqrt(new_acc_g + self.epsilon) * g
        new_acc_delta = self.rho * acc_delta + (1. - self.rho) * jnp.square(delta)
        return weight - delta - wd * weight, (new_acc_g, new_acc_delta)


@register
class Ftrl(Optimizer):
    """reference: optimizer.py Ftrl; op ftrl_update."""

    pure_update = True

    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1 = lamda1
        self.beta = beta

    def create_state(self, index, weight):
        return (nd_zeros(weight.shape, dtype=weight.dtype),   # z
                nd_zeros(weight.shape, dtype=weight.dtype))   # n

    def _update_impl(self, weight, grad, states, lr, wd):
        z, n = states
        g = _clip(grad * self.rescale_grad, self.clip_gradient)
        new_n = n + jnp.square(g)
        sigma = (jnp.sqrt(new_n) - jnp.sqrt(n)) / lr
        new_z = z + g - sigma * weight
        w = jnp.where(
            jnp.abs(new_z) <= self.lamda1,
            jnp.zeros_like(weight),
            -(new_z - jnp.sign(new_z) * self.lamda1) /
            ((self.beta + jnp.sqrt(new_n)) / lr + wd))
        return w, (new_z, new_n)


@register
class Adamax(Optimizer):
    """reference: optimizer.py Adamax."""

    def __init__(self, learning_rate=0.002, beta1=0.9, beta2=0.999, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2

    def create_state(self, index, weight):
        return (nd_zeros(weight.shape, dtype=weight.dtype),
                nd_zeros(weight.shape, dtype=weight.dtype))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        t = self._index_update_count[index]
        lr /= (1. - self.beta1 ** t)
        m_t, u_t = state
        g = _clip(grad._data * self.rescale_grad, self.clip_gradient) + \
            wd * weight._data
        new_m = self.beta1 * m_t._data + (1. - self.beta1) * g
        new_u = jnp.maximum(self.beta2 * u_t._data, jnp.abs(g))
        m_t._set_data(new_m)
        u_t._set_data(new_u)
        weight._set_data(weight._data - lr * new_m / new_u)


@register
class Nadam(Optimizer):
    """reference: optimizer.py Nadam."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, schedule_decay=0.004, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.schedule_decay = schedule_decay
        self.m_schedule = 1.

    def create_state(self, index, weight):
        return (nd_zeros(weight.shape, dtype=weight.dtype),
                nd_zeros(weight.shape, dtype=weight.dtype))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        t = self._index_update_count[index]
        g = _clip(grad._data * self.rescale_grad, self.clip_gradient) + \
            wd * weight._data
        momentum_t = self.beta1 * (1. - 0.5 * 0.96 ** (t * self.schedule_decay))
        momentum_t_1 = self.beta1 * (1. - 0.5 * 0.96 **
                                     ((t + 1) * self.schedule_decay))
        self.m_schedule = self.m_schedule * momentum_t
        m_schedule_next = self.m_schedule * momentum_t_1
        m_t, v_t = state
        g_prime = g / (1. - self.m_schedule)
        new_m = self.beta1 * m_t._data + (1. - self.beta1) * g
        new_v = self.beta2 * v_t._data + (1. - self.beta2) * jnp.square(g)
        m_t_prime = new_m / (1. - m_schedule_next)
        v_t_prime = new_v / (1. - self.beta2 ** t)
        m_t_bar = (1. - momentum_t) * g_prime + momentum_t_1 * m_t_prime
        m_t._set_data(new_m)
        v_t._set_data(new_v)
        weight._set_data(weight._data - lr * m_t_bar /
                         (jnp.sqrt(v_t_prime) + self.epsilon))


@register
class Signum(Optimizer):
    """Sign-based SGD (op signsgd_update)."""

    pure_update = True

    def __init__(self, learning_rate=0.01, momentum=0.9, wd_lh=0.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.wd_lh = wd_lh

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return ()
        return (nd_zeros(weight.shape, dtype=weight.dtype),)

    def _update_impl(self, weight, grad, states, lr, wd):
        g = _clip(grad * self.rescale_grad, self.clip_gradient) + wd * weight
        if not states:
            return weight - lr * jnp.sign(g), ()
        mom = states[0]
        new_mom = self.momentum * mom - (1 - self.momentum) * g
        w = (1 - lr * self.wd_lh) * weight + lr * jnp.sign(new_mom) \
            if self.wd_lh else weight + lr * jnp.sign(new_mom)
        return w, (new_mom,)


@register
class LARS(Optimizer):
    """Layer-wise Adaptive Rate Scaling (You et al. 2017) — the standard
    large-batch SGD for TPU vision training (MLPerf ResNet-50/TPU trains
    batch 4k-32k with it).

    NEW capability relative to the reference (the large-batch era
    postdates MXNet 0.12); pairs with the fused Module step and the
    batch-512+ ResNet config the MFU work targets.  Per layer:

        local_lr = eta * ||w|| / (||g|| + wd * ||w|| + eps)
        mom      = momentum * mom + local_lr * (g + wd * w)
        w       -= lr * mom

    Bias/BatchNorm params (ndim == 1) skip the trust-ratio adaptation
    and weight decay, per the paper's recipe.
    """

    pure_update = True

    def __init__(self, learning_rate=0.1, momentum=0.9, eta=0.001,
                 epsilon=1e-9, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.eta = eta
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (nd_zeros(weight.shape, dtype=weight.dtype),)

    def _update_impl(self, weight, grad, states, lr, wd):
        # lr folds INTO the momentum buffer (You et al. Algorithm 1 and
        # this file's SGD convention): an lr schedule scales only new
        # contributions, not the accumulated momentum
        g = _clip(grad * self.rescale_grad, self.clip_gradient)
        mom = states[0]
        if weight.ndim <= 1:    # bias / BN gamma-beta: plain momentum SGD
            new_mom = self.momentum * mom - lr * g
            return weight + new_mom, (new_mom,)
        w_norm = _l2norm(weight)
        g_norm = _l2norm(g)
        trust = jnp.where(
            (w_norm > 0) & (g_norm > 0),
            self.eta * w_norm / (g_norm + wd * w_norm + self.epsilon),
            jnp.float32(1.0)).astype(weight.dtype)
        new_mom = self.momentum * mom - lr * trust * (g + wd * weight)
        return weight + new_mom, (new_mom,)


@register
class LAMB(Optimizer):
    """Layer-wise adaptive Adam for large-batch training (You et al.
    2019 — BERT in 76 minutes).  NEW capability relative to the
    reference; the large-batch companion of LARS for the transformer
    track.

        m, v   = adam moments (bias-corrected)
        r      = m_hat / (sqrt(v_hat) + eps) + wd * w
        ratio  = ||w|| / ||r||   (1 where either norm is 0)
        w     -= lr * ratio * r
    """

    pure_update = True
    needs_t = True

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-6, lower_bound=None, upper_bound=None,
                 **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.lower_bound = lower_bound
        self.upper_bound = upper_bound

    def create_state(self, index, weight):
        return (nd_zeros(weight.shape, dtype=weight.dtype),
                nd_zeros(weight.shape, dtype=weight.dtype))

    def _update_impl(self, weight, grad, states, lr, wd, t=None):
        mean, var = states
        if t is None:
            t = self._index_update_count.get(0, self.num_update) or 1
        g = _clip(grad * self.rescale_grad, self.clip_gradient)
        m = self.beta1 * mean + (1. - self.beta1) * g
        v = self.beta2 * var + (1. - self.beta2) * jnp.square(g)
        # fp32 scalars (not python floats) so ``t`` may be traced
        m_hat = m / (1. - jnp.float32(self.beta1) ** t)
        v_hat = v / (1. - jnp.float32(self.beta2) ** t)
        r = m_hat / (jnp.sqrt(v_hat) + self.epsilon) + wd * weight
        w_norm = _l2norm(weight)
        if self.lower_bound is not None:
            w_norm = jnp.maximum(w_norm, self.lower_bound)
        if self.upper_bound is not None:
            w_norm = jnp.minimum(w_norm, self.upper_bound)
        r_norm = _l2norm(r)
        ratio = jnp.where((w_norm > 0) & (r_norm > 0),
                          w_norm / r_norm,
                          jnp.float32(1.0)).astype(weight.dtype)
        return weight - lr * ratio * r, (m, v)


@register
class Test(Optimizer):
    """reference: optimizer.py Test — for unit tests."""

    pure_update = True

    def create_state(self, index, weight):
        return (nd_zeros(weight.shape, dtype=weight.dtype),)

    def _update_impl(self, weight, grad, states, lr, wd):
        return weight + grad * self.rescale_grad, (states[0],)


# ccSGD is an alias of SGD in late reference versions
_OPT_REGISTRY.alias("ccsgd", "sgd")


class Updater:
    """Applies an optimizer per keyed weight (reference: optimizer.py
    get_updater/Updater — the object KVStore installs server- or local-side)."""

    def __init__(self, optimizer: Optimizer):
        self.optimizer = optimizer
        self.states = {}
        self.states_synced = {}

    def __call__(self, index, grad, weight):
        if index not in self.states:
            self.states[index] = \
                self.optimizer.create_state_multi_precision(index, weight)
            self.states_synced[index] = True
        elif not self.states_synced[index]:
            self.states[index] = self.sync_state_context(
                self.states[index], weight.context)
            self.states_synced[index] = True
        self.optimizer.update_multi_precision(index, weight, grad,
                                              self.states[index])

    def sync_state_context(self, state, context):
        if isinstance(state, NDArray):
            return state.as_in_context(context)
        if isinstance(state, (tuple, list)):
            return tuple(self.sync_state_context(i, context) if i is not None
                         else None for i in state)
        return state

    def set_states(self, states):
        # bytes = a trusted local blob (checkpoint file); an already-
        # loaded object comes from the kvstore server, which decodes
        # peer blobs through its restricted unpickler first
        if isinstance(states, (bytes, bytearray)):
            # analysis: allow(unsafe-pickle): bytes here are a trusted LOCAL blob (a checkpoint file this user loaded); kvstore peer blobs were already decoded by the server's restricted unpickler
            states = pickle.loads(states)
        if isinstance(states, tuple) and len(states) == 2:
            self.states, self.optimizer = states
        else:
            self.states = states
        self.states_synced = dict.fromkeys(self.states.keys(), False)

    def get_states(self, dump_optimizer=False):
        return pickle.dumps((self.states, self.optimizer)
                            if dump_optimizer else self.states)


def get_updater(optimizer: Optimizer) -> Updater:
    return Updater(optimizer)
