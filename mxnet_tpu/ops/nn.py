"""Neural-network layer ops.

TPU-native equivalents of the reference's legacy stateful ops
(src/operator/{fully_connected,convolution,pooling,batch_norm,activation,
dropout,deconvolution,lrn,instance_norm,upsampling}.cc plus the cuDNN
wrappers src/operator/cudnn_*.h).  Where the reference auto-tunes cuDNN
algorithms (cudnn_algoreg-inl.h), here convs lower to
``lax.conv_general_dilated`` and XLA picks the MXU tiling — no algorithm
registry needed.  Convs default to NCHW user-facing layout (MXNet default);
XLA's layout assignment transposes internally to the TPU-preferred layout.
``layout="NHWC"`` (reference: the Convolution/Pooling layout attr) runs the
activation path channels-last — the MLPerf-TPU ResNet convention — while
weights stay OIHW so checkpoints are layout-agnostic.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax._src import source_info_util

from .. import tracing
from ..base import tag_for_remat as _ckpt_name

from .registry import register, alias


def _pair(v, n=2):
    if isinstance(v, (int, float)):
        return (int(v),) * n
    t = tuple(int(x) for x in v)
    return t if len(t) == n else t * n


# --------------------------------------------------------------------------
# FullyConnected (reference: src/operator/fully_connected.cc)
# --------------------------------------------------------------------------
@register("FullyConnected", arg_names=["data", "weight", "bias"],
          attr_defaults={"num_hidden": 0, "no_bias": False, "flatten": True})
def _fully_connected(data, weight, bias=None, num_hidden=0, no_bias=False,
                     flatten=True, **kw):
    if flatten and data.ndim > 2:
        data = data.reshape(data.shape[0], -1)
    out = jnp.matmul(data, weight.T)
    if not no_bias and bias is not None:
        out = out + bias
    return _ckpt_name(out, "matmul_out", weight.shape[-1], weight.shape[0])


# --------------------------------------------------------------------------
# Convolution / Deconvolution (reference: src/operator/convolution.cc,
# deconvolution.cc; cudnn_convolution-inl.h)
# --------------------------------------------------------------------------
_CONV_DN = {  # spatial-rank -> (lhs, rhs, out) dimension_numbers
    1: ("NCH", "OIH", "NCH"),
    2: ("NCHW", "OIHW", "NCHW"),
    3: ("NCDHW", "OIDHW", "NCDHW"),
}
# accepted layout attr values per spatial rank (reference: the layout
# enum on Convolution/Pooling params); anything else must FAIL loudly —
# a typo silently falling back to channels-first would mislabel every
# measurement made with it
_LAYOUTS = {1: {None, "NCW"}, 2: {None, "NCHW", "NHWC"}, 3: {None, "NCDHW"}}


def _check_layout(layout, rank):
    """Validate and return True iff the channels-last (NHWC) path."""
    if layout not in _LAYOUTS.get(rank, {None}):
        raise ValueError(
            f"unsupported layout {layout!r} for {rank}d conv/pool "
            f"(allowed: {sorted(x for x in _LAYOUTS[rank] if x)})")
    return layout == "NHWC"


# A strided convolution over a handful of input channels starves the MXU:
# its contraction is C_in deep a tap (forward, weight gradient), its data
# gradient has C_in output channels.  Folding each s x s block of pixels
# into channels ("space-to-depth", what the MLPerf ResNet-50 TPU
# submissions do to the stem) makes it a stride-1 convolution over
# C_in * s * s channels with ceil(k / s) taps an axis: the same sums (the
# taps past k multiply zeros), s * s times the depth.  The rule reads the
# operands' shapes and nothing else; the weight argument, its gradient and
# every checkpoint keep (O, C_in, k, k).
# What it costs is copies of the input (pad, phase transpose, re-tiling)
# and of its gradient, which grow with C_in, where the plain convolution's
# time grows with k * k whatever C_in is; so the rule is k * k against
# C_in.  Set on a TPU v5 lite (PERF.md, PR 33: b256 bf16, ms a call of
# forward + data gradient reduced to [C_in] + weight gradient, plain |
# folded).  Folded, k*k/C_in >= 12: 7x7/2 C3 224^2 8.63 | 6.72 (64^2
# 0.77 | 0.51, NHWC 6.82 | 4.52), C1 7.45 | 4.47, C4 8.30 | 7.16; 11x11/4
# C3 5.47 | 2.40; 5x5/2 C1 3.24 | 2.40.  Left as written: 5x5/2 C3 4.15 |
# 4.08 and 7x7/2 C8 2.40 | 2.32 (level), 7x7/2 C16 3.26 | 4.83; 3x3/2
# slower folded at every C_in (C3 2.39 | 3.19, C1 2.12 | 2.32, C32 2.24 |
# 5.83); two taps a stride (4x4/2 C3 5.10 | 4.67, level at 64^2) were
# timed at that one shape, and more than 128 folded channels not at all.
_FOLD_MIN_KK_PER_C_IN = 12
_FOLD_MIN_TAPS = 3
_FOLD_MAX_CHANNELS = 128


def _folds_stride(data, weight, stride, dilate, pad, num_group, nhwc):
    """The stride to fold into channels, or 0 where the convolution stays
    as written: 2-D, one group, no dilation, a square stride under a square
    kernel, inside the ladder above, on an input the kernel fits in."""
    if data.ndim != 4 or num_group != 1 or any(d != 1 for d in dilate):
        return 0
    c, (k, kw), (s, sw) = weight.shape[1], weight.shape[2:], stride
    if k != kw or s != sw or s < 2 or -(-k // s) < _FOLD_MIN_TAPS:
        return 0
    if k * k < _FOLD_MIN_KK_PER_C_IN * c or c * s * s > _FOLD_MAX_CHANNELS:
        return 0
    size = data.shape[1:3] if nhwc else data.shape[2:]
    return s if all(n + 2 * p >= k for n, p in zip(size, pad)) else 0


def _fold_blocks(x, s, nhwc=False):
    """The s x s blocks of the two spatial axes folded into channels:
    (N, C, H, W) -> (N, C*s*s, H/s, W/s), channel c*s*s + s*a + b."""
    if nhwc:
        n, h, w, c = x.shape
        x = x.reshape(n, h // s, s, w // s, s, c).transpose(0, 1, 3, 5, 2, 4)
        return x.reshape(n, h // s, w // s, c * s * s)
    n, c, h, w = x.shape
    x = x.reshape(n, c, h // s, s, w // s, s).transpose(0, 1, 3, 5, 2, 4)
    return x.reshape(n, c * s * s, h // s, w // s)


def _conv_space_to_depth(data, weight, s, pad, nhwc):
    """The stride-s convolution of `data` with the (O, C, k, k) `weight`
    as one stride-1 VALID convolution of their s x s foldings."""
    _, c, k, _ = weight.shape
    taps = -(-k // s)
    sp0 = 1 if nhwc else 2
    cfg = [(0, 0, 0)] * 4
    for i in (0, 1):
        size = data.shape[sp0 + i]
        out = (size + 2 * pad[i] - k) // s + 1
        # input index s*i + u with no offset: p before, and after whatever
        # (a crop where negative) makes the length s * (out - 1 + taps)
        cfg[sp0 + i] = (pad[i], s * (out - 1 + taps) - size - pad[i], 0)
    x = _fold_blocks(lax.pad(data, jnp.zeros((), data.dtype), cfg), s, nhwc)
    w = jnp.pad(weight, ((0, 0), (0, 0)) + ((0, s * taps - k),) * 2)
    # the barrier's transpose stands between the folded weight gradient
    # and its un-folding: without it XLA:TPU's simplifier matches the two
    # into the plain convolution's weight gradient (window 112 x 112,
    # rhs_dilate 2 for the ResNet stem) and its time (PERF.md, PR 33)
    w = lax.optimization_barrier(_fold_blocks(w, s))
    tracing.instant("mx.conv.space_to_depth", "ops", args={
        "node": _node_name(), "c_in": c, "kernel": k, "stride": s,
        "folded_channels": c * s * s, "folded_kernel": taps,
        "layout": "NHWC" if nhwc else "NCHW"})
    return lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "OIHW", "NHWC") if nhwc else _CONV_DN[2])


def _node_name():
    """The symbol node being traced (executor: jax.named_scope(<node>)),
    '' for an eager call or shape inference."""
    scopes = [e.name for e in source_info_util.current_name_stack().stack
              if isinstance(e, source_info_util.Scope)]
    return scopes[-1] if scopes else ""


@register("Convolution", arg_names=["data", "weight", "bias"],
          attr_defaults={"kernel": (), "stride": (), "dilate": (), "pad": (),
                         "num_filter": 0, "num_group": 1, "no_bias": False,
                         "layout": None, "workspace": 1024,
                         "cudnn_tune": None, "cudnn_off": False})
def _convolution(data, weight, bias=None, kernel=(), stride=(), dilate=(),
                 pad=(), num_filter=0, num_group=1, no_bias=False,
                 layout=None, **kw):
    rank = data.ndim - 2
    stride = _pair(stride, rank) if stride else (1,) * rank
    dilate = _pair(dilate, rank) if dilate else (1,) * rank
    pad = _pair(pad, rank) if pad else (0,) * rank
    nhwc = _check_layout(layout, rank)
    fold = _folds_stride(data, weight, stride, dilate, pad, num_group, nhwc)
    if fold:
        out = _conv_space_to_depth(data, weight, fold, pad, nhwc)
    else:
        # NHWC activations (reference: conv layout param, convolution.cc)
        # keep the WEIGHT in MXNet's OIHW — checkpoints stay
        # layout-agnostic and XLA relayouts the filter once at compile time
        dn = ("NHWC", "OIHW", "NHWC") if nhwc else _CONV_DN[rank]
        out = lax.conv_general_dilated(
            data, weight,
            window_strides=stride,
            padding=tuple((p, p) for p in pad),
            rhs_dilation=dilate,
            dimension_numbers=dn,
            feature_group_count=num_group)
    if not no_bias and bias is not None:
        out = out + (bias if nhwc
                     else bias.reshape((1, -1) + (1,) * rank))
    # identity outside remat; a rematerialised loop body (by K and N) and
    # MXNET_REMAT_POLICY=save_matmuls (executor.maybe_mirror) keep conv
    # outputs and recompute only the cheap elementwise chains
    return _ckpt_name(out, "conv_out", weight.size // weight.shape[0],
                      weight.shape[0])


@register("Deconvolution", arg_names=["data", "weight", "bias"],
          attr_defaults={"kernel": (), "stride": (), "dilate": (), "pad": (),
                         "adj": (), "target_shape": (), "num_filter": 0,
                         "num_group": 1, "no_bias": True, "layout": None,
                         "workspace": 512})
def _deconvolution(data, weight, bias=None, kernel=(), stride=(), dilate=(),
                   pad=(), adj=(), target_shape=(), num_filter=0, num_group=1,
                   no_bias=True, layout=None, **kw):
    """Transposed convolution = gradient of Convolution wrt data
    (reference: deconvolution-inl.h)."""
    rank = data.ndim - 2
    stride = _pair(stride, rank) if stride else (1,) * rank
    dilate = _pair(dilate, rank) if dilate else (1,) * rank
    pad = _pair(pad, rank) if pad else (0,) * rank
    adj = _pair(adj, rank) if adj else (0,) * rank
    kernel = _pair(kernel, rank) if kernel else weight.shape[2:]
    # effective kernel extent
    pads = []
    for k, p, d, a in zip(kernel, pad, dilate, adj):
        ke = d * (k - 1) + 1
        pads.append((ke - 1 - p, ke - 1 - p + a))
    # weight layout for deconv in MXNet: (in_ch, out_ch/group, *k);
    # transposed conv = input-dilated conv with the spatially-flipped,
    # in/out-swapped kernel
    w = jnp.swapaxes(weight, 0, 1) if num_group == 1 \
        else _group_swap(weight, num_group)
    w = _deconv_flip(w)
    out = lax.conv_general_dilated(
        data, w,
        window_strides=(1,) * rank,
        padding=tuple(pads),
        lhs_dilation=stride,
        rhs_dilation=dilate,
        dimension_numbers=_CONV_DN[rank],
        feature_group_count=num_group)
    if not no_bias and bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * rank)
    return out


def _group_swap(w, g):
    # (C_in, C_out/g, *k) grouped -> rhs for conv with feature_group_count=g
    cin, cog = w.shape[0], w.shape[1]
    wk = w.reshape((g, cin // g) + w.shape[1:])
    wk = jnp.swapaxes(wk, 1, 2)  # (g, C_out/g, C_in/g, *k)
    return wk.reshape((g * cog, cin // g) + w.shape[2:])


def _deconv_flip(w):
    return jnp.flip(w, axis=tuple(range(2, w.ndim)))


# --------------------------------------------------------------------------
# Pooling (reference: src/operator/pooling.cc, nn/pool.cuh)
# --------------------------------------------------------------------------
@register("Pooling", arg_names=["data"],
          attr_defaults={"kernel": (), "stride": (), "pad": (),
                         "pool_type": "max", "global_pool": False,
                         "pooling_convention": "valid", "cudnn_off": False,
                         "layout": None})
def _pooling(data, kernel=(), stride=(), pad=(), pool_type="max",
             global_pool=False, pooling_convention="valid", layout=None,
             **kw):
    rank = data.ndim - 2
    nhwc = _check_layout(layout, rank)
    sp0 = 1 if nhwc else 2  # first spatial axis
    if global_pool:
        ax = tuple(range(sp0, sp0 + rank))
        if pool_type == "max":
            return jnp.max(data, axis=ax, keepdims=True)
        return jnp.mean(data, axis=ax, keepdims=True)
    kernel = _pair(kernel, rank)
    stride = _pair(stride, rank) if stride else (1,) * rank
    pad = _pair(pad, rank) if pad else (0,) * rank
    window = (1,) + kernel + (1,) if nhwc else (1, 1) + kernel
    strides = (1,) + stride + (1,) if nhwc else (1, 1) + stride

    if pooling_convention == "full":
        # ceil-mode output: pad right edge enough to cover
        sp_pads = []
        for i in range(rank):
            in_sz = data.shape[sp0 + i]
            out_sz = int(np.ceil((in_sz + 2 * pad[i] - kernel[i]) / stride[i])) + 1
            need = (out_sz - 1) * stride[i] + kernel[i] - in_sz - pad[i]
            sp_pads.append((pad[i], max(need, pad[i])))
    else:
        sp_pads = [(p, p) for p in pad]
    pads = tuple([(0, 0)] + sp_pads + [(0, 0)] if nhwc
                 else [(0, 0), (0, 0)] + sp_pads)

    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) else jnp.iinfo(data.dtype).min
        return lax.reduce_window(data, init, lax.max, window, strides, pads)
    if pool_type in ("avg", "sum"):
        s = lax.reduce_window(data, 0.0, lax.add, window, strides, pads)
        if pool_type == "sum":
            return s
        # count_include_pad=True matches MXNet default avg pooling
        return s / np.prod(kernel)
    raise ValueError(pool_type)


@register("UpSampling", variadic=True,
          attr_defaults={"scale": 1, "sample_type": "nearest",
                         "num_args": 1, "workspace": 512, "num_filter": 0,
                         "multi_input_mode": "concat"})
def _upsampling(*args, scale=1, sample_type="nearest", num_args=1,
                num_filter=0, multi_input_mode="concat", **kw):
    """reference: src/operator/upsampling.cc (nearest mode)."""
    outs = []
    for data in args:
        n, c, h, w = data.shape
        x = jnp.repeat(jnp.repeat(data, scale, axis=2), scale, axis=3)
        outs.append(x)
    if len(outs) == 1:
        return outs[0]
    if multi_input_mode == "sum":
        return sum(outs)
    return jnp.concatenate(outs, axis=1)


# --------------------------------------------------------------------------
# Normalization (reference: batch_norm.cc, instance_norm.cc, lrn.cc)
# --------------------------------------------------------------------------
def _bn_geometry(data, axis):
    red = tuple(i for i in range(data.ndim) if i != axis)
    bshape = tuple(data.shape[axis] if i == axis else 1
                   for i in range(data.ndim))
    n = int(np.prod([data.shape[i] for i in red]))
    return red, bshape, n, jnp.promote_types(data.dtype, jnp.float32)


# The one read's variance carries about 1 + 3·m1²/var times the rounding of
# a sum about the batch mean (m1: the batch mean less the shift).  Where
# m1²/var passes this margin times the data's precision over the
# accumulator's, the statistics are taken again about the batch mean:
# bfloat16 data 4 standard deviations from the shift, float32 data 1/64.
_BN_ONE_READ_MARGIN = 2.0 ** -12


def _bn_train_fwd(data, gamma, beta, shift, axis, eps, fix_gamma):
    """The batch statistics, accumulated in float32, folded into a
    per-channel scale and offset that are cast to ``data``'s dtype and
    applied to it.  Residuals: ``data`` and per-channel vectors only.

    Both sums are taken in one read, about ``shift`` (the gradient-stopped
    moving mean: it depends on nothing computed from ``data``, so the
    reduction can ride in the fusion that produces ``data``).  Where the
    batch mean lies far from it, as at the first step (``shift`` 0), a
    ``lax.cond`` reads ``data`` once more about the batch mean and
    corrects both statistics (the corrected two-pass sums)."""
    red, bshape, n, acc = _bn_geometry(data, axis)
    k = shift.astype(acc)
    xk = data.astype(acc) - k.reshape(bshape)
    m1 = jnp.sum(xk, axis=red) / n
    m2 = jnp.sum(xk * xk, axis=red) / n
    var = jnp.maximum(m2 - m1 * m1, 0)
    far = (jnp.finfo(data.dtype).eps / jnp.finfo(acc).eps
           * _BN_ONE_READ_MARGIN)

    def recentre():
        mu = k + m1
        xc = data.astype(acc) - mu.reshape(bshape)
        d = jnp.sum(xc, axis=red) / n
        return mu + d, jnp.sum(xc * xc, axis=red) / n - d * d

    mean, var = lax.cond(jnp.any(m1 * m1 > far * var), recentre,
                         lambda: (k + m1, var))
    rstd = lax.rsqrt(var + eps)
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    scale = g * rstd
    offset = beta - mean * scale
    out = (data * scale.reshape(bshape).astype(data.dtype)
           + offset.reshape(bshape).astype(data.dtype))
    return (out, mean, var), (data, mean, rstd, scale, gamma, beta, shift)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _bn_train(data, gamma, beta, shift, axis, eps, fix_gamma):
    return _bn_train_fwd(data, gamma, beta, shift, axis, eps, fix_gamma)[0]


def _bn_train_bwd(axis, eps, fix_gamma, res, cts):
    """One multi-output reduction (``a = Σ dy``, ``b = Σ dy·x̂``) and one
    elementwise pass, float32 arithmetic on the stored inputs, centred on
    the batch mean; the cotangents of the ``mean`` / ``var`` outputs are
    added exactly, folded into the pass's per-channel coefficients:
    ``dx = scale·dy + (x − mean)·(2·d_var − scale·rstd·b) / N
    + (d_mean − scale·a) / N``."""
    data, mean, rstd, scale, gamma, beta, shift = res
    dy, d_mean, d_var = cts
    red, bshape, n, acc = _bn_geometry(data, axis)
    xc = data.astype(acc) - mean.reshape(bshape)
    dyf = dy.astype(acc)
    a = jnp.sum(dyf, axis=red)
    b = jnp.sum(dyf * xc, axis=red) * rstd
    c_x = (2 * d_var - scale * rstd * b) / n
    c_0 = (d_mean - scale * a) / n
    dx = (dyf * scale.reshape(bshape) + xc * c_x.reshape(bshape)
          + c_0.reshape(bshape))
    dgamma = jnp.zeros_like(gamma) if fix_gamma else b.astype(gamma.dtype)
    return (dx.astype(data.dtype), dgamma, a.astype(beta.dtype),
            jnp.zeros_like(shift))


_bn_train.defvjp(_bn_train_fwd, _bn_train_bwd)


@register("BatchNorm", arg_names=["data", "gamma", "beta"],
          aux_names=["moving_mean", "moving_var"], num_aux=2, num_outputs=3,
          num_visible=1, takes_is_train=True,
          attr_defaults={"eps": 1e-3, "momentum": 0.9, "fix_gamma": True,
                         "use_global_stats": False, "output_mean_var": False,
                         "axis": 1, "cudnn_off": False})
def _batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
                momentum=0.9, fix_gamma=True, use_global_stats=False,
                output_mean_var=False, axis=1, is_train=True, **kw):
    """reference: src/operator/batch_norm.cc.

    Training returns (out, batch_mean, batch_var, new_moving_mean,
    new_moving_var); the trailing pair is written back into the aux arrays by
    the dispatcher (functional replacement for in-kernel aux mutation).

    Mixed-precision contract (the TPU ResNet recipe): the DATA path stays in
    the compute dtype end-to-end — statistics are accumulated in float32
    from the low-precision input, folded into per-channel scale/offset in
    float32, and only those small vectors are cast back, so the (N,C,H,W)
    activation never round-trips HBM in fp32.  gamma/beta/moving_* are
    master-precision (fp32) inputs; outputs mean/var/new_moving_* stay fp32.
    Training runs ``_bn_train``, one custom VJP written for the fewest
    reads of the activation; inference and ``use_global_stats`` fold the
    moving statistics.
    """
    ax = axis % data.ndim
    bshape = tuple(data.shape[ax] if i == ax else 1 for i in range(data.ndim))
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    if not jnp.issubdtype(data.dtype, jnp.floating):
        # integer input (e.g. a raw uint8 batch hitting bn_data): the
        # scale/offset fold below would truncate to the integer dtype —
        # promote the data path to fp32 instead
        data = data.astype(jnp.float32)
    if is_train and not use_global_stats:
        # one count each time this path is traced (or run eagerly), never
        # per step of a compiled program
        from .. import profiler
        profiler.record_dispatch("batch_norm.train_vjp")
        out, mean, var = _bn_train(data, gamma, beta,
                                   lax.stop_gradient(moving_mean),
                                   ax, float(eps), bool(fix_gamma))
        # the moving statistics carry no gradient into data
        mean_, var_ = lax.stop_gradient(mean), lax.stop_gradient(var)
        new_mm = moving_mean * momentum + mean_ * (1 - momentum)
        new_mv = moving_var * momentum + var_ * (1 - momentum)
        return out, mean, var, new_mm, new_mv
    scale = g * lax.rsqrt(moving_var + eps)
    offset = beta - moving_mean * scale
    out = (data * scale.reshape(bshape).astype(data.dtype)
           + offset.reshape(bshape).astype(data.dtype))
    return out, moving_mean, moving_var


@register("InstanceNorm", arg_names=["data", "gamma", "beta"],
          attr_defaults={"eps": 1e-3})
def _instance_norm(data, gamma, beta, eps=1e-3, **kw):
    """reference: src/operator/instance_norm.cc"""
    red = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=red, keepdims=True)
    var = jnp.var(data, axis=red, keepdims=True)
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    out = (data - mean) * lax.rsqrt(var + eps)
    return out * gamma.reshape(bshape) + beta.reshape(bshape)


@register("LayerNorm", arg_names=["data", "gamma", "beta"], num_outputs=3,
          num_visible=1,
          attr_defaults={"axis": -1, "eps": 1e-5, "output_mean_var": False})
def _layer_norm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False, **kw):
    """Transformer-era addition (post-dates the reference; kept because the
    TPU build treats attention workloads as first-class, SURVEY.md §5.7)."""
    ax = axis % data.ndim
    mean = jnp.mean(data, axis=ax, keepdims=True)
    var = jnp.var(data, axis=ax, keepdims=True)
    out = (data - mean) * lax.rsqrt(var + eps)
    bshape = tuple(data.shape[ax] if i == ax else 1 for i in range(data.ndim))
    out = out * gamma.reshape(bshape) + beta.reshape(bshape)
    return out, jnp.squeeze(mean, ax), jnp.squeeze(var, ax)


@register("RMSNorm", arg_names=["data", "gamma"],
          attr_defaults={"axis": -1, "eps": 1e-6})
def _rms_norm(data, gamma, axis=-1, eps=1e-6, **kw):
    """Root-mean-square norm (Zhang & Sennrich 2019; the norm of the Llama
    line and of Ouro): ``data / sqrt(mean(data^2) + eps) * gamma`` along
    ``axis``, no centring and no offset.  The statistics and the scaling
    are taken in float32 whatever the data's dtype, and the result is cast
    back to it; under mixed precision only the data is cast
    (executor.AMP_SPLIT_OPS), the gain keeps its master precision."""
    ax = axis % data.ndim
    x = data.astype(jnp.promote_types(data.dtype, jnp.float32))
    y = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=ax, keepdims=True) + eps)
    bshape = tuple(data.shape[ax] if i == ax else 1 for i in range(data.ndim))
    return (y * gamma.astype(x.dtype).reshape(bshape)).astype(data.dtype)


@register("LRN", arg_names=["data"],
          attr_defaults={"alpha": 1e-4, "beta": 0.75, "knorm": 2.0, "nsize": 5})
def _lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5, **kw):
    """reference: src/operator/lrn.cc — cross-channel local response norm."""
    sq = jnp.square(data)
    pad = nsize // 2
    sq_pad = jnp.pad(sq, ((0, 0), (pad, pad), (0, 0), (0, 0)))
    windows = sum(sq_pad[:, i:i + data.shape[1]] for i in range(nsize))
    return data / jnp.power(knorm + alpha / nsize * windows, beta)


# --------------------------------------------------------------------------
# Activations (reference: activation.cc, leaky_relu.cc)
# --------------------------------------------------------------------------
@register("Activation", arg_names=["data"], attr_defaults={"act_type": "relu"})
def _activation(data, act_type="relu", **kw):
    if act_type == "relu":
        return jax.nn.relu(data)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jax.nn.softplus(data)
    if act_type == "softsign":
        return jax.nn.soft_sign(data)
    raise ValueError(act_type)


@register("LeakyReLU", arg_names=["data", "gamma"], needs_rng=True,
          takes_is_train=True,
          attr_defaults={"act_type": "leaky", "slope": 0.25,
                         "lower_bound": 0.125, "upper_bound": 0.334})
def _leaky_relu(key, data, gamma=None, act_type="leaky", slope=0.25,
                lower_bound=0.125, upper_bound=0.334, is_train=True, **kw):
    """reference: src/operator/leaky_relu.cc (leaky/prelu/elu/rrelu/selu/gelu)."""
    if act_type == "leaky":
        return jnp.where(data > 0, data, slope * data)
    if act_type == "elu":
        return jnp.where(data > 0, data, slope * (jnp.exp(data) - 1))
    if act_type == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (data.ndim - 2)) if gamma.ndim == 1 else gamma
        return jnp.where(data > 0, data, g * data)
    if act_type == "selu":
        return 1.0507009873554805 * jnp.where(
            data > 0, data, 1.6732632423543772 * (jnp.exp(data) - 1))
    if act_type == "gelu":
        return jax.nn.gelu(data)
    if act_type == "rrelu":
        if is_train:
            s = jax.random.uniform(key, data.shape, data.dtype,
                                   lower_bound, upper_bound)
        else:
            s = (lower_bound + upper_bound) / 2.0
        return jnp.where(data > 0, data, s * data)
    raise ValueError(act_type)


@register("Dropout", arg_names=["data"], needs_rng=True, takes_is_train=True,
          num_outputs=2, num_visible=1,
          attr_defaults={"p": 0.5, "mode": "training", "axes": ()})
def _dropout(key, data, p=0.5, mode="training", axes=(), is_train=True, **kw):
    """reference: src/operator/dropout.cc — returns (out, mask)."""
    if not is_train and mode != "always":
        return data, jnp.ones_like(data)
    if p <= 0.0:
        return data, jnp.ones_like(data)
    shape = list(data.shape)
    for a in (axes or ()):
        shape[a] = 1
    keep = jax.random.bernoulli(key, 1.0 - p, tuple(shape))
    mask = keep.astype(data.dtype) / (1.0 - p)
    return data * mask, jnp.broadcast_to(mask, data.shape)


# --------------------------------------------------------------------------
# Softmax family (reference: nn/softmax.cc, softmax_output.cc)
# --------------------------------------------------------------------------
@register("softmax", arg_names=["data"],
          attr_defaults={"axis": -1, "temperature": None})
def _softmax(data, axis=-1, temperature=None, **kw):
    if temperature:
        data = data / temperature
    return jax.nn.softmax(data, axis=axis)


@register("log_softmax", arg_names=["data"],
          attr_defaults={"axis": -1, "temperature": None})
def _log_softmax(data, axis=-1, temperature=None, **kw):
    if temperature:
        data = data / temperature
    return jax.nn.log_softmax(data, axis=axis)


@register("SoftmaxActivation", arg_names=["data"],
          attr_defaults={"mode": "instance"})
def _softmax_activation(data, mode="instance", **kw):
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


def _softmax_output_fwd(data, label, grad_scale, ignore_label, use_ignore,
                        multi_output, normalization, smooth_alpha):
    if multi_output:
        out = jax.nn.softmax(data, axis=1)
    else:
        out = jax.nn.softmax(data, axis=-1)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def _softmax_output_core(data, label, grad_scale, ignore_label, use_ignore,
                         multi_output, normalization, smooth_alpha):
    return _softmax_output_fwd(data, label, grad_scale, ignore_label,
                               use_ignore, multi_output, normalization,
                               smooth_alpha)


def _softmax_output_vjp_fwd(data, label, grad_scale, ignore_label, use_ignore,
                            multi_output, normalization, smooth_alpha):
    out = _softmax_output_fwd(data, label, grad_scale, ignore_label,
                              use_ignore, multi_output, normalization,
                              smooth_alpha)
    return out, (out, label)


def _softmax_output_vjp_bwd(grad_scale, ignore_label, use_ignore,
                            multi_output, normalization, smooth_alpha,
                            res, g):
    (out, label) = res
    axis = 1 if multi_output else -1
    nclass = out.shape[axis]
    if label.ndim == out.ndim:
        onehot = label  # dense per-class label
    else:
        lab = label.astype(jnp.int32)
        onehot = jax.nn.one_hot(lab, nclass, axis=axis, dtype=out.dtype)
        if smooth_alpha:
            onehot = onehot * (1 - smooth_alpha) + smooth_alpha / nclass
    grad = out - onehot
    valid = None
    if use_ignore and label.ndim != out.ndim:
        keep = (label.astype(jnp.int32) != int(ignore_label))
        grad = grad * jnp.expand_dims(keep, axis).astype(out.dtype)
        valid = jnp.maximum(jnp.sum(keep), 1).astype(out.dtype)
    if normalization == "batch":
        grad = grad / out.shape[0]
    elif normalization == "valid":
        if valid is None:
            valid = jnp.asarray(
                np.prod([s for i, s in enumerate(out.shape) if i != (axis % out.ndim)]),
                out.dtype)
        grad = grad / valid
    grad = grad * grad_scale
    return (grad, jnp.zeros_like(label))


_softmax_output_core.defvjp(_softmax_output_vjp_fwd, _softmax_output_vjp_bwd)


@register("SoftmaxOutput", arg_names=["data", "label"],
          aliases=("Softmax",),
          attr_defaults={"grad_scale": 1.0, "ignore_label": -1.0,
                         "multi_output": False, "use_ignore": False,
                         "preserve_shape": False, "normalization": "null",
                         "out_grad": False, "smooth_alpha": 0.0})
def _softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0,
                    multi_output=False, use_ignore=False, preserve_shape=False,
                    normalization="null", out_grad=False, smooth_alpha=0.0, **kw):
    """reference: src/operator/softmax_output.cc — forward is softmax; the
    head gradient is (p - onehot(label)) * grad_scale, expressed here as a
    jax.custom_vjp so jax.grad of any loss-shaped executor reproduces the
    reference's implicit-loss semantics."""
    return _softmax_output_core(data, label, grad_scale, ignore_label,
                                use_ignore, multi_output, normalization,
                                smooth_alpha)


def _make_regression_output(name, link, grad_fn):
    @functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
    def core(data, label, grad_scale):
        return link(data)

    def fwd(data, label, grad_scale):
        out = link(data)
        return out, (out, label)

    def bwd(grad_scale, res, g):
        out, label = res
        grad = grad_fn(out, label.reshape(out.shape)) * grad_scale
        return (grad, jnp.zeros_like(label))

    core.defvjp(fwd, bwd)

    @register(name, arg_names=["data", "label"],
              attr_defaults={"grad_scale": 1.0})
    def _op(data, label, grad_scale=1.0, **kw):
        return core(data, label, grad_scale)
    return _op


_make_regression_output("LinearRegressionOutput", lambda x: x,
                        lambda o, l: (o - l))
_make_regression_output("MAERegressionOutput", lambda x: x,
                        lambda o, l: jnp.sign(o - l))
_make_regression_output("LogisticRegressionOutput", jax.nn.sigmoid,
                        lambda o, l: (o - l))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _svm_core(data, label, margin, reg, use_linear):
    return data


def _svm_fwd(data, label, margin, reg, use_linear):
    return data, (data, label)


def _svm_bwd(margin, reg, use_linear, res, g):
    # one-vs-all hinge gradients, the reference's L1_SVM/L2_SVM kernels
    # (svm_output.cc:30,48) vectorized: true-class margin pushes up,
    # every other class pushes down; like the other loss heads the seed
    # gradient is replaced, not chained.
    data, label = res
    f32 = data.astype(jnp.float32)
    onehot = jax.nn.one_hot(label.astype(jnp.int32), data.shape[-1],
                            dtype=jnp.float32)
    if use_linear:
        g_true = -(margin > f32).astype(jnp.float32) * reg
        g_other = (margin > -f32).astype(jnp.float32) * reg
    else:
        g_true = -2.0 * reg * (margin - f32) * (margin > f32)
        g_other = 2.0 * reg * (margin + f32) * (margin > -f32)
    grad = onehot * g_true + (1.0 - onehot) * g_other
    return grad.astype(data.dtype), jnp.zeros_like(label)


_svm_core.defvjp(_svm_fwd, _svm_bwd)


@register("SVMOutput", arg_names=["data", "label"],
          attr_defaults={"margin": 1.0, "regularization_coefficient": 1.0,
                         "use_linear": False})
def _svm_output(data, label, margin=1.0, regularization_coefficient=1.0,
                use_linear=False, **kw):
    """reference: src/operator/svm_output.cc — forward is identity, the
    LOSS lives in backward: one-vs-all (squared) hinge on the margins
    (L2_SVM default, L1_SVM with use_linear)."""
    return _svm_core(data, label, float(margin),
                     float(regularization_coefficient), bool(use_linear))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _makeloss_core(data, grad_scale, valid_thresh, normalization):
    return data


def _makeloss_fwd(data, grad_scale, valid_thresh, normalization):
    return data, data


def _makeloss_bwd(grad_scale, valid_thresh, normalization, data, g):
    # the head MAKES its output a loss: gradient is the CONSTANT
    # grad_scale (reference make_loss-inl.h:102-116), normalized by
    # batch size ('batch') or by the count of elements above
    # valid_thresh ('valid') — the seed gradient is replaced.
    if normalization == "batch":
        # 0-d data (e.g. x.sum()) counts as batch 1 — the reference's
        # ndarrays are never 0-d, so its divide-by-shape[0] saw 1 here
        scale = grad_scale / (data.shape[0] if data.ndim else 1)
        return (jnp.full(data.shape, scale, data.dtype),)
    if normalization == "valid":
        valid = jnp.maximum(
            jnp.sum((data > valid_thresh).astype(jnp.float32)), 1.0)
        return ((grad_scale / valid).astype(data.dtype)
                * jnp.ones_like(data),)
    return (jnp.full(data.shape, grad_scale, data.dtype),)


_makeloss_core.defvjp(_makeloss_fwd, _makeloss_bwd)


@register("MakeLoss", arg_names=["data"],
          attr_defaults={"grad_scale": 1.0, "valid_thresh": 0.0,
                         "normalization": "null"})
def _makeloss(data, grad_scale=1.0, valid_thresh=0.0, normalization="null", **kw):
    """reference: src/operator/make_loss.cc — forward is identity, the
    backward writes grad_scale (normalized per the mode), replacing the
    seed like the other loss heads."""
    normalization = str(normalization)
    if normalization not in ("null", "batch", "valid"):
        # reference rejects invalid enum values at op creation — a typo
        # must not silently train with unnormalized gradients
        raise ValueError("MakeLoss normalization must be one of "
                         "'null'/'batch'/'valid', got %r" % normalization)
    return _makeloss_core(data, float(grad_scale), float(valid_thresh),
                          normalization)


@register("_contrib_ExpectedExitLoss", arg_names=["gate", "loss"],
          num_outputs=2, aliases=("expected_exit_loss",),
          attr_defaults={"steps": 1, "beta": 0.0})
def _expected_exit_loss(gate, loss, steps=1, beta=0.0, **kw):
    """The objective of a looped model with a learned exit (Zhu et al.
    2025, "Scaling Latent Reasoning via Looped Language Models"): ``gate``
    holds the exit gate's logit and ``loss`` the loss of each of ``steps``
    loop steps for each of N items, step-major (anything that reshapes to
    ``(steps, N)``).  With ``lambda_t = sigmoid(gate_t)`` the exit
    distribution is ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` for
    ``t < steps`` and the rest of the mass at the last step; returns the
    per-item ``sum_t p_t loss_t + beta sum_t p_t log p_t`` as ``(N,)`` and
    ``p`` as ``(steps, N)``.  Everything in float32 (or wider), in log
    space (executor.AMP_FP32_OPS)."""
    steps = int(steps)
    ft = jnp.promote_types(loss.dtype, jnp.float32)
    z = gate.astype(ft).reshape(steps, -1)
    per = loss.astype(ft).reshape(steps, -1)
    log_stay = jax.nn.log_sigmoid(-z)[:-1]          # log(1 - lambda_j)
    before = jnp.concatenate([jnp.zeros_like(z[:1]),
                              jnp.cumsum(log_stay, axis=0)])
    log_p = before + jnp.concatenate(
        [jax.nn.log_sigmoid(z)[:-1], jnp.zeros_like(z[:1])])
    p = jnp.exp(log_p)
    return jnp.sum(p * (per + beta * log_p), axis=0), p


@register("softmax_cross_entropy", arg_names=["data", "label"])
def _softmax_ce(data, label, **kw):
    logp = jax.nn.log_softmax(data, axis=-1)
    lab = label.astype(jnp.int32)
    return -jnp.sum(jnp.take_along_axis(logp, lab[:, None], axis=-1))


# --- legacy _v1 aliases (reference: batch_norm_v1.cc, convolution_v1.cc,
# pooling_v1.cc — older implementations of the same math, kept for graph
# compatibility; one registration path here, so they are true aliases) ------
alias("BatchNorm_v1", "BatchNorm")
alias("Convolution_v1", "Convolution")
alias("Pooling_v1", "Pooling")


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _klreg_core(data, moving_avg, sparseness_target, penalty):
    return data


def _klreg_fwd(data, moving_avg, sparseness_target, penalty):
    return data, moving_avg


def _klreg_bwd(sparseness_target, penalty, moving_avg, g):
    rho = sparseness_target
    pen = penalty * (-rho / moving_avg + (1.0 - rho) / (1.0 - moving_avg))
    unit_shape = (1,) + pen.shape if g.ndim == pen.ndim + 1 else pen.shape
    return g + pen.reshape(unit_shape).astype(g.dtype), jnp.zeros_like(moving_avg)


_klreg_core.defvjp(_klreg_fwd, _klreg_bwd)


@register("IdentityAttachKLSparseReg", arg_names=["data"], num_aux=1,
          aux_names=["moving_avg"], takes_is_train=True,
          attr_defaults={"sparseness_target": 0.1, "penalty": 0.001,
                         "momentum": 0.9})
def _identity_attach_kl_sparse_reg(data, moving_avg, sparseness_target=0.1,
                                   penalty=0.001, momentum=0.9,
                                   is_train=False, **kw):
    """Identity forward; attaches the KL sparseness penalty grad
    penalty * (-rho/mu + (1-rho)/(1-mu)) in backward, where mu is the
    momentum-averaged per-unit mean activation kept as aux state
    (reference: src/operator/identity_attach_KL_sparse_reg-inl.h:62-110).
    The reference updates the moving average inside Backward; here it is
    updated in the training forward (same per-step observable state) so the
    op stays a pure function with an aux output."""
    if is_train:
        flat = data.reshape(data.shape[0], -1)
        avg = lax.stop_gradient(flat.mean(axis=0).reshape(moving_avg.shape))
        ma = momentum * moving_avg + (1.0 - momentum) * avg
        out = _klreg_core(data, ma, float(sparseness_target), float(penalty))
        return out, ma
    return _klreg_core(data, moving_avg, float(sparseness_target),
                       float(penalty))
