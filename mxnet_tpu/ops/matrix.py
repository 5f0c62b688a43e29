"""Shape-manipulation and linear-algebra-adjacent tensor ops.

TPU-native equivalent of src/operator/tensor/matrix_op.cc (transpose, reshape,
slice, concat, ...) and tensor/dot-inl.h (dot/batch_dot).  dot/batch_dot map
straight onto ``lax.dot_general`` so they tile onto the MXU; everything else
is jnp shape plumbing that XLA folds into layout changes.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp
from jax import lax

from ..base import tag_for_remat as _ckpt_name

from .registry import register, alias
from ..base import MXNetError


@register("Reshape", arg_names=["data"], aliases=("reshape",),
          attr_defaults={"shape": (), "reverse": False})
def _reshape(data, shape=(), reverse=False, **kw):
    """MXNet reshape with special codes 0 (copy dim), -1 (infer), -2 (copy
    rest), -3 (merge two dims), -4 (split dim) — reference matrix_op.cc."""
    shape = tuple(int(s) for s in shape)
    src = list(data.shape)
    if reverse:
        src = src[::-1]
        shape = tuple(reversed(shape))
    out = []
    i = 0  # index into src
    j = 0
    while j < len(shape):
        s = shape[j]
        if s == 0:
            out.append(src[i]); i += 1
        elif s == -1:
            out.append(-1); i += 1
        elif s == -2:
            out.extend(src[i:]); i = len(src)
        elif s == -3:
            out.append(src[i] * src[i + 1]); i += 2
        elif s == -4:
            d1, d2 = shape[j + 1], shape[j + 2]
            if d1 == -1:
                d1 = src[i] // d2
            if d2 == -1:
                d2 = src[i] // d1
            out.extend([d1, d2]); i += 1; j += 2
        else:
            out.append(s); i += 1
        j += 1
    if reverse:
        out = out[::-1]
    return jnp.reshape(data, tuple(out))


@register("Flatten", arg_names=["data"], aliases=("flatten",))
def _flatten(data, **kw):
    return jnp.reshape(data, (data.shape[0], -1))


@register("transpose", arg_names=["data"], attr_defaults={"axes": ()})
def _transpose(data, axes=(), **kw):
    axes = tuple(axes) or None
    return jnp.transpose(data, axes)


@register("expand_dims", arg_names=["data"], attr_defaults={"axis": 0})
def _expand_dims(data, axis=0, **kw):
    return jnp.expand_dims(data, axis)


@register("squeeze", arg_names=["data"], attr_defaults={"axis": None})
def _squeeze(data, axis=None, **kw):
    return jnp.squeeze(data, axis=axis if axis is None else tuple(
        (axis,) if isinstance(axis, int) else axis))


def _slice_tuple(begin, end, step=()):
    step = tuple(step) or (None,) * len(begin)
    return tuple(slice(b, e, s) for b, e, s in zip(begin, end, step))


@register("slice", arg_names=["data"], aliases=("crop",),
          attr_defaults={"begin": (), "end": (), "step": ()})
def _slice(data, begin=(), end=(), step=(), **kw):
    return data[_slice_tuple(begin, end, step)]


@register("_slice_assign", arg_names=["lhs", "rhs"],
          aliases=("_crop_assign",),
          attr_defaults={"begin": (), "end": (), "step": ()})
def _slice_assign(lhs, rhs, begin=(), end=(), step=(), **kw):
    """reference: tensor/matrix_op.cc _slice_assign — functional update of
    lhs[begin:end] = rhs (the TPU-native form of the reference's in-place
    kernel; XLA turns the copy into an in-place DUS when buffers are
    donated)."""
    return lhs.at[_slice_tuple(begin, end, step)].set(rhs)


@register("_slice_assign_scalar", arg_names=["data"],
          aliases=("_crop_assign_scalar",),
          attr_defaults={"scalar": 0.0, "begin": (), "end": (), "step": ()})
def _slice_assign_scalar(data, scalar=0.0, begin=(), end=(), step=(), **kw):
    return data.at[_slice_tuple(begin, end, step)].set(scalar)


@register("reshape_like", arg_names=["lhs", "rhs"])
def _reshape_like(lhs, rhs, **kw):
    """reference: tensor/elemwise_unary_op.cc reshape_like"""
    return lhs.reshape(rhs.shape)


@register("cast_storage", arg_names=["data"],
          attr_defaults={"stype": "default"})
def _cast_storage(data, stype="default", **kw):
    """reference: tensor/cast_storage-inl.h.  At the jax level every array
    is dense; actual RSP/CSR container conversion happens in the NDArray
    frontend (ndarray/sparse.py cast_storage), which routes through this op
    for the dense leg."""
    return jnp.asarray(data)


@register("_sparse_retain", arg_names=["data", "indices"],
          aliases=("sparse_retain",))
def _sparse_retain_op(data, indices, **kw):
    """reference: tensor/sparse_retain.cc — keep the listed rows, zero the
    rest (dense semantics of the RSP op; RowSparseNDArray.retain keeps the
    O(rows) container form)."""
    keep = jnp.zeros((data.shape[0],), jnp.bool_).at[
        indices.astype(jnp.int32)].set(True)
    return jnp.where(keep.reshape((-1,) + (1,) * (data.ndim - 1)),
                     data, jnp.zeros((), data.dtype))


@register("slice_axis", arg_names=["data"],
          attr_defaults={"axis": 0, "begin": 0, "end": None})
def _slice_axis(data, axis=0, begin=0, end=None, **kw):
    idx = [slice(None)] * data.ndim
    idx[axis] = slice(begin, end)
    return data[tuple(idx)]


@register("slice_like", arg_names=["data", "shape_like"],
          attr_defaults={"axes": ()})
def _slice_like(data, shape_like, axes=(), **kw):
    axes = tuple(axes) or tuple(range(min(data.ndim, shape_like.ndim)))
    idx = [slice(None)] * data.ndim
    for a in axes:
        idx[a] = slice(0, shape_like.shape[a])
    return data[tuple(idx)]


@register("Concat", variadic=True, aliases=("concat",),
          attr_defaults={"dim": 1, "num_args": 0})
def _concat(*args, dim=1, num_args=0, **kw):
    """reference: src/operator/concat.cc"""
    return jnp.concatenate(args, axis=dim)


@register("stack", variadic=True, attr_defaults={"axis": 0, "num_args": 0})
def _stack(*args, axis=0, num_args=0, **kw):
    return jnp.stack(args, axis=axis)


@register("SliceChannel", arg_names=["data"], num_outputs=-1,
          aliases=("split",),
          attr_defaults={"num_outputs": 1, "axis": 1, "squeeze_axis": False})
def _split(data, num_outputs=1, axis=1, squeeze_axis=False, **kw):
    """reference: src/operator/slice_channel.cc"""
    parts = jnp.split(data, num_outputs, axis=axis)
    if squeeze_axis:
        parts = [jnp.squeeze(p, axis=axis) for p in parts]
    return tuple(parts)


@register("dot", arg_names=["lhs", "rhs"],
          attr_defaults={"transpose_a": False, "transpose_b": False})
def _dot(lhs, rhs, transpose_a=False, transpose_b=False, **kw):
    """MXU-mapped matmul (reference: tensor/dot-inl.h).

    MXNet dot contracts the last axis of lhs with the first axis of rhs for
    ndim>2 operands.
    """
    if transpose_a:
        lhs = jnp.transpose(lhs)
    if transpose_b:
        rhs = jnp.transpose(rhs)
    return _ckpt_name(jnp.tensordot(lhs, rhs, axes=1), "matmul_out",
                      rhs.shape[0], int(np.prod(rhs.shape[1:])))


@register("batch_dot", arg_names=["lhs", "rhs"],
          attr_defaults={"transpose_a": False, "transpose_b": False})
def _batch_dot(lhs, rhs, transpose_a=False, transpose_b=False, **kw):
    if transpose_a:
        lhs = jnp.swapaxes(lhs, -1, -2)
    if transpose_b:
        rhs = jnp.swapaxes(rhs, -1, -2)
    return _ckpt_name(jnp.matmul(lhs, rhs), "matmul_out",
                      lhs.shape[-1], rhs.shape[-1])


@register("tile", arg_names=["data"], attr_defaults={"reps": ()})
def _tile(data, reps=(), **kw):
    return jnp.tile(data, tuple(reps))


@register("repeat", arg_names=["data"],
          attr_defaults={"repeats": 1, "axis": None})
def _repeat(data, repeats=1, axis=None, **kw):
    return jnp.repeat(data, repeats, axis=axis)


@register("flip", arg_names=["data"], aliases=("reverse",),
          attr_defaults={"axis": 0})
def _flip(data, axis=0, **kw):
    ax = (axis,) if isinstance(axis, int) else tuple(axis)
    return jnp.flip(data, axis=ax)


@register("SwapAxis", arg_names=["data"], aliases=("swapaxes",),
          attr_defaults={"dim1": 0, "dim2": 0})
def _swapaxes(data, dim1=0, dim2=0, **kw):
    """reference: src/operator/swapaxis.cc"""
    return jnp.swapaxes(data, dim1, dim2)


@register("Pad", arg_names=["data"], aliases=("pad",),
          attr_defaults={"mode": "constant", "pad_width": (), "constant_value": 0})
def _pad(data, mode="constant", pad_width=(), constant_value=0, **kw):
    """reference: src/operator/pad.cc — pad_width is a flat 2*ndim tuple."""
    pw = tuple(pad_width)
    pairs = tuple((pw[2 * i], pw[2 * i + 1]) for i in range(len(pw) // 2))
    if mode == "constant":
        return jnp.pad(data, pairs, constant_values=constant_value)
    jmode = {"edge": "edge", "reflect": "reflect"}[mode]
    return jnp.pad(data, pairs, mode=jmode)


@register("Crop", variadic=True,
          attr_defaults={"num_args": 1, "offset": (0, 0), "h_w": (0, 0),
                         "center_crop": False})
def _crop(*args, num_args=1, offset=(0, 0), h_w=(0, 0), center_crop=False, **kw):
    """reference: src/operator/crop.cc (NCHW spatial crop)."""
    data = args[0]
    if len(args) > 1:
        th, tw = args[1].shape[2], args[1].shape[3]
    else:
        th, tw = h_w
    if center_crop:
        oh = (data.shape[2] - th) // 2
        ow = (data.shape[3] - tw) // 2
    else:
        oh, ow = offset
    return data[:, :, oh:oh + th, ow:ow + tw]


@register("space_to_depth", arg_names=["data"], attr_defaults={"block_size": 1})
def _space_to_depth(data, block_size=1, **kw):
    b = block_size
    n, c, h, w = data.shape
    x = data.reshape(n, c, h // b, b, w // b, b)
    x = x.transpose(0, 3, 5, 1, 2, 4)
    return x.reshape(n, c * b * b, h // b, w // b)


@register("depth_to_space", arg_names=["data"], attr_defaults={"block_size": 1})
def _depth_to_space(data, block_size=1, **kw):
    b = block_size
    n, c, h, w = data.shape
    x = data.reshape(n, b, b, c // (b * b), h, w)
    x = x.transpose(0, 3, 4, 1, 5, 2)
    return x.reshape(n, c // (b * b), h * b, w * b)


@register("diag", arg_names=["data"], attr_defaults={"k": 0})
def _diag(data, k=0, **kw):
    return jnp.diag(data, k=k) if data.ndim <= 2 else jnp.diagonal(data, offset=k)


@register("shape_array", arg_names=["data"], differentiable=False)
def _shape_array(data, **kw):
    return jnp.asarray(data.shape, dtype=jnp.int64)


@register("size_array", arg_names=["data"], differentiable=False)
def _size_array(data, **kw):
    return jnp.asarray([data.size], dtype=jnp.int64)
