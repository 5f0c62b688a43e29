"""BatchNorm's training pass as one custom VJP (ops/nn.py ``_bn_train``).

The forward takes both per-channel sums about the gradient-stopped moving
mean in one read, accumulated in float32, and reads again about the batch
mean only where that lies far from the shift; the backward is one reduction
(``Σ dy``, ``Σ dy·x̂``) and one elementwise pass, centred on the batch mean
in float32.  Everything here is held against ``jax.grad`` of a float64
two-pass reference; the inference and global-stats path is held to the
formula it had before the VJP, bit for bit.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

import mxnet_tpu as mx
from mxnet_tpu import autograd, models, profiler
from mxnet_tpu.ops.nn import _batch_norm

EPS = 1e-3
MOM = 0.9


def _ref(x, gamma, beta, axis, fix_gamma, eps=EPS):
    """float64 two-pass BatchNorm: (out, mean, var)."""
    x = x.astype(jnp.float64)
    ax = axis % x.ndim
    red = tuple(i for i in range(x.ndim) if i != ax)
    bshape = tuple(-1 if i == ax else 1 for i in range(x.ndim))
    mean = jnp.mean(x, axis=red)
    var = jnp.mean(jnp.square(x - mean.reshape(bshape)), axis=red)
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    scale = (g.astype(jnp.float64) * lax.rsqrt(var + eps)).reshape(bshape)
    out = (x - mean.reshape(bshape)) * scale \
        + beta.astype(jnp.float64).reshape(bshape)
    return out, mean, var


def _case(axis, dtype, shape=(4, 3, 6, 5), seed=0):
    rs = np.random.RandomState(seed)
    ax = axis % len(shape)
    c = shape[ax]
    bshape = tuple(c if i == ax else 1 for i in range(len(shape)))
    loc = rs.uniform(-2, 2, c).reshape(bshape)
    spread = rs.uniform(0.5, 2, c).reshape(bshape)
    x = jnp.asarray(rs.randn(*shape) * spread + loc, dtype)
    gamma = jnp.asarray(rs.uniform(0.5, 1.5, c), jnp.float32)
    beta = jnp.asarray(rs.randn(c), jnp.float32)
    mm = jnp.asarray(rs.randn(c), jnp.float32)
    mv = jnp.asarray(rs.uniform(0.5, 2, c), jnp.float32)
    w = jnp.asarray(rs.randn(*shape), dtype)     # the output's cotangent
    return x, gamma, beta, mm, mv, w


def _f64(a):
    return np.asarray(a, np.float64)


def _rel_l2(a, ref):
    a, ref = _f64(a), _f64(ref)
    return np.linalg.norm(a - ref) / np.linalg.norm(ref)


def _cosine(a, ref):
    a, ref = _f64(a).ravel(), _f64(ref).ravel()
    return float(a @ ref / np.sqrt((a @ a) * (ref @ ref)))


def _train(x, gamma, beta, mm, mv, axis, fix_gamma, **kw):
    return _batch_norm(x, gamma, beta, mm, mv, eps=EPS, momentum=MOM,
                       fix_gamma=fix_gamma, axis=axis, is_train=True, **kw)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("fix_gamma", [True, False])
@pytest.mark.parametrize("axis", [1, -1])
def test_forward_and_moving_statistics(axis, fix_gamma, dtype):
    x, gamma, beta, mm, mv, _ = _case(axis, dtype)
    out, mean, var, new_mm, new_mv = _train(x, gamma, beta, mm, mv, axis,
                                            fix_gamma)
    r_out, r_mean, r_var = _ref(x, gamma, beta, axis, fix_gamma)
    assert out.dtype == x.dtype and mean.dtype == jnp.float32
    np.testing.assert_allclose(_f64(mean), r_mean, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(_f64(var), r_var, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(_f64(new_mm), _f64(mm) * MOM
                               + _f64(r_mean) * (1 - MOM), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(_f64(new_mv), _f64(mv) * MOM
                               + _f64(r_var) * (1 - MOM), rtol=1e-5,
                               atol=1e-6)
    if dtype == "float32":
        np.testing.assert_allclose(_f64(out), r_out, rtol=1e-5, atol=1e-5)
    else:
        # the recipe's own roundings: scale and offset cast to bf16 and
        # applied in bf16 — a few roundings of the output's size
        rounding = np.abs(_f64(r_out.astype(jnp.bfloat16)) - r_out).max()
        assert np.abs(_f64(out) - r_out).max() <= 4 * rounding


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("fix_gamma", [True, False])
@pytest.mark.parametrize("axis", [1, -1])
def test_gradients_against_float64(axis, fix_gamma, dtype):
    x, gamma, beta, mm, mv, w = _case(axis, dtype)

    def loss(x, gamma, beta):
        out = _train(x, gamma, beta, mm, mv, axis, fix_gamma)[0]
        return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32))

    def ref_loss(x, gamma, beta):
        out = _ref(x, gamma, beta, axis, fix_gamma)[0]
        return jnp.sum(out * w.astype(jnp.float64))

    got = jax.grad(loss, (0, 1, 2))(x, gamma, beta)
    want = jax.grad(ref_loss, (0, 1, 2))(
        x.astype(jnp.float64), gamma.astype(jnp.float64),
        beta.astype(jnp.float64))
    assert got[0].dtype == x.dtype and got[1].dtype == gamma.dtype
    tol = 1e-5 if dtype == "float32" else 1e-2
    assert _rel_l2(got[0], want[0]) <= tol
    assert _rel_l2(got[2], want[2]) <= tol
    if fix_gamma:
        assert not np.any(_f64(got[1]))
    else:
        assert _rel_l2(got[1], want[1]) <= tol


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_cotangents_on_mean_and_var_are_exact(dtype):
    """``output_mean_var=True``: nonzero cotangents arrive on the batch
    mean and variance besides the output's."""
    axis = 1
    x, gamma, beta, mm, mv, w = _case(axis, dtype, seed=3)
    rs = np.random.RandomState(4)
    u = jnp.asarray(rs.randn(x.shape[1]) * 5)
    v = jnp.asarray(rs.randn(x.shape[1]) * 5)

    def loss(x, gamma, beta):
        out, mean, var = _train(x, gamma, beta, mm, mv, axis, False,
                                output_mean_var=True)[:3]
        return (jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32))
                + jnp.sum(mean * u) + jnp.sum(var * v))

    def ref_loss(x, gamma, beta):
        out, mean, var = _ref(x, gamma, beta, axis, False)
        return (jnp.sum(out * w.astype(jnp.float64))
                + jnp.sum(mean * u) + jnp.sum(var * v))

    got = jax.grad(loss, (0, 1, 2))(x, gamma, beta)
    want = jax.grad(ref_loss, (0, 1, 2))(
        x.astype(jnp.float64), gamma.astype(jnp.float64),
        beta.astype(jnp.float64))
    tol = 1e-5 if dtype == "float32" else 1e-2
    for g, r in zip(got, want):
        assert _rel_l2(g, r) <= tol


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("batch", [4, 256])
def test_mean_far_from_the_shift_is_held_to_bf16_rounding(batch, dtype):
    """The shift's worst case: a channel whose mean is 100 times its
    standard deviation while ``moving_mean`` is still 0, at 256 and at
    16,384 values a channel.  The one read about 0 alone reads this
    channel's bf16 variance 1.3 % off at 16,384 values on the CPU, whose
    float32 sums grow in sequence; the second read, about the batch mean,
    brings both statistics to float32's rounding at either count.  The
    output is held to what the configuration's fold, scale and offset
    cast to bf16 and applied in bf16, does with the exact statistics,
    give or take one bf16 step of the offset (0.5 at an offset of 100:
    exact statistics may round it either way); float32 data, to what
    rounding the exact output to bf16 does.  The gradients' cosine to the
    float64 truth is at least 0.999 (autodiff of the same forward, without
    the custom VJP, reads a bf16 ``gamma`` cosine of −0.32 at 16,384)."""
    rs = np.random.RandomState(5)
    x = rs.randn(batch, 3, 8, 8).astype(np.float32)
    x[:, 0] += 100.0
    x = jnp.asarray(x, dtype)
    gamma = jnp.asarray(rs.rand(3) + 0.5, jnp.float32)
    beta = jnp.asarray(rs.randn(3), jnp.float32)
    w = jnp.asarray(rs.randn(*x.shape), dtype)
    mm, mv = jnp.zeros(3, jnp.float32), jnp.ones(3, jnp.float32)
    out, mean, var = _train(x, gamma, beta, mm, mv, 1, False)[:3]
    r_out, r_mean, r_var = _ref(x, gamma, beta, 1, False)
    assert np.all(np.abs(_f64(mean) - r_mean) <= 1e-5 * np.sqrt(r_var))
    assert np.all(np.abs(_f64(var) - r_var) <= 1e-5 * r_var)
    err = np.abs(_f64(out) - r_out)[:, 0].max()
    if dtype == "float32":
        bound = np.abs(_f64(r_out.astype(jnp.bfloat16)) - r_out)[:, 0].max()
    else:
        scale = gamma * lax.rsqrt(r_var + EPS)
        offset = beta - r_mean * scale
        fold = (x * scale.astype(jnp.bfloat16).reshape(1, -1, 1, 1)
                + offset.astype(jnp.bfloat16).reshape(1, -1, 1, 1))
        step = 2.0 ** (np.floor(np.log2(abs(float(offset[0])))) - 7)
        bound = np.abs(_f64(fold) - r_out)[:, 0].max() + step
    assert err <= bound, (err, bound)

    def loss(x, gamma, beta):
        out = _train(x, gamma, beta, mm, mv, 1, False)[0]
        return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32))

    def ref_loss(x, gamma, beta):
        return jnp.sum(_ref(x, gamma, beta, 1, False)[0]
                       * w.astype(jnp.float64))

    got = jax.grad(loss, (0, 1, 2))(x, gamma, beta)
    want = jax.grad(ref_loss, (0, 1, 2))(
        x.astype(jnp.float64), gamma.astype(jnp.float64),
        beta.astype(jnp.float64))
    for g, r in zip(got, want):
        assert _cosine(g, r) >= 0.999


def test_bf16_backward_is_centred():
    """bf16 data whose channel means are ten standard deviations from 0:
    the gradients' cosine to the float64 truth is at least 0.999.  The
    formula before the custom VJP (autodiff of the bf16 scale and offset
    applied to the uncentred activation: ``Σ dy·x`` and ``μ·Σ dy``
    cancel after bf16 rounding) read a ``gamma`` gradient cosine of 0.417
    here on the CPU, and 0.99991 for ``data``; autodiff of this forward,
    the one read with the same fold, reads 0.946 for ``gamma``, 0.99968
    for ``beta`` and 0.99984 for ``data``."""
    rs = np.random.RandomState(0)
    shape = (8, 4, 16, 16)
    x = jnp.asarray(rs.randn(*shape) + 10.0, jnp.bfloat16)
    gamma = jnp.asarray(rs.rand(4) + 0.5, jnp.float32)
    beta = jnp.asarray(rs.randn(4), jnp.float32)
    w = jnp.asarray(rs.randn(*shape), jnp.bfloat16)
    mm, mv = jnp.zeros(4, jnp.float32), jnp.ones(4, jnp.float32)

    def loss(x, gamma, beta):
        out = _train(x, gamma, beta, mm, mv, 1, False)[0]
        return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32))

    def ref_loss(x, gamma, beta):
        return jnp.sum(_ref(x, gamma, beta, 1, False)[0]
                       * w.astype(jnp.float64))

    got = jax.grad(loss, (0, 1, 2))(x, gamma, beta)
    want = jax.grad(ref_loss, (0, 1, 2))(
        x.astype(jnp.float64), gamma.astype(jnp.float64),
        beta.astype(jnp.float64))
    for g, r in zip(got, want):
        assert _cosine(g, r) >= 0.999


def _parent_train(data, gamma, beta, eps, fix_gamma, axis):
    """The training formula as it stood before the VJP, its statistics
    accumulated in ``data``'s own dtype when that is float64."""
    ax = axis % data.ndim
    red = tuple(i for i in range(data.ndim) if i != ax)
    bshape = tuple(data.shape[ax] if i == ax else 1 for i in range(data.ndim))
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    xf = data.astype(jnp.promote_types(data.dtype, jnp.float32))
    mean = jnp.mean(xf, axis=red)
    var = jnp.var(xf, axis=red)
    scale = g * lax.rsqrt(var + eps)
    offset = beta - mean * scale
    return (data * scale.reshape(bshape).astype(data.dtype)
            + offset.reshape(bshape).astype(data.dtype)), mean, var


@pytest.mark.parametrize("fix_gamma", [True, False])
@pytest.mark.parametrize("axis", [1, -1])
def test_float64_the_vjp_is_the_parent_formulas_gradient(axis, fix_gamma):
    """Run in float64, where rounding leaves the two no room to differ,
    the custom VJP's value and gradients (the ``mean`` / ``var`` outputs'
    cotangents included) are those of autodiff through the formula it
    replaced: what float32 runs of the two differ by is rounding."""
    x, gamma, beta, mm, mv, w = _case(axis, "float64", seed=11)
    gamma, beta = gamma.astype(jnp.float64), beta.astype(jnp.float64)
    rs = np.random.RandomState(12)
    u = jnp.asarray(rs.randn(gamma.shape[0]))
    v = jnp.asarray(rs.randn(gamma.shape[0]))

    def objective(stats):
        def loss(x, gamma, beta):
            out, mean, var = stats(x, gamma, beta)
            return jnp.sum(out * w) + jnp.sum(mean * u) + jnp.sum(var * v)
        return jax.value_and_grad(loss, (0, 1, 2))(x, gamma, beta)

    got = objective(lambda x, g, b: _train(
        x, g, b, mm.astype(jnp.float64), mv.astype(jnp.float64), axis,
        fix_gamma, output_mean_var=True)[:3])
    want = objective(lambda x, g, b: _parent_train(x, g, b, EPS, fix_gamma,
                                                   axis))
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-12)
    for g, r in zip(got[1], want[1]):
        assert g.dtype == jnp.float64
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-10,
                                   atol=1e-12)


def _parent_inference(data, gamma, beta, moving_mean, moving_var, eps,
                      fix_gamma, axis):
    """The inference / global-stats formula as it stood before the VJP."""
    ax = axis % data.ndim
    bshape = tuple(data.shape[ax] if i == ax else 1 for i in range(data.ndim))
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    scale = g * lax.rsqrt(moving_var + eps)
    offset = beta - moving_mean * scale
    return (data * scale.reshape(bshape).astype(data.dtype)
            + offset.reshape(bshape).astype(data.dtype))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("mode", ["inference", "use_global_stats"])
def test_inference_and_global_stats_are_the_parent_formula(mode, dtype):
    for axis, fix_gamma in ((1, False), (-1, True)):
        x, gamma, beta, mm, mv, _ = _case(axis, dtype, seed=7)
        kw = ({"is_train": False} if mode == "inference"
              else {"is_train": True, "use_global_stats": True})
        before = profiler.dispatch_counts().get("batch_norm.train_vjp", 0)
        res = _batch_norm(x, gamma, beta, mm, mv, eps=EPS, momentum=MOM,
                          fix_gamma=fix_gamma, axis=axis, **kw)
        assert len(res) == 3
        assert profiler.dispatch_counts().get(
            "batch_norm.train_vjp", 0) == before
        want = _parent_inference(x, gamma, beta, mm, mv, EPS, fix_gamma,
                                 axis)
        np.testing.assert_array_equal(np.asarray(res[0]), np.asarray(want))
        assert res[1] is mm and res[2] is mv


def test_integer_input_is_promoted():
    x = jnp.asarray(np.random.RandomState(8).randint(0, 256, (4, 3, 5, 5)),
                    jnp.uint8)
    gamma, beta = jnp.ones(3, jnp.float32), jnp.zeros(3, jnp.float32)
    out, mean, var = _train(x, gamma, beta, jnp.zeros(3), jnp.ones(3), 1,
                            True)[:3]
    r_out, r_mean, _ = _ref(x, gamma, beta, 1, True)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(_f64(out), r_out, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(_f64(mean), r_mean, rtol=1e-6)


def test_the_vjp_nests_under_a_checkpoint():
    """``MXNET_BACKWARD_DO_MIRROR`` puts the op inside ``jax.checkpoint``."""
    x, gamma, beta, mm, mv, w = _case(1, "float32", seed=9)

    def loss(x, gamma, beta):
        out = _train(x, gamma, beta, mm, mv, 1, False)[0]
        return jnp.sum(out * w)

    plain = jax.grad(loss, (0, 1, 2))(x, gamma, beta)
    remat = jax.grad(jax.checkpoint(loss), (0, 1, 2))(x, gamma, beta)
    for a, b in zip(plain, remat):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-6)


def test_gluon_batch_norm_trains_through_the_vjp():
    """The gluon layer under ``autograd.record``: gradients of data,
    gamma and beta and the running statistics against the reference."""
    from mxnet_tpu.gluon import nn
    x, _, _, _, _, w = _case(1, "float32", seed=10)
    layer = nn.BatchNorm(in_channels=3, momentum=MOM, epsilon=EPS)
    layer.initialize()
    xd = mx.nd.array(np.asarray(x))
    xd.attach_grad()
    with autograd.record():
        out = layer(xd)
        loss = (out * mx.nd.array(np.asarray(w))).sum()
    loss.backward()
    p = layer.collect_params()
    gamma = p[layer.prefix + "gamma"]
    beta = p[layer.prefix + "beta"]

    def ref_loss(x, g, b):
        return jnp.sum(_ref(x, g, b, 1, False)[0] * w.astype(jnp.float64))

    want = jax.grad(ref_loss, (0, 1, 2))(
        x.astype(jnp.float64), jnp.ones(3, jnp.float64),
        jnp.zeros(3, jnp.float64))
    assert _rel_l2(xd.grad.asnumpy(), want[0]) <= 1e-5
    assert _rel_l2(gamma.grad().asnumpy(), want[1]) <= 1e-5
    assert _rel_l2(beta.grad().asnumpy(), want[2]) <= 1e-5
    r_mean = _ref(x, jnp.ones(3), jnp.zeros(3), 1, False)[1]
    np.testing.assert_allclose(
        p[layer.prefix + "running_mean"].data().asnumpy(),
        _f64(r_mean) * (1 - MOM), rtol=1e-5, atol=1e-6)


def test_one_count_a_batch_norm_node_in_a_trace_of_resnet50():
    """A trace of ResNet-50 v2's fused training step runs each of its 51
    BatchNorm nodes' training path once; an inference trace runs none."""
    import jax.numpy as jnp
    sym = models.resnet(num_layers=50, image_shape="3,224,224",
                        num_classes=1000)
    nodes = [n for n in sym.nodes()
             if not n.is_variable and n.op == "BatchNorm"]
    assert len(nodes) == 51
    mod = mx.mod.Module(sym, compute_dtype=jnp.bfloat16)
    mod.bind(data_shapes=[("data", (2, 3, 224, 224))],
             label_shapes=[("softmax_label", (2,))])
    mod.init_params(mx.initializer.Zero())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    mod.forward(mx.io.DataBatch(data=[mx.nd.zeros((2, 3, 224, 224))],
                                label=[mx.nd.zeros((2,))]), is_train=True)
    before = profiler.dispatch_counts().get("batch_norm.train_vjp", 0)
    mod._lower_fused_step()
    after = profiler.dispatch_counts()["batch_norm.train_vjp"]
    assert after - before == 51
    mod._exec._out_aval_list(False)       # the shape-only inference trace
    assert profiler.dispatch_counts()["batch_norm.train_vjp"] == after
