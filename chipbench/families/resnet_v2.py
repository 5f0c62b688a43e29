"""ResNet, pre-activation (v2): He et al. 2016, "Identity Mappings in Deep
Residual Networks", as laid out by the reference implementation's
``example/image-classification/symbols/resnet.py`` (the parameter names are
that file's).  ``build`` asks the program for its symbol; everything else
here is the yardstick's own: the plain reference, the FLOP count."""
import functools

UNITS = {18: (2, 2, 2, 2), 50: (3, 4, 6, 3)}
BN_EPS = 2e-5
OUTPUT_WEIGHT = "fc1_weight"


def _arch(cfg):
    n = int(cfg["builder"]["num_layers"])
    bottleneck = n >= 50
    filters = (64, 256, 512, 1024, 2048) if bottleneck \
        else (64, 64, 128, 256, 512)
    shape = tuple(int(x) for x in cfg["builder"]["image_shape"].split(","))
    if shape[1] <= 32:
        raise ValueError("resnet_v2: the reference has the ImageNet stem "
                         "only; image_shape %r takes the CIFAR one" % (shape,))
    return UNITS[n], filters, bottleneck, shape


def build(cfg, traffic):
    from mxnet_tpu import models
    _, _, _, shape = _arch(cfg)
    b = int(traffic["batch"])
    return {"symbol": models.resnet(**cfg["builder"]),
            "data_shapes": [("data", (b,) + shape)],
            "label_shapes": [("softmax_label", (b,))],
            "items_per_step": b, "output_weight": OUTPUT_WEIGHT}


def make_batch(cfg, traffic, key):
    """One resident batch, made on the device: images uniform in [-1, 1),
    labels uniform over the classes (float32, as MXNet iterators give)."""
    import jax
    import jax.numpy as jnp
    _, _, _, shape = _arch(cfg)
    b, classes = int(traffic["batch"]), int(cfg["builder"]["num_classes"])

    @jax.jit
    def make(key):
        kx, ky = jax.random.split(key)
        x = jax.random.uniform(kx, (b,) + shape, jnp.float32, -1.0, 1.0)
        y = jax.random.randint(ky, (b,), 0, classes, jnp.int32)
        return x, y.astype(jnp.float32)
    return make(key)


def output_sample(cfg, traffic, seed):
    return None     # all batch x classes probabilities are compared


# -- the plain reference ----------------------------------------------------
def _forward(cfg, params, data, cd):
    """Probabilities (N, classes) in float32.  ``cd`` is the compute dtype:
    parameters are cast to it where they meet an activation, activations
    stay in it, BatchNorm takes its statistics in float32 and folds them
    into a per-channel scale and offset that are cast to ``cd``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    f32 = jnp.float32
    units, filters, bottleneck, _ = _arch(cfg)

    def conv(x, name, stride, pad):
        w = params[name + "_weight"].astype(cd)
        return lax.conv_general_dilated(
            x, w, (stride, stride), ((pad, pad), (pad, pad)),
            dimension_numbers=("NCHW", "OIHW", "NCHW"))

    def bn(x, name, fix_gamma=False):
        xf = x.astype(f32)
        mean = jnp.mean(xf, axis=(0, 2, 3))
        var = jnp.mean(jnp.square(xf - mean[None, :, None, None]),
                       axis=(0, 2, 3))
        gamma = params[name + "_gamma"].astype(f32)
        if fix_gamma:
            gamma = jnp.ones_like(gamma)
        scale = gamma * lax.rsqrt(var + f32(BN_EPS))
        offset = params[name + "_beta"].astype(f32) - mean * scale
        return (x * scale.astype(cd)[None, :, None, None]
                + offset.astype(cd)[None, :, None, None])

    def relu(x):
        return jnp.maximum(x, jnp.zeros((), x.dtype))

    def unit(x, name, nf, stride, dim_match):
        act1 = relu(bn(x, name + "_bn1"))
        if bottleneck:
            y = conv(act1, name + "_conv1", 1, 0)
            y = conv(relu(bn(y, name + "_bn2")), name + "_conv2", stride, 1)
            y = conv(relu(bn(y, name + "_bn3")), name + "_conv3", 1, 0)
        else:
            y = conv(act1, name + "_conv1", stride, 1)
            y = conv(relu(bn(y, name + "_bn2")), name + "_conv2", 1, 1)
        return y + (x if dim_match else conv(act1, name + "_sc", stride, 0))

    x = bn(data.astype(cd), "bn_data", fix_gamma=True)
    x = relu(bn(conv(x, "conv0", 2, 3), "bn0"))
    x = lax.reduce_window(x, np.array(-np.inf, x.dtype), lax.max,
                          (1, 1, 3, 3), (1, 1, 2, 2),
                          ((0, 0), (0, 0), (1, 1), (1, 1)))
    for i, n_units in enumerate(units):
        for j in range(n_units):
            # one unit's activations at a time are kept for the backward
            # pass; the float32 run at batch 256 does not fit otherwise
            step = jax.checkpoint(functools.partial(
                unit, name="stage%d_unit%d" % (i + 1, j + 1),
                nf=filters[i + 1], stride=(1 if i == 0 or j else 2),
                dim_match=j > 0))
            x = step(x)
    x = relu(bn(x, "bn1"))
    x = jnp.mean(x.astype(f32), axis=(2, 3)).astype(cd)
    logits = (x @ params["fc1_weight"].astype(cd).T
              + params["fc1_bias"].astype(cd))
    return jax.nn.softmax(logits.astype(f32), axis=-1)


def reference(cfg, traffic, params, data, label, compute_dtype, sample=None):
    """(mean loss, probabilities, {tensor: delta of one SGD-momentum step})
    from float32 master ``params``; plain jax.numpy and lax, nothing of
    ``mxnet_tpu``.  The objective is the mean cross-entropy over the
    batch (MXNet: summed head gradient, rescale_grad = 1/batch)."""
    import jax
    import jax.numpy as jnp
    from chipbench.common import sgd_momentum_delta
    cd = jnp.dtype(compute_dtype)

    def objective(p, data, label):
        probs = _forward(cfg, p, data, cd)
        lab = label.astype(jnp.int32)
        picked = jnp.take_along_axis(probs, lab[:, None], axis=1)[:, 0]
        loss = -jnp.mean(jnp.log(picked))
        return loss, (loss, probs)

    # the batch is an argument: a closed-over array would be a constant of
    # the program, and no other seed would find it cached
    @jax.jit
    def step(p, data, label):
        with jax.default_matmul_precision("highest"):
            grads, (loss, probs) = jax.grad(objective, has_aux=True)(
                p, data, label)
        return loss, probs, sgd_momentum_delta(p, grads, cfg["optimizer"])
    return step(params, data, label)


# -- operations the model requires -------------------------------------------
def model_flops(cfg, traffic):
    """Multiply-adds x 2 of every convolution and the classifier, forward,
    times 3 for forward + backward (each has a data and a weight
    gradient; bn_data's beta needs the stem's), per step.  Elementwise
    work and normalisation are not counted; nothing is recomputed."""
    units, filters, bottleneck, (c, h, w) = _arch(cfg)
    macs = 0

    def conv(cin, cout, k, stride, hw):
        nonlocal macs
        out = ((hw[0] + 2 * (k // 2) - k) // stride + 1,
               (hw[1] + 2 * (k // 2) - k) // stride + 1)
        macs += cin * cout * k * k * out[0] * out[1]
        return out

    hw = conv(c, filters[0], 7, 2, (h, w))
    hw = ((hw[0] + 2 - 3) // 2 + 1, (hw[1] + 2 - 3) // 2 + 1)
    cin = filters[0]
    for i, n_units in enumerate(units):
        nf = filters[i + 1]
        for j in range(n_units):
            stride = 1 if i == 0 or j else 2
            if j == 0:
                conv(cin, nf, 1, stride, hw)           # projection shortcut
            if bottleneck:
                conv(cin, nf // 4, 1, 1, hw)
                out = conv(nf // 4, nf // 4, 3, stride, hw)
                conv(nf // 4, nf, 1, 1, out)
            else:
                out = conv(cin, nf, 3, stride, hw)
                conv(nf, nf, 3, 1, out)
            hw, cin = out, nf
    macs += cin * int(cfg["builder"]["num_classes"])
    return 3.0 * 2.0 * macs * int(traffic["batch"])
