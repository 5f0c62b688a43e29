"""Model zoo coverage: every builder constructs, infers shape, and runs one
forward/backward on tiny inputs (mirrors reference symbols/ being exercised
by example configs + test_forward.py)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models


SMALL = [
    ("mlp", dict(num_classes=10), (2, 1, 28, 28)),
    ("lenet", dict(num_classes=10), (2, 1, 28, 28)),
    ("resnet", dict(num_layers=18, num_classes=10,
                    image_shape="3,32,32"), (2, 3, 32, 32)),
    pytest.param("resnet", dict(num_layers=50, num_classes=10,
                                image_shape="3,64,64"), (1, 3, 64, 64),
                 marks=pytest.mark.slow),  # deep-variant sweep; CI tier
    pytest.param("resnext", dict(num_layers=50, num_classes=10,
                                 image_shape="3,64,64", num_group=4),
                 (1, 3, 64, 64), marks=pytest.mark.slow),
    ("mobilenet", dict(num_classes=10, multiplier=0.25), (1, 3, 64, 64)),
    ("squeezenet", dict(num_classes=10), (1, 3, 64, 64)),
]

LARGE = [
    ("alexnet", dict(num_classes=1000), (1, 3, 224, 224)),
    ("densenet", dict(num_layers=121, num_classes=1000), (1, 3, 224, 224)),
    ("vgg", dict(num_layers=11, num_classes=1000), (1, 3, 224, 224)),
    ("inception-bn", dict(num_classes=1000), (1, 3, 224, 224)),
    ("inception-v3", dict(num_classes=1000), (1, 3, 299, 299)),
]


@pytest.mark.parametrize("net,kwargs,dshape", SMALL)
def test_small_models_forward_backward(net, kwargs, dshape):
    symbol = models.get_symbol(net, **kwargs)
    arg_shapes, out_shapes, _ = symbol.infer_shape(data=dshape)
    assert out_shapes[0] == (dshape[0], kwargs["num_classes"])
    ex = symbol.simple_bind(mx.cpu(), data=dshape,
                            softmax_label=(dshape[0],))
    ex.forward(is_train=True)
    out = ex.outputs[0].asnumpy()
    assert out.shape == (dshape[0], kwargs["num_classes"])
    assert np.allclose(out.sum(axis=1), 1.0, atol=1e-4)
    ex.backward()
    # every trainable arg got a gradient
    for name, g in ex.grad_dict.items():
        if name in ("data", "softmax_label"):
            continue
        assert np.isfinite(g.asnumpy()).all(), name


@pytest.mark.parametrize("net,kwargs,dshape", LARGE)
def test_large_models_shape_only(net, kwargs, dshape):
    symbol = models.get_symbol(net, **kwargs)
    arg_shapes, out_shapes, _ = symbol.infer_shape(data=dshape)
    assert out_shapes[0] == (dshape[0], kwargs["num_classes"])


def test_resnet50_imagenet_shapes():
    symbol = models.resnet(num_layers=50, num_classes=1000,
                           image_shape="3,224,224")
    args = symbol.list_arguments()
    arg_shapes, out_shapes, _ = symbol.infer_shape(data=(2, 3, 224, 224))
    n_params = sum(int(np.prod(s)) for name, s in zip(args, arg_shapes)
                   if name not in ("data", "softmax_label"))
    # ResNet-50 ~25.5M params (reference zoo resnet-50 checkpoint size)
    assert 24e6 < n_params < 27e6, n_params


def test_unknown_network():
    with pytest.raises(ValueError):
        models.get_symbol("nonexistent")


def _plain_stem(monkeypatch):
    """Make ``Convolution`` compute every convolution as written (what the
    tree did before the op folded strided few-channel ones)."""
    from mxnet_tpu.ops import nn
    monkeypatch.setattr(nn, "_FOLD_MIN_TAPS", 1 << 30)


def test_s2d_stem_equivalent_to_conv7(monkeypatch, conv_fold_instants):
    """The default builder's 7x7/s2 stem runs through space-to-depth
    inside ``Convolution`` (the MLPerf-TPU trick, now the op's own) and
    computes the SAME function as the plain 7x7/s2 convolution from the
    same ``(64, 3, 7, 7)`` weight; only ``conv0`` folds."""
    from mxnet_tpu import tracing
    rs = np.random.RandomState(3)
    B = 2
    x = rs.uniform(-1, 1, (B, 3, 64, 64)).astype('f')
    net = models.resnet(num_layers=18, num_classes=10, image_shape="3,64,64")

    def bind():
        ex = net.simple_bind(mx.cpu(), data=x.shape, softmax_label=(B,),
                             grad_req='null')
        ex.arg_dict['data'][:] = x
        return ex

    ex1 = bind()
    assert ex1.arg_dict['conv0_weight'].shape == (64, 3, 7, 7)
    for name, arr in ex1.arg_dict.items():
        if name not in ('data', 'softmax_label'):
            arr[:] = rs.uniform(-0.05, 0.05, arr.shape).astype('f')
    o1 = ex1.forward(is_train=False)[0].asnumpy()
    said = conv_fold_instants(nodes_only=True)
    assert said and all(
        a == {"node": "conv0", "c_in": 3, "kernel": 7, "stride": 2,
              "folded_channels": 12, "folded_kernel": 4, "layout": "NCHW"}
        for a in said), said

    _plain_stem(monkeypatch)
    tracing.reset()
    ex2 = bind()
    for name, arr in ex2.arg_dict.items():
        if name not in ('data', 'softmax_label'):
            arr[:] = ex1.arg_dict[name].asnumpy()
    o2 = ex2.forward(is_train=False)[0].asnumpy()
    assert conv_fold_instants(nodes_only=True) == []
    np.testing.assert_allclose(o1, o2, rtol=1e-4, atol=1e-5)


def test_checkpoint_of_plain_stem_loads_into_folded(monkeypatch, tmp_path,
                                                    conv_fold_instants):
    """A checkpoint written by a tree whose stem was a plain 7x7/s2
    convolution loads as it is: ``conv0_weight`` is (64, 3, 7, 7) in
    ``get_params()`` and on disk on both sides, and the first training
    step gives the same loss to float32 rounding."""
    rs = np.random.RandomState(11)
    B = 4
    x = rs.uniform(-1, 1, (B, 3, 64, 64)).astype('f')
    y = rs.randint(0, 10, (B,)).astype('f')
    net = models.resnet(num_layers=18, num_classes=10, image_shape="3,64,64")
    batch = mx.io.DataBatch(data=[mx.nd.array(x)], label=[mx.nd.array(y)])

    def first_step(arg_params=None, aux_params=None):
        mod = mx.mod.Module(net)
        mod.bind(data_shapes=[("data", x.shape)],
                 label_shapes=[("softmax_label", y.shape)])
        mx.random.seed(5)
        mod.init_params(mx.initializer.Xavier(), arg_params=arg_params,
                        aux_params=aux_params)
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1,
                                             "momentum": 0.9})
        before = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
        mod.forward(batch, is_train=True)
        probs = mod.get_outputs()[0].asnumpy()
        mod.update()
        loss = -np.log(probs[np.arange(B), y.astype(int)]).mean()
        return mod, before, loss

    with monkeypatch.context() as m:
        _plain_stem(m)
        parent, p0, loss_parent = first_step()
        assert conv_fold_instants(nodes_only=True) == []
        prefix = str(tmp_path / "parent")
        # the parameters the step started from, as the parent would save them
        mx.model.save_checkpoint(prefix, 0, net,
                                 {k: mx.nd.array(v) for k, v in p0.items()},
                                 parent.get_params()[1])
    _, args, auxs = mx.model.load_checkpoint(prefix, 0)
    assert args["conv0_weight"].shape == (64, 3, 7, 7)
    # aux states moved in the parent's step; its loss did not depend on them
    change, c0, loss_change = first_step(args, None)
    assert any(a["node"] == "conv0" for a in conv_fold_instants())
    assert change.get_params()[0]["conv0_weight"].shape == (64, 3, 7, 7)
    np.testing.assert_array_equal(c0["conv0_weight"], p0["conv0_weight"])
    np.testing.assert_allclose(loss_change, loss_parent, rtol=1e-5)
    change.save_checkpoint(str(tmp_path / "change"), 1)
    _, args2, _ = mx.model.load_checkpoint(str(tmp_path / "change"), 1)
    assert args2["conv0_weight"].shape == (64, 3, 7, 7)


def test_vit_trains_and_gqa():
    """ViT builder (models/vit.py): non-causal flash attention blocks,
    patch conv, GAP head — trains a small net above chance on a linearly
    separable toy task; GQA variant builds too."""
    rng = np.random.RandomState(0)
    n, nc = 64, 4
    y = rng.randint(0, nc, (n,)).astype('f')
    # class-dependent mean image: trivially learnable
    x = rng.randn(n, 3, 16, 16).astype('f') * 0.1
    for i in range(n):
        x[i] += int(y[i]) * 0.5

    net = models.vit(nc, image_shape=(3, 16, 16), patch_size=8,
                     num_layers=1, d_model=32, num_heads=4,
                     num_kv_heads=2)
    mod = mx.mod.Module(net)
    it = mx.io.NDArrayIter(x, y, batch_size=32, shuffle=True)
    mx.random.seed(5)
    mod.fit(it, num_epoch=12, optimizer='adam',
            optimizer_params={'learning_rate': 3e-3},
            initializer=mx.initializer.Xavier(),
            eval_metric='acc')
    it.reset()
    metric = mx.metric.Accuracy()
    mod.score(it, metric)
    acc = dict(metric.get_name_value())['accuracy']
    assert acc > 0.7, acc


@pytest.mark.parametrize("stem", ["conv7", "s2d"])
def test_nhwc_layout_matches_nchw(stem, monkeypatch, conv_fold_instants):
    """layout='NHWC' (channels-last activation path, MLPerf-TPU
    convention) computes the SAME function and gradients as the default
    NCHW graph from identical (layout-agnostic OIHW) weights, forward and
    backward — with the stem folded by ``Convolution`` in both layouts
    (``s2d``, the default) and with it computed as written (``conv7``)."""
    rs = np.random.RandomState(7)
    B = 2
    x = rs.uniform(-1, 1, (B, 3, 64, 64)).astype('f')
    y = rs.randint(0, 10, (B,)).astype('f')
    if stem == "conv7":
        _plain_stem(monkeypatch)
    kw = dict(num_layers=18, num_classes=10, image_shape="3,64,64")
    nchw = models.resnet(layout="NCHW", **kw)
    nhwc = models.resnet(layout="NHWC", **kw)
    ex1 = nchw.simple_bind(mx.cpu(), data=x.shape, softmax_label=(B,),
                           grad_req='write')
    for name, arr in ex1.arg_dict.items():
        if name in ('data', 'softmax_label'):
            continue
        arr[:] = rs.uniform(-0.05, 0.05, arr.shape).astype('f')
    ex2 = nhwc.simple_bind(mx.cpu(), data=x.shape, softmax_label=(B,),
                           grad_req='write')
    for name, arr in ex2.arg_dict.items():
        if name in ('data', 'softmax_label'):
            continue
        assert arr.shape == ex1.arg_dict[name].shape, name
        arr[:] = ex1.arg_dict[name].asnumpy()
    for ex in (ex1, ex2):
        ex.arg_dict['data'][:] = x
        ex.arg_dict['softmax_label'][:] = y
    o1 = ex1.forward(is_train=True)[0].asnumpy()
    o2 = ex2.forward(is_train=True)[0].asnumpy()
    np.testing.assert_allclose(o1, o2, rtol=1e-4, atol=1e-5)
    ex1.backward()
    ex2.backward()
    for name in ex1.grad_dict:
        if name in ('data', 'softmax_label'):
            continue
        g1 = ex1.grad_dict[name].asnumpy()
        g2 = ex2.grad_dict[name].asnumpy()
        np.testing.assert_allclose(
            g1, g2, rtol=2e-3, atol=2e-5,
            err_msg=f"{stem} grad mismatch for {name}")
    said = conv_fold_instants(nodes_only=True)
    assert sorted({a["layout"] for a in said}) == \
        ([] if stem == "conv7" else ["NCHW", "NHWC"])
