"""Precision-layout guard on the COMPILED fused train step.

The round-2/3 MFU work moved BatchNorm onto a bf16 data path with fp32
statistics (reference contract:
src/operator/cudnn_batch_norm-inl.h — fp32 stats over a low-precision
data path).  These tests pin that contract at the StableHLO level, on
CPU, so an AMP regression (an op silently upcasting the activation
stream to fp32 between conv fusions) is caught without chip time:

* every convolution in the lowered step consumes bf16 operands;
* every large dot/dot_general does too (the fp32 ops that remain are
  statistics reductions, the softmax/loss head, and the optimizer update
  on fp32 master weights — all small or param-shaped, not
  activation-shaped).
"""
import re

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models


def _lowered_resnet_step_hlo(compute_dtype,
                             num_layers=8, image_shape=(3, 28, 28)):
    import jax.numpy as jnp
    sym = models.resnet(num_classes=10, num_layers=num_layers,
                        image_shape=image_shape)
    mod = mx.mod.Module(sym, compute_dtype=compute_dtype and
                        jnp.dtype(compute_dtype))
    batch = 2
    it = mx.io.NDArrayIter(
        data=np.random.RandomState(0).uniform(
            -1, 1, (batch,) + tuple(image_shape)).astype(np.float32),
        label=np.zeros((batch,), np.float32), batch_size=batch)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    mod.forward(next(iter(it)), is_train=True)
    hlo = mod.fused_step_hlo()
    mod.update()
    return hlo


# one lowering serves both tests (tracing a ResNet step isn't free)
@pytest.fixture(scope="module")
def bf16_hlo():
    return _lowered_resnet_step_hlo("bfloat16")


def _op_operand_dtypes(hlo, op):
    """dtypes of tensor operands for every `op` application in the text."""
    out = []
    for m in re.finditer(r"stablehlo\.%s[^\n]*:\s*\(([^)]*)\)" % op, hlo):
        dts = re.findall(r"tensor<[^>]*?x?([a-z]+[0-9]+)>", m.group(1))
        out.append(dts)
    return out


def test_bf16_step_has_no_fp32_convolution(bf16_hlo):
    convs = _op_operand_dtypes(bf16_hlo, "convolution")
    assert convs, "no convolutions found in lowered step HLO"
    bad = [dts for dts in convs if "f32" in dts]
    assert not bad, (
        "fp32 convolutions in bf16 fused step (AMP regression): %r"
        % bad[:5])


def test_bf16_step_dots_are_bf16(bf16_hlo):
    dots = _op_operand_dtypes(bf16_hlo, "dot_general")
    assert dots, "no dot_general found in lowered step HLO"
    bad = [dts for dts in dots if "f32" in dts]
    assert not bad, (
        "fp32 dot_general in bf16 fused step (AMP regression): %r"
        % bad[:5])


def test_fp32_mode_keeps_fp32_convolution():
    hlo = _lowered_resnet_step_hlo(None)
    convs = _op_operand_dtypes(hlo, "convolution")
    assert convs and all("f32" in dts for dts in convs)


def _sweep_step_hlo(remat_policy):
    """Lower the fused step under a remat setting — the configs the chip
    sweeps measure; an fp32 activation leak in one of them would waste the
    chip session.

    The 7x7/s2 stem only exists on the imagenet branch (height > 32,
    models/resnet.py), so this lowers a 64x64 ResNet-18 — 28x28 would
    silently test the cifar stem, which ``Convolution`` does not fold.
    """
    import os
    old = {k: os.environ.pop(k, None)
           for k in ("MXNET_BACKWARD_DO_MIRROR", "MXNET_REMAT_POLICY")}
    try:
        if remat_policy:
            os.environ["MXNET_BACKWARD_DO_MIRROR"] = "1"
            if remat_policy not in ("1", "full"):
                os.environ["MXNET_REMAT_POLICY"] = remat_policy
        return _lowered_resnet_step_hlo("bfloat16", num_layers=18,
                                        image_shape=(3, 64, 64))
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


@pytest.mark.parametrize("remat", [
    None,
    "save_matmuls",
    "1",        # full remat: the stem is then traced twice
], ids=["s2d-None", "s2d-save_matmuls", "s2d-1"])
def test_sweep_configs_keep_bf16_convs(remat):
    """The default builder's stem reaches the lowered step as the folded
    convolution ``Convolution`` makes of it (ops/nn.py): 12 input
    channels, 4 x 4 taps, bf16 operands — and no convolution of the step
    takes an fp32 operand, under each remat setting."""
    hlo = _sweep_step_hlo(remat)
    operands = [m.group(1) for m in re.finditer(
        r"stablehlo\.convolution[^\n]*:\s*\(([^)]*)\)", hlo)]
    # non-vacuous stem check: the forward convolves the folded input with
    # the folded weight, and nothing in the step is a 7 x 7 over 3 channels
    assert "tensor<2x12x35x35xbf16>, tensor<64x12x4x4xbf16>" in operands, \
        "folded stem not present in lowered HLO"
    assert not re.search(r"stablehlo\.convolution[^\n]*x3x7x7x", hlo)
    convs = _op_operand_dtypes(hlo, "convolution")
    assert convs, "no convolutions found in lowered step"
    for dts in convs:
        assert all(d == "bf16" for d in dts), (remat, dts)
