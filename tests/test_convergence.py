"""Convergence evidence that needs no chip.

Two layers:

1. A short-horizon ResNet-20 loss-trajectory GOLDEN on the CPU platform:
   deterministic data + seeds, recorded per-step NLL pinned to
   tests/golden/resnet20_loss_curve.json.  Any silent change to training
   dynamics (BN semantics, optimizer update, AMP split, initializer RNG)
   shows up as a trajectory mismatch — and the curve itself demonstrates
   real learning (loss must drop >40% over 24 steps).
   Regenerate after an INTENDED dynamics change:
   ``CONV_GOLDEN_REGEN=1 pytest tests/test_convergence.py -k golden``.

2. A real-data convergence run (slow-marked): ResNet-20 on sklearn's
   digits — the same trainer tools/chip_convergence_run.py drives on the
   chip — must reach >=0.90 test accuracy in 14 epochs on CPU.
   Full-horizon CPU evidence lives in docs/artifacts/digits_resnet_cpu
   .json (DIGITS_ARTIFACT_CPU=1), bar 0.97 as the chip run.

Anchor: the reference's published top-1 0.7527 story
(example/image-classification README).
"""
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import symbol as sym  # noqa: F401  (parity with siblings)

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "golden", "resnet20_loss_curve.json")


def _digits_batches(batch=50, steps=12):
    from sklearn.datasets import load_digits
    d = load_digits()
    x = (d.images / 16.0).astype(np.float32)
    y = d.target.astype(np.float32)
    x = x.repeat(3, axis=1).repeat(3, axis=2)
    x = np.pad(x, ((0, 0), (2, 2), (2, 2)))
    x = np.stack([x, x, x], axis=1)
    rs = np.random.RandomState(0)
    order = rs.permutation(len(x))
    x, y = x[order], y[order]
    return [(x[i * batch:(i + 1) * batch], y[i * batch:(i + 1) * batch])
            for i in range(steps)]


def _loss_curve(steps=24, batch=50):
    from mxnet_tpu import models
    net = models.resnet(num_classes=10, num_layers=20,
                        image_shape=(3, 28, 28))
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (batch, 3, 28, 28))],
             label_shapes=[("softmax_label", (batch,))])
    mx.random.seed(7)
    np.random.seed(7)
    mod.init_params(mx.initializer.Xavier(rnd_type="gaussian",
                                          magnitude=2.0))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9, "wd": 1e-4})
    losses = []
    for bx, by in _digits_batches(batch, steps):
        db = mx.io.DataBatch(data=[mx.nd.array(bx)],
                             label=[mx.nd.array(by)])
        mod.forward(db, is_train=True)
        prob = mod.get_outputs()[0].asnumpy()
        nll = -np.mean(np.log(np.maximum(
            prob[np.arange(len(by)), by.astype(int)], 1e-8)))
        losses.append(float(nll))
        mod.backward()
        mod.update()
    return losses


def test_resnet20_loss_trajectory_golden():
    losses = _loss_curve()
    # learning is real: >40% drop from the first to the min of last 3
    assert min(losses[-3:]) < 0.6 * losses[0], losses
    if os.environ.get("CONV_GOLDEN_REGEN"):
        with open(GOLDEN, "w") as f:
            json.dump({"losses": [round(l, 6) for l in losses],
                       "config": {"steps": 24, "batch": 50, "lr": 0.1,
                                  "momentum": 0.9, "wd": 1e-4,
                                  "seed": 7}}, f, indent=1)
        pytest.skip("golden regenerated")
    assert os.path.exists(GOLDEN), \
        "golden missing: run CONV_GOLDEN_REGEN=1 pytest -k golden"
    want = json.load(open(GOLDEN))["losses"]
    np.testing.assert_allclose(losses, want, rtol=2e-3, atol=2e-3,
                               err_msg="training dynamics drifted from "
                               "the pinned trajectory")


@pytest.mark.slow
def test_digits_convergence_cpu():
    # the same script the chip session runs, CPU-pinned, shortened
    import subprocess
    import sys
    env = dict(os.environ, DIGITS_CPU="1", DIGITS_EPOCHS="14")
    out = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools",
            "chip_convergence_run.py")],
        capture_output=True, text=True, env=env, timeout=1800)
    assert out.returncode == 0, out.stderr[-2000:]
    line = [l for l in out.stdout.splitlines()
            if l.startswith("SMOKE OK")][-1]
    res = json.loads(line[len("SMOKE OK "):])
    assert res["final_test_acc"] >= 0.90, (res, out.stdout[-1500:])
