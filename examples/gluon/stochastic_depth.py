"""Stochastic-depth ResNet as a custom gluon HybridBlock
(reference: example/gluon/... stochastic-depth — residual blocks that
randomly SKIP their conv branch during training, scaling it at test
time; Huang et al. 2016).

The gluon extensibility story: a user-defined HybridBlock whose
hybrid_forward makes a per-forward random keep/skip decision, composed
into a trainable net with ``gluon.Trainer`` + autograd.  The blocks run
EAGERLY (each op jit-cached individually): the keep decision is plain
host-side Python, so the skip path does zero conv work.  Do NOT
hybridize() this net — a whole-graph cache would bake one random
decision into the cached program and silently freeze the depth.

Run:  python examples/gluon/stochastic_depth.py [--epochs 8]
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import autograd, gluon, nd  # noqa: E402


class StochasticResidual(gluon.HybridBlock):
    """Residual block kept with probability `p_keep` during training;
    at inference the branch is always on, scaled by p_keep."""

    def __init__(self, channels, p_keep=0.8, rng=None, **kwargs):
        super().__init__(**kwargs)
        self.p_keep = p_keep
        self._rng = rng or np.random.RandomState(0)
        with self.name_scope():
            self.conv1 = gluon.nn.Conv2D(channels, 3, padding=1)
            self.bn1 = gluon.nn.BatchNorm()
            self.conv2 = gluon.nn.Conv2D(channels, 3, padding=1)
            self.bn2 = gluon.nn.BatchNorm()

    def hybrid_forward(self, F, x):
        if autograd.is_training() and self._rng.uniform() >= self.p_keep:
            # skipped: no conv compute at all, and the skipped block's
            # BatchNorm running stats stay untouched
            return F.Activation(x, act_type='relu')
        branch = self.bn2(self.conv2(
            F.Activation(self.bn1(self.conv1(x)), act_type='relu')))
        if autograd.is_training():
            return F.Activation(x + branch, act_type='relu')
        return F.Activation(x + self.p_keep * branch, act_type='relu')


def build_net(p_keep=0.8, seed=0):
    rng = np.random.RandomState(seed)
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Conv2D(16, 3, padding=1),
                gluon.nn.BatchNorm(),
                gluon.nn.Activation('relu'),
                StochasticResidual(16, p_keep, rng),
                StochasticResidual(16, p_keep, rng),
                gluon.nn.MaxPool2D(2),
                StochasticResidual(16, p_keep, rng),
                gluon.nn.GlobalAvgPool2D(),
                gluon.nn.Dense(10))
    return net


def run(epochs=8, batch=100, p_keep=0.8, seed=0, log=print):
    from sklearn.datasets import load_digits
    d = load_digits()
    x = (d.images / 16.0).astype(np.float32)[:, None, :, :]
    y = d.target.astype(np.float32)
    n = 1500

    mx.random.seed(seed)
    np.random.seed(seed)
    net = build_net(p_keep, seed)
    net.initialize(mx.initializer.Xavier())
    trainer = gluon.Trainer(net.collect_params(), 'adam',
                            {'learning_rate': 2e-3})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    for epoch in range(epochs):
        perm = np.random.permutation(n)
        total = 0.0
        for i in range(n // batch):
            sl = perm[i * batch:(i + 1) * batch]
            bx, by = nd.array(x[sl]), nd.array(y[sl])
            with autograd.record():
                out = net(bx)
                loss = loss_fn(out, by)
            loss.backward()
            trainer.step(batch)
            total += float(loss.mean().asscalar())
        log("epoch %d train loss %.4f" % (epoch, total / (n // batch)))

    # eval: deterministic scaled-branch path
    pred = net(nd.array(x[n:])).asnumpy().argmax(axis=1)
    acc = float((pred == y[n:]).mean())
    log("stochastic-depth test acc %.4f" % acc)
    return acc


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--epochs', type=int, default=8)
    ap.add_argument('--p-keep', type=float, default=0.8)
    a = ap.parse_args()
    acc = run(epochs=a.epochs, p_keep=a.p_keep)
    print("final stochastic-depth acc %.4f" % acc)


if __name__ == '__main__':
    main()
