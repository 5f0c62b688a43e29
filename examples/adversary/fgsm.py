"""Fast Gradient Sign Method adversarial examples
(reference: example/adversary/adversary_generation.ipynb — train a
small net, take d(loss)/d(input), perturb the image by
eps * sign(grad), watch accuracy collapse).

The distinctive API here is gradients THROUGH a trained module back to
the data: the reference bound its executor with inputs_need_grad; this
port trains with Module, then drives the attack imperatively with
``autograd`` over the module's parameters — same math, the tape instead
of a bound executor slot.

Run:  python examples/adversary/fgsm.py [--eps 0.15]
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import autograd, nd  # noqa: E402


def load_digits_data():
    from sklearn.datasets import load_digits
    d = load_digits()
    x = (d.images / 16.0).astype(np.float32)[:, None, :, :]  # (N,1,8,8)
    y = d.target.astype(np.float32)
    return x[:1500], y[:1500], x[1500:], y[1500:]


def net_symbol():
    data = mx.sym.Variable('data')
    h = mx.sym.Convolution(data, kernel=(3, 3), num_filter=16,
                           pad=(1, 1), name='c1')
    h = mx.sym.Activation(h, act_type='relu')
    h = mx.sym.Pooling(h, kernel=(2, 2), stride=(2, 2), pool_type='max')
    h = mx.sym.Flatten(h)
    h = mx.sym.FullyConnected(h, num_hidden=64, name='f1')
    h = mx.sym.Activation(h, act_type='relu')
    h = mx.sym.FullyConnected(h, num_hidden=10, name='f2')
    return mx.sym.SoftmaxOutput(h, name='softmax')


def train_model(xtr, ytr, epochs=6, batch=100, seed=0):
    it = mx.io.NDArrayIter(xtr, ytr, batch, shuffle=True,
                           last_batch_handle='discard')
    mx.random.seed(seed)
    mod = mx.mod.Module(net_symbol(), context=mx.cpu())
    mod.fit(it, num_epoch=epochs, optimizer='adam',
            optimizer_params={'learning_rate': 2e-3},
            initializer=mx.initializer.Xavier())
    return mod


def fgsm_attack(mod, x, y, eps):
    """eps * sign(d NLL / d x), computed on the tape against the trained
    module's parameters."""
    args, _ = mod.get_params()
    w = {k: v for k, v in args.items()}
    xv = nd.array(x)
    xv.attach_grad()
    with autograd.record():
        h = nd.Convolution(xv, w['c1_weight'], w['c1_bias'],
                           kernel=(3, 3), pad=(1, 1), num_filter=16)
        h = nd.relu(h)
        h = nd.Pooling(h, kernel=(2, 2), stride=(2, 2), pool_type='max')
        h = nd.Flatten(h)
        h = nd.relu(nd.FullyConnected(h, w['f1_weight'], w['f1_bias'],
                                      num_hidden=64))
        logits = nd.FullyConnected(h, w['f2_weight'], w['f2_bias'],
                                   num_hidden=10)
        logp = nd.log_softmax(logits)
        idx = nd.one_hot(nd.array(y), 10)
        loss = -(logp * idx).sum() / len(y)
    loss.backward()
    return np.clip(x + eps * np.sign(xv.grad.asnumpy()), 0.0, 1.0)


def accuracy(mod, x, y, batch=100):
    it = mx.io.NDArrayIter(x, y, batch)
    return mod.score(it, 'acc')[0][1]


def run(eps=0.15, epochs=6, log=print):
    xtr, ytr, xte, yte = load_digits_data()
    mod = train_model(xtr, ytr, epochs=epochs)
    clean = accuracy(mod, xte, yte)
    x_adv = fgsm_attack(mod, xte, yte, eps)
    adv = accuracy(mod, x_adv, yte)
    log("clean acc %.4f -> adversarial acc %.4f (eps=%.3f, "
        "mean |dx|=%.4f)" % (clean, adv, eps,
                             float(np.abs(x_adv - xte).mean())))
    return clean, adv


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--eps', type=float, default=0.15)
    ap.add_argument('--epochs', type=int, default=6)
    a = ap.parse_args()
    clean, adv = run(eps=a.eps, epochs=a.epochs)
    print("fgsm done: clean %.4f adversarial %.4f" % (clean, adv))


if __name__ == '__main__':
    main()
