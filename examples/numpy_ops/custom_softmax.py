"""Training through a pure-numpy custom operator
(reference: example/numpy-ops/custom_softmax.py — a softmax loss head
written as a Python CustomOp: numpy forward, hand-written backward
``prob - onehot``, plugged into a symbolic net and trained).

This is the extensibility story: ops the framework doesn't ship can be
written in Python/numpy and still participate in symbolic training —
the executor routes them through ``jax.pure_callback`` so the rest of
the graph remains one compiled XLA program.

Run:  python examples/numpy_ops/custom_softmax.py [--epochs 10]
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import mxnet_tpu as mx  # noqa: E402


@mx.operator.register("numpy_softmax")
class NumpySoftmaxProp(mx.operator.CustomOpProp):
    """reference custom_softmax.py NumpySoftmaxProp: loss head, no top
    grad (the gradient is defined by the op itself)."""

    def __init__(self):
        super().__init__(need_top_grad=False)

    def list_arguments(self):
        return ['data', 'label']

    def list_outputs(self):
        return ['output']

    def infer_shape(self, in_shape):
        data_shape = in_shape[0]
        label_shape = (in_shape[0][0],)
        return [data_shape, label_shape], [data_shape], []

    def create_operator(self, ctx, shapes, dtypes):
        return NumpySoftmax()


class NumpySoftmax(mx.operator.CustomOp):
    def forward(self, is_train, req, in_data, out_data, aux):
        x = in_data[0].asnumpy()
        y = np.exp(x - x.max(axis=1, keepdims=True))
        y /= y.sum(axis=1, keepdims=True)
        self.assign(out_data[0], req[0], y)

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        lab = in_data[1].asnumpy().ravel().astype(int)
        y = out_data[0].asnumpy().copy()
        y[np.arange(lab.shape[0]), lab] -= 1.0
        self.assign(in_grad[0], req[0], y)


def net_symbol():
    data = mx.sym.Variable('data')
    label = mx.sym.Variable('softmax_label')
    h = mx.sym.FullyConnected(data, num_hidden=64, name='fc1')
    h = mx.sym.Activation(h, act_type='relu')
    h = mx.sym.FullyConnected(h, num_hidden=10, name='fc2')
    return mx.sym.Custom(h, label, op_type='numpy_softmax',
                         name='softmax')


def run(epochs=10, batch=100, seed=0, log=print):
    from sklearn.datasets import load_digits
    d = load_digits()
    x = (d.images.reshape(len(d.images), -1) / 16.0).astype(np.float32)
    y = d.target.astype(np.float32)
    n = 1500
    # seed numpy BEFORE building the iterators: NDArrayIter's shuffle
    # draws from global np.random at construction time
    np.random.seed(seed)
    mx.random.seed(seed)
    train = mx.io.NDArrayIter(x[:n], y[:n], batch, shuffle=True,
                              last_batch_handle='discard')
    test = mx.io.NDArrayIter(x[n:], y[n:], batch)
    mod = mx.mod.Module(net_symbol(), context=mx.cpu())
    mod.fit(train, num_epoch=epochs, optimizer='sgd',
            optimizer_params={'learning_rate': 0.1, 'momentum': 0.9},
            initializer=mx.initializer.Xavier())
    acc = mod.score(test, 'acc')[0][1]
    log("numpy-softmax custom op test acc %.4f" % acc)
    return acc


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--epochs', type=int, default=10)
    a = ap.parse_args()
    acc = run(epochs=a.epochs)
    print("final custom-op acc %.4f" % acc)


if __name__ == '__main__':
    main()
