"""Deep net with an SVM loss head
(reference: example/svm_mnist/svm_mnist.py — the same MLP trained with
``SVMOutput`` (squared hinge loss on one-vs-all margins) instead of
softmax cross-entropy, the "deep learning features + SVM objective"
recipe).

Run:  python examples/svm/svm_digits.py [--epochs 12]
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import mxnet_tpu as mx  # noqa: E402


def svm_net(regularization_coefficient=1.0, use_linear=False):
    data = mx.sym.Variable('data')
    h = mx.sym.FullyConnected(data, num_hidden=128, name='fc1')
    h = mx.sym.Activation(h, act_type='relu')
    h = mx.sym.FullyConnected(h, num_hidden=10, name='fc2')
    return mx.sym.SVMOutput(
        h, name='svm',
        regularization_coefficient=regularization_coefficient,
        use_linear=use_linear)


def run(epochs=12, batch=100, use_linear=False, seed=0, log=print):
    from sklearn.datasets import load_digits
    d = load_digits()
    x = (d.images.reshape(len(d.images), -1) / 16.0).astype(np.float32)
    y = d.target.astype(np.float32)
    n = 1500
    # the SVM head names its label 'svm_label' — both the iterator and
    # the module must agree (reference svm_mnist.py used the same pair)
    train = mx.io.NDArrayIter(x[:n], y[:n], batch, shuffle=True,
                              last_batch_handle='discard',
                              label_name='svm_label')
    test = mx.io.NDArrayIter(x[n:], y[n:], batch, label_name='svm_label')
    mx.random.seed(seed)
    mod = mx.mod.Module(svm_net(use_linear=use_linear), context=mx.cpu(),
                        label_names=('svm_label',))
    mod.fit(train, num_epoch=epochs, optimizer='sgd',
            optimizer_params={'learning_rate': 0.1, 'momentum': 0.9,
                              'wd': 1e-4},
            initializer=mx.initializer.Xavier())
    acc = mod.score(test, 'acc')[0][1]
    log("svm (%s hinge) test acc %.4f"
        % ("linear" if use_linear else "squared", acc))
    return acc


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--epochs', type=int, default=12)
    ap.add_argument('--use-linear', action='store_true')
    a = ap.parse_args()
    acc = run(epochs=a.epochs, use_linear=a.use_linear)
    print("final svm acc %.4f" % acc)


if __name__ == '__main__':
    main()
