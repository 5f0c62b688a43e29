"""Stacked autoencoder with greedy layerwise pretraining
(reference: example/autoencoder/{autoencoder,mnist_sae}.py — pretrain
each encoder/decoder pair on the previous layer's codes, then finetune
the whole reconstruction stack end-to-end).

The workflow the reference demonstrated: building symbols per stage,
transferring trained weights between Modules by parameter NAME
(get_params -> set_params with allow_missing), and a two-phase training
schedule.  Data: sklearn digits (64-d), dims 64-32-16.

Run:  python examples/autoencoder/stacked_ae.py [--pretrain-epochs 8]
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import mxnet_tpu as mx  # noqa: E402


def pair_sym(i, n_in, n_hidden):
    data = mx.sym.Variable('data')
    label = mx.sym.Variable('recon_label')
    enc = mx.sym.Activation(
        mx.sym.FullyConnected(data, num_hidden=n_hidden,
                              name='enc%d' % i), act_type='relu')
    dec = mx.sym.FullyConnected(enc, num_hidden=n_in, name='dec%d' % i)
    return mx.sym.LinearRegressionOutput(dec, label, name='recon')


def full_sym(dims):
    """encoder chain then mirrored decoder chain, names matching the
    stage symbols so pretrained weights transfer by name."""
    data = mx.sym.Variable('data')
    label = mx.sym.Variable('recon_label')
    h = data
    for i, d in enumerate(dims[1:]):
        h = mx.sym.Activation(
            mx.sym.FullyConnected(h, num_hidden=d, name='enc%d' % i),
            act_type='relu')
    for i in reversed(range(len(dims) - 1)):
        h = mx.sym.FullyConnected(h, num_hidden=dims[i],
                                  name='dec%d' % i)
        if i > 0:
            h = mx.sym.Activation(h, act_type='relu')
    return mx.sym.LinearRegressionOutput(h, label, name='recon')


def _fit(sym, x, y_label, epochs, lr, batch=100, params=None, seed=0):
    it = mx.io.NDArrayIter(x, y_label, batch, shuffle=True,
                           last_batch_handle='discard',
                           label_name='recon_label')
    mx.random.seed(seed)
    mod = mx.mod.Module(sym, context=mx.cpu(),
                        label_names=('recon_label',))
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.initializer.Xavier())
    if params:
        mod.set_params(*params, allow_missing=True, allow_extra=True)
    mod.init_optimizer(optimizer='adam',
                       optimizer_params={'learning_rate': lr})
    for _ in range(epochs):
        it.reset()
        for b in it:
            mod.forward(b, is_train=True)
            mod.backward()
            mod.update()
    return mod


def _encode(x, args, i):
    w = args['enc%d_weight' % i].asnumpy()
    b = args['enc%d_bias' % i].asnumpy()
    return np.maximum(x @ w.T + b, 0.0)


def run(pretrain_epochs=8, finetune_epochs=8, dims=(64, 32, 16),
        seed=0, log=print):
    from sklearn.datasets import load_digits
    d = load_digits()
    x = (d.images.reshape(len(d.images), -1) / 16.0).astype(np.float32)
    # NDArrayIter(shuffle=True) draws from GLOBAL np.random at
    # construction — seed it here or no later seeding makes runs
    # reproducible
    np.random.seed(seed)

    # greedy layerwise pretraining: stage i reconstructs stage i-1 codes
    arg_all, aux_all = {}, {}
    cur = x
    for i in range(len(dims) - 1):
        mod = _fit(pair_sym(i, dims[i], dims[i + 1]), cur, cur,
                   pretrain_epochs, 2e-3, seed=seed + i)
        args, auxs = mod.get_params()
        arg_all.update(args)
        aux_all.update(auxs)
        cur = _encode(cur, args, i)
        log("pretrained stack %d (%d -> %d)" % (i, dims[i], dims[i + 1]))

    def recon_mse(mod):
        it = mx.io.NDArrayIter(x, x, 100, label_name='recon_label')
        out = mod.predict(it).asnumpy()
        return float(((out - x[:len(out)]) ** 2).mean())

    # reconstruction error with pretrained weights only (0 epochs =
    # just bind + load the stage params), then finetune end-to-end
    pre_mse = recon_mse(_fit(full_sym(dims), x, x, 0, 1e-3,
                             params=(arg_all, aux_all), seed=seed))
    mod = _fit(full_sym(dims), x, x, finetune_epochs, 1e-3,
               params=(arg_all, aux_all), seed=seed)
    ft_mse = recon_mse(mod)
    log("recon mse pretrained %.5f -> finetuned %.5f" % (pre_mse, ft_mse))
    return pre_mse, ft_mse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--pretrain-epochs', type=int, default=8)
    ap.add_argument('--finetune-epochs', type=int, default=8)
    a = ap.parse_args()
    pre, ft = run(pretrain_epochs=a.pretrain_epochs,
                  finetune_epochs=a.finetune_epochs)
    print("final ae mse %.5f (pretrain-only %.5f)" % (ft, pre))


if __name__ == '__main__':
    main()
