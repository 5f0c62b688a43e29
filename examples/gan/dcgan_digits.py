"""DCGAN with the two-module adversarial training loop
(reference: example/gan/dcgan.py — generator + discriminator Modules,
two optimizers, and the custom alternating loop that feeds the
discriminator's INPUT gradient into the generator's backward).

TPU-native notes vs the reference:
 * same Module mechanics: `modD` binds with ``inputs_need_grad=True`` so
   ``get_input_grads()`` yields dL/d(fake image), which drives
   ``modG.backward(out_grads=...)`` — the structural capability this
   example exists to exercise;
 * every forward/backward/update is one fused XLA program per module
   (no per-op kernel launches to schedule);
 * data: sklearn's bundled ``digits`` upscaled to 32x32 (this
   environment has no egress for MNIST), generator architecture is the
   same Deconvolution→BN→relu ladder at one scale smaller.

Run:  python examples/gan/dcgan_digits.py [--epochs 3] [--batch 64]
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import mxnet_tpu as mx  # noqa: E402


def make_generator(ngf=16, nc=1):
    """rand (B, z, 1, 1) -> image (B, nc, 32, 32); reference
    make_dcgan_sym's generator one Deconv rung shorter."""
    no_bias, fix_gamma, eps = True, True, 1e-5 + 1e-12
    rand = mx.sym.Variable('rand')
    g = mx.sym.Deconvolution(rand, name='g1', kernel=(4, 4),
                             num_filter=ngf * 4, no_bias=no_bias)
    g = mx.sym.BatchNorm(g, name='gbn1', fix_gamma=fix_gamma, eps=eps)
    g = mx.sym.Activation(g, name='gact1', act_type='relu')
    g = mx.sym.Deconvolution(g, name='g2', kernel=(4, 4), stride=(2, 2),
                             pad=(1, 1), num_filter=ngf * 2,
                             no_bias=no_bias)
    g = mx.sym.BatchNorm(g, name='gbn2', fix_gamma=fix_gamma, eps=eps)
    g = mx.sym.Activation(g, name='gact2', act_type='relu')
    g = mx.sym.Deconvolution(g, name='g3', kernel=(4, 4), stride=(2, 2),
                             pad=(1, 1), num_filter=ngf, no_bias=no_bias)
    g = mx.sym.BatchNorm(g, name='gbn3', fix_gamma=fix_gamma, eps=eps)
    g = mx.sym.Activation(g, name='gact3', act_type='relu')
    g = mx.sym.Deconvolution(g, name='g4', kernel=(4, 4), stride=(2, 2),
                             pad=(1, 1), num_filter=nc, no_bias=no_bias)
    return mx.sym.Activation(g, name='gact4', act_type='tanh')


def make_discriminator(ndf=16, fix_gamma=True):
    """image -> P(real); reference make_dcgan_sym's discriminator."""
    no_bias, eps = True, 1e-5 + 1e-12
    data = mx.sym.Variable('data')
    label = mx.sym.Variable('label')
    d = mx.sym.Convolution(data, name='d1', kernel=(4, 4), stride=(2, 2),
                           pad=(1, 1), num_filter=ndf, no_bias=no_bias)
    d = mx.sym.LeakyReLU(d, name='dact1', act_type='leaky', slope=0.2)
    d = mx.sym.Convolution(d, name='d2', kernel=(4, 4), stride=(2, 2),
                           pad=(1, 1), num_filter=ndf * 2, no_bias=no_bias)
    d = mx.sym.BatchNorm(d, name='dbn2', fix_gamma=fix_gamma, eps=eps)
    d = mx.sym.LeakyReLU(d, name='dact2', act_type='leaky', slope=0.2)
    d = mx.sym.Convolution(d, name='d3', kernel=(4, 4), stride=(2, 2),
                           pad=(1, 1), num_filter=ndf * 4, no_bias=no_bias)
    d = mx.sym.BatchNorm(d, name='dbn3', fix_gamma=fix_gamma, eps=eps)
    d = mx.sym.LeakyReLU(d, name='dact3', act_type='leaky', slope=0.2)
    d = mx.sym.Convolution(d, name='d4', kernel=(4, 4), num_filter=1,
                           no_bias=no_bias)
    d = mx.sym.Flatten(d)
    return mx.sym.LogisticRegressionOutput(data=d, label=label,
                                           name='dloss')


def load_digits_32():
    from sklearn.datasets import load_digits
    d = load_digits()
    x = (d.images / 16.0).astype(np.float32)     # (N, 8, 8) in [0, 1]
    x = x.repeat(4, axis=1).repeat(4, axis=2)    # 32x32
    x = x[:, None, :, :] * 2.0 - 1.0             # (N, 1, 32, 32) in [-1,1]
    return x


def train(epochs=3, batch=64, zdim=32, lr=0.0002, ctx=None, seed=0,
          log=print):
    ctx = ctx or mx.cpu()
    mx.random.seed(seed)
    rng = np.random.RandomState(seed)
    x = load_digits_32()

    symG, symD = make_generator(), make_discriminator()

    modG = mx.mod.Module(symG, data_names=('rand',), label_names=None,
                         context=ctx)
    modG.bind(data_shapes=[('rand', (batch, zdim, 1, 1))])
    modG.init_params(mx.initializer.Normal(0.02))
    modG.init_optimizer(optimizer='adam',
                        optimizer_params={'learning_rate': lr,
                                          'beta1': 0.5})

    modD = mx.mod.Module(symD, data_names=('data',),
                         label_names=('label',), context=ctx)
    # inputs_need_grad: the generator trains on dL_D/d(input)
    modD.bind(data_shapes=[('data', (batch, 1, 32, 32))],
              label_shapes=[('label', (batch,))],
              inputs_need_grad=True)
    modD.init_params(mx.initializer.Normal(0.02))
    modD.init_optimizer(optimizer='adam',
                        optimizer_params={'learning_rate': lr,
                                          'beta1': 0.5})

    ones = mx.nd.ones((batch,))
    zeros = mx.nd.zeros((batch,))
    history = []
    for epoch in range(epochs):
        perm = rng.permutation(len(x))
        d_loss_sum = g_loss_sum = 0.0
        nbatch = 0
        for i in range(len(x) // batch):
            real = mx.nd.array(x[perm[i * batch:(i + 1) * batch]])
            noise = mx.nd.array(rng.randn(batch, zdim, 1, 1)
                                .astype(np.float32))

            # generator forward -> fake batch
            modG.forward(mx.io.DataBatch(data=[noise]), is_train=True)
            fake = modG.get_outputs()[0]

            # discriminator on fake (label 0) — update
            modD.forward(mx.io.DataBatch(data=[fake], label=[zeros]),
                         is_train=True)
            pf = modD.get_outputs()[0].asnumpy()
            modD.backward()
            modD.update()
            # discriminator on real (label 1) — update
            modD.forward(mx.io.DataBatch(data=[real], label=[ones]),
                         is_train=True)
            pr = modD.get_outputs()[0].asnumpy()
            modD.backward()
            modD.update()

            # generator step: run D on fake with label=REAL, take the
            # input gradient, push it back through G (the reference's
            # modG.backward(diffD) move)
            modD.forward(mx.io.DataBatch(data=[fake], label=[ones]),
                         is_train=True)
            modD.backward()
            diffD = modD.get_input_grads()
            modG.backward(out_grads=diffD)
            modG.update()

            eps = 1e-7
            d_loss_sum += float(-(np.log(pr + eps).mean()
                                  + np.log(1 - pf + eps).mean()))
            g_loss_sum += float(-np.log(pf + eps).mean())
            nbatch += 1
        history.append({'epoch': epoch,
                        'd_loss': d_loss_sum / nbatch,
                        'g_loss': g_loss_sum / nbatch})
        log("epoch %d d_loss %.4f g_loss %.4f"
            % (epoch, history[-1]['d_loss'], history[-1]['g_loss']))

    # a sheet of generated samples, as the reference visualized
    modG.forward(mx.io.DataBatch(data=[mx.nd.array(
        rng.randn(batch, zdim, 1, 1).astype(np.float32))]),
        is_train=False)
    samples = modG.get_outputs()[0].asnumpy()
    return history, samples


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--epochs', type=int, default=3)
    ap.add_argument('--batch', type=int, default=64)
    ap.add_argument('--zdim', type=int, default=32)
    ap.add_argument('--lr', type=float, default=0.0002)
    a = ap.parse_args()
    history, samples = train(epochs=a.epochs, batch=a.batch, zdim=a.zdim,
                             lr=a.lr)
    print("final d_loss %.4f g_loss %.4f; %d samples in [%.2f, %.2f]"
          % (history[-1]['d_loss'], history[-1]['g_loss'],
             len(samples), samples.min(), samples.max()))


if __name__ == '__main__':
    main()
