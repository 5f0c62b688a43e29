"""Neural style transfer — optimization OVER THE INPUT image
(reference: example/neural-style/nstyle.py — pretrained VGG19 features,
content loss + Gram-matrix style losses, and a gradient loop that
updates the IMAGE, not the network).

What this port exercises is the distinctive API shape: gradients with
respect to an input array (``x.attach_grad()`` + ``autograd.record``),
multi-term losses over intermediate feature maps, and an optimizer
stepped manually on a non-parameter array — the reference drove the
same loop through executor ``backward`` to the input slot.

Adaptations for this environment (zero egress): the feature extractor
is a small fixed random conv pyramid (random CNN features carry enough
texture statistics for a demonstrable style loss), and content/style
images are built from sklearn's digits.  The optimization itself — the
thing the example is about — is unchanged.

Run:  python examples/neural_style/nstyle.py [--iters 60]
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import autograd, nd  # noqa: E402


def make_feature_params(channels=(8, 16, 32), seed=3):
    """Fixed random conv stack: 3x3 convs, stride 2 between scales."""
    rng = np.random.RandomState(seed)
    params = []
    cin = 1
    for cout in channels:
        w = rng.randn(cout, cin, 3, 3).astype(np.float32)
        w *= np.sqrt(2.0 / (cin * 9))
        params.append(nd.array(w))
        cin = cout
    return params


def features(x, params):
    """Forward through the fixed pyramid; returns per-scale activations."""
    feats = []
    h = x
    for k, w in enumerate(params):
        h = nd.Convolution(h, w, kernel=(3, 3), pad=(1, 1),
                           stride=(2, 2) if k else (1, 1),
                           num_filter=w.shape[0], no_bias=True)
        h = nd.Activation(h, act_type='relu')
        feats.append(h)
    return feats


def gram(feat):
    """Style statistic (reference nstyle.py style_gram): channel
    co-occurrence of a (1, C, H, W) feature map."""
    c = feat.shape[1]
    flat = feat.reshape((c, -1))
    n = flat.shape[1]
    return nd.dot(flat, flat.T) / n


def digits_image(index, size=32):
    from sklearn.datasets import load_digits
    d = load_digits()
    img = (d.images[index] / 16.0).astype(np.float32)
    img = img.repeat(size // 8, axis=0).repeat(size // 8, axis=1)
    return img[None, None, :, :]  # (1, 1, H, W)


def transfer(content_idx=0, style_idx=7, iters=60, lr=0.05,
             content_weight=1.0, style_weight=30.0, seed=0, log=print):
    params = make_feature_params()
    content = nd.array(digits_image(content_idx))
    style = nd.array(digits_image(style_idx))

    # fixed targets (no grads): deep layer for content, Grams for style
    content_target = features(content, params)[-1]
    style_targets = [gram(f) for f in features(style, params)]

    rng = np.random.RandomState(seed)
    x = nd.array(content.asnumpy()
                 + 0.1 * rng.randn(*content.shape).astype(np.float32))
    x.attach_grad()
    opt = mx.optimizer.Adam(learning_rate=lr)
    state = opt.create_state(0, x)

    losses = []
    for it in range(iters):
        with autograd.record():
            feats = features(x, params)
            c_loss = ((feats[-1] - content_target) ** 2).mean()
            s_loss = sum(((gram(f) - t) ** 2).mean()
                         for f, t in zip(feats, style_targets))
            loss = content_weight * c_loss + style_weight * s_loss
        loss.backward()
        opt.update(0, x, x.grad, state)
        losses.append(float(loss.asscalar()))
        if it % 20 == 0:
            log("iter %d loss %.5f (content %.5f style %.5f)"
                % (it, losses[-1], float(c_loss.asscalar()),
                   float(s_loss.asscalar())))
    return x, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--iters', type=int, default=60)
    ap.add_argument('--lr', type=float, default=0.05)
    a = ap.parse_args()
    x, losses = transfer(iters=a.iters, lr=a.lr)
    print("loss %.5f -> %.5f over %d iters"
          % (losses[0], losses[-1], len(losses)))


if __name__ == '__main__':
    main()
