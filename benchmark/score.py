#!/usr/bin/env python
"""Inference throughput sweep over the model zoo (reference:
example/image-classification/benchmark_score.py — imgs/sec per model per
batch size).

Runs each symbolic model's forward through a jitted executor on the
default device; prints one line per (model, batch).  With --dtype
bfloat16 the compute_dtype mixed-precision path is used.
"""
import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                '..'))
import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import models  # noqa: E402
from mxnet_tpu.executor import Executor  # noqa: E402


def score(network, batch_size, image_shape, num_classes, dtype, repeat):
    kwargs = {}
    if network == 'resnet':
        kwargs['num_layers'] = 50
    if network == 'vit':
        kwargs.update(patch_size=16, num_layers=12, d_model=384,
                      num_heads=6)   # ViT-S/16
    sym = models.get_symbol(network, num_classes=num_classes,
                            image_shape=','.join(map(str, image_shape)),
                            **kwargs)
    import jax.numpy as jnp
    compute_dtype = None if dtype == 'float32' else jnp.dtype(dtype)
    shapes = {'data': (batch_size,) + tuple(image_shape)}
    lbl = [n for n in sym.list_arguments() if n.endswith('label')]
    for n in lbl:
        shapes[n] = (batch_size,)
    ex = Executor.simple_bind(sym, mx.tpu(0), grad_req='null',
                              shapes=shapes, compute_dtype=compute_dtype)
    import jax.numpy as jnp2
    rng = np.random.RandomState(0)
    for name in ex.arg_dict:
        if name not in shapes:
            # device arrays: numpy here would re-upload all weights on
            # every timed forward (measuring the host link, not the chip)
            ex.arg_dict[name]._set_data(
                jnp2.asarray(rng.uniform(-0.05, 0.05,
                                         ex.arg_dict[name].shape)
                             .astype(np.float32)))
    ex.forward(is_train=False)[0].wait_to_read()  # compile
    t0 = time.perf_counter()
    for _ in range(repeat):
        ex.forward(is_train=False)[0].wait_to_read()
    dt = time.perf_counter() - t0
    return batch_size * repeat / dt


if __name__ == '__main__':
    p = argparse.ArgumentParser()
    p.add_argument('--networks', type=str,
                   default='alexnet,resnet,inception_bn,mobilenet')
    p.add_argument('--batch-sizes', type=str, default='1,32')
    p.add_argument('--image-shape', type=str, default='3,224,224')
    p.add_argument('--num-classes', type=int, default=1000)
    p.add_argument('--dtype', type=str, default='float32')
    p.add_argument('--repeat', type=int, default=10)
    args = p.parse_args()
    shape = tuple(int(x) for x in args.image_shape.split(','))
    for net in args.networks.split(','):
        for bs in (int(b) for b in args.batch_sizes.split(',')):
            ips = score(net, bs, shape, args.num_classes, args.dtype,
                        args.repeat)
            print('network: %-14s batch: %-4d dtype: %s  %.1f imgs/sec'
                  % (net, bs, args.dtype, ips), flush=True)
