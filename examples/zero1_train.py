#!/usr/bin/env python
"""ZeRO-1 training example — both APIs, virtual mesh out of the box.

The reference's big-model memory lever was update-on-kvstore: push the
optimizer into parameter servers so workers hold no state
(kvstore_dist_server.h applies updates server-side).  The SPMD form is
ZeRO-1: every dp rank owns 1/dp of each optimizer-state array and GSPMD
schedules reduce-scatter(grads) → sharded update → all-gather(params)
inside the one fused step.  docs/design/kvstore.md has the design note.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  JAX_PLATFORMS=cpu python examples/zero1_train.py
"""
import argparse
import logging
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                '..'))
import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import parallel as par  # noqa: E402


def module_api(mesh, x, y, epochs):
    """Symbolic Module path: zero_stage=1 is one constructor argument."""
    data = mx.sym.Variable('data')
    net = mx.sym.FullyConnected(data, num_hidden=64, name='fc1')
    net = mx.sym.Activation(net, act_type='relu')
    net = mx.sym.FullyConnected(net, num_hidden=10, name='fc2')
    net = mx.sym.SoftmaxOutput(net, name='softmax')

    mod = mx.mod.Module(net, mesh=mesh, zero_stage=1)
    it = mx.io.NDArrayIter(data=x, label=y, batch_size=64, shuffle=True)
    mod.fit(it, num_epoch=epochs,
            optimizer='adam', optimizer_params={'learning_rate': 1e-3},
            eval_metric='acc',
            batch_end_callback=mx.callback.Speedometer(64, 10))
    # show a sharded Adam moment: each chip holds 1/dp of it
    name = 'fc1_weight'
    moment = mod._opt_states[name][-1]
    logging.info("%s adam moment: global %s, per-chip shard %s", name,
                 moment.shape,
                 moment._data.addressable_shards[0].data.shape)
    return mod


def gluon_api(mesh, x, y, epochs):
    """Gluon path: place params on the mesh, then Trainer(zero_stage=1)."""
    from mxnet_tpu import gluon, autograd, nd
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    net = gluon.nn.Sequential()
    net.add(gluon.nn.Dense(64, activation='relu'))
    net.add(gluon.nn.Dense(10))
    net.initialize(mx.initializer.Xavier())

    xs = nd.array(x)
    ys = nd.array(y)
    net(xs[:1])                              # materialize deferred shapes
    net.collect_params().place(mesh)         # params → mesh (replicated)
    xs._set_data(jax.device_put(xs._data, NamedSharding(mesh, P('dp'))))
    ys._set_data(jax.device_put(ys._data, NamedSharding(mesh, P('dp'))))

    trainer = gluon.Trainer(net.collect_params(), 'adam',
                            {'learning_rate': 1e-3},
                            mesh=mesh, zero_stage=1)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    for epoch in range(epochs):
        with autograd.record():
            loss = loss_fn(net(xs), ys)
        loss.backward()
        trainer.step(xs.shape[0])
        logging.info("epoch %d loss %.4f", epoch,
                     float(loss.mean().asnumpy()))
    return net


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--epochs', type=int, default=2)
    ap.add_argument('--api', choices=['module', 'gluon', 'both'],
                    default='both')
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)

    mesh = par.make_mesh()  # dp = all visible devices
    dp = par.mesh_shape(mesh)['dp']
    logging.info("mesh: dp=%d", dp)

    rng = np.random.RandomState(0)
    n = 64 * 8
    x = rng.randn(n, 32).astype(np.float32)
    y = rng.randint(0, 10, (n,)).astype(np.float32)

    if args.api in ('module', 'both'):
        module_api(mesh, x, y, args.epochs)
    if args.api in ('gluon', 'both'):
        gluon_api(mesh, x, y, args.epochs)
    logging.info("done")


if __name__ == '__main__':
    main()
