"""Profiling a training loop
(reference: example/profiler/profiler_executor.py — set the profiler
config, bracket the hot loop with profiler state changes, dump a
chrome://tracing JSON).

Same workflow here, two capture layers:
 * ``mx.profiler`` — host-side op/scope events, chrome-trace JSON
   (load it at chrome://tracing or perfetto.dev);
 * on real hardware pass ``--xplane-dir DIR`` (or set
   ``MXNET_PROFILER_XLA_LOGDIR``) to also capture the XLA xplane trace
   (put both on one timeline with ``python tools/trace_merge.py``).

Run:  python examples/profiler/profile_training.py
"""
import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import mxnet_tpu as mx  # noqa: E402


def run(iters=12, batch=64, out="profile_training.json",
        xplane_dir=None, log=print):
    rng = np.random.RandomState(0)
    x = rng.randn(512, 1, 28, 28).astype(np.float32)
    y = rng.randint(0, 10, 512).astype(np.float32)
    it = mx.io.NDArrayIter(x, y, batch, last_batch_handle='discard')

    data = mx.sym.Variable('data')
    net = mx.sym.Convolution(data, kernel=(3, 3), num_filter=16,
                             name='c1')
    net = mx.sym.Activation(net, act_type='relu')
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=10, name='f1')
    net = mx.sym.SoftmaxOutput(net, name='softmax')
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer='sgd',
                       optimizer_params={'learning_rate': 0.1})

    # warm up OUTSIDE the capture so the trace shows steady-state steps,
    # not the first-step XLA compile (reference profiler_executor.py
    # skipped warmup the same way)
    b0 = next(iter(it))
    mod.forward(b0, is_train=True)
    mod.backward()
    mod.update()

    # mode='all' records eager AND symbolic op events; xla_logdir (or
    # the MXNET_PROFILER_XLA_LOGDIR env) makes set_state('run') also
    # capture the device xplane trace — no manual jax.profiler calls
    mx.profiler.set_config(mode='all', filename=out,
                           xla_logdir=xplane_dir)
    mx.profiler.set_state('run')
    n = 0
    it.reset()
    for bt in it:
        with mx.profiler.scope('train_step'):
            mod.forward(bt, is_train=True)
            mod.backward()
            mod.update()
        n += 1
        if n >= iters:
            break
    mx.profiler.set_state('stop')
    mx.profiler.dump()

    with open(out) as f:
        events = json.load(f)['traceEvents']
    steps = [e for e in events if e.get('name') == 'train_step']
    log("captured %d events (%d train_step scopes) -> %s"
        % (len(events), len(steps), out))
    return len(events), len(steps)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--iters', type=int, default=12)
    ap.add_argument('--out', type=str, default='profile_training.json')
    ap.add_argument('--xplane-dir', type=str, default=None)
    a = ap.parse_args()
    n_events, n_steps = run(iters=a.iters, out=a.out,
                            xplane_dir=a.xplane_dir)
    print("profiler example done: %d events, %d steps"
          % (n_events, n_steps))


if __name__ == '__main__':
    main()
