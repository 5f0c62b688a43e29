#!/usr/bin/env python
"""Model-parallel training example (reference:
example/model-parallel-lstm + tests/python/unittest/test_model_parallel.py).

The reference places layer groups on different GPUs with
``group2ctx``/``__ctx_group__`` and lets the nnvm PlaceDevice pass insert
cross-device copies.  TPU-native, placement is DECLARATIVE: build a
dp×tp mesh, derive Megatron-style sharding rules for the symbol
(FC/conv weights split along output features over ``tp``), and GSPMD
inserts the collectives.  The same script runs an LSTM LM with its
projection layers tensor-sharded — the modern form of the reference's
model-parallel LSTM.

Runs on the virtual CPU mesh out of the box:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/model_parallel/train_model_parallel.py --synthetic
"""
import argparse
import logging
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                '..'))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                '..', '..'))
import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import parallel as par  # noqa: E402


def build_lstm_lm(vocab, num_embed, num_hidden, seq_len):
    data = mx.sym.Variable('data')
    emb = mx.sym.Embedding(data, input_dim=vocab, output_dim=num_embed,
                           name='embed')
    cell = mx.rnn.FusedRNNCell(num_hidden, num_layers=2, mode='lstm',
                               prefix='lstm_')
    out, _ = cell.unroll(seq_len, emb, merge_outputs=True, layout='NTC')
    out = mx.sym.Reshape(out, shape=(-1, num_hidden))
    # the projection FC is the tensor-sharded hot matmul
    fc = mx.sym.FullyConnected(out, num_hidden=vocab, name='decoder')
    label = mx.sym.Reshape(mx.sym.Variable('softmax_label'), shape=(-1,))
    return mx.sym.SoftmaxOutput(fc, label, name='softmax')


def synthetic_corpus(n, seq_len, vocab, seed=0):
    rs = np.random.RandomState(seed)
    first = rs.randint(0, vocab, (n, 1))
    seq = (first + np.arange(seq_len + 1)) % vocab  # learnable pattern
    return (seq[:, :seq_len].astype('float32'),
            seq[:, 1:].astype('float32'))


if __name__ == '__main__':
    logging.basicConfig(level=logging.INFO)
    ap = argparse.ArgumentParser()
    ap.add_argument('--tp', type=int, default=2,
                    help='tensor-parallel ways (mesh tp axis)')
    ap.add_argument('--seq-len', type=int, default=12)
    ap.add_argument('--vocab', type=int, default=64)
    ap.add_argument('--num-embed', type=int, default=32)
    ap.add_argument('--num-hidden', type=int, default=64)
    ap.add_argument('--batch-size', type=int, default=16)
    ap.add_argument('--num-epochs', type=int, default=4)
    ap.add_argument('--num-examples', type=int, default=512)
    ap.add_argument('--synthetic', action='store_true')
    args = ap.parse_args()

    net = build_lstm_lm(args.vocab, args.num_embed, args.num_hidden,
                        args.seq_len)
    mesh = par.make_mesh(tp=args.tp)  # dp = remaining devices
    rules = par.tp_rules_for_symbol(net, mesh)
    logging.info('mesh: %s; %d sharded params', mesh.shape,
                 len(rules.rules) if hasattr(rules, 'rules') else -1)

    x, y = synthetic_corpus(args.num_examples, args.seq_len, args.vocab)
    it = mx.io.NDArrayIter(x, y, args.batch_size, shuffle=True)

    mod = mx.mod.Module(net, mesh=mesh, sharding_rules=rules,
                        data_names=('data',),
                        label_names=('softmax_label',))
    metric = mx.metric.Perplexity(ignore_label=None)
    mod.fit(it, num_epoch=args.num_epochs, optimizer='adam',
            optimizer_params={'learning_rate': 3e-3},
            initializer=mx.initializer.Xavier(),
            eval_metric=metric,
            batch_end_callback=mx.callback.Speedometer(args.batch_size,
                                                       16))
    # show the decoder weight really is sharded over tp
    w = mod._exec.arg_dict['decoder_weight']._data
    shard_shapes = sorted({s.data.shape for s in w.addressable_shards})
    logging.info('decoder_weight global %s, shard shapes %s',
                 tuple(w.shape), shard_shapes)
    print('model-parallel training done; decoder shards:', shard_shapes)
