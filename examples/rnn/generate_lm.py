#!/usr/bin/env python
"""Train a small transformer LM, then generate with a KV cache.

Demonstrates the inference path the reference lacks a modern analog for:
``models.transformer_decode_step`` shares parameter names with
``models.transformer_lm``, so trained weights load directly into a
single-token decode graph whose rolled KV cache rides Module
``state_names`` (set_states/get_states) — each step is one jitted
program with static shapes.

  python examples/rnn/generate_lm.py --synthetic --num-epochs 25
"""
import argparse
import logging
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                '..', '..'))
import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import models  # noqa: E402


def synthetic_corpus(n, seq_len, vocab, seed=0):
    rs = np.random.RandomState(seed)
    first = rs.randint(0, vocab, (n, 1))
    seq = (first + np.arange(seq_len + 1)) % vocab
    return seq[:, :seq_len].astype('float32'), seq[:, 1:].astype('float32')


if __name__ == '__main__':
    logging.basicConfig(level=logging.INFO)
    ap = argparse.ArgumentParser()
    ap.add_argument('--vocab', type=int, default=50)
    ap.add_argument('--seq-len', type=int, default=16)
    ap.add_argument('--num-layers', type=int, default=2)
    ap.add_argument('--d-model', type=int, default=64)
    ap.add_argument('--num-heads', type=int, default=4)
    ap.add_argument('--num-kv-heads', type=int, default=2)
    ap.add_argument('--num-epochs', type=int, default=25)
    ap.add_argument('--batch-size', type=int, default=32)
    ap.add_argument('--gen-len', type=int, default=12)
    ap.add_argument('--beam', type=int, default=0,
                    help='beam size (0 = greedy argmax)')
    ap.add_argument('--synthetic', action='store_true')
    args = ap.parse_args()

    if args.gen_len > args.seq_len:
        # gen_len steps consume positions 0..gen_len-1, which must fit
        # the trained positional embedding (clamping would silently
        # degrade generations — see transformer_decode_step docs)
        raise SystemExit(
            f"--gen-len {args.gen_len} must not exceed --seq-len "
            f"{args.seq_len}")
    kw = dict(num_layers=args.num_layers, d_model=args.d_model,
              num_heads=args.num_heads, num_kv_heads=args.num_kv_heads)
    net = models.transformer_lm(args.vocab, args.seq_len, **kw)
    x, y = synthetic_corpus(512, args.seq_len, args.vocab)
    it = mx.io.NDArrayIter(x, y, args.batch_size, shuffle=True)
    mod = mx.mod.Module(net, context=mx.tpu(0), data_names=('data',),
                        label_names=('softmax_label',))
    mod.fit(it, num_epoch=args.num_epochs, optimizer='adam',
            optimizer_params={'learning_rate': 5e-3},
            initializer=mx.initializer.Xavier(),
            eval_metric=mx.metric.Perplexity(ignore_label=None))
    arg_params, aux_params = mod.get_params()

    prompts = (np.array([3, 7, 11, 20]) % args.vocab).astype('float32')
    B = len(prompts) * max(args.beam, 1)
    dec = models.transformer_decode_step(args.vocab, args.seq_len, B, **kw)
    state_names = []
    for i in range(args.num_layers):
        state_names += [f'layer{i}_k_cache', f'layer{i}_v_cache']
    state_names.append('cur_pos')
    dmod = mx.mod.Module(dec, context=mx.tpu(0), data_names=('data',),
                         label_names=None, state_names=state_names)
    dmod.bind(data_shapes=[('data', (B,))], for_training=False)
    dmod.init_params(arg_params=arg_params, aux_params=aux_params)
    dmod.set_states(value=0)

    # beam_size=1 IS greedy (pinned by
    # test_beam_search_beam1_equals_greedy) — one decode path, no drift
    seqs, scores = models.beam_search(dmod, prompts, max(args.beam, 1),
                                      args.gen_len)
    label = 'beam' if args.beam > 1 else 'greedy'
    for b in range(len(prompts)):
        print('generated (%s, score %.3f):' % (label, scores[b, 0]),
              ' '.join(str(int(t)) for t in seqs[b, 0]))
    print('generation done')
