"""Encoder-decoder seq2seq with teacher forcing
(reference: the rnn example family's encoder-decoder scripts — encode a
source sequence into LSTM states, hand those states to a decoder as its
``begin_state``, teacher-force the target during training, decode
greedily at inference).

Task: output the INPUT SEQUENCE REVERSED — position i of the output
depends on position L-1-i of the input, so nothing short of real
encoder-state transport solves it.

Framework surface: two LSTM stacks composed in ONE symbol with
``unroll(begin_state=encoder_states)``, per-step softmax heads, Module
training, and an iterative greedy decode that re-feeds the generated
prefix.

Run:  python examples/rnn/seq2seq_reverse.py [--epochs 20]
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import rnn  # noqa: E402

GO = 1  # decoder start token; PAD=0; real symbols start at 2


def seq2seq_symbol(seq_len, vocab, num_hidden=64, num_embed=32):
    src = mx.sym.Variable('data')           # (N, T) source tokens
    tgt_in = mx.sym.Variable('tgt_in')      # (N, T) <go> + target[:-1]
    label = mx.sym.Variable('softmax_label')

    embed = mx.sym.Embedding(src, input_dim=vocab, output_dim=num_embed,
                             name='src_embed')
    enc = rnn.LSTMCell(num_hidden, prefix='enc_')
    _, enc_states = enc.unroll(seq_len, inputs=embed, layout='NTC',
                               merge_outputs=True)

    dembed = mx.sym.Embedding(tgt_in, input_dim=vocab,
                              output_dim=num_embed, name='tgt_embed')
    dec = rnn.LSTMCell(num_hidden, prefix='dec_')
    # the seq2seq move: decoder starts FROM the encoder's final states
    dec_out, _ = dec.unroll(seq_len, inputs=dembed,
                            begin_state=enc_states, layout='NTC',
                            merge_outputs=True)
    pred = mx.sym.Reshape(dec_out, shape=(-1, num_hidden))
    pred = mx.sym.FullyConnected(pred, num_hidden=vocab, name='cls')
    return mx.sym.SoftmaxOutput(pred, mx.sym.Reshape(label, shape=(-1,)),
                                name='softmax')


def make_data(num=3000, seq_len=6, vocab=12, seed=0):
    rng = np.random.RandomState(seed)
    src = rng.randint(2, vocab, (num, seq_len))
    tgt = src[:, ::-1].copy()
    tgt_in = np.concatenate([np.full((num, 1), GO), tgt[:, :-1]], axis=1)
    return (src.astype(np.float32), tgt_in.astype(np.float32),
            tgt.astype(np.float32))


def train(epochs=20, batch=64, seq_len=6, vocab=12, seed=0, log=print):
    src, tgt_in, tgt = make_data(seq_len=seq_len, vocab=vocab, seed=seed)
    n = int(0.9 * len(src))
    np.random.seed(seed)
    mx.random.seed(seed)
    train_it = mx.io.NDArrayIter(
        {'data': src[:n], 'tgt_in': tgt_in[:n]}, {'softmax_label': tgt[:n]},
        batch, shuffle=True, last_batch_handle='discard')
    mod = mx.mod.Module(seq2seq_symbol(seq_len, vocab),
                        data_names=('data', 'tgt_in'), context=mx.cpu())
    mod.bind(data_shapes=train_it.provide_data,
             label_shapes=train_it.provide_label)
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer='adam',
                       optimizer_params={'learning_rate': 5e-3})
    for epoch in range(epochs):
        train_it.reset()
        for b in train_it:
            mod.forward(b, is_train=True)
            mod.backward()
            mod.update()

    # greedy decode on held-out sources: re-unroll with the generated
    # prefix in the teacher slot (PAD for the not-yet-generated tail)
    vsrc, vtgt = src[n:n + batch], tgt[n:n + batch]
    dec_in = np.zeros_like(vsrc)
    dec_in[:, 0] = GO
    steps = []
    for t in range(seq_len):
        mod.forward(mx.io.DataBatch(
            data=[mx.nd.array(vsrc), mx.nd.array(dec_in)],
            label=[mx.nd.array(np.zeros_like(vsrc))]), is_train=False)
        prob = mod.get_outputs()[0].asnumpy().reshape(
            batch, seq_len, vocab)
        step_tok = prob[:, t].argmax(axis=1)
        steps.append(step_tok)
        if t + 1 < seq_len:
            dec_in[:, t + 1] = step_tok
    generated = np.stack(steps, axis=1)
    token_acc = float((generated == vtgt).mean())
    seq_acc = float((generated == vtgt).all(axis=1).mean())
    log("greedy decode: token acc %.4f, full-sequence acc %.4f"
        % (token_acc, seq_acc))
    return token_acc, seq_acc


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--epochs', type=int, default=20)
    a = ap.parse_args()
    tok, seq = train(epochs=a.epochs)
    print("final seq2seq token acc %.4f seq acc %.4f" % (tok, seq))


if __name__ == '__main__':
    main()
