"""Sorting short digit sequences with a bidirectional LSTM
(reference: example/bi-lstm-sort/lstm_sort.py — the classic seq->seq
toy proving bidirectional context: each output position must know the
WHOLE input to emit the sorted element).

Where the reference hand-unrolled forward and backward LSTM stacks and
spliced them per step (lstm.py bi_lstm_unroll over SliceChannel), here
``rnn.BidirectionalCell`` composes two LSTMCells and ``unroll`` builds
the same computation — then one Dense head per step predicts the sorted
token.  Trained with Module on synthetic data (the reference generated
its sequences synthetically too).

Run:  python examples/rnn/bi_lstm_sort.py [--epochs 15]
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import rnn  # noqa: E402


def sort_symbol(seq_len, vocab, num_hidden=64, num_embed=32):
    data = mx.sym.Variable('data')
    label = mx.sym.Variable('softmax_label')
    embed = mx.sym.Embedding(data, input_dim=vocab, output_dim=num_embed,
                             name='embed')
    cell = rnn.BidirectionalCell(
        rnn.LSTMCell(num_hidden, prefix='l_'),
        rnn.LSTMCell(num_hidden, prefix='r_'))
    outputs, _ = cell.unroll(seq_len, inputs=embed, merge_outputs=True,
                             layout='NTC')
    # per-step classification over the vocabulary
    pred = mx.sym.Reshape(outputs, shape=(-1, 2 * num_hidden))
    pred = mx.sym.FullyConnected(pred, num_hidden=vocab, name='cls')
    label_flat = mx.sym.Reshape(label, shape=(-1,))
    return mx.sym.SoftmaxOutput(pred, label_flat, name='softmax')


def make_data(num=2000, seq_len=6, vocab=10, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randint(0, vocab, (num, seq_len))
    y = np.sort(x, axis=1)
    return x.astype(np.float32), y.astype(np.float32)


def train(epochs=15, batch=64, seq_len=6, vocab=10, seed=0, log=print):
    x, y = make_data(seq_len=seq_len, vocab=vocab, seed=seed)
    n = int(0.9 * len(x))
    train_it = mx.io.NDArrayIter(x[:n], y[:n], batch, shuffle=True,
                                 last_batch_handle='discard')
    val_it = mx.io.NDArrayIter(x[n:], y[n:], batch,
                               last_batch_handle='discard')
    mx.random.seed(seed)
    mod = mx.mod.Module(sort_symbol(seq_len, vocab), context=mx.cpu())
    mod.bind(data_shapes=train_it.provide_data,
             label_shapes=train_it.provide_label)
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer='adam',
                       optimizer_params={'learning_rate': 5e-3})

    acc = None
    for epoch in range(epochs):
        train_it.reset()
        for b in train_it:
            mod.forward(b, is_train=True)
            mod.backward()
            mod.update()
        # per-token accuracy on held-out sequences
        val_it.reset()
        correct = total = 0
        for b in val_it:
            mod.forward(b, is_train=False)
            pred = mod.get_outputs()[0].asnumpy().argmax(axis=1)
            lab = b.label[0].asnumpy().reshape(-1)
            correct += int((pred == lab).sum())
            total += len(lab)
        acc = correct / total
        log("epoch %d val per-token acc %.4f" % (epoch, acc))
    return mod, acc


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--epochs', type=int, default=15)
    ap.add_argument('--batch', type=int, default=64)
    ap.add_argument('--seq-len', type=int, default=6)
    a = ap.parse_args()
    _, acc = train(epochs=a.epochs, batch=a.batch, seq_len=a.seq_len)
    print("final sort acc %.4f" % acc)


if __name__ == '__main__':
    main()
