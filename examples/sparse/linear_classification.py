"""Sparse end-to-end linear classification
(reference: benchmark/python/sparse/sparse_end2end.py — CSR inputs,
row_sparse weight gradients, kvstore row_sparse_pull of just the rows a
batch touches, and a sparse optimizer update that leaves untouched rows
alone).

TPU-native shape of the same pipeline:
 * the CSR batch's column indices drive ``nd.Embedding(sparse_grad=True)``
   — mathematically X_csr · W with O(nnz) work, and autograd returns the
   gradient as a RowSparseNDArray over exactly the touched rows (the
   reference's ``mx.symbol.sparse.dot`` + row_sparse grad);
 * before each step the touched rows are fetched with
   ``kv.row_sparse_pull(row_ids=...)`` — the reference's
   ``row_sparse_pull(kv, 'w', data, ...)`` move;
 * the optimizer's sparse path updates ONLY the touched rows (lazy
   update semantics, as the reference documents for sparse sgd/adam);
 * the whole run is asserted densify-free: the O(nnz) claim is checked
   by the densify telltale, not taken on faith.

Run:  python examples/sparse/linear_classification.py [--epochs 5]
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import autograd, nd  # noqa: E402
from mxnet_tpu.ndarray import sparse  # noqa: E402


def make_dataset(num_samples=2048, num_features=10000, nnz=16, seed=0):
    """Synthetic multi-hot dataset with a planted sparse weight: each row
    has `nnz` active features with +-1 values; the label is the sign of
    the planted weight's response (what criteo/avazu look like once
    hashed, reference sparse_end2end.py's data shape)."""
    rng = np.random.RandomState(seed)
    planted = rng.randn(num_features).astype(np.float32)
    cols = np.stack([rng.choice(num_features, nnz, replace=False)
                     for _ in range(num_samples)])          # (N, nnz)
    vals = rng.choice([-1.0, 1.0], (num_samples, nnz)).astype(np.float32)
    margin = (planted[cols] * vals).sum(axis=1)
    y = (margin > 0).astype(np.float32)
    return cols.astype(np.float32), vals, y, planted


def train(epochs=5, batch=128, num_features=10000, nnz=16, lr=0.5,
          optimizer='sgd', seed=0, log=print):
    cols, vals, y, planted = make_dataset(num_features=num_features,
                                          nnz=nnz, seed=seed)
    n = len(y)
    kv = mx.kv.create('local')

    w = nd.zeros((num_features, 1))
    w.attach_grad(stype='row_sparse')   # autograd emits row_sparse grads
    bias = nd.zeros((1,))
    bias.attach_grad()
    kv.init('w', w)

    opt = mx.optimizer.create(optimizer, learning_rate=lr)
    w_state = opt.create_state(0, w)
    b_state = opt.create_state(1, bias)

    densify_start = sparse.DENSIFY_COUNT
    history = []
    for epoch in range(epochs):
        loss_sum = 0.0
        correct = 0
        for i in range(n // batch):
            sl = slice(i * batch, (i + 1) * batch)
            bc = nd.array(cols[sl])          # (B, nnz) column ids
            bv = nd.array(vals[sl])          # (B, nnz) values
            by = nd.array(y[sl])             # (B,)

            # the reference's row_sparse_pull: fetch only touched rows,
            # and VERIFY them against the published weight (the store
            # holds what the last kv.push sent)
            row_ids = np.unique(cols[sl]).astype(np.float32)
            pulled = sparse.zeros('row_sparse', w.shape)
            kv.row_sparse_pull('w', out=pulled, row_ids=nd.array(row_ids))
            np.testing.assert_allclose(
                pulled.data.asnumpy(),
                w.asnumpy()[row_ids.astype(int)], rtol=1e-6, atol=1e-7,
                err_msg="row_sparse_pull returned stale/wrong rows")

            with autograd.record():
                # X_csr . W via embedding-gather: O(nnz), sparse grad
                emb = nd.Embedding(bc, w, input_dim=num_features,
                                   output_dim=1, sparse_grad=True)
                logits = (emb.reshape((batch, nnz)) * bv).sum(axis=1) \
                    + bias
                p = nd.sigmoid(logits)
                eps = 1e-7
                loss = -(by * nd.log(p + eps)
                         + (1 - by) * nd.log(1 - p + eps)).mean()
            loss.backward()

            assert isinstance(w.grad, sparse.RowSparseNDArray), \
                "gradient densified — the O(nnz) contract broke"
            opt.update(0, w, w.grad, list(w_state))
            opt.update(1, bias, bias.grad, list(b_state))
            # the reference's sparse push: publish updated rows
            kv.push('w', w)

            loss_sum += float(loss.asscalar())
            correct += int(((p.asnumpy() > 0.5) == (y[sl] > 0.5)).sum())
        history.append({'epoch': epoch,
                        'loss': loss_sum / (n // batch),
                        'acc': correct / ((n // batch) * batch)})
        log("epoch %d loss %.4f acc %.4f"
            % (epoch, history[-1]['loss'], history[-1]['acc']))
    # O(nnz) held end-to-end: nothing on the sparse path densified
    assert sparse.DENSIFY_COUNT == densify_start, \
        "sparse path densified %d time(s)" \
        % (sparse.DENSIFY_COUNT - densify_start)
    return history, w


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--epochs', type=int, default=5)
    ap.add_argument('--batch', type=int, default=128)
    ap.add_argument('--num-features', type=int, default=10000)
    ap.add_argument('--optimizer', type=str, default='sgd')
    a = ap.parse_args()
    history, _ = train(epochs=a.epochs, batch=a.batch,
                       num_features=a.num_features, optimizer=a.optimizer)
    print("final acc %.4f" % history[-1]['acc'])


if __name__ == '__main__':
    main()
