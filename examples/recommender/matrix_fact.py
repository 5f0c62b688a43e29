"""Matrix-factorization recommender
(reference: example/recommenders/matrix_fact.py / demo1-MF.ipynb — the
classic MovieLens MF: user & item embeddings, dot-product score,
trained with the legacy FeedForward estimator).

Same shape here: two Embedding towers composed symbolically, an
elementwise-dot score head, LinearRegressionOutput loss, trained through
``mx.model.FeedForward`` (the estimator the reference demo uses) over a
multi-input NDArrayIter.  Data is a synthetic MovieLens stand-in (zero
egress): ratings generated from planted low-rank factors + noise, so
recoverable structure exists and RMSE has a meaningful floor.

Run:  python examples/recommender/matrix_fact.py [--epochs 10]
"""
import argparse
import math
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import mxnet_tpu as mx  # noqa: E402


def plain_net(max_user, max_item, hidden=16):
    """reference matrix_fact.py plain_net: embed users & items, dot."""
    user = mx.sym.Variable('user')
    item = mx.sym.Variable('item')
    score = mx.sym.Variable('score')
    user = mx.sym.Embedding(user, input_dim=max_user, output_dim=hidden,
                            name='user_embed')
    item = mx.sym.Embedding(item, input_dim=max_item, output_dim=hidden,
                            name='item_embed')
    pred = user * item
    pred = mx.sym.sum(pred, axis=1)
    pred = mx.sym.Flatten(pred)
    return mx.sym.LinearRegressionOutput(data=pred, label=score,
                                         name='lro')


def make_ratings(num_users=200, num_items=100, num_ratings=8000, rank=4,
                 noise=0.1, seed=0):
    rng = np.random.RandomState(seed)
    U = rng.randn(num_users, rank).astype(np.float32) / math.sqrt(rank)
    V = rng.randn(num_items, rank).astype(np.float32) / math.sqrt(rank)
    u = rng.randint(0, num_users, num_ratings)
    i = rng.randint(0, num_items, num_ratings)
    r = (U[u] * V[i]).sum(axis=1) + noise * rng.randn(num_ratings)
    return (u.astype(np.float32), i.astype(np.float32),
            r.astype(np.float32))


def rmse_metric():
    def rmse(label, pred):
        pred = pred.reshape(-1)
        return float(np.sqrt(((label - pred) ** 2).mean()))
    return mx.metric.np(rmse, name='rmse')


def train(epochs=30, batch=256, hidden=8, lr=0.02, seed=0, log=print):
    num_users, num_items = 200, 100
    u, i, r = make_ratings(num_users, num_items, seed=seed)
    n_train = int(0.9 * len(r))
    train_it = mx.io.NDArrayIter(
        {'user': u[:n_train], 'item': i[:n_train]},
        {'score': r[:n_train]}, batch_size=batch, shuffle=True,
        last_batch_handle='discard')
    val_it = mx.io.NDArrayIter(
        {'user': u[n_train:], 'item': i[n_train:]},
        {'score': r[n_train:]}, batch_size=batch)

    mx.random.seed(seed)
    np.random.seed(seed)
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter('ignore', DeprecationWarning)
        # init at the data's scale: the score is a dot of TWO embeddings,
        # so tiny init (0.05^2 per term) starts the model ~10x below the
        # rating magnitudes and sgd crawls; Normal(0.3) + adam converges
        # to the noise floor in ~30 epochs
        model = mx.model.FeedForward(
            plain_net(num_users, num_items, hidden), ctx=mx.cpu(),
            num_epoch=epochs, optimizer='adam', learning_rate=lr,
            initializer=mx.initializer.Normal(0.3))
    model.fit(train_it, eval_data=val_it, eval_metric=rmse_metric())
    val_rmse = model.score(val_it, rmse_metric())
    log("validation rmse %.4f" % val_rmse)
    return model, val_rmse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--epochs', type=int, default=30)
    ap.add_argument('--batch', type=int, default=256)
    ap.add_argument('--hidden', type=int, default=8)
    a = ap.parse_args()
    _, val_rmse = train(epochs=a.epochs, batch=a.batch, hidden=a.hidden)
    print("final rmse %.4f" % val_rmse)


if __name__ == '__main__':
    main()
