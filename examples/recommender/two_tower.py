"""Two-tower embedding retrieval on the row-sparse kvstore wire.

The canonical sparse-PS workload (reference: example/recommenders +
the row_sparse embedding path, src/kvstore/kvstore_dist_server.h
DataHandleRowSparse): a user tower and an item tower, each a single
``sparse_grad=True`` Embedding, trained on a synthetic clickstream.
Each step touches only the batch's rows, so under ``--kvstore
dist_async`` the gluon Trainer's one-list-push step rides the
row-sparse wire — only touched rows move, striped across however many
servers ``MXT_SERVER_URIS`` names.

After training the item tower doubles as a retrieval head: serving
scores are ``user_embed @ item_table.T``, which is exactly a
``FullyConnected(no_bias)`` whose weight IS the item table — so the
live table serves top-k through :class:`ServingReplica` with the
normal bucketed predict path, and a weight refresh is a data swap
(zero recompiles).

Run:  python examples/recommender/two_tower.py [--epochs 10] [--serve]
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import autograd, gluon, nd  # noqa: E402


def make_clickstream(num_users=64, num_items=256, events=4096, rank=4,
                     pool=16, seed=0):
    """Synthetic clickstream (zero egress): planted low-rank affinity,
    positives drawn from each user's top-``pool`` items, negatives
    uniform.  Returns (user, item, label) plus the planted preference
    pools the retrieval metric scores against."""
    rng = np.random.RandomState(seed)
    U = rng.randn(num_users, rank).astype(np.float32)
    V = rng.randn(num_items, rank).astype(np.float32)
    prefs = np.argsort(-(U @ V.T), axis=1)[:, :pool]   # per-user pool
    users = rng.randint(0, num_users, events)
    picks = rng.randint(0, pool, events)
    pos = prefs[users, picks]
    neg = rng.randint(0, num_items, events)
    u = np.concatenate([users, users])
    i = np.concatenate([pos, neg])
    y = np.concatenate([np.ones(events, np.float32),
                        np.zeros(events, np.float32)])
    perm = rng.permutation(len(y))
    return (u[perm].astype(np.float32), i[perm].astype(np.float32),
            y[perm], prefs)


def build_towers(num_users, num_items, dim, seed=0):
    """The two towers; prefixes pin the param names ('user_embed_weight',
    'item_scores_weight') to the SERVING symbol's, so a replica
    refreshes straight from the training kvstore by name."""
    mx.random.seed(seed)
    user_tower = gluon.nn.Embedding(num_users, dim, sparse_grad=True,
                                    prefix='user_embed_')
    item_tower = gluon.nn.Embedding(num_items, dim, sparse_grad=True,
                                    prefix='item_scores_')
    init = mx.initializer.Normal(0.3)
    user_tower.initialize(init)
    item_tower.initialize(init)
    return user_tower, item_tower


def train(user_tower, item_tower, stream, epochs=10, batch=64, lr=0.5,
          kvstore='device', log=print):
    """SGD over dot-product click regression.  Every grad is a
    RowSparseNDArray (only the batch's rows), so the dist_async step —
    one list push, one batched pull — moves O(touched rows) bytes."""
    u, i, y, _prefs = stream
    params = (list(user_tower.collect_params().values())
              + list(item_tower.collect_params().values()))
    trainer = gluon.Trainer(params, 'sgd', {'learning_rate': lr},
                            kvstore=kvstore)
    n = len(y)
    for epoch in range(epochs):
        total = 0.0
        for lo in range(0, n - batch + 1, batch):
            uids = nd.array(u[lo:lo + batch])
            iids = nd.array(i[lo:lo + batch])
            label = nd.array(y[lo:lo + batch])
            with autograd.record():
                ue = user_tower(uids)
                ve = item_tower(iids)
                score = mx.nd.sum(ue * ve, axis=1)
                loss = mx.nd.sum((score - label) ** 2)
            loss.backward()
            trainer.step(batch)
            total += float(loss.asnumpy())
        log("epoch %d click mse %.4f" % (epoch, total / n))
    return trainer


def hit_rate(user_tower, item_tower, prefs, k=10):
    """Retrieval metric: fraction of users whose top-k retrieved items
    intersect their planted preference pool."""
    ut = user_tower.weight.data().asnumpy()
    it = item_tower.weight.data().asnumpy()
    scores = ut @ it.T
    topk = np.argsort(-scores, axis=1)[:, :k]
    hits = [len(set(topk[r]) & set(prefs[r])) > 0
            for r in range(ut.shape[0])]
    return float(np.mean(hits))


def serving_symbol(num_users, num_items, dim):
    """user ids -> user embedding -> scores over EVERY item: the
    FullyConnected weight is the item table itself."""
    user = mx.sym.Variable('user')
    emb = mx.sym.Embedding(user, input_dim=num_users, output_dim=dim,
                           name='user_embed')
    return mx.sym.FullyConnected(emb, num_hidden=num_items, no_bias=True,
                                 name='item_scores')


def serve_topk(user_tower, item_tower, num_users, num_items, dim, k=10,
               param_servers=None):
    """Stand up a ServingReplica on the trained tables and return
    (replica, client, topk) where topk(ids) -> (n, k) item ids."""
    from mxnet_tpu.serving import ServingClient, ServingReplica
    params = {'user_embed_weight': user_tower.weight.data(),
              'item_scores_weight': item_tower.weight.data()}
    rep = ServingReplica(
        serving_symbol(num_users, num_items, dim), {'user': ()}, params,
        buckets=[1, 4, 16], max_wait_s=0.0, param_servers=param_servers)
    rep.start_background()
    cli = ServingClient(f"127.0.0.1:{rep.port}")

    def topk(ids):
        scores = cli.predict(np.asarray(ids, np.float32),
                             name='user')[0]
        return np.argsort(-scores, axis=1)[:, :k]

    return rep, cli, topk


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--epochs', type=int, default=10)
    ap.add_argument('--batch', type=int, default=64)
    ap.add_argument('--dim', type=int, default=8)
    ap.add_argument('--users', type=int, default=64)
    ap.add_argument('--items', type=int, default=256)
    ap.add_argument('--lr', type=float, default=0.5)
    ap.add_argument('--kvstore', default='device',
                    help="'device' (local) or 'dist_async' "
                         "(needs MXT_SERVER_URIS)")
    ap.add_argument('--serve', action='store_true',
                    help='stand up a ServingReplica and query top-k')
    a = ap.parse_args()
    stream = make_clickstream(a.users, a.items)
    user_tower, item_tower = build_towers(a.users, a.items, a.dim)
    train(user_tower, item_tower, stream, epochs=a.epochs, batch=a.batch,
          lr=a.lr, kvstore=a.kvstore)
    hr = hit_rate(user_tower, item_tower, stream[3])
    print("final hit@10 %.3f" % hr)
    if a.serve:
        rep, cli, topk = serve_topk(user_tower, item_tower, a.users,
                                    a.items, a.dim)
        try:
            got = topk(np.arange(min(4, a.users)))
            hits = [len(set(got[r]) & set(stream[3][r])) > 0
                    for r in range(got.shape[0])]
            print("served top-k for %d users, %d hit their pool"
                  % (got.shape[0], sum(hits)))
        finally:
            cli.close()
            rep.stop()
        print("serving done")


if __name__ == '__main__':
    main()
