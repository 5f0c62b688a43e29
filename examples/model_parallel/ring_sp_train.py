#!/usr/bin/env python
"""Long-context LM training with ring attention over the ``sp`` axis.

The long-context recipe this framework ships (no reference analog —
MXNet 0.12 predates sequence parallelism, SURVEY.md §5.7): tokens are
sharded along the SEQUENCE over the sp ring, attention runs as the
exact blockwise ring (``parallel.ring_attention`` — K/V rotate via
ppermute, online softmax, O((S/n)^2) score memory per device), and the
loss head is the chunked CE (``ops/chunked_loss.py`` — the (N, V)
logits never materialize).  Peak per-device memory is therefore
independent of BOTH quadratic attention scores AND the vocab axis: the
two walls that cap context length.

One jitted SPMD train step over a dp×sp mesh; GSPMD shards the
embedding/FFN math from the input shardings, ring attention rides
shard_map inside the same program.

Runs on the virtual CPU mesh out of the box:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/model_parallel/ring_sp_train.py --steps 30
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                '..', '..'))
import jax  # noqa: E402
import numpy as np  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from mxnet_tpu import parallel as par  # noqa: E402
from mxnet_tpu.ops.chunked_loss import chunked_lm_loss  # noqa: E402


def init_params(key, vocab, d_model, d_ff, heads):
    ks = jax.random.split(key, 6)
    s = lambda k, shp, fan: (jax.random.normal(k, shp) / np.sqrt(fan))
    return {
        "embed": s(ks[0], (vocab, d_model), d_model),
        "wqkv": s(ks[1], (d_model, 3 * d_model), d_model),
        "wo": s(ks[2], (d_model, d_model), d_model),
        "w1": s(ks[3], (d_model, d_ff), d_model),
        "w2": s(ks[4], (d_ff, d_model), d_ff),
        "head_b": jnp.zeros((vocab,)),
    }


def model_loss(params, tokens, labels, mesh, heads):
    B, S = tokens.shape
    d_model = params["embed"].shape[1]
    hd = d_model // heads
    x = params["embed"][tokens.astype(jnp.int32)]          # (B, S, D)
    qkv = x @ params["wqkv"]                               # (B, S, 3D)
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def bhsd(t):  # (B, S, D) -> (B, H, S, hd)
        return t.reshape(B, S, heads, hd).transpose(0, 2, 1, 3)

    # the sp ring: exact causal attention with seq-sharded q/k/v
    a = par.ring_attention(bhsd(q), bhsd(k), bhsd(v), mesh, causal=True)
    a = a.transpose(0, 2, 1, 3).reshape(B, S, d_model)
    x = x + a @ params["wo"]
    x = x + jax.nn.gelu(x @ params["w1"]) @ params["w2"]
    # chunked CE against the TIED embedding: no (B*S, V) logits
    loss = chunked_lm_loss(x.reshape(B * S, d_model), params["embed"],
                           params["head_b"],
                           labels.reshape(B * S), 4)
    return loss.mean()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=80)
    ap.add_argument("--vocab", type=int, default=64)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--lr", type=float, default=1.0)
    args = ap.parse_args()
    if args.steps < 1:
        ap.error("--steps must be >= 1")

    mesh = par.make_mesh(dp=2, sp=4, devices=jax.devices())
    data_sh = NamedSharding(mesh, P("dp", "sp"))   # (B, S) tokens
    rep = NamedSharding(mesh, P())

    rs = np.random.RandomState(0)
    first = rs.randint(0, args.vocab, (args.batch, 1))
    seq = (first + np.arange(args.seq + 1)) % args.vocab
    tokens = jax.device_put(seq[:, :-1].astype(np.int32), data_sh)
    labels = jax.device_put(seq[:, 1:].astype(np.int32), data_sh)

    params = jax.device_put(
        init_params(jax.random.PRNGKey(0), args.vocab, args.d_model,
                    4 * args.d_model, args.heads), rep)

    @jax.jit
    def step(params, tokens, labels):
        loss, grads = jax.value_and_grad(model_loss)(
            params, tokens, labels, mesh, args.heads)
        params = jax.tree_util.tree_map(
            lambda p, g: p - args.lr * g, params, grads)
        return params, loss

    first_loss = None
    for i in range(args.steps):
        params, loss = step(params, tokens, labels)
        if first_loss is None:
            first_loss = float(loss)
        if i % 10 == 0:
            print("step %d loss %.4f" % (i, float(loss)), flush=True)
    final = float(loss)
    print("ring-sp train: loss %.4f -> %.4f" % (first_loss, final),
          flush=True)
    assert final < 0.5 * first_loss, (first_loss, final)


if __name__ == "__main__":
    main()
