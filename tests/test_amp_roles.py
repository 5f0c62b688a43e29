"""Mixed precision casts activations, not whole numbers or constants
(executor._amp_uncast_inputs): an op's label or index input, and a value
on its way to one through ops that only move elements, keep their dtype
under ``compute_dtype=bfloat16`` (bf16 holds eight bits: 49151 would
become 49152, 257 would become 256); so does a table no Variable reaches."""
import numpy as np

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import models, sym
from mxnet_tpu.executor import build_interpreter

ROWS, TOP = 49152, 49151.0


def run_bf16(symbol, **values):
    run, names, _ = build_interpreter(symbol, jnp.bfloat16)
    outs, _ = run(tuple(jnp.asarray(values[n]) for n in names), (),
                  jax.random.PRNGKey(0), True)
    return [np.asarray(o.astype(jnp.float32)) for o in outs]


def ids():
    """Float32 ids that bf16 cannot hold, as (2, 2), to be flattened on
    their way to the op."""
    return np.array([[TOP, 257.0], [1001.0, 3.0]], np.float32)


def flat(name="ids"):
    return sym.Reshape(sym.Variable(name), shape=(-1,))


def table():
    """Row r holds r in column 0 (exactly, in two bf16-exact parts)."""
    t = np.zeros((ROWS, 2), np.float32)
    t[:, 0] = (np.arange(ROWS) // 256) * 256
    t[:, 1] = np.arange(ROWS) % 256
    return t


def rows_of(out):
    return (out[:, 0] + out[:, 1]).tolist()


WANT = [TOP, 257.0, 1001.0, 3.0]


def test_float32_ids_survive_bf16_into_embedding_and_take():
    emb = sym.Embedding(flat(), sym.Variable("w"), input_dim=ROWS,
                        output_dim=2)
    assert rows_of(run_bf16(emb, ids=ids(), w=table())[0]) == WANT
    took = sym.take(sym.Variable("w"), flat())
    assert rows_of(run_bf16(took, ids=ids(), w=table())[0]) == WANT


def test_float32_ids_survive_bf16_into_pick_and_one_hot():
    a = np.zeros((4, ROWS), np.float32)
    a[np.arange(4), np.array(WANT, int)] = 1.0
    picked = sym.pick(sym.Variable("a"), flat())
    assert run_bf16(picked, ids=ids(), a=a)[0].tolist() == [1.0] * 4
    hot = run_bf16(sym.one_hot(flat(), depth=ROWS), ids=ids())[0]
    assert hot.argmax(axis=1).tolist() == [int(x) for x in WANT]


def test_float32_labels_survive_bf16_into_the_loss_heads():
    rng = np.random.default_rng(0)
    h = rng.normal(size=(4, 8)).astype(np.float32)
    w = rng.normal(size=(ROWS, 8)).astype(np.float32)
    loss = sym.chunked_lm_loss(sym.Variable("h"), sym.Variable("w"),
                               sym.zeros((ROWS,)),
                               sym.tile(flat("label"), reps=(1,)),
                               num_chunks=8)
    got = run_bf16(loss, h=h, w=w, label=ids())[0]
    hb = np.asarray(jnp.asarray(h).astype(jnp.bfloat16).astype(jnp.float32))
    wb = np.asarray(jnp.asarray(w).astype(jnp.bfloat16).astype(jnp.float32))
    logits = hb.astype(np.float64) @ wb.astype(np.float64).T
    lse = np.log(np.exp(logits - logits.max(1, keepdims=True)).sum(1)) \
        + logits.max(1)
    want = lse - logits[np.arange(4), np.array(WANT, int)]
    np.testing.assert_allclose(got, want, rtol=1e-3)
    # SoftmaxOutput's head gradient is p - onehot(label): its row
    out = sym.SoftmaxOutput(sym.Variable("x"), flat("label"))
    run, names, _ = build_interpreter(out, jnp.bfloat16)
    x = jnp.zeros((4, ROWS), jnp.float32)

    def f(x):
        vals = {"x": x, "label": jnp.asarray(ids())}
        return run(tuple(vals[n] for n in names), (), None, True)[0][0]
    _, vjp = jax.vjp(f, x)
    grad = np.asarray(vjp(jnp.ones((4, ROWS), jnp.float32))[0])
    assert grad.argmin(axis=1).tolist() == [int(v) for v in WANT]


def test_an_activation_is_still_cast_and_an_index_read_twice_is_split():
    """The same float32 value as an index (kept) and as an activation
    (cast): the role is the argument's, not the value's."""
    v = sym.Variable("ids")
    both = sym.Group([sym.take(sym.Variable("w"), flat()),
                      sym.Reshape(v, shape=(-1,)) * 1.0])
    rows, scaled = run_bf16(both, ids=ids(), w=table())
    assert rows_of(rows) == WANT
    assert scaled.tolist() == [49152.0, 256.0, 1000.0, 3.0]   # bf16's


def test_a_table_of_constants_keeps_its_dtype_until_an_activation_meets_it():
    """Rotary angles at position 4095: computed in float32 from the
    position range, rounded to bf16 once, where q meets them."""
    from mxnet_tpu.models.transformer import _rope_tables
    cos, sin = _rope_tables(4096, 128, 1e6)
    got_cos, got_sin = run_bf16(sym.Group([cos, sin]))
    inv = np.exp(np.arange(64, dtype=np.float32)
                 * np.float32(-2.0 * np.log(1e6) / 128))
    ang = np.arange(4096, dtype=np.float32)[:, None] * inv[None, :]
    np.testing.assert_allclose(got_cos[0, 0], np.cos(ang), atol=2e-3)
    np.testing.assert_allclose(got_sin[0, 0], np.sin(ang), atol=2e-3)
    # had the range gone through bf16, position 4095 would be 4096: off
    # by a radian in the fastest pair
    assert abs(got_sin[0, 0, 4095, 0] - np.sin(4095.0)) < 2e-3
    assert abs(np.sin(4096.0) - np.sin(4095.0)) > 0.4
    # where an activation meets the table, the product is bf16
    q = sym.broadcast_mul(sym.Variable("q"), cos)
    out = build_interpreter(q, jnp.bfloat16)[0](
        (jnp.ones((1, 1, 4096, 64), jnp.float32),), (), None, True)[0][0]
    assert out.dtype == jnp.bfloat16


def test_the_top_ids_train_their_own_rows_through_a_bf16_module():
    """The looped LM's labels reach ``chunked_lm_loss`` and its float32
    ids the embedding.  Every id and label is 49150: bf16 would make it
    49152, which the lookup clips to the last row, 49151 (so id 49151
    itself cannot tell; 49150 can): the embedding row and the head row
    that move are 49150's."""
    s = models.looped_transformer_lm(ROWS, 4, num_layers=1, d_model=8,
                                     num_heads=2, d_ff=8, loop_steps=2,
                                     ce_chunks=8)
    mod = mx.mod.Module(s, context=mx.cpu(), compute_dtype=jnp.bfloat16)
    mod.bind(data_shapes=[("data", (1, 4))],
             label_shapes=[("softmax_label", (1, 4))])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    before = {n: v.asnumpy() for n, v in mod.get_params()[0].items()}
    top = mx.nd.array(np.full((1, 4), TOP - 1, np.float32))
    mod.forward(mx.io.DataBatch(data=[top], label=[top]), is_train=True)
    mod.update()
    after = mod.get_params()[0]
    moved = np.abs(after["tok_embed_weight"].asnumpy()
                   - before["tok_embed_weight"]).sum(axis=1)
    assert moved.nonzero()[0].tolist() == [int(TOP) - 1]
    head = (after["lm_head_weight"].asnumpy()
            - before["lm_head_weight"])
    assert np.abs(head).sum(axis=1).argmax() == int(TOP) - 1


def test_gpt_style_labels_reach_softmax_output_exact():
    """``transformer_lm``'s float32 labels go through a Reshape to
    SoftmaxOutput: under bf16 the head's gradient is at the label's row."""
    s = models.transformer_lm(1000, 8, num_layers=1, d_model=32,
                              num_heads=2)
    mod = mx.mod.Module(s, context=mx.cpu(), compute_dtype=jnp.bfloat16)
    mod.bind(data_shapes=[("data", (1, 8))],
             label_shapes=[("softmax_label", (1, 8))])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    before = mod.get_params()[0]["lm_head_bias"].asnumpy()
    labels = np.array([[999., 777, 513, 257, 301, 5, 6, 7]], np.float32)
    data = mx.nd.NDArray(jnp.arange(8, dtype=jnp.int32).reshape(1, 8))
    mod.forward(mx.io.DataBatch(data=[data], label=[mx.nd.array(labels)]),
                is_train=True)
    mod.update()
    rose = mod.get_params()[0]["lm_head_bias"].asnumpy() - before
    assert sorted(np.argsort(-rose)[:8].tolist()) == sorted(
        int(x) for x in labels[0])
