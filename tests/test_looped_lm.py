"""The looped LM (``models.looped_transformer_lm``: Ouro's layout) and the
two ops it brought: ``RMSNorm`` and ``_contrib_ExpectedExitLoss``.  The
model is held to chipbench's plain reference
(chipbench/families/looped_lm.py, nothing of ``mxnet_tpu`` in it) at d 64,
2 layers, 3 loop steps, V 257, S 16."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import models, nd, sym  # noqa: E402
from mxnet_tpu.executor import build_interpreter  # noqa: E402
from mxnet_tpu.models import transformer as tf  # noqa: E402

BUILDER = {"vocab_size": 257, "num_layers": 2, "d_model": 64, "num_heads": 4,
           "d_ff": 96, "loop_steps": 3, "rope_base": 1e6, "norm_eps": 1e-6,
           "exit_beta": 0.05, "ce_chunks": 4}
TRAFFIC = {"batch": 2, "seq_len": 16}
OPT = {"name": "sgd", "learning_rate": 0.05, "momentum": 0.9, "wd": 0.0,
       "wd_suffixes": ["_weight", "_gamma"]}


# -- RMSNorm --------------------------------------------------------------------
def rms_numpy(x, g, eps):
    x = x.astype(np.float64)
    return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps) * g


def test_rmsnorm_against_numpy():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(3, 5, 32)).astype(np.float32) * 3
    g = (1 + 0.2 * rng.normal(size=(32,))).astype(np.float32)
    got = nd.RMSNorm(nd.array(x), nd.array(g), eps=1e-6).asnumpy()
    np.testing.assert_allclose(got, rms_numpy(x, g, 1e-6), rtol=2e-6,
                               atol=2e-6)
    # another axis
    got = nd.RMSNorm(nd.array(x), nd.array(g[:5]), axis=1, eps=1e-3).asnumpy()
    want = np.moveaxis(rms_numpy(np.moveaxis(x, 1, -1), g[:5], 1e-3), -1, 1)
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
    # the gain's shape comes from the data
    s = sym.RMSNorm(sym.Variable("data"), name="norm")
    assert s.list_arguments() == ["data", "norm_gamma"]
    assert s.infer_shape(data=(3, 5, 32))[0] == [(3, 5, 32), (32,)]


def test_rmsnorm_takes_bf16_data_and_keeps_float32_statistics():
    """Rows of 2048 values near 300: their squares' mean carries past what
    bf16 can add up, and the gain keeps digits bf16 has not: the result is
    the float32 computation rounded once."""
    rng = np.random.default_rng(1)
    x = (300 + rng.normal(size=(4, 2048))).astype(np.float32)
    g = (1 + rng.normal(size=(2048,)) * 1e-3).astype(np.float32)
    xb = jnp.asarray(x).astype(jnp.bfloat16)
    want = rms_numpy(np.asarray(xb.astype(jnp.float32)), g, 1e-6)
    # through the executor at bf16: only the data is cast, not the gain
    run, names, _ = build_interpreter(
        sym.RMSNorm(sym.Variable("data"), name="norm"), jnp.bfloat16)
    vals = {"data": jnp.asarray(x), "norm_gamma": jnp.asarray(g)}
    out = run(tuple(vals[n] for n in names), (), None, True)[0][0]
    assert out.dtype == jnp.bfloat16
    rounded = jnp.asarray(want, jnp.float32).astype(jnp.bfloat16)
    assert np.array_equal(np.asarray(out.astype(jnp.float32)),
                          np.asarray(rounded.astype(jnp.float32)))


# -- the exit distribution and the objective ---------------------------------------
def test_exit_distribution_sums_to_one_and_the_objective_is_its_expectation():
    rng = np.random.default_rng(2)
    T, N, beta = 4, 7, 0.05
    gate = rng.normal(size=(T * N, 1)).astype(np.float32) * 3
    loss = rng.uniform(1, 6, size=(T * N,)).astype(np.float32)
    obj, p = nd.contrib.ExpectedExitLoss(nd.array(gate), nd.array(loss),
                                         steps=T, beta=beta)
    obj, p = obj.asnumpy(), p.asnumpy()
    assert p.shape == (T, N) and obj.shape == (N,)
    np.testing.assert_allclose(p.sum(axis=0), 1.0, rtol=1e-6)
    lam = 1 / (1 + np.exp(-gate.reshape(T, N).astype(np.float64)))
    want_p = np.empty((T, N))
    stay = np.ones(N)
    for t in range(T):
        want_p[t] = stay * (lam[t] if t < T - 1 else 1.0)
        stay = stay * (1 - lam[t])
    np.testing.assert_allclose(p, want_p, rtol=1e-5)
    want = (want_p * (loss.reshape(T, N) + beta * np.log(want_p))).sum(0)
    np.testing.assert_allclose(obj, want, rtol=1e-5)
    # gates that never exit early: all mass on the last step
    _, p = nd.contrib.ExpectedExitLoss(nd.array(np.full((T * N, 1), -80.0)),
                                       nd.array(loss), steps=T, beta=beta)
    np.testing.assert_allclose(p.asnumpy()[-1], 1.0)
    # one step: the loss itself, whatever the gate says
    obj, p = nd.contrib.ExpectedExitLoss(nd.array(gate[:N]),
                                         nd.array(loss[:N]), steps=1,
                                         beta=beta)
    np.testing.assert_allclose(obj.asnumpy(), loss[:N], rtol=1e-6)
    np.testing.assert_allclose(p.asnumpy(), 1.0)


# -- the model against the plain reference -----------------------------------------
def family():
    from chipbench.harness import Resolver
    return Resolver().module("families", "looped_lm")


def start(symbol, seed=0):
    shapes = {"data": (TRAFFIC["batch"], TRAFFIC["seq_len"]),
              "softmax_label": (TRAFFIC["batch"], TRAFFIC["seq_len"])}
    rng = np.random.default_rng(seed)
    params = {}
    for n, s in zip(symbol.list_arguments(),
                    symbol.infer_shape(**shapes)[0]):
        if n in shapes:
            continue
        if n.endswith("_gamma"):
            params[n] = (1 + 0.1 * rng.normal(size=s)).astype(np.float32)
        elif n.endswith("_bias"):
            params[n] = (0.3 * rng.normal(size=s)).astype(np.float32)
        else:
            params[n] = (rng.normal(size=s) / np.sqrt(s[-1])
                         ).astype(np.float32)
    # a gate that matters: exits spread over the steps
    params["exit_gate_weight"] *= 4
    data = rng.integers(0, BUILDER["vocab_size"], shapes["data"])
    label = rng.integers(0, BUILDER["vocab_size"], shapes["data"])
    return params, data.astype(np.int32), label.astype(np.float32), shapes


def module_step(symbol, params, data, label, shapes):
    """(outputs, {tensor: first-step delta}) through Module's fused
    step."""
    mod = mx.mod.Module(symbol, context=mx.cpu())
    mod.bind(data_shapes=[("data", shapes["data"])],
             label_shapes=[("softmax_label", shapes["softmax_label"])])
    mod.init_params(arg_params={n: nd.array(v) for n, v in params.items()})
    mod.init_optimizer(optimizer="sgd", optimizer_params={
        "learning_rate": OPT["learning_rate"], "momentum": OPT["momentum"]})
    mod.forward(mx.io.DataBatch(data=[nd.NDArray(jnp.asarray(data))],
                                label=[nd.array(label)]), is_train=True)
    mod.update()
    outs = [o.asnumpy() for o in mod.get_outputs()]
    after = mod.get_params()[0]
    return outs, {n: after[n].asnumpy() - params[n] for n in params}


def test_model_against_the_plain_reference():
    fam = family()
    cfg = {"builder": BUILDER, "optimizer": OPT}
    symbol = fam.build(cfg, TRAFFIC)["symbol"]
    params, data, label, shapes = start(symbol)
    assert sorted(params) == sorted(
        n for n in symbol.list_arguments() if n not in shapes)
    (probs, per_token), deltas = module_step(symbol, params, data, label,
                                             shapes)
    sample = np.arange(32, dtype=np.int32)
    loss, out, want = fam.reference(
        cfg, TRAFFIC, {n: jnp.asarray(v) for n, v in params.items()},
        jnp.asarray(data), jnp.asarray(label), "float32", sample)
    # output 0: the last loop step's softmax
    assert probs.shape == (32, BUILDER["vocab_size"])
    np.testing.assert_allclose(probs.sum(axis=1), 1.0, rtol=1e-5)
    np.testing.assert_allclose(probs, np.asarray(out), rtol=2e-4, atol=1e-7)
    # the loss the driver reads from output 0
    picked = probs[np.arange(32), label.reshape(-1).astype(int)]
    assert -np.log(picked).mean() == pytest.approx(float(loss), rel=1e-5)
    # every tensor's first-step delta under the whole objective
    for n in sorted(params):
        ref = np.asarray(want[n])
        assert np.linalg.norm(ref) > 0, n
        err = np.linalg.norm(deltas[n] - ref) / np.linalg.norm(ref)
        assert err < 2e-3, (n, err)
    # output 1 is the per-token objective: its sum over the batch's
    # sequences over B is what the reference differentiates
    from chipbench.families import looped_lm  # the same file, as a module
    objective, _ = looped_lm._objective(
        cfg, TRAFFIC, {n: jnp.asarray(v) for n, v in params.items()},
        jnp.asarray(data), jnp.asarray(label), jnp.dtype("float32"), sample)
    assert per_token.shape == (32,)
    assert per_token.sum() / TRAFFIC["batch"] == pytest.approx(
        float(objective), rel=1e-5)


def test_one_loop_step_is_the_plain_decoder():
    """``loop_steps=1``: the exit distribution is all on the one step, the
    objective is the cross-entropy, and the loop node runs its body once:
    the same numbers as the same layers written out with no loop node."""
    kw = dict(BUILDER, loop_steps=1)
    looped = models.looped_transformer_lm(kw.pop("vocab_size"),
                                          TRAFFIC["seq_len"], **kw)
    V, S, d = BUILDER["vocab_size"], TRAFFIC["seq_len"], BUILDER["d_model"]
    x = sym.Embedding(sym.Variable("data"), input_dim=V, output_dim=d,
                      name="tok_embed")
    rope = tf._rope_tables(S, d // BUILDER["num_heads"], BUILDER["rope_base"])
    for i in range(BUILDER["num_layers"]):
        x = tf._sandwich_layer(x, S, d, BUILDER["num_heads"],
                               BUILDER["d_ff"], f"layer{i}", rope_cs=rope,
                               norm_eps=BUILDER["norm_eps"])
    x = sym.Reshape(sym.RMSNorm(x, name="final_norm", eps=1e-6),
                    shape=(-1, d))
    logits = sym.FullyConnected(x, num_hidden=V, no_bias=True,
                                name="lm_head")
    plain = sym.SoftmaxOutput(logits, sym.Reshape(
        sym.Variable("softmax_label"), shape=(-1,)), name="softmax")
    assert "_foreach" not in plain.tojson() and "_foreach" in looped.tojson()
    params, data, label, shapes = start(looped)
    (probs, per_token), deltas = module_step(looped, params, data, label,
                                             shapes)
    shared = {n: v for n, v in params.items()
              if not n.startswith("exit_gate")}
    (want_probs,), want = module_step(plain, shared, data, label, shapes)
    np.testing.assert_allclose(probs, want_probs, rtol=1e-4, atol=1e-8)
    picked = want_probs[np.arange(32), label.reshape(-1).astype(int)]
    np.testing.assert_allclose(per_token, -np.log(picked), rtol=1e-4)
    for n in shared:
        err = np.linalg.norm(deltas[n] - want[n]) / np.linalg.norm(want[n])
        assert err < 1e-3, (n, err)
    # with one step the gate decides nothing, and learns nothing
    assert np.abs(deltas["exit_gate_weight"]).max() == 0


def test_the_model_lists_each_looped_weight_once_and_saves_what_it_lists(
        tmp_path):
    kw = dict(BUILDER)
    symbol = models.looped_transformer_lm(kw.pop("vocab_size"),
                                          TRAFFIC["seq_len"], **kw)
    names = symbol.list_arguments()
    assert len(names) == len(set(names)) == 2 + 3 + 2 * 8 + 2
    loops = [n for n in symbol.nodes() if n.op == "_foreach"]
    assert len(loops) == 1 and loops[0].attrs["remat"] is True
    assert loops[0].attrs["num_iter"] == BUILDER["loop_steps"]
    assert set(loops[0].attrs["free_names"]) == {
        n for n in names if n.startswith(("layer", "final_norm"))}
    mod = mx.mod.Module(symbol, context=mx.cpu())
    mod.bind(data_shapes=[("data", (2, 16))],
             label_shapes=[("softmax_label", (2, 16))])
    mod.init_params(mx.initializer.Xavier())
    gains = [v.asnumpy() for n, v in mod.get_params()[0].items()
             if n.endswith("_gamma")]
    assert len(gains) == 9 and all((g == 1).all() for g in gains)
    prefix = str(tmp_path / "ouro")
    mod.save_checkpoint(prefix, 1)
    loaded, args, aux = mx.model.load_checkpoint(prefix, 1)
    assert loaded.list_arguments() == names and not aux
    assert sorted(args) == sorted(n for n in names
                                  if n not in ("data", "softmax_label"))
