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
    if "exit_gate_weight" in params:
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


# -- what the loop keeps for its backward pass (base.tag_for_remat) -------------
def grad_jaxpr(symbol, params, data, label):
    """The jaxpr of d(the last output's sum)/d(every parameter), bf16."""
    run, names, _ = build_interpreter(symbol, jnp.bfloat16)
    vals = dict(params, data=data, softmax_label=label)

    def f(*args):
        return run(args, (), None, True)[0][-1].astype(jnp.float32).sum()
    return jax.make_jaxpr(jax.grad(f, argnums=tuple(
        i for i, n in enumerate(names) if n in params)))(
        *[jnp.asarray(vals[n]) for n in names])


def looped_symbol(remat=True):
    kw = dict(BUILDER)
    symbol = models.looped_transformer_lm(kw.pop("vocab_size"),
                                          TRAFFIC["seq_len"], **kw)
    loop, = [n for n in symbol.nodes() if n.op == "_foreach"]
    loop.attrs["remat"] = remat         # the model itself always says True
    return symbol


def test_the_loop_keeps_the_kernels_residuals_and_the_long_matmuls(
        jaxpr_eqns):
    """The gradient of the model's rematerialised loop: ``flash_fwd`` runs
    in the forward scan alone, its output and log-sum-exp named there
    beside the two matmuls a layer whose contraction is at least their
    width (``proj`` 64 -> 64, ``fc2`` 96 -> 64); the checkpoint's backward
    makes ``qkv`` and ``fc1`` again (K < N) with every RMSNorm, and runs
    the backward kernel on what was kept."""
    symbol = looped_symbol()
    params, data, label, _ = start(symbol)
    found = list(jaxpr_eqns(grad_jaxpr(symbol, params, data, label)))
    layers = BUILDER["num_layers"]

    def at(path, prim):         # directly under these primitives
        return [e for p, e in found if p == path and e.primitive.name == prim]
    kernels = {}                # kernel: is each call under the checkpoint
    for p, e in found:
        if e.primitive.name == "pallas_call":
            kernels.setdefault(e.params["name"], []).append("remat2" in p)
    assert kernels == {"flash_fwd": [False] * layers,
                       "flash_bwd_dkv_dq": [True] * layers}
    named = at(("scan",), "name")
    assert len(named) == sum(e.primitive.name == "name" for _, e in found)
    assert sorted(e.params["name"] for e in named) == sorted(
        ["attn_out", "attn_lse", "matmul_out", "matmul_out"] * layers)
    assert [e.outvars[0].aval.shape for e in named
            if e.params["name"] == "matmul_out"] == [
        (32, BUILDER["d_model"])] * (2 * layers)
    # four matmuls a layer: 2 made again + 8 gradients, for 4 + 8
    assert len(at(("scan", "remat2"), "dot_general")) == 10 * layers
    assert len(at(("scan", "remat2"), "rsqrt")) == 4 * layers + 1


def test_keeping_changes_no_number_of_the_models_step():
    """Module's fused step over the rematerialised loop against the same
    loop keeping every activation: the same outputs, and the same
    first-step deltas to float32's rounding (the two programs fuse their
    chains differently; tests/test_control_flow.py has a body small enough
    to be equal bit for bit)."""
    params, data, label, shapes = start(looped_symbol())
    outs, deltas = module_step(looped_symbol(True), params, data, label,
                               shapes)
    want_outs, want = module_step(looped_symbol(False), params, data, label,
                                  shapes)
    for got, ref in zip(outs, want_outs):
        np.testing.assert_array_equal(got, ref)
    for n in sorted(params):
        err = np.linalg.norm(deltas[n] - want[n]) / np.linalg.norm(want[n])
        assert err < 2e-6, (n, err)


@pytest.mark.parametrize("model", ["transformer_lm", "loop_without_remat"])
def test_a_step_without_a_rematerialised_loop_names_nothing(jaxpr_eqns,
                                                            model):
    """The tags are identities there: no ``name`` primitive and no
    checkpoint in the gradient of ``transformer_lm`` (GPT-2's layout:
    flash attention, ``FullyConnected``) nor of a loop that keeps its
    activations -- the programs of every model without such a loop lower
    as they did before the tags existed."""
    if model == "transformer_lm":
        symbol = models.transformer_lm(
            BUILDER["vocab_size"], TRAFFIC["seq_len"], num_layers=2,
            d_model=64, num_heads=4, d_ff=96)
    else:
        symbol = looped_symbol(remat=False)
    params, data, label, _ = start(symbol)
    prims = {e.primitive.name for _, e in jaxpr_eqns(
        grad_jaxpr(symbol, params, data, label))}
    assert "pallas_call" in prims and "dot_general" in prims
    assert not {"name", "remat2"} & prims


def test_the_lowering_says_what_the_loop_keeps(loop_lower_instants):
    """``kept`` of ``mx.loop.lower``, from the shapes: a layer keeps its
    attention output (B, H, S, D) and log-sum-exp (B, H, S) float32, and
    the outputs of ``proj`` and ``fc2`` (B S, d); bf16 under AMP."""
    symbol = looped_symbol()
    params, data, label, _ = start(symbol)
    grad_jaxpr(symbol, params, data, label)
    said = [a for a in loop_lower_instants() if a["node"] == "loop"]
    tokens = TRAFFIC["batch"] * TRAFFIC["seq_len"]
    layers, d = BUILDER["num_layers"], BUILDER["d_model"]
    assert said[-1]["kept"] == {"attn_out": layers * tokens * d * 2,
                                "attn_lse": layers * tokens
                                * BUILDER["num_heads"] * 4,
                                "matmul_out": layers * 2 * tokens * d * 2}
