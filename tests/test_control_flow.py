"""The loop node (``mx.sym.contrib.foreach`` -> op ``_foreach``,
mxnet_tpu/ops/control_flow.py): a sub-Symbol traced once and lowered to one
``lax.scan``, held to the same graph unrolled in Python over shared
Variables."""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import profiler, sym, tracing
from mxnet_tpu.base import MXNetError
from mxnet_tpu.executor import Executor

STEPS, BATCH, WIDTH = 3, 4, 8


def cell(h, weight, bias, gain, name):
    """One iteration: a dense layer, a residual, an RMSNorm."""
    y = sym.FullyConnected(h, weight=weight, bias=bias, num_hidden=WIDTH,
                           name=f"{name}_fc")
    return sym.RMSNorm(sym.tanh(y) + h, gamma=gain, name=f"{name}_norm")


def shared():
    return (sym.Variable("fc_weight"), sym.Variable("fc_bias"),
            sym.Variable("norm_gamma"))


def looped(remat, variables=None):
    """(every iteration's state stacked, the last state), as one node."""
    w, b, g = variables or shared()

    def body(_, h):
        h = cell(h, w, b, g, "cell")
        return h, h
    stacked, last = sym.contrib.foreach(body, None, sym.Variable("data"),
                                        num_iter=STEPS, remat=remat,
                                        name="loop")
    return sym.Group([stacked, last])


def unrolled():
    w, b, g = shared()
    h, states = sym.Variable("data"), []
    for t in range(STEPS):
        h = cell(h, w, b, g, f"cell{t}")
        states.append(sym.expand_dims(h, axis=0))
    return sym.Group([sym.Concat(*states, dim=0), h])


def values(seed=0):
    rng = np.random.default_rng(seed)
    return {"data": rng.normal(size=(BATCH, WIDTH)).astype(np.float32),
            "fc_weight": (rng.normal(size=(WIDTH, WIDTH)) * 0.5
                          ).astype(np.float32),
            "fc_bias": rng.normal(size=(WIDTH,)).astype(np.float32) * 0.1,
            "norm_gamma": 1 + 0.1 * rng.normal(size=(WIDTH,)
                                               ).astype(np.float32)}


def fwd_bwd(symbol, compute_dtype, vals=None):
    """(outputs, {argument: gradient}) under fixed random cotangents."""
    ex = Executor.simple_bind(symbol, mx.cpu(), grad_req="write",
                              shapes={"data": (BATCH, WIDTH)},
                              compute_dtype=compute_dtype)
    for n, v in (vals or values()).items():
        ex.arg_dict[n]._set_data(jnp.asarray(v))
    outs = ex.forward(is_train=True)
    rng = np.random.default_rng(1)
    cts = [mx.nd.NDArray(jnp.asarray(rng.normal(size=o.shape), o.dtype))
           for o in outs]
    ex.backward(cts)
    return ([np.asarray(o.asnumpy(), np.float32) for o in outs],
            {n: np.asarray(g.asnumpy(), np.float32)
             for n, g in ex.grad_dict.items()})


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_loop_equals_the_unrolled_graph(remat, dtype):
    cd = None if dtype == "float32" else jnp.bfloat16
    tol = 2e-5 if cd is None else 3e-2
    outs, grads = fwd_bwd(looped(remat), cd)
    want_outs, want_grads = fwd_bwd(unrolled(), cd)
    assert outs[0].shape == (STEPS, BATCH, WIDTH)
    for got, want in zip(outs, want_outs):
        np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    assert set(grads) == set(want_grads) == {"data", "fc_weight", "fc_bias",
                                             "norm_gamma"}
    for n in grads:         # a weight's gradient: the sum over iterations
        np.testing.assert_allclose(grads[n], want_grads[n], rtol=tol,
                                   atol=tol * np.abs(want_grads[n]).max())


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_one_fused_module_step_equals_the_unrolled_graph(remat, dtype):
    cd = None if dtype == "float32" else jnp.bfloat16

    def step(states_and_last):
        loss = sym.MakeLoss(sym.sum(sym.square(states_and_last[1])))
        mod = mx.mod.Module(loss, data_names=["data"], label_names=None,
                            context=mx.cpu(), compute_dtype=cd)
        mod.bind(data_shapes=[("data", (BATCH, WIDTH))])
        start = {n: mx.nd.array(v) for n, v in values().items()
                 if n != "data"}
        mod.init_params(arg_params=start)
        mod.init_optimizer(optimizer="sgd", optimizer_params={
            "learning_rate": 0.1, "momentum": 0.9})
        mod.forward(mx.io.DataBatch(data=[mx.nd.array(values()["data"])]),
                    is_train=True)
        mod.update()
        return {n: v.asnumpy() for n, v in mod.get_params()[0].items()}

    got, want = step(looped(remat)), step(unrolled())
    assert sorted(got) == ["fc_bias", "fc_weight", "norm_gamma"]
    for n in got:
        moved = np.abs(want[n] - values()[n]).max()
        assert moved > 0
        np.testing.assert_allclose(got[n], want[n], rtol=0,
                                   atol=(1e-5 if cd is None else 5e-2)
                                   * moved)


def test_every_tool_sees_each_weight_once():
    s = looped(True)
    assert s.list_arguments() == ["data", "fc_weight", "fc_bias",
                                  "norm_gamma"]
    assert s.list_auxiliary_states() == []
    assert s.list_outputs() == ["loop_output0", "loop_output1"]
    args, outs, aux = s.infer_shape(data=(BATCH, WIDTH))
    assert args == [(BATCH, WIDTH), (WIDTH, WIDTH), (WIDTH,), (WIDTH,)]
    assert outs == [(STEPS, BATCH, WIDTH), (BATCH, WIDTH)] and aux == []
    # a weight met outside the loop as well is still one argument
    variables = shared()
    both = sym.Group([looped(True, variables), sym.sum(variables[0])])
    assert both.list_arguments().count("fc_weight") == 1


def test_weights_the_body_creates_are_lifted_and_inferred():
    def body(_, h):
        h = sym.FullyConnected(h, num_hidden=WIDTH, name="dense")
        return h, h
    _, last = sym.contrib.foreach(body, None, sym.Variable("data"),
                                  num_iter=2, name="loop")
    assert last.list_arguments() == ["data", "dense_weight", "dense_bias"]
    args, outs, _ = last.infer_shape(data=(BATCH, WIDTH))
    assert args[1:] == [(WIDTH, WIDTH), (WIDTH,)]
    assert outs == [(BATCH, WIDTH)]


def test_data_is_scanned_and_states_are_carried():
    """A running sum over the leading axis: two data, two states, lists in
    and lists out."""
    def body(xs, states):
        x, y = xs
        total, count = states
        total = total + x * y
        return [total, x], [total, count + 1.0]
    outs, states = sym.contrib.foreach(
        body, [sym.Variable("x"), sym.Variable("y")],
        [sym.Variable("total0"), sym.Variable("count0")], name="scan")
    net = sym.Group(outs + states)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(5, 3)).astype(np.float32)
    y = rng.normal(size=(5, 3)).astype(np.float32)
    got = net.eval(x=mx.nd.array(x), y=mx.nd.array(y),
                   total0=mx.nd.zeros((3,)), count0=mx.nd.zeros((1,)))
    running = np.cumsum(x * y, axis=0)
    np.testing.assert_allclose(got[0].asnumpy(), running, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got[1].asnumpy(), x)
    np.testing.assert_allclose(got[2].asnumpy(), running[-1], rtol=1e-5,
                               atol=1e-6)
    assert got[3].asnumpy().tolist() == [5.0]


def test_json_round_trip():
    s = looped(True)
    text = s.tojson()
    node = [n for n in json.loads(text)["nodes"] if n["op"] == "_foreach"][0]
    assert node["attrs"]["remat"] == "True"
    assert json.loads(node["attrs"]["subgraph"])["nodes"]   # a nested graph
    again = sym.load_json(text)
    assert again.tojson() == text
    assert again.list_arguments() == s.list_arguments()
    assert again.infer_shape(data=(BATCH, WIDTH)) \
        == s.infer_shape(data=(BATCH, WIDTH))
    feed = {n: mx.nd.array(v) for n, v in values().items()}
    for a, b in zip(s.eval(**feed), again.eval(**feed)):
        np.testing.assert_array_equal(a.asnumpy(), b.asnumpy())


def test_a_loop_in_a_loop():
    w = sym.Variable("w")

    def inner(_, h):
        h = sym.broadcast_mul(h, w)
        return h, h

    def outer(_, h):
        _, h = sym.contrib.foreach(inner, None, h, num_iter=2, name="in")
        return h, h
    _, last = sym.contrib.foreach(outer, None, sym.Variable("data"),
                                  num_iter=3, remat=True, name="out")
    assert last.list_arguments() == ["data", "w"]
    got = last.eval(data=mx.nd.ones((2,)), w=mx.nd.array([2.0, 0.5]))[0]
    np.testing.assert_allclose(got.asnumpy(), [64.0, 1.0 / 64.0])


def bind(symbol):
    return Executor.simple_bind(symbol, mx.cpu(),
                                shapes={"data": (BATCH, WIDTH)})


def test_a_body_with_auxiliary_states_is_refused_by_name():
    def body(_, h):
        h = sym.BatchNorm(h, name="bn")
        return h, h
    _, last = sym.contrib.foreach(body, None, sym.Variable("data"),
                                  num_iter=2, name="loop")
    with pytest.raises(MXNetError, match=r"loop.*auxiliary states.*"
                                         r"bn_moving_mean.*bn \(BatchNorm\)"):
        bind(last)


def test_a_body_with_an_rng_op_is_refused_by_name():
    def body(_, h):
        h = sym.Dropout(h, p=0.5, name="drop")
        return h, h
    _, last = sym.contrib.foreach(body, None, sym.Variable("data"),
                                  num_iter=2, name="loop")
    with pytest.raises(MXNetError, match=r"loop.*RNG ops.*drop \(Dropout\)"):
        bind(last)


def test_what_foreach_itself_refuses():
    data = sym.Variable("data")
    with pytest.raises(MXNetError, match="num_iter"):
        sym.contrib.foreach(lambda _, h: (h, h), None, data)
    with pytest.raises(MXNetError, match="2 states for 1"):
        sym.contrib.foreach(lambda _, h: (h, [h, h]), None, data, num_iter=2)
    with pytest.raises(TypeError, match="init_states"):
        sym.contrib.foreach(lambda _, h: (h, h), None, 3.0, num_iter=2)


def test_the_body_is_traced_once_a_program_and_says_so(monkeypatch):
    """``loop.body_trace`` counts interpretations of the body under a
    trace, ``mx.loop.lower`` says what was lowered: once for each program
    that holds the node, however many iterations it runs."""
    monkeypatch.setenv("MXNET_TRACE", "1")
    tracing.reconfigure()
    tracing.reset()
    try:
        s = looped(True)
        ex = bind(s)
        for n, v in values().items():
            ex.arg_dict[n]._set_data(jnp.asarray(v))
        before = profiler.dispatch_counts().get("loop.body_trace", 0)
        ex.forward(is_train=False)[0].asnumpy()
        first = profiler.dispatch_counts()["loop.body_trace"] - before
        # the executor's shape-only trace and the jitted forward: 3
        # iterations, not 3 traces each
        assert 1 <= first <= 2
        ex.forward(is_train=False)[0].asnumpy()     # cached: no trace
        assert profiler.dispatch_counts()["loop.body_trace"] - before == first
        said = [r["args"] for r in tracing.ring_records()
                if r["name"] == "mx.loop.lower"]
        assert said and all(a["iterations"] == STEPS and a["remat"] is True
                            and a["weights_lifted"] == 3 for a in said)
        assert {"loop"} <= {a["node"] for a in said}
        assert said[-1]["carried"] == [[[BATCH, WIDTH], "float32"]]
    finally:
        monkeypatch.delenv("MXNET_TRACE")
        tracing.reconfigure()


def test_remat_is_a_checkpoint_in_the_program():
    def jaxpr(remat):
        s = looped(remat)
        from mxnet_tpu.executor import build_interpreter
        run, names, _ = build_interpreter(s)
        vals = values()

        def f(*args):
            return run(args, (), None, True)[0][1].sum()
        return str(jax.make_jaxpr(jax.grad(f, argnums=1))(
            *[jnp.asarray(vals[n]) for n in names]))
    assert "checkpoint" in jaxpr(True) or "remat" in jaxpr(True)
    assert "checkpoint" not in jaxpr(False) and "remat" not in jaxpr(False)
    assert jaxpr(True).count("scan") >= 1


# -- what a rematerialised body keeps (base.tag_for_remat) ----------------------
def ffn_loop(remat, nested=False):
    """A body with a matmul of each kind: ``up`` (WIDTH -> 2 WIDTH: K < N,
    made again in the backward pass), ``down`` (2 WIDTH -> WIDTH: K >= N,
    kept) and an RMSNorm chain; ``nested`` runs that loop twice inside a
    rematerialised loop of its own."""
    up, down, gain = (sym.Variable("up_weight"), sym.Variable("down_weight"),
                      sym.Variable("norm_gamma"))

    def body(_, h):
        y = sym.FullyConnected(h, weight=up, no_bias=True,
                               num_hidden=2 * WIDTH, name="up")
        y = sym.FullyConnected(sym.tanh(y), weight=down, no_bias=True,
                               num_hidden=WIDTH, name="down")
        h = sym.RMSNorm(y + h, gamma=gain, name="norm")
        return h, h

    def outer(_, h):
        _, h = sym.contrib.foreach(body, None, h, num_iter=STEPS,
                                   remat=remat, name="in")
        return h, h
    _, last = sym.contrib.foreach(outer if nested else body, None,
                                  sym.Variable("data"), remat=remat,
                                  num_iter=2 if nested else STEPS,
                                  name="loop")
    return last


def ffn_values():
    rng = np.random.default_rng(2)
    return {"data": rng.normal(size=(BATCH, WIDTH)).astype(np.float32),
            "up_weight": (rng.normal(size=(2 * WIDTH, WIDTH)) * 0.4
                          ).astype(np.float32),
            "down_weight": (rng.normal(size=(WIDTH, 2 * WIDTH)) * 0.4
                            ).astype(np.float32),
            "norm_gamma": 1 + 0.1 * rng.normal(size=(WIDTH,)
                                               ).astype(np.float32)}


def grad_jaxpr(symbol, vals, compute_dtype=None):
    """The jaxpr of d(sum of output 0)/d(every argument but the data)."""
    from mxnet_tpu.executor import build_interpreter
    run, names, _ = build_interpreter(symbol, compute_dtype)

    def f(*args):
        return run(args, (), None, True)[0][0].astype(jnp.float32).sum()
    return jax.make_jaxpr(jax.grad(f, argnums=tuple(
        i for i, n in enumerate(names) if n != "data")))(
        *[jnp.asarray(vals[n]) for n in names])


def test_a_rematerialised_body_keeps_its_long_matmuls(jaxpr_eqns):
    """In the gradient of a ``remat=True`` loop the checkpoint's backward
    (``remat2`` inside the backward scan) makes ``up`` again and both
    matmuls' two gradients, five ``dot_general`` for the six of a whole
    recomputation, and the RMSNorm chain; ``down``'s output is named in
    the forward scan and kept."""
    found = list(jaxpr_eqns(grad_jaxpr(ffn_loop(True), ffn_values())))

    def at(path, prim):         # directly under these primitives
        return [e for p, e in found if p == path and e.primitive.name == prim]
    named, = at(("scan",), "name")
    assert named.params["name"] == "matmul_out"
    assert named.outvars[0].aval.shape == (BATCH, WIDTH)
    assert sum(e.primitive.name == "name" for _, e in found) == 1
    assert len(at(("scan",), "dot_general")) == 2        # forward: up, down
    assert len(at(("scan", "remat2"), "dot_general")) == 5
    assert at(("scan", "remat2"), "rsqrt") and at(("scan", "remat2"), "tanh")


@pytest.mark.parametrize("nested", [False, True], ids=["flat", "nested"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_remat_changes_no_gradient(dtype, nested):
    """What is kept is what the forward computed: ``remat=True`` gives the
    gradients of ``remat=False``, in float32 bit for bit, for a loop in a
    loop too."""
    cd = None if dtype == "float32" else jnp.bfloat16
    outs, grads = fwd_bwd(ffn_loop(True, nested), cd, ffn_values())
    want_outs, want = fwd_bwd(ffn_loop(False, nested), cd, ffn_values())
    np.testing.assert_array_equal(outs[0], want_outs[0])
    assert sorted(grads) == ["data", "down_weight", "norm_gamma",
                             "up_weight"]
    for n in grads:
        assert np.abs(want[n]).max() > 0
        if cd is None:
            np.testing.assert_array_equal(grads[n], want[n])
        else:
            np.testing.assert_allclose(grads[n], want[n], rtol=3e-2,
                                       atol=3e-2 * np.abs(want[n]).max())


@pytest.mark.parametrize("nested", [False, True], ids=["flat", "nested"])
def test_a_loop_without_remat_names_nothing(jaxpr_eqns, nested):
    """The tags are identities outside a rematerialised body: the program
    of a ``remat=False`` loop holds no ``name`` primitive (the guard for
    every program that has no such loop), a ``remat=True`` one does."""
    def prims(remat):
        return {eqn.primitive.name for _, eqn in jaxpr_eqns(
            grad_jaxpr(ffn_loop(remat, nested), ffn_values()))}
    assert "name" in prims(True) and "remat2" in prims(True)
    assert not {"name", "remat2"} & prims(False)


def test_the_lowering_says_what_it_keeps(loop_lower_instants):
    """``mx.loop.lower`` carries ``kept``: the names the body's checkpoint
    keeps and the bytes an iteration they hold, from the shapes."""
    fwd_bwd(ffn_loop(True, nested=True), jnp.bfloat16, ffn_values())
    said = loop_lower_instants()
    # shape inference runs the op under no node's scope, in float32
    assert said[0]["node"] == "" and said[0]["kept"] == {
        "matmul_out": BATCH * WIDTH * 4}
    last = {a["node"]: a["kept"] for a in said}
    # the inner body holds the matmuls, the outer one only the inner loop
    assert last["in"] == {"matmul_out": BATCH * WIDTH * 2}
    assert last["loop"] == {}
    fwd_bwd(ffn_loop(False), None, ffn_values())
    assert all(a["kept"] == {} and a["remat"] is False
               for a in loop_lower_instants()[len(said):])
