"""Auxiliary-subsystem tests: profiler, monitor, visualization,
test_utils, custom op (model: tests/python/unittest/test_profiler.py,
test_operator.py custom-op section, test_viz.py — SURVEY.md §4/§5)."""
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, test_utils


def _mlp():
    data = mx.sym.Variable('data')
    net = mx.sym.FullyConnected(data, num_hidden=8, name='fc1')
    net = mx.sym.Activation(net, act_type='relu')
    net = mx.sym.FullyConnected(net, num_hidden=4, name='fc2')
    return mx.sym.SoftmaxOutput(net, name='softmax')


def test_profiler_chrome_trace(tmp_path):
    f = str(tmp_path / 'profile.json')
    mx.profiler.profiler_set_config(mode='all', filename=f)
    mx.profiler.profiler_set_state('run')
    a = mx.nd.array(np.ones((16, 16), 'float32'))
    b = mx.nd.dot(a, a)
    (b + 1).asnumpy()
    ex = mx.Executor.simple_bind(_mlp(), shapes={'data': (4, 10),
                                                 'softmax_label': (4,)})
    ex.forward()[0].asnumpy()
    mx.profiler.profiler_set_state('stop')
    mx.profiler.dump_profile()
    with open(f) as fin:
        trace = json.load(fin)
    names = {e['name'] for e in trace['traceEvents']}
    assert 'dot' in names
    assert 'mx.executor.forward.call' in names
    for e in trace['traceEvents']:
        assert e['ph'] == 'X' and 'ts' in e and 'dur' in e


def test_monitor():
    ex = mx.Executor.simple_bind(_mlp(), shapes={'data': (4, 10),
                                                 'softmax_label': (4,)})
    mon = mx.Monitor(interval=1, pattern='fc.*')
    mon.install(ex)
    mon.tic()
    ex.arg_dict['data']._set_data(
        np.random.RandomState(0).randn(4, 10).astype('float32'))
    ex.forward()
    res = mon.toc()
    names = [k for _, k, _ in res]
    assert any('fc1' in n for n in names)
    assert all('softmax' not in n for n in names)


def test_print_summary():
    out = mx.viz.print_summary(_mlp(), shape={'data': (4, 10)})
    assert 'fc1(FullyConnected)' in out
    assert 'Total params:' in out
    # fc1: 10*8+8 = 88; fc2: 8*4+4 = 36
    assert 'Total params: 124' in out


def test_check_numeric_gradient():
    data = mx.sym.Variable('data')
    sym = mx.sym.sum(data * data)  # d/dx = 2x
    x = np.random.RandomState(0).randn(3, 4).astype('float32')
    test_utils.check_numeric_gradient(sym, {'data': x})


def test_check_symbolic_forward_backward():
    data = mx.sym.Variable('data')
    sym = mx.sym.square(data)
    x = np.random.RandomState(1).randn(3, 3).astype('float32')
    test_utils.check_symbolic_forward(sym, [x], [x * x])
    test_utils.check_symbolic_backward(sym, [x], [np.ones_like(x)],
                                       [2 * x])


def test_check_consistency_cpu_contexts():
    """Multi-context consistency using two CPU contexts, the reference's
    GPU-free strategy (test_utils.py:1203; SURVEY.md §4)."""
    sym = _mlp()
    ctx_list = [
        {'ctx': mx.cpu(0), 'data': (4, 10),
         'type_dict': {'data': np.float32}},
        {'ctx': mx.cpu(1), 'data': (4, 10),
         'type_dict': {'data': np.float64}},
    ]
    test_utils.check_consistency(sym, ctx_list)


def test_assert_almost_equal_tolerances():
    a = np.array([1.0, 2.0], np.float32)
    test_utils.assert_almost_equal(a, a + 1e-7)
    with pytest.raises(AssertionError):
        test_utils.assert_almost_equal(a, a + 1e-2)


# -- custom op ------------------------------------------------------------
@mx.operator.register("scale2x")
class Scale2xProp(mx.operator.CustomOpProp):
    def __init__(self, factor='2.0'):
        super().__init__(need_top_grad=True)
        self.factor = float(factor)

    def infer_shape(self, in_shape):
        return in_shape, [in_shape[0]], []

    def create_operator(self, ctx, shapes, dtypes):
        return Scale2x(self.factor)


class Scale2x(mx.operator.CustomOp):
    def __init__(self, factor):
        self.factor = factor

    def forward(self, is_train, req, in_data, out_data, aux):
        self.assign(out_data[0], req[0],
                    in_data[0].asnumpy() * self.factor)

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        self.assign(in_grad[0], req[0],
                    out_grad[0].asnumpy() * self.factor)


def test_custom_op_eager_and_grad():
    x_np = np.random.RandomState(0).randn(3, 4).astype('float32')
    x = mx.nd.array(x_np)
    out = mx.nd.Custom(x, op_type='scale2x', factor='3.0')
    np.testing.assert_allclose(out.asnumpy(), x_np * 3.0, rtol=1e-6)
    x.attach_grad()
    with autograd.record():
        y = mx.nd.Custom(x, op_type='scale2x', factor='3.0')
        loss = mx.nd.sum(y * y)
    loss.backward()
    np.testing.assert_allclose(x.grad.asnumpy(), 2 * 9 * x_np, rtol=1e-5)


def test_custom_op_symbolic_module():
    """Custom op inside a Module training graph (the reference's
    test_operator custom-op-in-symbol case)."""
    data = mx.sym.Variable('data')
    net = mx.sym.FullyConnected(data, num_hidden=8, name='fc1')
    net = mx.sym.Custom(net, op_type='scale2x', name='c0')
    net = mx.sym.FullyConnected(net, num_hidden=2, name='fc2')
    net = mx.sym.SoftmaxOutput(net, name='softmax')
    rng = np.random.RandomState(0)
    x = rng.randn(16, 6).astype('float32')
    y = (x.sum(1) > 0).astype('float32')
    it = mx.io.NDArrayIter(data=x, label=y, batch_size=16)
    mod = mx.mod.Module(net)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer='sgd',
                       optimizer_params={'learning_rate': 0.3})
    batch = next(iter(it))
    first = None
    for i in range(30):
        mod.forward(batch, is_train=True)
        if first is None:
            out = mod.get_outputs()[0].asnumpy()
            first = -np.log(out[np.arange(16), y.astype(int)] +
                            1e-9).mean()
        mod.backward()
        mod.update()
    out = mod.get_outputs()[0].asnumpy()
    last = -np.log(out[np.arange(16), y.astype(int)] + 1e-9).mean()
    assert last < first * 0.5, (first, last)


def test_custom_op_unregistered_raises():
    with pytest.raises(mx.MXNetError):
        mx.nd.Custom(mx.nd.array(np.zeros((2, 2), 'float32')),
                     op_type='no_such_op')


def test_trace_merge_tool(tmp_path):
    """tools/trace_merge.py: host chrome-trace + xplane on one timeline
    (SURVEY §5.1's merge requirement)."""
    import subprocess
    import sys

    logdir = str(tmp_path / "xp")
    host_json = tmp_path / "host.json"
    try:
        mx.profiler.profiler_set_config(filename=str(host_json),
                                        mode="all", xla_logdir=logdir)
        mx.profiler.set_state("run")
        x = mx.nd.array(np.random.RandomState(0).rand(64, 64).astype("f"))
        mx.nd.dot(x, x).asnumpy()
        mx.profiler.set_state("stop")
        mx.profiler.dump_profile()
    finally:
        # restore the singleton — a stale xla_logdir would silently turn
        # every later profiler test into a device capture
        import mxnet_tpu.profiler as _prof
        _prof._profiler._xla_logdir = None
        mx.profiler.profiler_set_config()

    out = tmp_path / "merged.json"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "trace_merge.py"),
         str(host_json), logdir, "-o", str(out)],
        capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-500:]
    m = json.loads(out.read_text())
    evs = m["traceEvents"]
    cats = {e.get("cat") for e in evs}
    assert "device" in cats, "no device rows merged"
    assert any(e.get("ph") == "X" and e.get("cat") != "device"
               for e in evs), "no host rows merged"
    assert m["metadata"]["device_events"] > 0
    # device rows carry process metadata naming the plane
    assert any(e.get("ph") == "M" and "device:" in
               str(e.get("args", {}).get("name", "")) for e in evs)
