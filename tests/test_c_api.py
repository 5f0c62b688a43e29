"""Flat C ABI (native/c_api.{h,cc}) + cpp/ consumer tests.

Covers both boundary modes:
 * in-process: libmxtpu_c.so dlopen'd into this interpreter via ctypes
   (Py_IsInitialized short-circuits embedding; handles/ops/symbols work
   against the live runtime) — fast, runs in the default gate.
 * out-of-process (marked slow): real C/C++ programs embedding CPython —
   cpp/capi_smoke.c (pure C, the binding-consumer contract) and
   cpp/predict_golden.cc (C++ Predictor vs Python forward equivalence,
   the reference's tests/python/gpu/test_forward.py pattern over
   c_predict_api consumers).
"""
import ctypes
import os
import subprocess

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(ROOT, "mxnet_tpu", "native")
CPP = os.path.join(ROOT, "cpp")
LIB = os.path.join(NATIVE, "libmxtpu_c.so")

H = ctypes.c_uint64


def _build_lib():
    # Always invoke make: its dependency graph (which includes c_api.h)
    # decides staleness — a hand-rolled mtime check here would miss
    # header edits and silently test a stale ABI.
    r = subprocess.run(["make", "-C", NATIVE, "libmxtpu_c.so"],
                       capture_output=True, text=True)
    if r.returncode != 0 and not os.path.exists(LIB):
        pytest.skip("cannot build libmxtpu_c.so: %s" % r.stderr[-400:])
    return LIB


@pytest.fixture(scope="module")
def lib():
    path = _build_lib()
    lib = ctypes.CDLL(path)
    lib.MXTGetLastError.restype = ctypes.c_char_p
    return lib


def _invoke(lib, op, handles, params=None, max_out=8):
    params = params or {}
    n = len(params)
    keys = (ctypes.c_char_p * n)(*[k.encode() for k in params])
    vals = (ctypes.c_char_p * n)(*[str(v).encode() for v in params.values()])
    ins = (H * len(handles))(*handles)
    outs = (H * max_out)()
    nout = ctypes.c_int(max_out)
    rc = lib.MXTImperativeInvoke(op.encode(), len(handles), ins, n,
                                 keys, vals, ctypes.byref(nout), outs)
    assert rc == 0, lib.MXTGetLastError()
    return [outs[i] for i in range(nout.value)]


def _to_numpy(lib, h):
    ndim = ctypes.c_int()
    assert lib.MXTNDArrayGetNDim(H(h), ctypes.byref(ndim)) == 0
    shape = (ctypes.c_int64 * max(ndim.value, 1))()
    assert lib.MXTNDArrayGetShape(H(h), shape) == 0
    shp = tuple(shape[i] for i in range(ndim.value))
    nbytes = ctypes.c_size_t()
    assert lib.MXTNDArrayGetNBytes(H(h), ctypes.byref(nbytes)) == 0
    buf = np.zeros(shp, dtype=np.float32)
    assert buf.nbytes == nbytes.value
    rc = lib.MXTNDArraySyncCopyToCPU(
        H(h), buf.ctypes.data_as(ctypes.c_void_p), nbytes)
    assert rc == 0, lib.MXTGetLastError()
    return buf


def _from_numpy(lib, arr):
    arr = np.ascontiguousarray(arr, dtype=np.float32)
    shape = (ctypes.c_int64 * arr.ndim)(*arr.shape)
    h = H()
    rc = lib.MXTNDArrayFromData(arr.ctypes.data_as(ctypes.c_void_p),
                                shape, arr.ndim, b"float32", 1, 0,
                                ctypes.byref(h))
    assert rc == 0, lib.MXTGetLastError()
    return h.value


def test_ndarray_roundtrip_and_ops(lib):
    x = np.array([[1, -2], [3, -4]], dtype=np.float32)
    h = _from_numpy(lib, x)
    (r,) = _invoke(lib, "relu", [h])
    np.testing.assert_array_equal(_to_numpy(lib, r), np.maximum(x, 0))
    (p,) = _invoke(lib, "_plus_scalar", [h], {"scalar": 10})
    np.testing.assert_array_equal(_to_numpy(lib, p), x + 10)
    # two-input op
    (s,) = _invoke(lib, "elemwise_add", [h, h])
    np.testing.assert_array_equal(_to_numpy(lib, s), x + x)
    # dtype string protocol
    need = ctypes.c_size_t()
    assert lib.MXTNDArrayGetDType(H(h), None, 0, ctypes.byref(need)) == 0
    buf = ctypes.create_string_buffer(need.value)
    assert lib.MXTNDArrayGetDType(H(h), buf, need.value,
                                  ctypes.byref(need)) == 0
    assert buf.value == b"float32"
    for hh in (h, r, p, s):
        assert lib.MXTNDArrayFree(H(hh)) == 0


def test_error_handling(lib):
    x = _from_numpy(lib, np.zeros((2, 2), np.float32))
    outs = (H * 1)()
    nout = ctypes.c_int(1)
    rc = lib.MXTImperativeInvoke(b"no_such_op", 1, (H * 1)(x), 0, None,
                                 None, ctypes.byref(nout), outs)
    assert rc == -1
    assert b"no_such_op" in lib.MXTGetLastError()
    # freed handle use fails cleanly
    assert lib.MXTNDArrayFree(H(x)) == 0
    ndim = ctypes.c_int()
    assert lib.MXTNDArrayGetNDim(H(x), ctypes.byref(ndim)) == -1
    assert b"handle" in lib.MXTGetLastError()


def test_save_load(lib, tmp_path):
    x = _from_numpy(lib, np.arange(6, dtype=np.float32).reshape(2, 3))
    path = str(tmp_path / "arrs.params").encode()
    names = (ctypes.c_char_p * 1)(b"w")
    assert lib.MXTNDArraySave(path, 1, (H * 1)(x), names) == 0
    num = ctypes.c_int()
    handles = (H * 4)()
    need = ctypes.c_size_t()
    nbuf = ctypes.create_string_buffer(256)
    rc = lib.MXTNDArrayLoad(path, ctypes.byref(num), handles, 4, nbuf,
                            256, ctypes.byref(need))
    assert rc == 0, lib.MXTGetLastError()
    assert num.value == 1 and nbuf.value == b"w"
    np.testing.assert_array_equal(
        _to_numpy(lib, handles[0]),
        np.arange(6, dtype=np.float32).reshape(2, 3))


def test_symbol_roundtrip(lib):
    import mxnet_tpu as mx
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4,
                                name="fc")
    js = net.tojson().encode()
    h = H()
    assert lib.MXTSymbolCreateFromJSON(js, ctypes.byref(h)) == 0
    need = ctypes.c_size_t()
    assert lib.MXTSymbolListArguments(h, None, 0, ctypes.byref(need)) == 0
    buf = ctypes.create_string_buffer(need.value)
    assert lib.MXTSymbolListArguments(h, buf, need.value,
                                      ctypes.byref(need)) == 0
    args = buf.value.decode().split("\n")
    assert args == ["data", "fc_weight", "fc_bias"]
    # JSON survives the boundary round trip
    assert lib.MXTSymbolSaveToJSON(h, None, 0, ctypes.byref(need)) == 0
    jbuf = ctypes.create_string_buffer(need.value)
    assert lib.MXTSymbolSaveToJSON(h, jbuf, need.value,
                                   ctypes.byref(need)) == 0
    import json
    assert json.loads(jbuf.value.decode())["nodes"]
    assert lib.MXTSymbolFree(h) == 0


def test_list_all_op_names(lib):
    need = ctypes.c_size_t()
    assert lib.MXTListAllOpNames(None, 0, ctypes.byref(need)) == 0
    buf = ctypes.create_string_buffer(need.value)
    assert lib.MXTListAllOpNames(buf, need.value, ctypes.byref(need)) == 0
    ops = buf.value.decode().split("\n")
    assert len(ops) > 300 and "relu" in ops


def _build_cpp(target):
    r = subprocess.run(["make", "-C", CPP, target], capture_output=True,
                       text=True)
    if r.returncode != 0:
        pytest.skip("cannot build cpp/%s: %s" % (target, r.stderr[-400:]))
    return os.path.join(CPP, target)


@pytest.mark.slow
def test_pure_c_embedding_smoke():
    """A plain C program (no Python process) drives the runtime."""
    exe = _build_cpp("capi_smoke")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([exe], capture_output=True, text=True, env=env,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-800:]
    assert "SMOKE OK" in r.stdout


@pytest.mark.slow
def test_cpp_predictor_matches_python_forward(tmp_path):
    """C++ Predictor output == Python Module forward on the same
    checkpoint (reference test_forward.py pattern)."""
    import mxnet_tpu as mx
    from mxnet_tpu import model as mx_model

    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, num_filter=4, kernel=(3, 3),
                             pad=(1, 1), name="conv")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=5, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")

    mod = mx.mod.Module(net, label_names=("softmax_label",))
    mod.bind(data_shapes=[("data", (2, 3, 8, 8))],
             label_shapes=[("softmax_label", (2,))])
    mx.random.seed(99)
    mod.init_params(mx.initializer.Xavier())
    arg, aux = mod.get_params()
    arg = {k: v for k, v in arg.items()}

    prefix = str(tmp_path / "tiny")
    mx_model.save_checkpoint(prefix, 0, net, arg, aux)

    rs = np.random.RandomState(3)
    x = rs.uniform(-1, 1, (2, 3, 8, 8)).astype(np.float32)
    from mxnet_tpu.io import DataBatch
    mod_inf = mx.mod.Module(net, label_names=("softmax_label",))
    mod_inf.bind(data_shapes=[("data", (2, 3, 8, 8))],
                 label_shapes=[("softmax_label", (2,))],
                 for_training=False)
    mod_inf.set_params(arg, aux)
    mod_inf.forward(DataBatch(data=[mx.nd.array(x)],
                              label=[mx.nd.zeros((2,))]), is_train=False)
    want = mod_inf.get_outputs()[0].asnumpy()

    exe = _build_cpp("predict_golden")
    inp = tmp_path / "input.bin"
    out = tmp_path / "output.bin"
    x.tofile(str(inp))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [exe, prefix + "-symbol.json", prefix + "-0000.params", str(inp),
         "2", "3", "8", "8", str(out)],
        capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-800:]
    got = np.fromfile(str(out), dtype=np.float32).reshape(want.shape)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_threaded_calls(lib):
    """The header promises 'calls may come from any thread' — hammer the
    ABI from 8 threads concurrently (create/invoke/copy/free) and check
    every result."""
    import threading

    errors = []

    def worker(seed):
        try:
            rs = np.random.RandomState(seed)
            for _ in range(10):
                a = rs.randn(4, 4).astype(np.float32)
                h = _from_numpy(lib, a)
                (r,) = _invoke(lib, "relu", [h])
                got = _to_numpy(lib, r)
                np.testing.assert_array_equal(got, np.maximum(a, 0))
                (s,) = _invoke(lib, "elemwise_add", [h, r])
                np.testing.assert_allclose(_to_numpy(lib, s),
                                           a + np.maximum(a, 0),
                                           rtol=1e-6)
                for hh in (h, r, s):
                    assert lib.MXTNDArrayFree(H(hh)) == 0
        except Exception as e:  # noqa: BLE001
            errors.append((seed, repr(e)))

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    # a deadlocked worker must FAIL the test, not time out silently
    assert not any(t.is_alive() for t in threads), "worker hung"
    assert not errors, errors[:3]


def test_predictor_reshape(lib, tmp_path):
    """MXTPredReshape: batch switch keeps weights (reference:
    MXPredReshape, c_predict_api.h)."""
    import mxnet_tpu as mx
    from mxnet_tpu import model as mx_model
    from mxnet_tpu.io import DataBatch

    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        mx.sym.Variable("data"), num_hidden=5, name="fc"), name="softmax")
    mod = mx.mod.Module(net, label_names=("softmax_label",))
    mod.bind(data_shapes=[("data", (2, 6))],
             label_shapes=[("softmax_label", (2,))])
    mx.random.seed(12)
    mod.init_params(mx.initializer.Xavier())
    arg, aux = mod.get_params()
    prefix = str(tmp_path / "p")
    mx_model.save_checkpoint(prefix, 0, net, arg, aux)

    with open(prefix + "-symbol.json", "rb") as f:
        js = f.read()
    names = (ctypes.c_char_p * 1)(b"data")
    indptr = (ctypes.c_int64 * 2)(0, 2)
    shape2 = (ctypes.c_int64 * 2)(2, 6)
    pred = H()
    rc = lib.MXTPredCreate(js, (prefix + "-0000.params").encode(), 1, 0,
                           1, names, indptr, shape2, ctypes.byref(pred))
    assert rc == 0, lib.MXTGetLastError()

    # reshape to batch 4 and forward
    shape4 = (ctypes.c_int64 * 2)(4, 6)
    assert lib.MXTPredReshape(pred, 1, names, indptr, shape4) == 0, \
        lib.MXTGetLastError()
    x = np.random.RandomState(3).rand(4, 6).astype(np.float32)
    assert lib.MXTPredSetInput(pred, b"data",
                               x.ctypes.data_as(
                                   ctypes.POINTER(ctypes.c_float)),
                               x.size) == 0, lib.MXTGetLastError()
    assert lib.MXTPredForward(pred) == 0, lib.MXTGetLastError()
    out = np.zeros((4, 5), np.float32)
    assert lib.MXTPredGetOutput(pred, 0,
                                out.ctypes.data_as(
                                    ctypes.POINTER(ctypes.c_float)),
                                out.size) == 0, lib.MXTGetLastError()

    mod4 = mx.mod.Module(net, label_names=("softmax_label",))
    mod4.bind(data_shapes=[("data", (4, 6))],
              label_shapes=[("softmax_label", (4,))], for_training=False)
    mod4.set_params(arg, aux)
    mod4.forward(DataBatch([mx.nd.array(x)], [mx.nd.zeros((4,))]),
                 is_train=False)
    want = mod4.get_outputs()[0].asnumpy()
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
    # wrong names must fail clearly
    bad = (ctypes.c_char_p * 1)(b"nope")
    assert lib.MXTPredReshape(pred, 1, bad, indptr, shape4) == -1
    assert b"must match" in lib.MXTGetLastError()
    assert lib.MXTPredFree(pred) == 0


def test_autograd_through_c_abi(lib):
    """Record → backward → read gradient, all through the flat C ABI
    (reference: MXAutogradSetIsRecording/BackwardEx, c_api_ndarray.cc)."""
    x = np.array([[1.0, -2.0], [3.0, -0.5]], np.float32)
    hx = _from_numpy(lib, x)
    assert lib.MXTNDArrayAttachGrad(H(hx), b"write") == 0, \
        lib.MXTGetLastError()

    prev = ctypes.c_int(-1)
    assert lib.MXTAutogradSetIsRecording(1, ctypes.byref(prev)) == 0
    assert prev.value == 0
    rec = ctypes.c_int(-1)
    assert lib.MXTAutogradIsRecording(ctypes.byref(rec)) == 0
    assert rec.value == 1
    try:
        (r,) = _invoke(lib, "relu", [hx])
        (s,) = _invoke(lib, "sum", [r])
    finally:
        assert lib.MXTAutogradSetIsRecording(0, ctypes.byref(prev)) == 0

    assert lib.MXTAutogradBackward(1, (H * 1)(s), 0, 1) == 0, \
        lib.MXTGetLastError()
    g = H()
    assert lib.MXTNDArrayGetGrad(H(hx), ctypes.byref(g)) == 0, \
        lib.MXTGetLastError()
    grad = _to_numpy(lib, g.value)
    np.testing.assert_array_equal(grad, (x > 0).astype(np.float32))
    for hh in (hx, r, s, g.value):
        assert lib.MXTNDArrayFree(H(hh)) == 0


def test_autograd_c_abi_guard_rails(lib):
    x = _from_numpy(lib, np.ones((2, 2), np.float32))
    # invalid grad_req must error, not silently become write/null
    assert lib.MXTNDArrayAttachGrad(H(x), b"nope") == -1
    assert b"grad_req" in lib.MXTGetLastError()
    # clear-tape entry exists and succeeds even with nothing recorded
    assert lib.MXTAutogradClearTape() == 0
    assert lib.MXTNDArrayFree(H(x)) == 0


def test_sync_copy_from_cpu(lib):
    """In-place host->device update of an existing handle."""
    h = _from_numpy(lib, np.zeros((2, 3), np.float32))
    newv = np.arange(6, dtype=np.float32).reshape(2, 3)
    rc = lib.MXTNDArraySyncCopyFromCPU(
        H(h), newv.ctypes.data_as(ctypes.c_void_p), newv.nbytes)
    assert rc == 0, lib.MXTGetLastError()
    np.testing.assert_array_equal(_to_numpy(lib, h), newv)
    # size mismatch errors cleanly
    small = np.zeros(2, np.float32)
    assert lib.MXTNDArraySyncCopyFromCPU(
        H(h), small.ctypes.data_as(ctypes.c_void_p), small.nbytes) == -1
    assert b"buffer size" in lib.MXTGetLastError()
    assert lib.MXTNDArrayFree(H(h)) == 0


# ------------------------------------------------- training surface (r4)

def _lcg_dataset(n=256, d=8):
    """EXACT replica of cpp/train_smoke.c's LCG dataset so the C and
    Python fits see identical bytes."""
    state = 12345
    mask = (1 << 64) - 1

    def uniform():
        nonlocal state
        state = (state * 6364136223846793005 + 1442695040888963407) & mask
        return np.float32((state >> 33) / 2147483648.0)

    x = np.zeros((n, d), np.float32)
    y = np.zeros(n, np.float32)
    for i in range(n):
        cls = i % 2
        y[i] = cls
        for j in range(d):
            noise = uniform() - np.float32(0.5)
            scale = np.float32(1.0) if j % 3 == 0 else np.float32(0.3)
            x[i, j] = noise + (np.float32(0.9) if cls
                               else np.float32(-0.9)) * scale
    return x, y


def _python_fit_nll():
    """The same fit cpp/train_smoke.c runs, natively in Python."""
    import mxnet_tpu as mx
    x, y = _lcg_dataset()
    net = mx.sym.FullyConnected(mx.sym.Variable('data'), num_hidden=16,
                                name='fc1')
    net = mx.sym.Activation(net, act_type='relu', name='relu1')
    net = mx.sym.FullyConnected(net, num_hidden=2, name='fc2')
    net = mx.sym.SoftmaxOutput(net, name='softmax')
    mx.random.seed(7)
    mod = mx.mod.Module(net, context=mx.cpu())
    it = mx.io.NDArrayIter(x, y, batch_size=64, shuffle=False,
                           last_batch_handle='discard')
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.initializer.Xavier(rnd_type='gaussian',
                                          magnitude=2.0))
    mod.init_optimizer(optimizer='sgd',
                       optimizer_params={'learning_rate': 0.2,
                                         'momentum': 0.9})
    nll = 0.0
    cnt = 0
    for _ in range(8):
        it.reset()
        nll, cnt = 0.0, 0
        for b in it:
            mod.forward(b, is_train=True)
            prob = mod.get_outputs()[0].asnumpy()
            lab = b.label[0].asnumpy().astype(int)
            p = np.maximum(prob[np.arange(len(lab)), lab], 1e-8)
            nll += float(-np.log(p).sum())
            cnt += len(lab)
            mod.backward()
            mod.update()
    return nll / cnt


@pytest.mark.slow
def test_c_train_smoke_cross_asserted():
    """A pure-C program TRAINS end-to-end (Module + DataIter + KVStore +
    RecordIO rows) out-of-process, and its final loss matches the same
    fit run natively in Python (VERDICT r3 item 4)."""
    exe = _build_cpp("train_smoke")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([exe], capture_output=True, text=True, env=env,
                       timeout=900)
    assert r.returncode == 0, (r.stdout[-500:], r.stderr[-1500:])
    line = [l for l in r.stdout.splitlines()
            if l.startswith("TRAIN OK")][-1]
    c_nll = float(line.split("nll=")[1])
    assert c_nll < 0.25
    py_nll = _python_fit_nll()
    assert py_nll < 0.25
    # identical data/seed/arch: the two fits follow the same trajectory
    assert abs(c_nll - py_nll) < 5e-3, (c_nll, py_nll)


def test_dataiter_rows_in_process(lib):
    x = np.arange(24, dtype=np.float32).reshape(6, 4)
    y = np.arange(6, dtype=np.float32)
    xh, yh = _from_numpy(lib, x), _from_numpy(lib, y)
    it = H()
    rc = lib.MXTDataIterCreateFromArrays(H(xh), H(yh), 2, 0, b"pad",
                                         ctypes.byref(it))
    assert rc == 0, lib.MXTGetLastError()
    seen = []
    for _ in range(2):  # two epochs: BeforeFirst resets correctly
        assert lib.MXTDataIterBeforeFirst(it) == 0
        seen.append([])
        has = ctypes.c_int()
        assert lib.MXTDataIterNext(it, ctypes.byref(has)) == 0
        while has.value:
            bh = H()
            assert lib.MXTDataIterGetData(it, ctypes.byref(bh)) == 0
            batch = _to_numpy(lib, bh.value)
            assert batch.shape == (2, 4)
            lh = H()
            assert lib.MXTDataIterGetLabel(it, ctypes.byref(lh)) == 0
            seen[-1].extend(_to_numpy(lib, lh.value).tolist())
            pad = ctypes.c_int()
            assert lib.MXTDataIterGetPadNum(it, ctypes.byref(pad)) == 0
            assert pad.value == 0
            lib.MXTNDArrayFree(bh)
            lib.MXTNDArrayFree(lh)
            assert lib.MXTDataIterNext(it, ctypes.byref(has)) == 0
    assert seen[0] == seen[1] == [0.0, 1.0, 2.0, 3.0, 4.0, 5.0]
    assert lib.MXTDataIterFree(it) == 0
    # the registry of creatable iterators is reported
    need = ctypes.c_size_t()
    assert lib.MXTListDataIters(None, 0, ctypes.byref(need)) == 0
    buf = ctypes.create_string_buffer(need.value)
    assert lib.MXTListDataIters(buf, need, ctypes.byref(need)) == 0
    names = buf.value.decode().split("\n")
    assert "NDArrayIter" in names and "CSVIter" in names


def test_kvstore_rows_in_process(lib):
    kv = H()
    assert lib.MXTKVStoreCreate(b"local", ctypes.byref(kv)) == 0
    w = _from_numpy(lib, np.array([1., 2., 3.], np.float32))
    g = _from_numpy(lib, np.array([.1, .1, .1], np.float32))
    out = _from_numpy(lib, np.zeros(3, np.float32))
    key = (ctypes.c_char_p * 1)(b"p")
    assert lib.MXTKVStoreInit(kv, 1, key, (H * 1)(w)) == 0
    lrk = (ctypes.c_char_p * 1)(b"learning_rate")
    lrv = (ctypes.c_char_p * 1)(b"0.5")
    assert lib.MXTKVStoreSetOptimizer(kv, b"sgd", 1, lrk, lrv) == 0
    assert lib.MXTKVStorePush(kv, 1, key, (H * 1)(g), 0) == 0
    assert lib.MXTKVStorePull(kv, 1, key, (H * 1)(out), 0) == 0
    np.testing.assert_allclose(_to_numpy(lib, out),
                               [0.95, 1.95, 2.95], rtol=1e-6)
    for h in (w, g, out):
        lib.MXTNDArrayFree(H(h))
    assert lib.MXTKVStoreFree(kv) == 0


def test_recordio_rows_in_process(lib, tmp_path):
    path = str(tmp_path / "t.rec").encode()
    wr = H()
    assert lib.MXTRecordIOWriterCreate(path, ctypes.byref(wr)) == 0
    recs = [b"one", b"", b"twotwo", b"three33"]  # incl. legal empty rec
    for rec in recs:
        assert lib.MXTRecordIOWriterWriteRecord(wr, rec, len(rec)) == 0
    assert lib.MXTRecordIOWriterFree(wr) == 0
    rd = H()
    assert lib.MXTRecordIOReaderCreate(path, ctypes.byref(rd)) == 0
    got = []
    while True:
        need = ctypes.c_size_t()
        eof = ctypes.c_int()
        assert lib.MXTRecordIOReaderReadRecord(
            rd, None, 0, ctypes.byref(need), ctypes.byref(eof)) == 0
        if eof.value:
            break
        if need.value == 0:  # legal empty record, delivered in one call
            got.append(b"")
            continue
        buf = ctypes.create_string_buffer(need.value)
        assert lib.MXTRecordIOReaderReadRecord(
            rd, buf, need, ctypes.byref(need), ctypes.byref(eof)) == 0
        got.append(buf.raw[:need.value])
    assert got == recs
    assert lib.MXTRecordIOReaderFree(rd) == 0


@pytest.mark.slow
def test_cpp_train_golden():
    """C++ header-API training (Module/DataIter RAII wrappers) +
    checkpoint->Predictor deployment round-trip, out-of-process."""
    exe = _build_cpp("train_golden")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([exe], capture_output=True, text=True, env=env,
                       timeout=900)
    assert r.returncode == 0, (r.stdout[-500:], r.stderr[-1500:])
    line = [l for l in r.stdout.splitlines()
            if l.startswith("TRAIN GOLDEN OK")][-1]
    assert float(line.split("nll=")[1]) < 0.25
