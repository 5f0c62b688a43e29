"""What tier-1 can say about the chip path without a chip.

chip_smoke.py is the proof that the main path runs on the TPU; it needs
one.  These pin the rules it relies on, on the CPU:

* ``Context`` resolution — ``tpu(i)`` is virtual device *i* only under the
  explicit CPU pin, an out-of-range id raises, and without the pin and
  without a TPU it raises instead of landing on the host;
* a Module bound to a context runs there, wherever its inputs were put;
* the compile cache is placed from outside or at one fixed path;
* the peak table is exact; the local launcher keeps W>1 workers off chips;
* ``chip_smoke.py`` refuses to run on anything but a TPU.

(The flash kernels' cross-lowering for TPU lives in test_attention.py.)
"""
import importlib.util
import os
import subprocess
import sys
import types

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_argv, **env_changes):
    env = dict(os.environ)
    for k, v in env_changes.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    argv = code_or_argv if isinstance(code_or_argv, list) \
        else [sys.executable, "-c", code_or_argv]
    return subprocess.run(argv, env=env, cwd=ROOT, capture_output=True,
                          text=True, timeout=120)


# -- Context resolution -------------------------------------------------------
def test_tpu_context_is_virtual_device_i_under_the_cpu_pin():
    from mxnet_tpu.context import platform_pinned_to_cpu
    assert platform_pinned_to_cpu()            # tests/conftest.py pinned it
    cpus = jax.local_devices(backend="cpu")
    assert len(cpus) == 8
    for i in (0, 3, 7):
        assert mx.tpu(i).jax_device() is cpus[i]
        assert mx.gpu(i).jax_device() is cpus[i]   # the reference's alias


@pytest.mark.parametrize("device_id", [8, 64, -1])
def test_out_of_range_tpu_context_raises(device_id):
    with pytest.raises(MXNetError, match="out of range"):
        mx.tpu(device_id).jax_device()


def test_tpu_context_without_pin_and_without_tpu_raises():
    """No pin, no chip: ``tpu(0)`` must not quietly become the host.  (On
    a machine that does have a TPU the same call resolves to it.)"""
    out = _run(
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu.base import MXNetError\n"
        "try:\n"
        "    print('RESOLVED', mx.tpu(0).jax_device().platform)\n"
        "except MXNetError as e:\n"
        "    print('RAISED', e)\n",
        JAX_PLATFORMS=None, XLA_FLAGS=None)
    assert out.returncode == 0, out.stderr[-800:]
    last = out.stdout.strip().splitlines()[-1]
    assert last == "RESOLVED tpu" or (
        last.startswith("RAISED") and "needs a TPU" in last
        and "JAX_PLATFORMS=cpu" in last), out.stdout[-400:]


def test_module_runs_on_its_context_wherever_inputs_sit():
    """The default context is cpu(0), so NDArrayIter arrays are committed
    to device 0; a Module bound to another device must move them there
    (on a chip: host-fed batches go TO the TPU, they do not drag the step
    onto the host backend) and keep all training state there."""
    dev = mx.tpu(3).jax_device()
    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(data, num_hidden=4, name="fc"), name="softmax")
    mod = mx.mod.Module(net, context=mx.tpu(3))
    it = mx.io.NDArrayIter(np.random.rand(8, 5).astype("f"),
                           np.zeros(8, "f"), batch_size=4)
    assert it.data[0][1]._data.devices() == {mx.cpu(0).jax_device()}
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    state = [mod._exec.arg_dict[n] for n in mod._update_names()]
    state += [s for n in mod._update_names() for s in mod._opt_states[n]]
    state += mod.get_outputs()
    for arr in state:
        assert arr._data.devices() == {dev}
    # the K-step scan takes the same route
    mod.run_steps(np.random.rand(2, 4, 5).astype("f"),
                  np.zeros((2, 4), "f"), k=2)
    assert mod._exec.arg_dict["fc_weight"]._data.devices() == {dev}


def test_fused_step_flops_is_a_number():
    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(data, num_hidden=4, name="fc"), name="softmax")
    mod = mx.mod.Module(net, context=mx.tpu(0))
    mod.bind(data_shapes=[("data", (4, 5))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params()
    mod.init_optimizer()
    mod.forward(mx.io.DataBatch(data=[mx.nd.ones((4, 5))],
                                label=[mx.nd.zeros((4,))]), is_train=True)
    assert mod.fused_step_flops() > 0


# -- compile cache placement ----------------------------------------------------
_CACHE_OPTIONS = ("jax_compilation_cache_dir",
                  "jax_persistent_cache_min_compile_time_secs",
                  "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def cache_config():
    """place_compile_cache mutates process-wide jax options; hand the
    suite back exactly what it had (tier-1 runs without a cache)."""
    before = {k: getattr(jax.config, k) for k in _CACHE_OPTIONS}
    yield
    for k, v in before.items():
        jax.config.update(k, v)


def test_compile_cache_env_set_touches_nothing(monkeypatch, cache_config):
    from chipbench import common as bc
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert bc.place_compile_cache() == "/some/dir"
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before
    assert not os.path.exists("/some/dir")


def test_compile_cache_unset_goes_to_the_fixed_checkout_path(
        monkeypatch, cache_config):
    """Before ``import jax`` the fixed in-checkout path is placed through
    the environment (a fresh interpreter shows it); in a process that has
    jax already, nothing is placed and the caller is told so."""
    from chipbench import common as bc
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert bc.place_compile_cache() is None
    assert "JAX_COMPILATION_CACHE_DIR" not in os.environ
    assert bc.COMPILE_CACHE_DIR == os.path.join(ROOT, ".jax_compile_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_compile_cache/" in f.read().split()
    out = _run("import sys\n"
               "from chipbench.common import place_compile_cache\n"
               "assert 'jax' not in sys.modules\n"
               "print(place_compile_cache())\n"
               "import jax\n"
               "print(jax.config.jax_compilation_cache_dir)\n",
               JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=None)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [bc.COMPILE_CACHE_DIR] * 2


def test_only_one_place_sets_the_compile_cache():
    """The cache is placed through ``JAX_COMPILATION_CACHE_DIR``
    (chipbench.common.place_compile_cache); no code sets jax's option."""
    hits = []
    for top, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("tests", "chiprun_out", "__pycache__")]
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(top, name), errors="replace") as f:
                    if "jax_compilation_cache_dir" in f.read():
                        hits.append(os.path.relpath(
                            os.path.join(top, name), ROOT))
    assert hits == []


# -- peaks ------------------------------------------------------------------------
def test_peak_table_is_exact_and_unknown_kinds_are_errors():
    from chipbench.common import load_peaks
    assert load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    for kind in ("TPU v5 lite pod", "tpu v5 lite", "v5", "TPU v9", "cpu"):
        with pytest.raises(KeyError, match="no peaks recorded"):
            load_peaks(kind)


# -- one process per chip ----------------------------------------------------------
def _launcher():
    spec = importlib.util.spec_from_file_location(
        "_launch_under_test", os.path.join(ROOT, "tools", "launch.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("workers,env_flag,ambient,want", [
    (4, [], None, "cpu"),                      # pinned, with a note
    (4, [], "cpu", "cpu"),
    (4, ["JAX_PLATFORMS=cpu"], "tpu", "cpu"),  # --env wins over ambient
    (1, [], None, None),                       # one worker may hold the chip
    (1, [], "tpu", "tpu"),
])
def test_local_launcher_keeps_multi_worker_jobs_on_cpu(
        monkeypatch, workers, env_flag, ambient, want):
    if ambient is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", ambient)
    args = types.SimpleNamespace(num_workers=workers, env=env_flag)
    assert _launcher()._local_platform(args) == want


def test_local_launcher_refuses_multi_worker_jobs_on_a_chip(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    args = types.SimpleNamespace(num_workers=2, env=[])
    with pytest.raises(SystemExit, match="same chips"):
        _launcher()._local_platform(args)


# -- the smoke itself ---------------------------------------------------------------
def test_chip_smoke_refuses_to_run_off_chip():
    out = _run([sys.executable, "chip_smoke.py"], JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert "'cpu'" in out.stderr and "needs a TPU" in out.stderr
    assert '"ok"' not in out.stdout         # no result line of any kind
