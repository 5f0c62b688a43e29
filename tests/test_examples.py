"""The examples tree runs end-to-end (VERDICT r1 item 7: each example
drives the public API on the CPU mesh).  These are the scripts users start
from, so they run in the default gate; the six that took 20 s or more
there are marked ``slow`` one by one and run in CI (-m "")."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_BOOT = (
    "import sys, runpy\n"
    "sys.path.insert(0, %r)\n" % ROOT +
    "from cpu_pin import pin_cpu\n"
    "pin_cpu(n_devices=None)\n"
    "script = sys.argv[1]\n"
    "sys.argv = sys.argv[1:]\n"
    "runpy.run_path(script, run_name='__main__')\n"
)


def _run(script, *args, timeout=420, env_extra=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.update(env_extra or {})
    out = subprocess.run(
        [sys.executable, "-c", _BOOT, os.path.join(ROOT, script)]
        + list(args),
        env=env, capture_output=True, text=True, timeout=timeout, cwd=ROOT)
    assert out.returncode == 0, (out.stdout[-1500:], out.stderr[-1500:])
    return out.stderr + out.stdout


def test_train_mnist_example():
    log = _run("examples/image_classification/train_mnist.py",
               "--synthetic", "--num-epochs", "2", "--batch-size", "64")
    assert "Validation-accuracy" in log


@pytest.mark.slow
def test_train_imagenet_example_benchmark():
    log = _run("examples/image_classification/train_imagenet.py",
               "--benchmark", "1", "--benchmark-iters", "2",
               "--batch-size", "4", "--num-layers", "18",
               "--num-classes", "10", "--num-epochs", "1",
               "--dtype", "bfloat16")
    assert "Train-accuracy" in log


def test_train_ptb_example():
    log = _run("examples/rnn/train_ptb.py", "--synthetic",
               "--num-epochs", "1", "--batch-size", "16",
               "--num-hidden", "32", "--num-embed", "16",
               "--buckets", "10,25")
    assert "Train-perplexity" in log


@pytest.mark.slow
def test_train_ssd_example():
    log = _run("examples/ssd/train_ssd.py", "--synthetic",
               "--num-epochs", "1", "--batch-size", "4")
    assert "loc_loss" in log


@pytest.mark.slow
def test_train_cifar10_example():
    log = _run("examples/image_classification/train_cifar10.py",
               "--synthetic", "--num-epochs", "2", "--batch-size", "32",
               "--num-examples", "512")
    assert "Validation-accuracy" in log


def test_fine_tune_example():
    log = _run("examples/image_classification/fine_tune.py",
               "--synthetic", "--num-epochs", "2", "--batch-size", "32",
               "--num-examples", "256")
    assert "fine-tune done" in log
    assert "Validation-accuracy" in log


def test_parse_log_tool():
    sample = (
        "INFO:root:Epoch[0] Batch [50]\tSpeed: 1234.5 samples/sec\t"
        "accuracy=0.5\n"
        "INFO:root:Epoch[0] Train-accuracy=0.61\n"
        "INFO:root:Epoch[0] Time cost=12.3\n"
        "INFO:root:Epoch[0] Validation-accuracy=0.55\n"
        "INFO:root:Epoch[1] Train-accuracy=0.75\n"
        "INFO:root:Epoch[1] Validation-accuracy=0.70\n")
    import tempfile
    with tempfile.NamedTemporaryFile('w', suffix='.log',
                                     delete=False) as f:
        f.write(sample)
        path = f.name
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools/parse_log.py"), path,
         "--format", "csv"], capture_output=True, text=True)
    assert out.returncode == 0
    assert "0,0.61" in out.stdout and "1,0.75" in out.stdout
    assert "1234.5" in out.stdout


def test_model_parallel_example():
    log = _run("examples/model_parallel/train_model_parallel.py",
               "--synthetic", "--tp", "2", "--num-epochs", "2",
               "--num-examples", "128", "--batch-size", "16",
               env_extra={"XLA_FLAGS":
                          "--xla_force_host_platform_device_count=8"})
    assert "model-parallel training done" in log
    # decoder weight (vocab=64, hidden) sharded over tp=2 -> rows halved
    assert "(32," in log


@pytest.mark.slow
def test_generate_lm_example():
    log = _run("examples/rnn/generate_lm.py", "--synthetic",
               "--num-epochs", "12", "--num-layers", "1",
               "--d-model", "32", "--seq-len", "12", "--vocab", "30")
    assert "generation done" in log
    assert "generated (greedy" in log


def test_zero1_example():
    out = _run("examples/zero1_train.py", "--epochs", "1",
               env_extra={"XLA_FLAGS":
                          "--xla_force_host_platform_device_count=8"})
    assert "per-chip shard" in out and "done" in out


@pytest.mark.slow
def test_dcgan_example():
    """Two-module adversarial loop: D input-grads drive G backward
    (reference example/gan/dcgan.py pattern)."""
    log = _run("examples/gan/dcgan_digits.py", "--epochs", "1",
               "--batch", "32", "--zdim", "16", timeout=600)
    assert "final d_loss" in log
    # both losses parsed and finite (a collapsed-but-completed run still
    # proves the two-module loop mechanics this smoke exists for)
    import math
    import re
    m = re.search(r"final d_loss (-?[\d.]+) g_loss (-?[\d.]+)", log)
    assert m, log[-500:]
    assert math.isfinite(float(m.group(1))), m.group(0)
    assert math.isfinite(float(m.group(2))), m.group(0)


def test_sparse_end2end_example():
    """CSR->row_sparse end-to-end with the densify telltale armed
    (reference benchmark/python/sparse/sparse_end2end.py pattern)."""
    log = _run("examples/sparse/linear_classification.py", "--epochs",
               "4", "--num-features", "2000", timeout=600)
    import re
    m = re.search(r"final acc ([\d.]+)", log)
    assert m, log[-500:]
    assert float(m.group(1)) > 0.75, log[-500:]


def test_matrix_fact_recommender_example():
    """FeedForward-driven MF (reference example/recommenders/
    matrix_fact.py): two embedding towers, dot score, custom np metric —
    must reach near the planted noise floor."""
    log = _run("examples/recommender/matrix_fact.py", "--epochs", "30",
               timeout=600)
    import re
    m = re.search(r"final rmse ([\d.]+)", log)
    assert m, log[-500:]
    assert float(m.group(1)) < 0.2, log[-300:]  # noise floor is 0.1


def test_two_tower_recommender_example():
    """Row-sparse two-tower retrieval (reference example/recommenders +
    the row_sparse embedding path): sparse_grad towers on a planted
    clickstream, then top-k served through a ServingReplica."""
    log = _run("examples/recommender/two_tower.py", "--epochs", "10",
               "--serve", timeout=600)
    import re
    m = re.search(r"final hit@10 ([\d.]+)", log)
    assert m, log[-500:]
    assert float(m.group(1)) > 0.8, log[-300:]
    assert "serving done" in log, log[-500:]


def test_neural_style_example():
    """Optimization over the INPUT (reference example/neural-style/
    nstyle.py): grads w.r.t. the image, Gram losses, manual Adam."""
    log = _run("examples/neural_style/nstyle.py", "--iters", "60",
               timeout=600)
    import re
    m = re.search(r"loss ([\d.]+) -> ([\d.]+)", log)
    assert m, log[-500:]
    assert float(m.group(2)) < 0.5 * float(m.group(1)), m.group(0)


def test_bi_lstm_sort_example():
    """Bidirectional LSTM seq->seq sort (reference example/bi-lstm-sort):
    every output position needs BOTH directions' context."""
    log = _run("examples/rnn/bi_lstm_sort.py", "--epochs", "10",
               timeout=900)
    import re
    m = re.search(r"final sort acc ([\d.]+)", log)
    assert m, log[-500:]
    assert float(m.group(1)) > 0.9, log[-300:]


def test_fgsm_adversary_example():
    """FGSM (reference example/adversary): input-gradient attack must
    collapse accuracy at eps=0.15."""
    log = _run("examples/adversary/fgsm.py", "--epochs", "6",
               timeout=900)
    import re
    m = re.search(r"clean (\d\.\d+) adversarial (\d\.\d+)", log)
    assert m, log[-500:]
    clean, adv = float(m.group(1)), float(m.group(2))
    assert clean > 0.75, clean
    assert adv < clean - 0.25, (clean, adv)


def test_svm_digits_example():
    """SVMOutput head training (reference example/svm_mnist)."""
    log = _run("examples/svm/svm_digits.py", "--epochs", "12",
               timeout=900)
    import re
    m = re.search(r"final svm acc ([\d.]+)", log)
    assert m, log[-500:]
    assert float(m.group(1)) > 0.85, log[-300:]


def test_numpy_ops_custom_softmax_example():
    """Pure-numpy CustomOp loss head inside symbolic training
    (reference example/numpy-ops/custom_softmax.py)."""
    log = _run("examples/numpy_ops/custom_softmax.py", "--epochs", "10",
               timeout=900)
    import re
    m = re.search(r"final custom-op acc ([\d.]+)", log)
    assert m, log[-500:]
    assert float(m.group(1)) > 0.85, log[-300:]


@pytest.mark.slow
def test_stochastic_depth_example():
    """Custom gluon HybridBlock with train-time random depth
    (reference example/gluon stochastic-depth pattern)."""
    log = _run("examples/gluon/stochastic_depth.py", "--epochs", "6",
               timeout=900)
    import re
    m = re.search(r"final stochastic-depth acc ([\d.]+)", log)
    assert m, log[-500:]
    assert float(m.group(1)) > 0.85, log[-300:]


def test_stacked_autoencoder_example():
    """Layerwise pretrain -> finetune workflow (reference
    example/autoencoder): finetuning must IMPROVE on pretrain-only."""
    log = _run("examples/autoencoder/stacked_ae.py", timeout=900)
    import re
    m = re.search(r"final ae mse ([\d.]+) \(pretrain-only ([\d.]+)\)", log)
    assert m, log[-500:]
    ft, pre = float(m.group(1)), float(m.group(2))
    assert ft < pre, (ft, pre)
    assert ft < 0.05, ft


def test_dqn_chain_example():
    """DQN agent loop (reference example/reinforcement-learning/dqn):
    must beat the distractor-policy ceiling (3.2/episode) decisively."""
    log = _run("examples/reinforcement_learning/dqn_chain.py",
               "--episodes", "250", timeout=900)
    import re
    m = re.search(r"final dqn mean return ([\d.]+)", log)
    assert m, log[-500:]
    assert float(m.group(1)) > 4.0, log[-300:]


def test_seq2seq_reverse_example():
    """Encoder-decoder seq2seq: decoder begin_state = encoder final
    states, teacher forcing, greedy decode (reverse task — unsolvable
    without real state transport)."""
    log = _run("examples/rnn/seq2seq_reverse.py", "--epochs", "15",
               timeout=900)
    import re
    m = re.search(r"final seq2seq token acc ([\d.]+) seq acc ([\d.]+)",
                  log)
    assert m, log[-500:]
    assert float(m.group(1)) > 0.9, log[-300:]


def test_profiler_example(tmp_path):
    """Profiler workflow (reference example/profiler): chrome-trace JSON
    with the bracketed train_step scopes present."""
    log = _run("examples/profiler/profile_training.py", "--out",
               str(tmp_path / "trace.json"), timeout=600)
    import re
    m = re.search(r"profiler example done: (\d+) events, (\d+) steps", log)
    assert m, log[-500:]
    assert int(m.group(2)) >= 8, m.group(0)


def test_every_example_script_has_a_smoke():
    """The PARITY claim 'every script smoke-tested' must stay true: each
    examples/ script is referenced by some test in this file."""
    import glob
    this = open(os.path.abspath(__file__)).read()
    missing = []
    for path in glob.glob(os.path.join(ROOT, "examples", "**", "*.py"),
                          recursive=True):
        rel = os.path.relpath(path, ROOT)
        base = os.path.basename(path)
        if base in ("common.py", "__init__.py"):
            continue
        if rel.replace(os.sep, "/") not in this:
            missing.append(rel)
    assert not missing, (
        "example scripts without a smoke test referencing them: %r"
        % sorted(missing))


def test_train_lm_transformer_example():
    """Transformer-LM flagship example (RoPE + SwiGLU variant smoke)."""
    log = _run("examples/rnn/train_lm_transformer.py", "--synthetic",
               "--num-epochs", "2", "--seq-len", "16", "--d-model", "32",
               "--num-heads", "2", "--batch-size", "16",
               "--pos-type", "rope", "--ffn-type", "swiglu",
               timeout=900)
    assert "Train-perplexity" in log or "perplexity" in log.lower(), \
        log[-500:]


def test_ring_sp_train_example():
    """Long-context recipe: ring attention over the sp axis + chunked CE
    in one SPMD step — loss collapses on the learnable shift corpus."""
    log = _run("examples/model_parallel/ring_sp_train.py",
               "--steps", "80", timeout=600,
               env_extra={"XLA_FLAGS":
                          "--xla_force_host_platform_device_count=8"})
    # the script itself asserts the convergence ratio before printing
    # this marker — its presence IS the pass condition
    assert "ring-sp train: loss" in log, log[-500:]
