"""The looped_lm family through chipbench's harness on the CPU: the tiny
cell ``tiny.ouro`` (data/, in no manifest), the fault that must fail it
(a program that runs one loop step fewer than the configuration says), the
configuration file against the catalog's published keys, and the readers
of the loop's three per-layer metrics on what they can read off the chip."""
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

from chipbench import harness  # noqa: E402


def run_cell(capsys, trace=0):
    rc = harness.main(["--workload", "tiny.ouro", "--seed", "3000000019",
                       "--seconds", "1", "--trace", str(trace),
                       "--root", DATA])
    got = capsys.readouterr()
    assert rc == 0
    return (json.loads(got.out.strip().splitlines()[-1]),
            got.err.strip().splitlines())


def test_tiny_ouro_is_correct_through_the_harness(capsys):
    line, err = run_cell(capsys)
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] >= 4
    assert line["device"]["platform"] == "cpu" and "rehearsal" in line
    ref = line["reference"]
    # float32 Module against the float32 reference: tight on every count
    assert ref["loss_rel"] < 1e-5 and ref["out_rel_l2"] < 1e-4
    assert ref["tensors"] == ref["decidable"] == 21 and not ref["failing"]
    assert ref["max_e_decidable"] < 2e-2 and ref["output_weight_e"] < 1e-3
    win = ref["window"]
    assert win["first_nonfinite_step"] is None
    assert win["compiles_in_window"] == 0
    assert win["loss_last_mean"] < win["loss_first_mean"]
    # the body was traced in set-up and never in the window
    window = [ln for ln in err if "] window on " in ln][0]
    assert '"loop.body_trace": 0' in window


def test_one_loop_step_fewer_is_not_correct(capsys, monkeypatch):
    """The program's builder ignores one loop step of the configuration's
    three: every number the check compares belongs to a different model,
    and it says so on the first step, before any window."""
    from mxnet_tpu import models
    real = models.looped_transformer_lm

    def one_step_short(vocab_size, seq_len, **kw):
        kw["loop_steps"] = int(kw["loop_steps"]) - 1
        return real(vocab_size, seq_len, **kw)
    monkeypatch.setattr(models, "looped_transformer_lm", one_step_short)
    line, err = run_cell(capsys)
    assert line["correct"] is False
    ref = line["reference"]
    assert ref["out_rel_l2"] > ref["out_tol"]
    assert len(ref["failing"]) > ref["tensors"] // 2
    assert ref["max_e_over_tol"] > 1.0
    assert err[-1].endswith("correct: False")


def test_the_configuration_keeps_every_published_number():
    """chipbench/configs/ouro-2.6b.json holds each key of the catalog row's
    ``config`` as published, the depth alone reduced, and its builder
    arguments are those keys."""
    with open(os.path.join(REPO, "chipbench", "configs",
                           "ouro-2.6b.json")) as f:
        cfg = json.load(f)
    published = {"head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
                 "intermediate_size": 5632, "max_position_embeddings": 65536,
                 "max_window_layers": 48, "model_type": "ouro",
                 "num_attention_heads": 16, "num_key_value_heads": 16,
                 "rms_norm_eps": 1e-06, "rope_scaling": None,
                 "rope_theta": 1000000, "sliding_window": None,
                 "tie_word_embeddings": False, "total_ut_steps": 4,
                 "early_exit_threshold": 1, "use_sliding_window": False,
                 "vocab_size": 49152}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["layer_types"] == ["full_attention"] * 48
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 4
    assert cfg["published"]["num_hidden_layers"] == 48
    b = cfg["builder"]
    assert (b["vocab_size"], b["num_layers"], b["d_model"], b["num_heads"],
            b["num_kv_heads"], b["d_ff"], b["loop_steps"], b["rope_base"],
            b["norm_eps"]) == (
        cfg["vocab_size"], cfg["num_hidden_layers"], cfg["hidden_size"],
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["intermediate_size"], cfg["total_ut_steps"], cfg["rope_theta"],
        cfg["rms_norm_eps"])
    assert b["d_model"] // b["num_heads"] == cfg["head_dim"]
    # no key of the configuration selects the mechanism: the builder's
    # loop node and its rematerialisation are the graph's own
    assert set(b) == {"vocab_size", "num_layers", "d_model", "num_heads",
                      "num_kv_heads", "d_ff", "loop_steps", "rope_base",
                      "norm_eps", "exit_beta", "ce_chunks"}
    for key in ("assumed", "departures", "deployment", "precision_recipe"):
        assert cfg[key]
    # the arithmetic PERF.md section 4 gives
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == cfg["published"]["parameters_a_layer"] == 51388416
    assert cfg["parameters_held"] == 4 * layer + 2 * 49152 * 2048 + 2048 \
        + 2049 == 406884353


def test_model_flops_and_kernel_costs_of_the_cell():
    _, cfg, traffic, fam, _ = harness.Resolver().cell("ouro.train.resident")
    dense = 16 * 2 * 4096 * 51380224 + 4 * 2 * 4096 * 2048 * 49152
    attn = 16 * 2 * 4096 ** 2 * 2048
    assert fam.model_flops(cfg, traffic) == 3.0 * (dense + attn)
    assert fam.model_flops(cfg, traffic) == pytest.approx(33.4e12, rel=2e-3)
    costs = fam.kernel_costs(cfg, traffic)
    # the prefixes the accepted flash readers look for, by the work
    # attention requires: 2 matmuls forward, 5 backward, the causal half
    assert set(costs) == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    one = 16 * 4096 * 4096 * 128            # 2 S^2 hd / 2, 16 heads
    assert costs["flash_fwd"]["flops"] == 2 * one
    assert costs["flash_bwd_dkv"]["flops"] == 5 * one
    assert costs["flash_fwd"]["calls_per_step"] == 32
    assert costs["flash_bwd_dkv"]["calls_per_step"] == 16


def test_the_loop_metrics_read_what_there_is(capsys):
    res = harness.Resolver()
    traces = res.module("layer_metrics", "loop_body_traces")
    body = res.module("layer_metrics", "loop_body_ms_per_step")
    again = res.module("layer_metrics", "loop_recompute_ms_per_step")
    # a run that was not traced: nothing, and no error
    assert body.read({"trace": None}) is None
    assert again.read({"trace": None}) is None
    # a traced record with no trace file behind it (another run's, or a
    # program without the loop): nothing, said on stderr, no error
    record = {"trace": {"steps": 10}}
    assert body.read(record) is None and again.read(record) is None
    # the counter: set-up of the tiny cell traces the body a fixed few
    # times (shape inference, the executor's shape-only forward, the fused
    # step), far fewer than its 3 loop steps x 28 window steps
    from mxnet_tpu import profiler
    before = profiler.dispatch_counts().get("loop.body_trace", 0)
    line, _ = run_cell(capsys)
    grown = traces.read({}) - before
    assert 3 <= grown <= 8
    assert line["attempted"] * 3 > grown
    assert (traces.UNIT, traces.SOURCE, traces.MOVES) == (
        "count", "program_counter", "setup_s")
