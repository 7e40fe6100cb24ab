"""Tests of what the Kimi-Linear cell adds to the benchmark (CPU only, tiny
sizes): (a) a tiny cell of ``drivers/kimi_linear_decode_serve.py`` runs end to
end from files written HERE, traced and untraced; (b) breaking the state
update underneath, and the control in the program's place, both come out NOT
correct; (c) the new reader on hand-made events; (d)
``harness/flops_kimi_linear.py`` against hand-worked values.
"""
import contextlib
import io
import json
import os
import sys
from unittest import mock

import pytest

CELLS = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CELLS)
import run as run_mod                                   # noqa: E402
from harness import flops_kimi_linear as fk             # noqa: E402
from harness import spec as spec_mod                    # noqa: E402
from harness.trace import Event                         # noqa: E402

TINY = {
    "driver": "kimi_linear_decode_serve", "reference": "kimi_linear",
    "control": "float8_e4m3", "param_dtype": "float32",
    "hidden_size": 64, "num_hidden_layers": 4, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "q_lora_rank": None, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 160, "moe_intermediate_size": 48,
    "num_experts": 8, "num_shared_experts": 1, "num_experts_per_token": 2,
    "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-5,
    "mla_use_nope": True, "moe_renormalize": True, "vocab_size": 128,
    "linear_attn_config": {
        "kda_layers": [1, 2, 3], "full_attn_layers": [4], "num_heads": 4,
        "head_dim": 8, "short_conv_kernel_size": 4},
    "experts_held": {"first": 2, "count": 4}, "initializer_range": 0.2}
CELL = "t_kimi"


def _write(root, rel, obj):
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def spec_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tiny_kimi_bench"))
    _write(root, "cells/configs/tiny_kimi.json", TINY)
    _write(root, "cells/traffic/tiny_longgen.json", {
        "kind": "backlog", "requests": 32, "block": 16,
        "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 0.6,
                       "min": 4, "max": 70},
        "output_len": {"dist": "lognormal", "median": 8, "sigma": 0.6,
                       "min": 2, "max": 20},
        "engine": {"batch_size": 4, "max_seq_len": 96, "block_size": 8,
                   "num_blocks": 65, "prefill_buckets": [16, 32],
                   "prefill_chunk": 32},
        "trace": {"delay_s": 0.1, "length_s": 0.3},
        "check": {"sample_requests": 6, "block_requests": 1},
        "limits": {"served_gap_ratio": 0.01}})
    cells = [CELL]
    names = ["decode_batch_fill_pct", "kv_blocks_high_water_pct",
             "step_mfu_pct.decode", "moe_tokens_per_expert",
             "moe_load_max_over_mean", "decode_prefill_device_pct",
             "decode_step_hbm_roofline", "kda_step_roofline",
             "kda_state_bytes_pct"]
    _write(root, "BENCHMARK.json", {
        "command": ["python3", "benchmark/cells/run.py"], "paths": ["cells"],
        "run_seconds": 1,
        "configs": [{"name": "tiny_kimi",
                     "file": "cells/configs/tiny_kimi.json"}],
        "workloads": [{"name": CELL, "config": "tiny_kimi",
                       "traffic": "tiny_longgen", "chips": 1}],
        "end_to_end": [{"name": "decode_tok_per_s", "unit": "tokens/s",
                        "workloads": cells},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": n, "unit": "x", "workloads": cells}
                      for n in names]})
    return root


def make_driver(spec_root, seed, seconds=2.0):
    import argparse
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=seconds,
                              trace=0, spec_root=spec_root, rehearse=True)
    spec, _, ctx, _ = run_mod.prepare(args)
    return spec.module("drivers", ctx.config["driver"]).Driver(ctx), ctx


# ---------------------------------------------------------------- (a) ----

def test_kimi_cell_traced_run_from_files_reports_the_counters_metrics(
        spec_root):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run_mod.main(["--spec-root", spec_root, "--rehearse",
                           "--workload", CELL, "--seed", str(2 ** 31 + 5),
                           "--seconds", "2", "--trace", "1"])
    assert rc == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["compared"]) == {"compiles_in_window", "never_answered",
                                     "served_gap_ratio"}
    assert line["compared"]["served_gap_ratio"]["value"] < 1e-3
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # no device plane on the CPU: the trace readers have nothing to read
    assert set(m) == {"decode_batch_fill_pct", "kv_blocks_high_water_pct",
                      "moe_tokens_per_expert", "moe_load_max_over_mean",
                      "kda_state_bytes_pct"}
    assert 0.0 < m["kda_state_bytes_pct"] < 100.0
    assert 0.0 < m["moe_tokens_per_expert"] <= 4 * 2 / 8 * 2.0


def test_kimi_cell_facts_count_the_model_and_breaking_the_state_shows(
        spec_root):
    """The facts of an untraced run; then the same run with the step's
    state update broken underneath (the decay left out: every state a plain
    sum) is NOT correct, and so is the control in the program's place."""
    driver, ctx = make_driver(spec_root, seed=5)
    facts = driver.run()
    driver.release()
    kda, steps = 3, facts["steps"]
    assert facts["experts_held"] == 4
    assert facts["kda_layer_steps"] == kda * steps
    assert facts["kda_rows_updated"] == kda * facts["step_tokens"]
    assert facts["moe_layer_steps"] == 3 * steps
    # one latent layer x 65 blocks x 8 x 128 lanes x 4; per slot and KDA
    # layer 4 heads x 8 x 8 floats of state and 3 x 96 floats of tail
    assert facts["kv_pool_bytes"] == 65 * 8 * 128 * 4
    assert facts["kv_state_bytes"] == kda * 4 * (4 * 8 * 8 + 3 * 96) * 4
    rows = facts["kda_rows_updated"] / steps
    assert facts["kda_kernel_bytes"] == rows * 2 * 4 * 8 * 8 * 4
    assert facts["kda_kernel_bytes"] < facts["kda_step_bytes"] \
        < facts["step_hbm_bytes"]
    assert facts["model_flops"] > 0 and facts["compiles_in_window"] == 0
    assert all(c.ok for c in driver.check())
    control = driver.check(control_in_place=True)
    assert not all(c.ok for c in control)
    assert control[-1].value == pytest.approx(1.0)

    from mxnet_tpu.kernels import kda as kernels
    real = kernels.kda_step

    def no_decay(state, layer, q, k, v, g, beta, active, **kw):
        return real(state, layer, q, k, v, 0.0 * g, beta, active, **kw)

    with mock.patch.object(kernels, "kda_step", no_decay):
        broken, _ = make_driver(spec_root, seed=5)
        broken.run()
        broken.release()
        compared = broken.check()
    assert not all(c.ok for c in compared), \
        [(c.name, c.value) for c in compared]


# ---------------------------------------------------------------- (c) ----

DEV, HOST = "/device:TPU:0", "/host:CPU"
EVENTS = [
    Event(DEV, "XLA Modules", "jit_step_fn(3)", 0, 1000),
    Event(DEV, "XLA Ops", "mx_kda_step", 100, 100),
    Event(DEV, "XLA Ops", "fusion.7", 200, 300),
    Event(DEV, "XLA Ops", "mx_kda_step.1", 500, 150),
    Event(DEV, "XLA Modules", "jit_prefill_fn(4)", 1000, 500),
    Event(DEV, "XLA Ops", "mx_kda_step", 1100, 900),     # not in a step
    Event(DEV, "XLA Modules", "jit_step_fn(3)", 2000, 1000),
    Event(DEV, "XLA Ops", "mx_kda_step", 2100, 200),
    Event(DEV, "XLA Ops", "mx_kda_step.1", 2500, 250),
    Event(DEV, "XLA Modules", "jit_step_fn(3)", 3000, 1000),
    Event(DEV, "XLA Ops", "mx_kda_step", 3100, 300),
    Event(DEV, "XLA Ops", "mx_kda_step.1", 3500, 350),
    Event(HOST, "python", "mx.decode.step", 0, 4000),
]


class _Run:
    def __init__(self, facts, events=EVENTS):
        self.facts, self.events = facts, events
        self.peaks = {"hbm_bytes_per_s": 800e9}
        self.chips = 1


def test_op_roofline_reader_on_hand_made_events():
    rd = spec_mod.Spec().module("readers", "trace_op_roofline_pct")
    args = {"bytes_fact": "kda_kernel_bytes", "module_pattern": "jit_step_fn",
            "op_pattern": "mx_kda_step"}
    # the kernels of the three steps last 250, 450 and 650 ns: median 450;
    # 180e3 bytes at 800e9 B/s are 225 ns
    assert rd.op_seconds_per_execution(EVENTS, "jit_step_fn",
                                       "mx_kda_step") \
        == pytest.approx([250e-9, 450e-9, 650e-9])
    assert rd.read(_Run({"kda_kernel_bytes": 180e3}), args) == \
        pytest.approx(100.0 * 225.0 / 450.0)
    assert rd.read(_Run({}), args) is None                  # no counters
    assert rd.read(_Run({"kda_kernel_bytes": 1.0}, events=None), args) \
        is None
    # a program without the kernel (the parent commit): nothing, not 0
    assert rd.read(_Run({"kda_kernel_bytes": 1.0}, events=[
        e for e in EVENTS if "kda" not in e.name]), args) is None
    assert rd.read(_Run({"kda_kernel_bytes": 1.0}, events=EVENTS[4:6]),
                   args) is None                            # no step program


# ---------------------------------------------------------------- (d) ----

SMALL = {"hidden_size": 8, "num_attention_heads": 2, "q_lora_rank": None,
         "kv_lora_rank": 3,
         "qk_nope_head_dim": 5, "qk_rope_head_dim": 2, "v_head_dim": 6,
         "intermediate_size": 10, "moe_intermediate_size": 7,
         "num_experts": 16, "num_shared_experts": 1, "num_hidden_layers": 4,
         "first_k_dense_replace": 1, "vocab_size": 11,
         "linear_attn_config": {"kda_layers": [1, 2, 3],
                                "full_attn_layers": [4], "num_heads": 2,
                                "head_dim": 4, "short_conv_kernel_size": 4}}


def test_flops_kimi_linear_against_hand_worked_values():
    assert fk.layer_counts(SMALL) == (3, 1, 1, 3)
    # KDA: H d_k = 8; qkv 8*24, two gates 2*(8*4 + 4*8), beta 8*2, out 8*8,
    # taps 4*24
    kda = 192 + 128 + 16 + 64 + 96
    assert fk.kda_projection_macs(SMALL) == kda
    assert fk.kda_state_flops(SMALL) == 7 * 2 * 4 * 4
    # MLA: q 8*2*7, kv_a 8*5, kv_b 3*2*11, out 2*6*8
    mla = 112 + 40 + 66 + 96
    assert fk.mla_projection_macs(SMALL) == mla
    assert fk.attention_pair_flops(SMALL, True) == 2 * 2 * (5 + 3)
    assert fk.attention_pair_flops(SMALL, False) == 2 * 2 * (7 + 6)
    outside = 3 * (2 * kda + 224) + 2 * mla + 6 * 8 * 10 \
        + 3 * (2 * 8 * 16 + 6 * 8 * 7)
    assert fk.token_flops_outside_attention_pairs(SMALL) == outside
    assert fk.routed_flops(SMALL, 5) == 5 * 2 * 3 * 8 * 7
    assert fk.head_flops(SMALL) == 2 * 8 * 11
    # prompt of 3 (pairs 1+2+3), then 2 steps (4 + 5 keys), ONE MLA layer
    assert fk.sequence_flops(SMALL, 3, 2) == \
        5 * outside + 6 * 52 + 9 * 32
    # weights: KDA kda + A_log 2 + dt_bias 8 + gain 4; MLA mla + gain 3; two
    # norms a layer; dense MLP 240; expert layers router 128 + shared 168
    w = 3 * (kda + 14) + (mla + 3) + 4 * 16 + 240 + 3 * (128 + 168) + 8 + 88
    assert fk.weights_outside_routed(SMALL) == w
    # 6 (row, layer) updates: state 2 heads x 4 x 4 floats, tail 3 x 24
    assert fk.kda_kernel_bytes(SMALL, 6) == 6 * 2 * 32 * 4
    assert fk.kda_step_bytes(SMALL, 6) == 6 * 2 * (32 * 4 + 72 * 2)
    assert fk.step_hbm_bytes(SMALL, 1.5, 10, 6) == \
        2 * (w + 1.5 * 3 * 8 * 7) + 2 * 10 * 1 * 5 + 6 * 2 * (128 + 144)


def test_flops_kimi_linear_counts_the_published_cut():
    with open(os.path.join(CELLS, "configs", "kimi_linear_ep8.json")) as f:
        cfg = json.load(f)
    # ISSUE 34's arithmetic: a KDA layer's mixer 39,514,272 parameters, an
    # MLA layer's 29,114,880; 2,097,152 B of state and 73,728 B of tail a
    # slot and KDA layer
    assert fk.kda_projection_macs(cfg) - 4 * 3 * 4096 + 32 + 4096 + 128 \
        + 4 * 3 * 4096 == 39_514_272
    assert fk.mla_projection_macs(cfg) + 512 == 29_114_880
    assert fk.kda_state_row_bytes(cfg) == 2_097_152
    assert fk.kda_step_bytes(cfg, 1) - fk.kda_kernel_bytes(cfg, 1) \
        == 2 * 73_728
    assert 6 * (2_097_152 + 73_728) == cfg["state_bytes_per_slot"]
    ref = spec_mod.Spec().module("references", "kimi_linear")
    # the issue counts 1,792 more: the seven routers' selection bias, which
    # the configuration assumes zero and holds no leaf for
    assert ref.param_count(cfg) == cfg["parameters"] == 2_092_550_080 - 1_792
    routed = 7 * 32 * 3 * 2304 * 1024
    assert fk.weights_outside_routed(cfg) == cfg["parameters"] - routed \
        - 20480 * 2304
