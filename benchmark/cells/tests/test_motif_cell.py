"""Tests of what the Motif-3 cell adds to the benchmark (CPU only, tiny
sizes): (a) a tiny cell of ``drivers/motif_decode_serve.py`` runs end to end
from files written HERE, traced; (b) its facts count the model, and the
control in the program's place comes out NOT correct; (c)
``harness/flops_motif.py`` against hand-worked values and the published
cut's parameter count.
"""
import contextlib
import io
import json
import os
import sys

import pytest

CELLS = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CELLS)
import run as run_mod                                   # noqa: E402
from harness import flops_motif as fm                   # noqa: E402
from harness import spec as spec_mod                    # noqa: E402

TINY = {
    "driver": "motif_decode_serve", "reference": "motif3",
    "control": "float8_e4m3", "param_dtype": "float32",
    "hidden_size": 64, "num_hidden_layers": 5, "n_dense_first_layers": 1,
    "layers_kept": [0, 9, 10, 11, 12], "num_attention_heads": 10,
    "num_key_value_heads": 2, "num_noise_heads": 2, "head_dim": 24,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "q_lora_rank": 32,
    "kv_lora_rank": 16, "intermediate_size": 96, "moe_intermediate_size": 48,
    "num_experts": 16, "num_shared_experts": 1, "experts_top_k": 4,
    "route_scale": 2.0, "rms_norm_eps": 1e-5, "vocab_size": 128,
    "sliding_window": 8, "sliding_window_period": 4, "max_window_layers": 9,
    "mhc_expansion_rate": 4, "mhc_sinkhorn_iters": 20, "rope_theta": 10000,
    "polynorm_output_scale": 0.5, "polynorm_bias_clamp": 0.5,
    "hidden_clamp": 1e6, "experts_held": {"first": 4, "count": 4},
    "initializer_range": 0.2}
CELL = "t_motif"


def _write(root, rel, obj):
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def spec_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tiny_motif_bench"))
    _write(root, "cells/configs/tiny_motif.json", TINY)
    _write(root, "cells/traffic/tiny_reason.json", {
        "kind": "backlog", "requests": 32, "block": 16,
        "prompt_len": {"dist": "lognormal", "median": 12, "sigma": 0.6,
                       "min": 4, "max": 40},
        "output_len": {"dist": "lognormal", "median": 16, "sigma": 0.6,
                       "min": 8, "max": 30},
        "engine": {"batch_size": 4, "max_seq_len": 80, "block_size": 8,
                   "num_blocks": 49, "prefill_buckets": [32],
                   "prefill_chunk": 32},
        "trace": {"delay_s": 0.1, "length_s": 0.3},
        "check": {"sample_requests": 6, "block_requests": 1},
        "limits": {"served_gap_ratio": 0.01}})
    cells = [CELL]
    names = ["decode_batch_fill_pct", "kv_blocks_high_water_pct",
             "step_mfu_pct.decode", "moe_tokens_per_expert",
             "moe_load_max_over_mean", "decode_step_hbm_roofline",
             "gdla_attn_roofline", "moe_expert_roofline",
             "gdla_cache_rows_pct"]
    _write(root, "BENCHMARK.json", {
        "command": ["python3", "benchmark/cells/run.py"], "paths": ["cells"],
        "run_seconds": 1,
        "configs": [{"name": "tiny_motif",
                     "file": "cells/configs/tiny_motif.json"}],
        "workloads": [{"name": CELL, "config": "tiny_motif",
                       "traffic": "tiny_reason", "chips": 1}],
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

def test_motif_cell_traced_run_from_files_reports_the_counters_metrics(
        spec_root):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run_mod.main(["--spec-root", spec_root, "--rehearse",
                           "--workload", CELL, "--seed", str(2 ** 31 + 9),
                           "--seconds", "2", "--trace", "1"])
    assert rc == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["compared"]["served_gap_ratio"]["value"] < 1e-3
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # no device plane on the CPU: the trace readers have nothing to read
    assert set(m) == {"decode_batch_fill_pct", "kv_blocks_high_water_pct",
                      "moe_tokens_per_expert", "moe_load_max_over_mean",
                      "gdla_cache_rows_pct"}
    # full layers read every live row (whole pages), window layers 8 rows
    # of contexts up to 70 long: between the two
    assert 20.0 < m["gdla_cache_rows_pct"] < 100.0 * 80 / 70


# ---------------------------------------------------------------- (b) ----

def test_motif_cell_facts_count_the_model_and_the_control_fails(spec_root):
    """The facts of an untraced run count the model's layers and pools; the
    control in the program's place is NOT correct. (A ring that never
    learns a decode token is `test_motif.py`'s.)"""
    driver, ctx = make_driver(spec_root, seed=3)
    facts = driver.run()
    driver.release()
    steps, rows = facts["steps"], facts["step_tokens"]
    assert facts["experts_held"] == 4
    assert facts["moe_layer_steps"] == 4 * steps
    assert facts["gdla_window_rows"] == 3 * 8 * rows
    assert facts["gdla_context_positions"] == 5 * facts["kv_live_tokens"]
    assert facts["kv_live_tokens"] <= facts["gdla_full_rows"] / 2 \
        < facts["kv_live_tokens"] + 8 * rows
    # two full layers x 49 blocks x 8 x 128 lanes x 4; per slot three
    # window layers' rings of 8 such rows
    assert facts["kv_pool_bytes"] == 2 * 49 * 8 * 128 * 4
    assert facts["kv_state_bytes"] == 3 * 4 * 8 * 128 * 4
    assert facts["gdla_attended_rows"] == facts["gdla_full_rows"] \
        + facts["gdla_window_rows"]
    assert facts["gdla_attn_bytes"] < facts["step_hbm_bytes"]
    assert facts["moe_expert_bytes"] < facts["step_hbm_bytes"]
    assert facts["model_flops"] > 0 and facts["compiles_in_window"] == 0
    assert all(c.ok for c in driver.check())
    control = driver.check(control_in_place=True)
    assert not all(c.ok for c in control)
    assert control[-1].value == pytest.approx(1.0)


# ---------------------------------------------------------------- (c) ----

SMALL = {"hidden_size": 8, "num_attention_heads": 6,
         "num_key_value_heads": 2, "head_dim": 5, "qk_rope_head_dim": 2,
         "v_head_dim": 4, "q_lora_rank": 3, "kv_lora_rank": 2,
         "intermediate_size": 10, "moe_intermediate_size": 7,
         "num_experts": 16, "num_shared_experts": 1, "num_hidden_layers": 3,
         "layers_kept": [0, 9, 11], "n_dense_first_layers": 2,
         "max_window_layers": 9, "sliding_window": 4,
         "sliding_window_period": 4, "mhc_expansion_rate": 2,
         "vocab_size": 11}


def test_flops_motif_against_hand_worked_values():
    # layer 0 dense and full, 9 window, 11 full
    assert fm.layer_counts(SMALL) == (1, 2, 2, 1)
    # G 2 groups of 2 signal + 1 noise: S = 2; dn 3
    # DQ 8*3, UQ 3*6*5, DKV 8*4, UK/UV 2*2*7, lambda 8*4, gate and W_O
    # 2 * 8*16
    proj = 24 + 90 + 32 + 28 + 32 + 256
    assert fm.gdla_projection_macs(SMALL) == proj
    # mHC: projection 16 x 8, the three mixes 16 + 32 + 16
    assert fm.mhc_flops(SMALL) == 2 * 16 * 8 + 2 * 64
    assert fm.attention_pair_flops(SMALL, True) == 2 * 6 * (4 + 2)
    assert fm.attention_pair_flops(SMALL, False) == 2 * 6 * (5 + 4)
    outside = 3 * (2 * proj + 2 * 384) + 6 * 8 * 10 \
        + 2 * (2 * 8 * 16 + 6 * 8 * 7)
    assert fm.token_flops_outside_attention_pairs(SMALL) == outside
    # keys seen: capped at the window of 4 from position 3 on
    assert fm._pairs(0, 6) == 21 and fm._pairs(0, 6, 4) == 1 + 2 + 3 + 4 * 3
    assert fm._pairs(5, 7, 4) == 8 and fm._pairs(2, 5, 4) == 3 + 4 + 4
    # prompt 3 then 3 steps: two full layers, one window layer
    assert fm.sequence_flops(SMALL, 3, 3) == \
        6 * outside + (2 * 6 + 6) * 108 + (2 * 15 + 12) * 72
    assert fm.routed_flops(SMALL, 5) == 5 * 6 * 8 * 7
    assert fm.attn_kernel_bytes(SMALL, 10, 6) == 16 * 4 * 2
    assert fm.expert_step_bytes(SMALL, 1.5) == 1.5 * 3 * 8 * 7 * 2
    assert fm.stream_bytes(SMALL, 2) == 2 * 2 * 2 * 3 * 2 * 8 * 4


def test_flops_motif_counts_the_published_cut():
    with open(os.path.join(CELLS, "configs", "motif3_ep8.json")) as f:
        cfg = json.load(f)
    # reckoned from the published widths: attention 91,750,400 a layer,
    # 3,928,227,840 in the matrices of the cut
    assert fm.gdla_projection_macs(cfg) == 91_750_400
    assert fm.layer_counts(cfg) == (1, 4, 2, 3)
    ref = spec_mod.Spec().module("references", "motif3")
    assert ref.param_count(cfg, matrices_only=True) == 3_928_227_840 \
        == cfg["parameters_reckoned"]["matrices"]
    assert ref.param_count(cfg) == cfg["parameters"]
    routed = 4 * 48 * (3 * 4096 * 1280 + 4)
    assert fm.weights_outside_routed(cfg) == cfg["parameters"] - routed \
        - 27520 * 4096
    # a full layer's pages and a window layer's ring, 640 lanes of bfloat16
    assert cfg["cache_bytes_per_token"] == 2 * 640 * 2
    assert cfg["ring_bytes_per_slot"] == 3 * 128 * 640 * 2
