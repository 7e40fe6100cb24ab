"""Tests of what the latent-attention expert cell adds to the benchmark (CPU
only, tiny sizes): (a) a tiny cell of ``drivers/moe_decode_serve.py`` runs end
to end from files written HERE, traced and untraced; (b) its control (the
reference with e4m3 operands, put in the program's place) comes out NOT
correct; (c) the two new readers on hand-made events; (d)
``harness/flops_moe_mla.py`` against hand-worked values.
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
from harness import flops_moe_mla as fm                 # noqa: E402
from harness import spec as spec_mod                    # noqa: E402
from harness.trace import Event                         # noqa: E402

TINY = {
    "driver": "moe_decode_serve", "reference": "pangu_umoe",
    "control": "float8_e4m3", "param_dtype": "float32",
    "hidden_size": 64, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 160, "moe_intermediate_size": 48,
    "n_routed_experts": 8, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-5,
    "rope_theta": 25600000, "vocab_size": 128,
    "experts_held": {"first": 2, "count": 4}, "initializer_range": 0.2}
CELL = "t_moe"


def _write(root, rel, obj):
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def spec_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tiny_moe_bench"))
    _write(root, "cells/configs/tiny_moe.json", TINY)
    _write(root, "cells/traffic/tiny_long.json", {
        "kind": "backlog", "requests": 32, "block": 16,
        "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 0.6,
                       "min": 4, "max": 70},
        "output_len": {"dist": "lognormal", "median": 8, "sigma": 0.6,
                       "min": 2, "max": 20},
        "engine": {"batch_size": 4, "max_seq_len": 96, "block_size": 8,
                   "num_blocks": 65, "prefill_buckets": [16, 32],
                   "prefill_chunk": 32},
        "trace": {"delay_s": 0.1, "length_s": 0.3},
        "check": {"sample_requests": 6, "block_requests": 2},
        "limits": {"served_gap_ratio": 0.01}})
    cells = [CELL]
    names = ["decode_batch_fill_pct", "kv_blocks_high_water_pct",
             "step_mfu_pct.decode", "moe_tokens_per_expert",
             "moe_load_max_over_mean", "decode_prefill_device_pct",
             "decode_step_hbm_roofline"]
    _write(root, "BENCHMARK.json", {
        "command": ["python3", "benchmark/cells/run.py"], "paths": ["cells"],
        "run_seconds": 1,
        "configs": [{"name": "tiny_moe",
                     "file": "cells/configs/tiny_moe.json"}],
        "workloads": [{"name": CELL, "config": "tiny_moe",
                       "traffic": "tiny_long", "chips": 1}],
        "end_to_end": [{"name": "decode_tok_per_s", "unit": "tokens/s",
                        "workloads": cells},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": n, "unit": "x", "workloads": cells}
                      for n in names]})
    return root


def run_cell(spec_root, trace_on, seed):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run_mod.main(["--spec-root", spec_root, "--rehearse",
                           "--workload", CELL, "--seed", str(seed),
                           "--seconds", "2", "--trace", str(trace_on)])
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def make_driver(spec_root, seed, seconds=2.0):
    import argparse
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=seconds,
                              trace=0, spec_root=spec_root, rehearse=True)
    spec, _, ctx, _ = run_mod.prepare(args)
    return spec.module("drivers", ctx.config["driver"]).Driver(ctx), ctx


# ---------------------------------------------------------------- (a) ----

def test_moe_cell_end_to_end_from_files(spec_root):
    line = run_cell(spec_root, 0, seed=2 ** 31 + 5)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"decode_tok_per_s", "setup_s"}
    assert set(line["compared"]) == {"compiles_in_window", "never_answered",
                                     "served_gap_ratio"}
    assert line["compared"]["served_gap_ratio"]["value"] < 1e-3


def test_moe_cell_traced_run_reports_the_counters_metrics(spec_root):
    line = run_cell(spec_root, 1, seed=17)
    assert line["correct"] is True, line["compared"]
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # no device plane on the CPU: the two trace readers have nothing to read
    assert set(m) == {"decode_batch_fill_pct", "kv_blocks_high_water_pct",
                      "moe_tokens_per_expert", "moe_load_max_over_mean"}
    # 4 of 8 experts held, top-2, at most 4 rows a step: a held expert sees
    # rows * 2 / 8 assignments a step and layer on average
    assert 0.0 < m["moe_tokens_per_expert"] <= 4 * 2 / 8 * 2.0
    assert m["moe_load_max_over_mean"] >= 1.0


def test_moe_cell_facts_count_the_model(spec_root):
    driver, ctx = make_driver(spec_root, seed=5)
    facts = driver.run()
    driver.release()
    held, layers = 4, 2
    assert facts["experts_held"] == held
    assert facts["moe_layer_steps"] == layers * facts["steps"]
    assert 0 < facts["moe_assignments"] <= facts["step_tokens"] * layers * 2
    assert facts["moe_busiest"] * held >= facts["moe_assignments"]
    # the pool: 3 layers x 65 blocks x 8 x 128 lanes (24 numbers padded) x 4
    assert facts["kv_pool_bytes"] == 3 * 65 * 8 * 128 * 4
    assert facts["model_flops"] > 0 and facts["step_hbm_bytes"] > 0
    assert facts["compile_s_setup"] > 0 and facts["compiles_in_window"] == 0
    assert all(c.ok for c in driver.check())


# ---------------------------------------------------------------- (b) ----

def test_moe_control_in_lower_precision_is_not_correct(spec_root):
    driver, ctx = make_driver(spec_root, seed=9)
    driver.run()
    driver.release()
    control = driver.check(control_in_place=True)
    assert not all(c.ok for c in control), \
        [(c.name, c.value) for c in control]
    assert control[-1].value == pytest.approx(1.0)


# ---------------------------------------------------------------- (c) ----

DEV, HOST = "/device:TPU:0", "/host:CPU"
EVENTS = [
    Event(DEV, "XLA Modules", "jit_step_fn(3)", 0, 400),
    Event(DEV, "XLA Modules", "jit_prefill_fn(4)", 500, 100),
    Event(DEV, "XLA Modules", "jit_step_fn(3)", 1000, 600),
    Event(DEV, "XLA Modules", "jit_prefill_fn(5)", 1700, 300),
    Event(DEV, "XLA Modules", "jit_step_fn(3)", 2000, 500),
    Event(HOST, "python", "mx.decode.step", 0, 2500),
]


class _Run:
    def __init__(self, facts, events=EVENTS, window_s=4000e-9):
        self.facts, self.events, self.trace_window_s = facts, events, window_s
        self.peaks = {"hbm_bytes_per_s": 800e9}
        self.chips = 1


def _reader(name):
    return spec_mod.Spec().module("readers", name)


def test_prefill_share_reader_on_hand_made_events():
    rd = _reader("trace_module_share_pct")
    args = {"pattern": "jit_prefill_fn"}
    # 100 + 300 ns of prefill programs in a 4000 ns stretch
    assert rd.read(_Run({}), args) == pytest.approx(10.0)
    assert rd.read(_Run({}, events=None), args) is None
    assert rd.read(_Run({}, events=EVENTS[:1]), args) is None


def test_hbm_roofline_reader_on_hand_made_events():
    rd = _reader("hbm_roofline_pct")
    args = {"bytes_fact": "step_hbm_bytes", "pattern": "jit_step_fn"}
    # 200e3 bytes at 800e9 B/s are 250 ns; the median step lasts 500 ns
    assert rd.read(_Run({"step_hbm_bytes": 200e3}), args) == \
        pytest.approx(100.0 * 250.0 / 500.0)
    assert rd.read(_Run({}), args) is None                  # no counters
    assert rd.read(_Run({"step_hbm_bytes": 1.0}, events=None), args) is None
    assert rd.read(_Run({"step_hbm_bytes": 1.0}, events=EVENTS[1:2]),
                   args) is None                            # no step program


# ---------------------------------------------------------------- (d) ----

SMALL = {"hidden_size": 8, "num_attention_heads": 2, "q_lora_rank": 4,
         "kv_lora_rank": 3, "qk_nope_head_dim": 5, "qk_rope_head_dim": 2,
         "v_head_dim": 6, "intermediate_size": 10,
         "moe_intermediate_size": 7, "n_routed_experts": 16,
         "n_shared_experts": 1, "num_hidden_layers": 3,
         "first_k_dense_replace": 1, "vocab_size": 11}


def test_flops_moe_mla_against_hand_worked_values():
    # projections: 8*4 + 4*2*(5+2) + 8*(3+2) + 3*2*(5+6) + 2*6*8 = 290 MACs
    assert fm.mla_projection_flops(SMALL) == 2 * 290
    # a pair: absorbed 2 heads * ((3+2) + 3); expanded 2 * ((5+2) + 6)
    assert fm.attention_pair_flops(SMALL, True) == 2 * 16
    assert fm.attention_pair_flops(SMALL, False) == 2 * 26
    assert fm.layer_counts(SMALL) == (1, 2)
    # outside pairs and routed experts: 3 layers of projections, one dense
    # MLP 3*8*10, two expert layers of router 8*16 + shared 3*8*7
    outside = 2 * (3 * 290 + 240 + 2 * (128 + 168))
    assert fm.token_flops_outside_attention_pairs(SMALL) == outside
    assert fm.routed_flops(SMALL, 5) == 5 * 2 * 3 * 8 * 7
    assert fm.head_flops(SMALL) == 2 * 8 * 11
    # prompt of 3 (pairs 1+2+3), then 2 steps (4 + 5 keys), 3 layers
    assert fm.sequence_flops(SMALL, 3, 2) == \
        5 * outside + 3 * (6 * 2 * 26 + 9 * 2 * 16)
    # weights outside the routed experts: a layer's attention 290 + norms
    # (4*8 + 4 + 3 = 39); dense MLP 240; expert layer 128 + 168; final norm
    # 8; head 88
    w = 3 * (290 + 39) + 240 + 2 * (128 + 168) + 8 + 88
    assert fm.weights_outside_routed(SMALL) == w
    # 1.5 experts touched a step, 10 live tokens: rows of 3 + 2 numbers
    assert fm.step_hbm_bytes(SMALL, 1.5, 10) == \
        2 * (w + 1.5 * 3 * 8 * 7) + 2 * 10 * 3 * 5


def test_flops_moe_mla_counts_the_published_cut():
    cfg_path = os.path.join(CELLS, "configs", "pangu_umoe_ep16.json")
    with open(cfg_path) as f:
        cfg = json.load(f)
    # ISSUE 28's arithmetic: an expert layer holds 245,760,000 parameters
    # outside its routed experts, the dense layer 621,281,280, the head
    # slice 147,456,000 (+ the final norm's 7,680)
    assert fm.weights_outside_routed(cfg) == \
        4 * 245_760_000 + 621_281_280 + 147_456_000 + 7_680
    ref = spec_mod.Spec().module("references", "pangu_umoe")
    assert ref.param_count(cfg) == cfg["parameters"] == 4_919_139_840
