"""Tests of what the EvaByte cell adds to the benchmark (CPU only, tiny sizes):
(a) a tiny cell of ``drivers/evabyte_decode_serve.py`` runs end to end from
files written HERE, traced and untraced; (b) breaking the timed path
underneath (the chunk summaries dropped from what a query attends; a window's
own summaries made visible before it closes) and the control in the
program's place all come out NOT correct; (c) the reference's windowed
forward against its own unblocked one; (d) ``harness/flops_evabyte.py``
against hand-worked values and the published cut; (e) the pool replay against
the engine itself.
"""
import contextlib
import io
import json
import os
import sys
from unittest import mock

import numpy as np
import pytest

CELLS = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CELLS)
import run as run_mod                                   # noqa: E402
from harness import flops_evabyte as fe                 # noqa: E402
from harness import spec as spec_mod                    # noqa: E402

TINY = {
    "driver": "evabyte_decode_serve", "reference": "evabyte",
    "cache_pages": "mxnet_tpu.models.evabyte:EvaByteConfig",
    "control": "float8_e4m3", "param_dtype": "float32",
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "intermediate_size": 160, "vocab_size": 96,
    "window_size": 64, "chunk_size": 4, "rope_theta": 100000,
    "rms_norm_eps": 1e-5, "init_std": 0.2}
CELL = "t_eva"
ENGINE = {"batch_size": 4, "max_seq_len": 256, "block_size": 4,
          "num_blocks": 120, "prefill_buckets": [16, 32],
          "prefill_chunk": 32}
TRAFFIC = {
    "kind": "backlog", "requests": 32, "block": 16,
    "prompt_len": {"dist": "lognormal", "median": 90, "sigma": 0.6,
                   "min": 8, "max": 230},
    "output_len": {"dist": "lognormal", "median": 6, "sigma": 0.6,
                   "min": 2, "max": 16},
    "engine": ENGINE,
    "trace": {"delay_s": 0.1, "length_s": 0.3},
    "check": {"sample_requests": 6, "block_requests": 1},
    "limits": {"served_gap_ratio": 0.01}}


def _write(root, rel, obj):
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def spec_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tiny_eva_bench"))
    _write(root, "cells/configs/tiny_eva.json", TINY)
    _write(root, "cells/traffic/tiny_bytes.json", TRAFFIC)
    cells = [CELL]
    names = ["decode_batch_fill_pct", "kv_blocks_high_water_pct",
             "step_mfu_pct.decode", "decode_step_hbm_roofline",
             "eva_attn_roofline", "eva_cache_rows_pct",
             "kv_blocks_released_live_pct"]
    _write(root, "BENCHMARK.json", {
        "command": ["python3", "benchmark/cells/run.py"], "paths": ["cells"],
        "run_seconds": 1,
        "configs": [{"name": "tiny_eva",
                     "file": "cells/configs/tiny_eva.json"}],
        "workloads": [{"name": CELL, "config": "tiny_eva",
                       "traffic": "tiny_bytes", "chips": 1}],
        "end_to_end": [{"name": "decode_tok_per_s", "unit": "tokens/s",
                        "workloads": cells},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": n, "unit": "x", "workloads": cells}
                      for n in names]})
    return root


def make_driver(spec_root, seed, seconds=3.0):
    import argparse
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=seconds,
                              trace=0, spec_root=spec_root, rehearse=True)
    spec, _, ctx, _ = run_mod.prepare(args)
    return spec.module("drivers", ctx.config["driver"]).Driver(ctx), ctx


# ---------------------------------------------------------------- (a) ----

def test_eva_cell_traced_run_from_files_reports_the_counters_metrics(
        spec_root):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run_mod.main(["--spec-root", spec_root, "--rehearse",
                           "--workload", CELL, "--seed", str(2 ** 31 + 5),
                           "--seconds", "3", "--trace", "1"])
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
                      "eva_cache_rows_pct", "kv_blocks_released_live_pct"}
    # a row past its first window attends fewer rows than its context has
    assert 0.0 < m["eva_cache_rows_pct"] < 100.0
    assert 0.0 < m["kv_blocks_released_live_pct"] < 100.0


def test_eva_cell_facts_count_the_model_and_breaking_the_path_shows(
        spec_root):
    """The facts of an untraced run; then the same run with the timed path
    broken underneath, two ways, is NOT correct, and so is the control in
    the program's place."""
    driver, ctx = make_driver(spec_root, seed=5)
    facts = driver.run()
    driver.release()
    steps = facts["steps"]
    assert facts["kv_pool_bytes"] == 2 * 2 * 120 * 4 * 64 * 4
    rows = facts["eva_window_rows"] + facts["eva_summary_rows"]
    assert facts["eva_attended_rows"] == rows
    assert 0 < rows < facts["eva_context_positions"]
    assert facts["eva_attn_bytes"] == rows / steps * 2 * 64 * 2 * 2
    assert facts["eva_attn_bytes"] < facts["step_hbm_bytes"]
    assert 0 < facts["kv_blocks_released_live"] < facts["kv_blocks_allocated"]
    assert facts["eva_chunks_pooled"] > 0
    assert facts["model_flops"] > 0 and facts["compiles_in_window"] == 0
    assert all(c.ok for c in driver.check())
    control = driver.check(control_in_place=True)
    assert not all(c.ok for c in control)
    assert control[-1].value == pytest.approx(1.0)

    from mxnet_tpu.models import evabyte as eva
    real = eva._virtual
    breaks = {
        # no summary is ever attended: a query sees its own window only
        "summaries dropped": mock.patch.object(
            eva.EvaByteConfig, "summary_pages", 0),
        # the open window's own summary pages stand in front of its rows:
        # visible before the window has closed
        "summary visible inside its own window": mock.patch.object(
            eva, "_virtual",
            lambda cfg, t, window, width: real(cfg, t, window + 1, width))}
    for what, broken_path in breaks.items():
        with broken_path:
            broken, _ = make_driver(spec_root, seed=5)
            broken.run()
            broken.release()
            compared = broken.check()
        assert not all(c.ok for c in compared), \
            (what, [(c.name, c.value) for c in compared])


# ---------------------------------------------------------------- (c) ----

def test_reference_windowed_forward_is_its_unblocked_forward():
    """The forward a window at a time against ONE mask over every position
    and every summary: sequences that end mid-window, on a window's edge and
    inside the first window."""
    import jax
    ref = spec_mod.Spec().module("references", "evabyte")
    with jax.default_matmul_precision("highest"):
        params = ref.init_params(TINY, jax.random.PRNGKey(1))
        rng = np.random.default_rng(0)
        for S in (40, 128, 212):
            tokens = rng.integers(0, TINY["vocab_size"], (2, S)).astype(
                np.int32)
            at = np.tile(np.arange(S), (2, 1))
            a = ref.logits_at(TINY, params, tokens, at)
            b = ref.logits_at(TINY, params, tokens, at, blocked=False)
            # float32 both, the same sums in another order
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)
    with pytest.raises(ValueError, match="multiple of chunk_size"):
        ref.logits_at(TINY, params, tokens[:, :37], at[:, :37])


# ---------------------------------------------------------------- (d) ----

def test_flops_evabyte_against_hand_worked_values():
    cfg = {"hidden_size": 8, "num_hidden_layers": 3,
           "num_attention_heads": 2, "intermediate_size": 20,
           "vocab_size": 10, "window_size": 16, "chunk_size": 4}
    # a token: 4 projections of 8 x 8 and an MLP of 3 x 8 x 20, twice (a
    # multiply-add is two), and 8 d of pooling, in 3 layers
    assert fe.token_flops_outside_attention_pairs(cfg) == 3 * (
        2 * (4 * 64 + 3 * 160) + 64)
    assert fe.attention_pair_flops(cfg) == 32
    # position 0: itself; 15: its window; 16: itself + window 0's 4 chunks;
    # 37: 6 of its window + 8 chunks
    assert [int(n) for n in fe.attended_rows(cfg, [0, 15, 16, 37])] \
        == [1, 16, 5, 14]
    pairs = sum(int(fe.attended_rows(cfg, t)) for t in range(20))
    assert fe.sequence_flops(cfg, 17, 3) == 20 * 3 * (
        2 * (4 * 64 + 3 * 160) + 64) + 3 * pairs * 32
    assert fe.head_flops(cfg) == 2 * 8 * 10
    assert fe.weights_read(cfg) == 3 * (4 * 64 + 3 * 160 + 16 + 16) + 8 + 80
    assert fe.attn_kernel_bytes(cfg, 10) == 10 * 2 * 8 * 2 * 3
    assert fe.step_hbm_bytes(cfg, 10) == 2 * fe.weights_read(cfg) \
        + fe.attn_kernel_bytes(cfg, 10)


def test_flops_evabyte_counts_the_published_cut():
    """The configuration's file against the reference's own count, the
    number it states, and the bytes a cached row and a page take."""
    spec = spec_mod.Spec()
    cfg = spec.config("evabyte_l8")
    ref = spec.module("references", "evabyte")
    assert fe.param_count(cfg) == ref.param_count(cfg) == cfg["parameters"] \
        == 1621757952
    row = cfg["num_hidden_layers"] * 2 * cfg["hidden_size"] * 2
    assert row == cfg["cache_bytes_per_row"] == fe.attn_kernel_bytes(cfg, 1)
    assert row * cfg["chunk_size"] == cfg["page_bytes"]
    assert set(cfg["reduced"]) == set(cfg["published"]) \
        == set(cfg["why_reduced"]) == {"num_hidden_layers", "num_pred_heads"}


# ---------------------------------------------------------------- (e) ----

@pytest.mark.parametrize("num_blocks", [ENGINE["num_blocks"], 64])
def test_pool_replay_holds_what_the_engine_holds(num_blocks):
    """`tools/pool_pages.py` against the engine it stands for: over the
    whole tiny backlog, with the pool far from full and with a pool that
    bounds admission (held prompts wait for each other's first step), both
    take the same blocks and hand the same blocks back while their
    sequences live, the engine's high water is the replay's, and no row is
    failed for want of a block."""
    sys.path.insert(0, os.path.join(CELLS, "tools"))
    import pool_pages
    from harness.traffic import make_requests
    seed = 11
    pages = pool_pages.page_function(TINY)
    want = pool_pages.replay(TRAFFIC, TINY, seed, pages,
                             num_blocks=num_blocks)
    assert want.released > 0 and want.high_water <= num_blocks - 1
    free = pool_pages.replay(TRAFFIC, TINY, seed, pages)
    assert (want.high_water < free.high_water) \
        == (num_blocks < ENGINE["num_blocks"])
    # a file that names no page function is replayed a block a block_size
    # positions, nothing handed back while a sequence lives
    plain = {k: v for k, v in TINY.items() if k != "cache_pages"}
    assert pool_pages.replay(TRAFFIC, plain, seed,
                             pool_pages.page_function(plain)).released == 0

    # every request waits before the loop starts: the backlog as a window
    # finds it, whatever the threads' timing
    import jax.numpy as jnp
    from mxnet_tpu.models.evabyte import EvaByteConfig, EvaByteDecodeModel
    from mxnet_tpu.serving.decode import DecodeEngine
    model = EvaByteDecodeModel(EvaByteConfig.from_dict(TINY), seed=0,
                               dtype=jnp.float32, flash="0")
    eng = DecodeEngine(**model.engine_kwargs(), name="replayed",
                       default_deadline_ms=None, autostart=False,
                       **dict(ENGINE, prefill_buckets=(16, 32),
                              num_blocks=num_blocks))
    streams = [eng.submit(r.prompt, max_new_tokens=r.max_new)
               for r in make_requests(TRAFFIC, TINY["vocab_size"], seed, 0.0)]
    eng.start()
    for s in streams:
        s.result_wait(600.0)
    st = eng.stats()
    eng.stop()
    assert st["served"] == len(streams)
    kv = st["kv"]
    assert (kv["blocks_high_water"], kv["allocs"],
            kv["blocks_released_live"]) == (want.high_water, want.taken,
                                            want.released)
    assert st["steps"] == want.steps
