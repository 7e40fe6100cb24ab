"""The Kimi-Linear family (mxnet_tpu/models/kimi_linear.py, kernels/kda.py)
against its plain reference's copy (kimi_linear_reference.py loads
benchmark/cells/references/kimi_linear.py by path), at a tiny preset in
float32 on the CPU.

Tolerance 1e-4 on logits of magnitude about 5: program and reference run the
same float32 arithmetic in another association (the chunked scan and the
step's rearranged update against the token-by-token recurrence, absorbed
against expanded attention, a grouped product against a masked dense one).
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import kimi_linear_reference as ref
from mxnet_tpu.kernels import kda
from mxnet_tpu.models import kimi_linear as K
from mxnet_tpu.models import moe_mla as M
from mxnet_tpu.models.decode_model import SlotPool
from mxnet_tpu.parallel.moe import routed_experts
from mxnet_tpu.serving import DecodeEngine

TOL = 1e-4
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
# two periods of KDA, KDA, KDA, MLA; a dense layer, then expert layers
TINY = {
    "hidden_size": 64, "num_hidden_layers": 8, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "q_lora_rank": None, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 160, "moe_intermediate_size": 48,
    "num_experts": 8, "num_shared_experts": 1, "num_experts_per_token": 2,
    "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-5,
    "mla_use_nope": True, "moe_renormalize": True, "vocab_size": 128,
    "linear_attn_config": {
        "kda_layers": [1, 2, 3, 5, 6, 7], "full_attn_layers": [4, 8],
        "num_heads": 4, "head_dim": 8, "short_conv_kernel_size": 4},
    "experts_held": {"first": 0, "count": 8}, "initializer_range": 0.2,
    "param_dtype": "float32"}
# one period (the isolation tests: half the programs to compile)
ONE_PERIOD = dict(TINY, num_hidden_layers=4, linear_attn_config=dict(
    TINY["linear_attn_config"], kda_layers=[1, 2, 3], full_attn_layers=[4]))
# the latent layers alone (NoPE, direct query) beside one KDA layer
MLA_HEAVY = dict(TINY, num_hidden_layers=3, linear_attn_config=dict(
    TINY["linear_attn_config"], kda_layers=[2], full_attn_layers=[1, 3]))


def cfg_of(config, **kw):
    return K.KimiLinearConfig.from_dict(
        config, block_k=16, step_row_block=2, step_col_blocks=2,
        **dict(dict(kda_chunk=8, kda_sub=4), **kw))


@pytest.fixture(scope="module")
def params():
    return ref.init_params(ONE_PERIOD, jax.random.PRNGKey(1))


def tokens_of(seed, shape):
    return np.random.default_rng(seed).integers(0, 128, shape) \
        .astype(np.int32)


def test_config_from_the_published_keys_and_the_cut():
    import json
    with open(os.path.join(REPO, "benchmark", "cells", "configs",
                           "kimi_linear_ep8.json")) as f:
        published = json.load(f)
    assert os.path.samefile(
        ref.logits_at.__code__.co_filename,
        os.path.join(REPO, "benchmark", "cells", "references",
                     "kimi_linear.py"))
    cfg = K.KimiLinearConfig.from_dict(published)
    assert (cfg.hidden_size, cfg.kda_num_heads, cfg.kda_head_dim,
            cfg.short_conv_kernel_size, cfg.kv_lora_rank) \
        == (2304, 32, 128, 4, 512)
    assert cfg.q_lora_rank is None and cfg.mla_use_nope
    assert cfg.experts_held == (0, 32) and cfg.num_experts == 256
    assert cfg.latent_width == 576 and cfg.cache_row_width == 640
    assert [cfg.is_kda(l) for l in range(8)] == [True] * 3 + [False] \
        + [True] * 3 + [False]
    assert [cfg.kind_index(l) for l in range(8)] == [0, 1, 2, 0, 3, 4, 5, 1]
    assert ref.param_count(published) == published["parameters"]
    model = K.KimiLinearDecodeModel(
        cfg, params={"embed": jnp.zeros((1, 1), jnp.bfloat16)}, flash="0")
    spec = model.cache_spec(11, 16, 256)
    per_slot = sum(int(np.prod(p.shape[2:])) * p.dtype.itemsize
                   * p.shape[0] for p in spec.values()
                   if isinstance(p, SlotPool))
    assert per_slot == published["state_bytes_per_slot"]
    assert spec["latent"].shape == (2, 11, 16, 640)
    assert 2 * 640 * 2 == published["cache_bytes_per_token"]
    shape = lambda t: jax.tree_util.tree_map(lambda x: x.shape, t)  # noqa: E731
    key = jax.random.PRNGKey(0)
    assert shape(jax.eval_shape(
        lambda k: K.init_kimi_linear(cfg_of(TINY), k), key)) \
        == shape(jax.eval_shape(lambda k: ref.init_params(TINY, k), key))
    for bad in (dict(TINY, moe_renormalize=False),
                dict(TINY, num_hidden_layers=7),
                dict(TINY, experts_held={"first": 6, "count": 4})):
        with pytest.raises(ValueError):
            K.KimiLinearConfig.from_dict(bad)


# ---------------------------------------------------------------------------
# (a) the chunked scan and the step against the recurrence
# ---------------------------------------------------------------------------
def delta_inputs(T, H, dk, seed, fast=False):
    r = np.random.default_rng(seed)
    unit = lambda t: t / np.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    q = unit(r.standard_normal((T, H, dk))) / np.sqrt(dk)
    k = unit(r.standard_normal((T, H, dk)))
    v = r.standard_normal((T, H, dk))
    # the fastest channels lose e^-16 a token: exp(-G) would overflow
    rate = np.exp(r.uniform(0, np.log(16.0) if fast else 0.5, (T, H, 1)))
    g = -rate * np.log1p(np.exp(r.standard_normal((T, H, dk))))
    beta = 1 / (1 + np.exp(-r.standard_normal((T, H))))
    s0 = r.standard_normal((H, dk, dk))
    return [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta, s0)]


@pytest.mark.parametrize("T,chunk,sub,fast,mm", [
    (40, 8, 4, False, "float32"),       # chunks divide the piece
    (37, 16, 8, False, "float32"),      # they do not: the last is padded
    (64, 64, 16, True, "float32"),      # one chunk, served lengths, fast decay
    (100, 32, 16, True, "float32"),     # neither divides, fast decay
    (5, 64, 16, False, "float32"),      # a piece shorter than a sub-chunk
    (24, 8, 8, False, "float32"),       # no sub-chunks: the direct form alone
    (37, 16, 8, False, "bfloat16"),     # the served branch: bfloat16 weights
    #                                     make the products against the state
    #                                     take bfloat16 operands
])
def test_chunked_scan_equals_the_recurrence(T, chunk, sub, fast, mm):
    """From a NON-ZERO incoming state: outputs and final state, in float32
    to rounding, and with bfloat16 operands in the products against the
    state (float32 sums, float32 state out) to bfloat16's."""
    q, k, v, g, beta, s0 = delta_inputs(T, 3, 8, T, fast)
    with jax.default_matmul_precision("highest"):
        want_o, want_s = kda.kda_recurrence(q, k, v, g, beta, s0)
        got_o, got_s = kda.kda_chunk_scan(q, k, v, g, beta, s0, chunk=chunk,
                                          sub=sub, mm_dtype=jnp.dtype(mm))
    assert got_o.dtype == got_s.dtype == jnp.float32
    assert np.isfinite(np.asarray(got_o)).all()
    tol = 1e-5 if mm == "float32" else 2 ** -7
    for got, want in ((got_o, want_o), (got_s, want_s)):
        err = np.abs(np.asarray(got - want)).max()
        assert err < tol * max(1.0, float(np.abs(np.asarray(want)).max()))
    if mm == "bfloat16":        # and the branch is the rounded one
        assert np.abs(np.asarray(got_s - want_s)).max() > 1e-5


@pytest.mark.parametrize("tier", ["lax", "interpret"])
@pytest.mark.parametrize("active", [
    [1, 0, 1, 1, 0, 1], [0] * 6, [1] * 6, [0, 0, 0, 1, 0, 0]])
def test_step_updates_the_active_rows_of_its_layer_only(tier, active):
    """`mx_kda_step` (interpreted) and the lax tier against one token of
    the recurrence; an inactive row, and every other layer, keep their bits
    and an inactive row reads 0."""
    B, H, dk = 6, 2, 8
    q, k, v, g, beta, _ = delta_inputs(B, H, dk, 1)
    state = jnp.asarray(np.random.default_rng(3).standard_normal(
        (3, B, H, dk, dk)), jnp.float32)
    active = jnp.asarray(active, bool)
    o, new = kda.kda_step(state, 1, q, k, v, g, beta, active,
                          interpret=tier == "interpret")
    for b in range(B):
        if active[b]:
            want_o, want_s = kda.kda_recurrence(
                *(t[b:b + 1] for t in (q, k, v, g, beta)), state[1, b])
            assert np.abs(np.asarray(o[b] - want_o[0])).max() < 1e-5
            assert np.abs(np.asarray(new[1, b] - want_s)).max() < 1e-5
        else:
            assert not np.asarray(o[b]).any()
            assert np.array_equal(new[1, b], state[1, b])
    assert np.array_equal(new[0], state[0])
    assert np.array_equal(new[2], state[2])


# ---------------------------------------------------------------------------
# (b), (c), (e) through a real DecodeEngine
# ---------------------------------------------------------------------------
class Recorder:
    """A DecodeModel whose bodies also hand every call's logits to the
    host: the engine sees the seam's three results."""

    def __init__(self, model):
        self.model, self.seen = model, []

    def _keep(self, kind):
        def keep(*arrays):
            self.seen.append((kind,) + tuple(np.asarray(a) for a in arrays))
        return keep

    def prefill_fn(self, params, cache, tokens, start, length, table, slot):
        m = self.model
        nid, cache, aux, logits = K.kimi_linear_decode_prefill(
            params, m.cfg, cache, tokens, start, length, table, slot,
            use_pallas=False, interpret=m.interpret, with_logits=True)
        jax.debug.callback(self._keep("prefill"), slot, start + length,
                           logits)
        return nid, cache, aux

    def step_fn(self, params, cache, token_ids, positions, tables, active):
        m = self.model
        ids, cache, aux, logits = K.kimi_linear_decode_step(
            params, m.cfg, cache, token_ids, positions, tables, active,
            use_pallas=False, interpret=m.interpret, with_logits=True)
        jax.debug.callback(self._keep("step"), positions, active, logits)
        return ids, cache, aux

    def engine_kwargs(self):
        return dict(self.model.engine_kwargs(), prefill_fn=self.prefill_fn,
                    step_fn=self.step_fn)


def engine_of(config, params, name, flash="0", **engine):
    rec = Recorder(K.KimiLinearDecodeModel(cfg_of(config), params=params,
                                           flash=flash))
    engine = dict(dict(block_size=4, num_blocks=64, batch_size=4,
                       max_seq_len=64, prefill_buckets=(8, 16),
                       prefill_chunk=16), **engine)
    eng = DecodeEngine(**rec.engine_kwargs(), name=name,
                       default_deadline_ms=None, **engine)
    assert eng.program_counts() == (len(engine["prefill_buckets"]), 1)
    return eng, rec


def serve(config, params, name, prompts, new_tokens=6, **kw):
    """Serve ``prompts`` together; (prompts with outputs, recorder, stats)."""
    eng, rec = engine_of(config, params, name, **kw)
    streams = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
    outs = [s.result_wait(180.0) for s in streams]
    jax.effects_barrier()
    stats = eng.stats()
    eng.stop()
    return list(zip(prompts, outs)), rec, stats


def worst_logit_gap(config, params, served, rec):
    """Largest |program logit - reference logit| over every call the
    recorder saw (a prefill piece's last position, every active row of
    every step), each held against the sequence whose reference logits at
    that position it agrees with best (a wrong row agrees with none)."""
    want = []
    for prompt, out in served:
        toks = np.asarray(list(prompt) + list(out), np.int32)[None]
        pos = np.arange(toks.shape[1], dtype=np.int32)[None]
        want.append(np.asarray(ref.logits_at(config, params, toks, pos))[0])
    worst, n = 0.0, 0
    for kind, *arrays in rec.seen:
        if kind == "prefill":
            _, end, logits = arrays
            rows = [(int(end) - 1, logits)]
        else:
            positions, active, logits = arrays
            rows = [(int(positions[r]), logits[r])
                    for r in range(len(active)) if active[r]]
        for position, logits in rows:
            worst = max(worst, min(np.abs(logits - w[position]).max()
                                   for w in want if position < len(w)))
            n += 1
    return worst, n


PROMPTS = [5, 13, 23, 37]        # whole (<= 16) and chunked (> 16) prompts


@pytest.mark.parametrize("config,flash,buckets", [
    (TINY, "interpret", (16,)), (ONE_PERIOD, "0", (8, 16)),
    (MLA_HEAVY, "0", (16,))],
    ids=["two_periods_kernels", "one_period_lax", "nope_direct_query"])
def test_prefill_in_pieces_then_steps_match_the_one_full_forward(
        config, flash, buckets):
    """(b), (e): logits of every piece and step through the engine against
    the reference's ONE full forward; KDA and MLA layers, dense then expert
    layers; NoPE and the direct query projection; the kernels' tier
    (`mx_kda_step` and the flash kernel, interpreted) and the lax tier."""
    params = ref.init_params(config, jax.random.PRNGKey(1))
    prompts = [list(tokens_of(10 + i, (n,))) for i, n in enumerate(PROMPTS)]
    served, rec, stats = serve(config, params, "kimi" + flash[:1]
                               + str(config["num_hidden_layers"]), prompts,
                               flash=flash, prefill_buckets=buckets)
    assert all(len(o) == 6 for _, o in served)
    assert stats["prefill_chunks"] >= 5         # 23 -> 2 pieces, 37 -> 3
    worst, n = worst_logit_gap(config, params, served, rec)
    assert n >= 7 + 20
    assert worst < TOL, worst
    kda_layers = len(config["linear_attn_config"]["kda_layers"])
    model = stats["model"]
    assert model["kda_layer_steps"] == stats["steps"] * kda_layers
    assert model["kda_rows_updated"] == kda_layers * (
        stats["tokens"] - stats["prefills"])
    assert model["prefill_kda_chunks"] == kda_layers * sum(
        -(-min(16, n - s) // 8) for n in PROMPTS for s in range(0, n, 16))
    assert stats["kv"]["state_bytes"] == kda_layers * 4 * (
        4 * 8 * 8 * 4 + 3 * 3 * 32 * 4)
    assert stats["kv"]["pool_bytes"] == (
        config["num_hidden_layers"] - kda_layers) * 64 * 4 * 128 * 4 \
        if config is not MLA_HEAVY else True


def test_the_kernel_tier_step_serves_the_lax_tiers_tokens():
    """Two latent layers beside a KDA layer, batched decode through a real
    engine: the step on the kernels' tier (`mx_paged_latent_attn` and
    `mx_kda_step`, interpreted) yields the lax tier's tokens; both tiers
    count what their latent walks read, ONE layer's."""
    params = ref.init_params(MLA_HEAVY, jax.random.PRNGKey(2))
    prompts = [list(tokens_of(30 + i, (n,)))
               for i, n in enumerate([3, 16, 29, 41, 7])]
    kw = dict(new_tokens=8, prefill_buckets=(16,))
    plain, _, lax_stats = serve(MLA_HEAVY, params, "kimisteplax", prompts,
                                **kw)
    kern, rec, stats = serve(MLA_HEAVY, params, "kimistepkern", prompts,
                             flash="interpret", **kw)
    assert [list(o) for _, o in kern] == [list(o) for _, o in plain]
    assert worst_logit_gap(MLA_HEAVY, params, kern, rec)[0] < TOL
    live = sum(len(q) + i + 1 for q, o in kern for i in range(len(o) - 1))
    m, lm = stats["model"], lax_stats["model"]
    assert m["kv_live_tokens"] == lm["kv_live_tokens"] == live
    rows = stats["tokens"] - stats["prefills"]
    assert live <= m["kv_walked_tokens"] < live + 4 * rows
    assert lm["kv_walked_tokens"] > m["kv_walked_tokens"]
    # the expert layers of every piece and step: the grouped kernel
    # (`mx_grouped_experts`, interpreted) on its tier, never on the other
    for pre in ("prefill_", ""):
        assert m[pre + "moe_assignments"] == lm[pre + "moe_assignments"]
        assert m[pre + "moe_form_grouped"] == m[pre + "moe_layer_steps"] > 0
        assert lm[pre + "moe_form_grouped"] == 0
        assert m[pre + "moe_rows_computed"] >= m[pre + "moe_assignments"]


def test_the_kernel_tier_step_keeps_its_latent_kernel_rows_major(params):
    """Kimi-Linear's latent layers hand `mx_paged_latent_attn` rows, as the
    per-head product after it reads them: ``latent_heads_major`` counts
    none over a trace of the kernel tier's step."""
    from mxnet_tpu import profiler
    model = K.KimiLinearDecodeModel(cfg_of(ONE_PERIOD), params=params,
                                    flash="interpret")
    sd = lambda s, d: jax.ShapeDtypeStruct(s, d)               # noqa: E731
    cache = jax.tree_util.tree_map(lambda a: sd(a.shape, a.dtype),
                                   model.cache_spec(16, 4, 4))
    profiler.lowering_counters(reset=True)
    jax.eval_shape(model.step_fn, model.params, cache, sd((4,), jnp.int32),
                   sd((4,), jnp.int32), sd((4, 4), jnp.int32),
                   sd((4,), jnp.bool_))
    assert profiler.lowering_counters()["latent_heads_major"] == 0


def test_mla_projection_without_rotary_does_not_read_positions(params):
    """(e) `mla_use_nope`: the same rows whatever the positions; with the
    rotary on, they differ."""
    cfg = cfg_of(TINY)
    lp = params["layers"][3]
    h = jnp.asarray(np.random.default_rng(0).standard_normal((5, 64)),
                    jnp.float32)
    a = M._mla_project(cfg, lp, h, jnp.arange(5))
    b = M._mla_project(cfg, lp, h, jnp.arange(5) + 7)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    import dataclasses
    rot = dataclasses.replace(cfg, mla_use_nope=False)
    c = M._mla_project(rot, lp, h, jnp.arange(5) + 7)
    assert np.array_equal(a[0], c[0]) and not np.allclose(a[2], c[2])


def test_a_slot_used_again_and_a_neighbour_in_prefill_leave_no_trace():
    """(c) state isolation, in ONE engine of two slots. A sequence served
    alone, then AGAIN through the slot that still holds its own final state
    while a neighbour's prompt arrives beside it in three pieces: bit for
    bit the same logits (a piece with ``start == 0`` starts from zero state
    whatever the slot held; a step leaves an inactive row's state and tail
    alone; a piece writes its own slot only). The neighbour, and a third
    sequence through the slot the first one left, agree with the
    reference."""
    config = ONE_PERIOD
    params = ref.init_params(config, jax.random.PRNGKey(1))
    first, second = list(tokens_of(1, (21,))), list(tokens_of(2, (37,)))
    eng, rec = engine_of(config, params, "kslots", batch_size=2,
                         prefill_buckets=(16,))

    def steps_of_slot0():
        jax.effects_barrier()
        out = [a[-1][0] for a in rec.seen if a[0] == "step" and a[2][0]]
        del rec.seen[:]
        return out

    alone = eng.submit(first, max_new_tokens=12).result_wait(180.0)
    want = steps_of_slot0()
    s1 = eng.submit(first, max_new_tokens=12)
    while not s1.tokens:                # decoding before the neighbour comes
        pass
    s2 = eng.submit(second, max_new_tokens=3)
    out1, out2 = s1.result_wait(180.0), s2.result_wait(180.0)
    jax.effects_barrier()
    seen = list(rec.seen)
    got = steps_of_slot0()
    assert eng.stats()["prefill_chunks"] == 2 + 2 + 3 and len(out2) == 3
    assert out1 == alone and len(got) == len(want) == 11
    for a, b in zip(got, want):
        assert np.array_equal(a, b)
    # the third sequence takes slot 0, which holds the first one's state
    out3 = eng.submit(second, max_new_tokens=5).result_wait(180.0)
    jax.effects_barrier()
    rec.seen[:0] = seen
    eng.stop()
    worst, n = worst_logit_gap(
        config, params, [(first, out1), (second, out2), (second, out3)], rec)
    assert n >= 2 + 3 + 3 + 11 + 2 + 4 and worst < TOL, worst


# ---------------------------------------------------------------------------
# (d) the expert shares
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("tier", [{}, {"interpret": True}],
                         ids=["lax", "interpret"])
def test_all_eight_shares_add_up_to_the_uncut_layer(params, tier):
    """The routed parts that all 8 shares of a layer give, the shared
    expert counted ONCE, add up to the uncut reference's expert layer; on
    the lax tier and through the grouped kernel (interpreted)."""
    lp = params["layers"][1]
    x = jnp.asarray(np.random.default_rng(2).standard_normal((19, 64)),
                    jnp.float32)
    want = np.asarray(ref.expert_layer(TINY, lp, x, "float32"))
    total = 0.0
    for first in range(8):
        share = dict(lp, **{k: lp[k][first:first + 1] for k in
                            ("experts_gate", "experts_up", "experts_down")})
        part, counts, cost = routed_experts(
            share, x, held=(first, 1), top_k=2,
            scale=TINY["routed_scaling_factor"], **tier)
        assert int(counts.sum()) > 0
        assert int(cost["moe_form_grouped"]) == bool(tier)
        total = total + part
    shared = M._gated_mlp(x, lp["shared_gate"], lp["shared_up"],
                          lp["shared_down"])
    assert np.abs(np.asarray(total + shared) - want).max() < 1e-5
