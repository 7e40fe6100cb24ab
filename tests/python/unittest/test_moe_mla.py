"""The latent-attention expert decoder (mxnet_tpu/models/moe_mla.py,
parallel/moe.py::routed_experts) against its plain reference's copy
(pangu_umoe_reference.py loads benchmark/cells/references/pangu_umoe.py by
path), at a tiny preset in float32 on the CPU.

Tolerance 1e-4 on logits of magnitude about 7: program and reference run the
same float32 arithmetic in a different association (absorbed against expanded
attention, a grouped product against a masked dense one, blockwise softmax),
which costs a few 1e-6; the same comparison with bfloat16 operands reads 1e-2
and more, and one test holds that it FAILS the tolerance.
"""
import functools
import os
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import pangu_umoe_reference as ref
from mxnet_tpu.kernels import paged_attention as paged
from mxnet_tpu.kernels.paged_attention import walk_plan
from mxnet_tpu.models import moe_mla as M
from mxnet_tpu.models.moe_mla import (MoEMLAConfig, MoEMLADecodeModel,
                                      init_moe_mla, moe_mla_forward)
from mxnet_tpu.parallel.moe import routed_experts
from mxnet_tpu.serving import DecodeEngine

TOL = 1e-4
TINY = {
    "hidden_size": 64, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 160, "moe_intermediate_size": 48,
    "n_routed_experts": 8, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-5,
    "rope_theta": 25600000, "vocab_size": 128,
    "experts_held": {"first": 0, "count": 8}, "initializer_range": 0.2,
    "param_dtype": "float32"}
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def tiny(**kw):
    return dict(TINY, **kw)


def cfg_of(config, **kw):
    return MoEMLAConfig.from_dict(config, block_k=16, step_row_block=2,
                                  step_col_blocks=2, **kw)


@pytest.fixture(scope="module")
def params():
    return ref.init_params(TINY, jax.random.PRNGKey(1))


def tokens_of(seed, shape):
    return np.random.default_rng(seed).integers(0, 128, shape) \
        .astype(np.int32)


def test_the_tests_reference_is_the_benchmarks_file():
    """Not a copy of it: what `ref` holds was defined by that very file."""
    path = os.path.join(REPO, "benchmark", "cells", "references",
                        "pangu_umoe.py")
    for fn in (ref.init_params, ref.logits_at):
        assert os.path.samefile(fn.__code__.co_filename, path)


def test_config_from_the_published_keys_and_the_cut():
    import json
    with open(os.path.join(REPO, "benchmark", "cells", "configs",
                           "pangu_umoe_ep16.json")) as f:
        published = json.load(f)
    cfg = MoEMLAConfig.from_dict(published)
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.q_lora_rank,
            cfg.kv_lora_rank) == (7680, 128, 1536, 512)
    assert cfg.experts_held == (0, 16) and cfg.n_routed_experts == 256
    assert cfg.latent_width == 576 and cfg.cache_row_width == 640
    assert cfg.num_expert_layers == 4 and cfg.is_dense(0)
    assert ref.param_count(published) == published["parameters"]
    with pytest.raises(ValueError):
        MoEMLAConfig.from_dict(tiny(experts_held={"first": 6, "count": 4}))


def test_init_makes_the_references_tree_in_the_dtype_asked():
    cfg = cfg_of(TINY)
    mine = init_moe_mla(cfg, jax.random.PRNGKey(0), jnp.bfloat16)
    theirs = ref.init_params(TINY, jax.random.PRNGKey(0))
    shape = lambda t: jax.tree_util.tree_map(lambda x: x.shape, t)  # noqa: E731
    assert shape(mine) == shape(theirs)
    assert all(x.dtype == jnp.bfloat16
               for x in jax.tree_util.tree_leaves(mine))
    assert float(mine["layers"][1]["norm_q"][0]) == 1.0


@pytest.mark.parametrize("flash", [False, True])
def test_full_forward_logits_match_the_reference(params, flash):
    toks = tokens_of(0, (2, 32))
    pos = np.tile(np.arange(32, dtype=np.int32)[None], (2, 1))
    want = np.asarray(ref.logits_at(TINY, params, toks, pos))
    got = np.asarray(moe_mla_forward(params, cfg_of(TINY), toks,
                                     interpret=flash))
    assert np.abs(want).max() > 1.0
    assert np.abs(got - want).max() < TOL


# ---------------------------------------------------------------------------
# through a real DecodeEngine
# ---------------------------------------------------------------------------
PROMPTS = [5, 13, 23, 37]        # whole (<= 16) and chunked (> 16) prompts


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
        nid, cache, aux, logits = M.moe_mla_decode_prefill(
            params, self.model.cfg, cache, tokens, start, length, table,
            use_pallas=False, interpret=self.model.interpret,
            with_logits=True)
        jax.debug.callback(self._keep("prefill"), table, start + length,
                           logits, aux["prefill_kv_expanded_tokens"])
        return nid, cache, aux

    def step_fn(self, params, cache, token_ids, positions, tables, active):
        ids, cache, aux, logits = M.moe_mla_decode_step(
            params, self.model.cfg, cache, token_ids, positions, tables,
            active, use_pallas=False, interpret=self.model.interpret,
            with_logits=True)
        jax.debug.callback(self._keep("step"), tables, positions, active,
                           logits)
        return ids, cache, aux

    def engine_kwargs(self):
        return dict(self.model.engine_kwargs(), prefill_fn=self.prefill_fn,
                    step_fn=self.step_fn)


def serve(params, name, flash="0", new_tokens=6, prompts=PROMPTS, **engine):
    """Serve prompts of the lengths ``prompts`` together through a real
    engine (``engine`` overrides its tiny geometry); returns (prompts with
    their outputs, the recorder, the engine's closing stats)."""
    rec = Recorder(MoEMLADecodeModel(cfg_of(TINY), params=params,
                                     flash=flash))
    engine = dict(dict(block_size=4, num_blocks=64, batch_size=4,
                       max_seq_len=64, prefill_buckets=(8, 16),
                       prefill_chunk=16), **engine)
    eng = DecodeEngine(**rec.engine_kwargs(), name=name,
                       default_deadline_ms=None, **engine)
    family = (len(engine["prefill_buckets"]), 1)
    assert eng.program_counts() == family
    prompts = [list(tokens_of(10 + i, (n,))) for i, n in enumerate(prompts)]
    streams = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
    outs = [s.result_wait(120.0) for s in streams]
    jax.effects_barrier()
    stats = eng.stats()
    assert eng.program_counts() == family       # nothing compiled in service
    eng.stop()
    return list(zip(prompts, outs)), rec, stats


def prefill_spans(rec):
    """``(start + length, positions expanded)`` of every prefill piece the
    recorder saw."""
    return [(int(end), int(expanded))
            for kind, _, end, _, expanded in
            (a for a in rec.seen if a[0] == "prefill")]


def worst_logit_gap(params32, served, rec):
    """Largest |program logit - reference logit| over every call the
    recorder saw: a prefill piece's last position and every active row of
    every step, each held against the sequence whose reference logits at
    that position it agrees with best (a wrong row agrees with none)."""
    worst, n = 0.0, 0
    want = {}
    for i, (prompt, out) in enumerate(served):
        toks = np.asarray(prompt + out, np.int32)[None]
        pos = np.arange(toks.shape[1], dtype=np.int32)[None]
        want[i] = np.asarray(ref.logits_at(TINY, params32, toks, pos))[0]
    for kind, *arrays in rec.seen:
        if kind == "prefill":
            table, end, logits, _ = arrays
            rows = [(table, int(end) - 1, logits)]
        else:
            tables, positions, active, logits = arrays
            rows = [(tables[r], int(positions[r]), logits[r])
                    for r in range(len(active)) if active[r]]
        for _, position, logits in rows:
            errs = [np.abs(logits - w[position]).max()
                    for w in want.values() if position < len(w)]
            worst = max(worst, min(errs))
            n += 1
    return worst, n


def test_prefill_then_decode_through_the_engine_matches_the_full_forward(
        params):
    served, rec, stats = serve(params, "mla32")
    assert all(len(o) == 6 for _, o in served)
    assert stats["prefill_chunks"] >= 5         # 23 -> 2 pieces, 37 -> 3
    worst, n = worst_logit_gap(params, served, rec)
    # every prefill piece (1 + 1 + 2 + 3) and 5 steps of 4 rows were seen
    assert n >= 7 + 20
    assert worst < TOL, worst
    # greedy tokens equal the reference's own argmax where it is decisive
    for i, (prompt, out) in enumerate(served):
        toks = np.asarray(prompt + out[:-1], np.int32)[None]
        pos = (len(prompt) - 1 + np.arange(len(out), dtype=np.int32))[None]
        lg = np.asarray(ref.logits_at(TINY, params, toks, pos))[0]
        assert (lg.argmax(-1) == np.asarray(out)).all()


def test_the_flash_tier_prefill_serves_the_same_tokens(params):
    """Chunked prefill, then decode, through a real engine on the kernels'
    tier (interpreted): the flash kernel and, in every expert layer of
    every piece and step, the grouped kernel `mx_grouped_experts` serve the
    lax tier's tokens (packed buckets there), and both tiers count what
    their forms computed."""
    plain, _, lax_stats = serve(params, "mlalax")
    flash, rec, stats = serve(params, "mlaflash", flash="interpret")
    assert [o for _, o in flash] == [o for _, o in plain]
    assert worst_logit_gap(params, flash, rec)[0] < TOL
    m, lm = stats["model"], lax_stats["model"]
    for pre in ("prefill_", ""):
        assert m[pre + "moe_assignments"] == lm[pre + "moe_assignments"]
        assert m[pre + "moe_form_grouped"] == m[pre + "moe_layer_steps"]
        assert lm[pre + "moe_form_grouped"] == 0
        assert m[pre + "moe_rows_computed"] >= m[pre + "moe_assignments"]
    # the lax tier: one bucket of all a piece's rows for each of 8 experts
    pieces = [min(16, n - s) for n in PROMPTS for s in range(0, n, 16)]
    assert lm["prefill_moe_rows_computed"] == sum(
        2 * 8 * 2 * (8 if p <= 8 else 16) for p in pieces)


def test_the_kernel_tier_step_serves_the_lax_tiers_tokens(params):
    """Batched decode through a real engine, rows of ragged lengths joining
    and leaving: the step whose walk is `mx_paged_latent_attn`
    (interpreted) yields the lax walk's tokens, and both tiers count what
    their walks read."""
    kw = dict(new_tokens=9, prompts=[3, 16, 29, 41, 7, 12], batch_size=4)
    plain, _, lax_stats = serve(params, "steplax", **kw)
    kern, rec, stats = serve(params, "stepkern", flash="interpret", **kw)
    assert [o for _, o in kern] == [o for _, o in plain]
    assert worst_logit_gap(params, kern, rec)[0] < TOL
    live = sum(len(q) + i + 1 for q, o in kern for i in range(len(o) - 1))
    m, lm = stats["model"], lax_stats["model"]
    assert m["kv_live_tokens"] == lm["kv_live_tokens"] == live
    # a row's own pages of 4, whole: under a page a row and step over
    rows = stats["tokens"] - stats["prefills"]
    assert live <= m["kv_walked_tokens"] < live + 4 * rows
    # the lax walk: every row as far as its block of 2 rows' longest
    assert lm["kv_walked_tokens"] > m["kv_walked_tokens"]


def test_bfloat16_operands_fail_the_tolerance(params):
    """The same comparison with the program's operands (weights, cache,
    matrix products' inputs) in bfloat16, the reference on the very same
    bfloat16 VALUES in float32: the tolerance tells the two apart."""
    p16 = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), params)
    served, rec, _ = serve(p16, "mla16")
    worst, _ = worst_logit_gap(p16, served, rec)
    assert worst > 10 * TOL, worst


def test_absorbed_step_equals_expanded_attention(params):
    """One layer's attention for the LAST position of a sequence, over the
    same latent rows: absorbed (the step) against expanded keys and values
    and a plain softmax."""
    cfg = cfg_of(TINY)
    lp = params["layers"][1]
    rng = np.random.default_rng(3)
    n, bs, mb = 11, 4, 4
    h = jnp.asarray(rng.standard_normal((n, 64)), jnp.float32)
    pos = jnp.arange(n, dtype=jnp.int32)
    q_nope, q_rope, rows = M._mla_project(cfg, lp, h, pos)
    pool = jnp.zeros((3, 8, bs, cfg.cache_row_width), jnp.float32)
    table = jnp.asarray([5, 2, 7, 0], jnp.int32)
    pool = pool.at[1, table[pos // bs], pos % bs].set(
        M._cache_rows(rows, pool))
    plan = walk_plan(pos[-1:], table[None], bs, cfg.step_row_block,
                     cfg.step_col_blocks * bs)
    got, _ = M._absorbed_attention(
        cfg, lp, q_nope[-1:], q_rope[-1:], pool, 1, plan)  # [1, H * dv]
    k, v = M._mla_expand(cfg, rows, *M._expansion_weights(cfg, lp["wkv_b"]))
    q = jnp.concatenate([q_nope, q_rope], -1)[-1]         # [H, dn + dr]
    s = jnp.einsum("hd,htd->ht", q, k) / np.sqrt(q.shape[-1])
    want = jnp.einsum("ht,htd->hd", jax.nn.softmax(s, -1), v).reshape(1, -1)
    assert np.abs(np.asarray(got - want)).max() < 1e-5


# ---------------------------------------------------------------------------
# the prefill's live span, at the served cell's geometry and the tiny widths
# ---------------------------------------------------------------------------
LONG_T, LONG_BS = 4096, 16       # spans (1024, 2048, 4096) for every bucket


@functools.lru_cache(maxsize=None)
def long_prefill(bucket, tier, whole):
    """The jitted prefill of one bucket over a 4,096-position table; with
    ``whole`` the keys and values are made over the whole table, as before
    there were spans."""
    cfg = MoEMLAConfig.from_dict(TINY, block_k=256)

    def fn(params, cache, tokens, start, length, table):
        spans = (lambda C, T: (T,)) if whole else paged.chunk_spans
        with mock.patch.object(paged, "chunk_spans", spans):
            return M.moe_mla_decode_prefill(
                params, cfg, cache, tokens, start, length, table,
                interpret=tier == "interpret", with_logits=True)
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def long_cache():
    """A table of 256 shuffled blocks and a pool whose EVERY row holds
    numbers: what lies past a chunk's end is stale, not zero."""
    rng = np.random.default_rng(5)
    mb = LONG_T // LONG_BS
    table = (1 + rng.permutation(mb)).astype(np.int32)
    pool = rng.standard_normal((3, mb + 1, LONG_BS, 128)).astype(np.float32)
    pool[..., 24:] = 0.0                        # the pad of a latent row
    return table, pool


@pytest.mark.parametrize("tier", ["lax", "interpret"])
@pytest.mark.parametrize("bucket,start,length,span", [
    (256, 0, 5, 1024),              # a short first piece
    (256, 768, 256, 1024),          # ends ON the first span
    (1024, 0, 1024, 1024),          # fills it from the start
    (256, 1024, 1, 2048),           # one past it
    (512, 1000, 25, 2048),          # one past it, start on no boundary
    (1024, 1024, 1024, 2048),       # ends ON the second span
    (512, 2048, 1, 4096),           # one past it
    (1024, 2048, 1000, 4096),       # a third piece
    (256, 3840, 256, 4096),         # ends ON the table's end
    (1024, 3072, 1024, 4096),
])
def test_prefill_over_the_live_span_equals_the_whole_table(
        params, tier, bucket, start, length, span):
    """Next id, logits and every written cache row of a chunk whose keys and
    values are made over the chosen span equal those made over the whole
    table; the counters say which span it was."""
    table, pool = long_cache()
    toks = np.zeros((bucket,), np.int32)
    toks[:length] = tokens_of(start + length, (length,))
    args = (params, {"latent": jnp.asarray(pool)}, toks, np.int32(start),
            np.int32(length), table)
    nid, cache, aux, logits = long_prefill(bucket, tier, False)(*args)
    nid_w, cache_w, aux_w, logits_w = long_prefill(bucket, tier, True)(*args)
    assert int(aux["prefill_kv_live_tokens"]) == start + length
    assert int(aux["prefill_kv_expanded_tokens"]) == span
    assert int(aux_w["prefill_kv_expanded_tokens"]) == LONG_T
    assert int(nid) == int(nid_w)
    assert np.abs(np.asarray(logits_w)).max() > 1.0
    assert np.abs(np.asarray(logits - logits_w)).max() < TOL
    got, want = np.asarray(cache["latent"]), np.asarray(cache_w["latent"])
    at = start + np.arange(length)
    written = want[:, table[at // LONG_BS], at % LONG_BS]
    assert np.abs(written - pool[:, table[at // LONG_BS],
                                 at % LONG_BS]).max() > 0.1
    assert np.abs(got - want).max() < TOL


def test_a_prompt_of_three_pieces_takes_a_span_a_piece(params):
    """Through a real engine at the served cell's geometry: a prompt of
    2,085 tokens is prefilled as 1,024 + 1,024 + 37, its keys and values
    made over 1,024, 2,048 and 4,096 positions; every piece and step agrees
    with the reference's full forward."""
    served, rec, stats = serve(
        params, "mlaspans", new_tokens=3, prompts=[2085, 300],
        block_size=LONG_BS, num_blocks=160, batch_size=2,
        max_seq_len=LONG_T, prefill_buckets=(256, 512, 1024),
        prefill_chunk=1024)
    seen = sorted(prefill_spans(rec))
    assert seen == [(300, 1024), (1024, 1024), (2048, 2048), (2085, 4096)]
    m = stats["model"]
    assert m["prefill_kv_live_tokens"] == sum(e for e, _ in seen)
    assert m["prefill_kv_expanded_tokens"] == sum(x for _, x in seen)
    worst, n = worst_logit_gap(params, served, rec)
    assert n >= 4 + 4 and worst < TOL, worst


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------
def expert_inputs(seed=4, n=24):
    return jnp.asarray(np.random.default_rng(seed).standard_normal((n, 64)),
                       jnp.float32)


def share_of(lp, first, count):
    return dict(lp, **{k: lp[k][first:first + count]
                       for k in ("experts_gate", "experts_up",
                                 "experts_down")})


TIERS = {"lax": {}, "interpret": {"interpret": True}}
tiers = pytest.mark.parametrize("tier", list(TIERS))


@tiers
@pytest.mark.parametrize("count,buckets", [
    (1, (64, 256, 512)), (2, (64, 256, 512)), (4, (64, 256, 512)),
    (8, (64, 256, 512)),
    (2, (4, 16)), (8, (3,)), (4, (2, 5))])
def test_the_shares_add_up_to_the_uncut_layer(params, count, buckets, tier):
    """Over all shares of the experts, the routed parts summed plus the
    shared expert ONCE equal the uncut reference layer; on the lax tier in
    the packed form of the grouped product (every expert's rows fit the
    first bucket, or only the second) and in the ragged one (no bucket fits
    the busiest expert), on the kernel tier through the grouped kernel
    (interpreted) whatever the buckets."""
    lp, x = params["layers"][2], expert_inputs()
    want = np.asarray(ref.expert_layer(TINY, lp, x, "float32"))
    total, seen, grouped = 0.0, 0, 0
    for first in range(0, 8, count):
        part, counts, cost = routed_experts(
            share_of(lp, first, count), x, held=(first, count), top_k=2,
            scale=2.5, buckets=buckets, **TIERS[tier])
        total = total + part
        seen += int(counts.sum())
        grouped += int(cost["moe_form_grouped"])
        assert int(cost["moe_rows_computed"]) >= int(counts.sum())
    assert grouped == (8 // count if tier == "interpret" else 0)
    shared = M._gated_mlp(x, lp["shared_gate"], lp["shared_up"],
                          lp["shared_down"])
    assert seen == 24 * 2                       # every assignment, once
    assert np.abs(np.asarray(total + shared) - want).max() < 1e-5
    # and one share is what the reference gives for that share
    held = tiny(experts_held={"first": 8 - count, "count": count})
    got, _ = M._ffn(cfg_of(held), share_of(lp, 8 - count, count), x)
    want1 = ref.expert_layer(held, share_of(lp, 8 - count, count), x,
                             "float32")
    assert np.abs(np.asarray(got - want1)).max() < 1e-5


def to_expert_3(lp, x):
    """A routing that sends every token of ``x`` to expert 3."""
    lp = dict(lp)
    lp["router"] = lp["router"].at[0, 3].set(50.0)
    return lp, x.at[:, 0].set(4.0)


@tiers
@pytest.mark.parametrize("buckets", [(64, 256, 512), (8, 64), (8, 16)])
def test_no_assignment_is_dropped_when_every_token_goes_to_one_expert(
        params, buckets, tier):
    """A routing that sends all forty tokens to expert 3: forty rows where a
    uniform router sends ten. Nothing is dropped, whichever form of the
    grouped product the counts choose: the first bucket, the second, or
    (buckets of 8 and 16) the ragged one; on the kernel tier the grouped
    kernel over ALL sorted rows (every assignment is held: its last
    capacity)."""
    lp, x = to_expert_3(params["layers"][1], expert_inputs(n=40))
    part, counts, cost = routed_experts(lp, x, held=(0, 8), top_k=2,
                                        scale=2.5, buckets=buckets,
                                        **TIERS[tier])
    assert int(counts[3]) == 40 and int(counts.sum()) == 80
    assert int(cost["moe_form_grouped"]) == (tier == "interpret")
    want = ref.routed_part(TINY, lp, x, "float32")
    assert np.abs(np.asarray(part - want)).max() < 1e-5
    # held alone, expert 3 still takes all forty (a capacity would not)
    part3, c3, _ = routed_experts(share_of(lp, 3, 1), x, held=(3, 1),
                                  top_k=2, scale=2.5, buckets=buckets,
                                  **TIERS[tier])
    assert c3.tolist() == [40]


def numpy_top2(lp, x):
    sigma = 1 / (1 + np.exp(-(np.asarray(x, np.float64)
                              @ np.asarray(lp["router"], np.float64))))
    return np.argsort(-sigma, axis=1)[:, :2]


@tiers
def test_counts_equal_a_numpy_count_and_padding_counts_nowhere(params, tier):
    """Buckets of 2 and 3 (the ragged form on the lax tier); the grouped
    kernel on its tier, over the held rows alone: a padding row is nobody's
    row there either."""
    lp, x = params["layers"][1], expert_inputs(seed=6)
    top = numpy_top2(lp, x)
    valid = np.arange(24) % 3 != 0
    kw = dict(held=(2, 4), top_k=2, scale=2.5, buckets=(2, 3), **TIERS[tier])
    _, counts, _ = routed_experts(share_of(lp, 2, 4), x, **kw)
    assert counts.tolist() == [(top == e).sum() for e in range(2, 6)]
    part, counts, cost = routed_experts(share_of(lp, 2, 4), x,
                                        valid=jnp.asarray(valid), **kw)
    assert counts.tolist() == [(top[valid] == e).sum() for e in range(2, 6)]
    assert np.abs(np.asarray(part)[~valid]).max() == 0.0
    assert int(cost["moe_form_grouped"]) == (tier == "interpret")
    held = dict(tiny(), experts_held={"first": 2, "count": 4})
    want = np.asarray(ref.routed_part(held, share_of(lp, 2, 4), x, "float32"))
    assert np.abs(np.asarray(part) - want)[valid].max() < 1e-5


def tiles_of(counts, tile):
    """(row tile, expert) pairs that share rows: what the grouped kernel's
    grid walks, counted in NumPy."""
    ends = np.cumsum(counts)
    return sum(int((e - 1) // tile - (e - c) // tile + 1)
               for e, c in zip(ends, counts) if c)


@pytest.mark.parametrize("n,buckets", [(24, (2,)), (40, (4, 8)),
                                       (300, (16,)), (300, (64, 256))])
def test_what_a_form_computes_equals_a_numpy_count(params, n, buckets):
    """``moe_rows_computed`` and ``moe_form_grouped`` of both tiers: a
    packed form computes count x bucket rows, the ragged one every
    assignment's row, the grouped kernel a tile for every (row tile,
    expert) pair that share rows, over the smallest capacity that holds
    the held total."""
    from mxnet_tpu.parallel.moe import ROW_TILE, _held_caps
    lp, x = params["layers"][2], expert_inputs(n=n)
    counts = np.bincount(numpy_top2(lp, x).ravel(), minlength=8)
    fits = [b for b in buckets if b >= counts.max()]
    for tier, kw in TIERS.items():
        part, got, cost = routed_experts(lp, x, held=(0, 8), top_k=2,
                                         scale=2.5, buckets=buckets, **kw)
        assert got.tolist() == counts.tolist()
        if tier == "lax":
            rows, grouped = 8 * fits[0] if fits else 2 * n, 0
        else:
            cap = min(c for c in _held_caps(2 * n, 1.0) if c >= counts.sum())
            tile = min(cap, ROW_TILE)
            rows, grouped = tiles_of(counts, tile) * tile, 1
        assert (int(cost["moe_rows_computed"]),
                int(cost["moe_form_grouped"])) == (rows, grouped), tier
        want = ref.routed_part(TINY, lp, x, "float32")
        assert np.abs(np.asarray(part - want)).max() < 1e-5


@tiers
def test_every_row_to_one_expert_at_a_pieces_size(params, tier):
    """160 tokens, all to expert 3 and each to one more: every one of the
    320 assignments is held, so the grouped kernel runs over ALL sorted
    rows (its last capacity) and expert 3's 160 rows span two row tiles."""
    lp, x = to_expert_3(params["layers"][1], expert_inputs(n=160))
    part, counts, cost = routed_experts(lp, x, held=(0, 8), top_k=2,
                                        scale=2.5, **TIERS[tier])
    assert int(counts[3]) == 160 and int(counts.sum()) == 320
    assert int(cost["moe_form_grouped"]) == (tier == "interpret")
    want = ref.routed_part(TINY, lp, x, "float32")
    assert np.abs(np.asarray(part - want)).max() < 1e-5


@tiers
def test_no_row_held_at_all_gives_zeros(params, tier):
    """Every token is padding: no assignment, no row, no grid step; what
    the kernel's buffers hold is never read."""
    lp, x = params["layers"][1], expert_inputs(n=80)
    part, counts, cost = routed_experts(
        lp, x, held=(0, 8), top_k=2, scale=2.5, buckets=(256,),
        valid=jnp.zeros((80,), bool), **TIERS[tier])
    assert counts.tolist() == [0] * 8
    assert np.abs(np.asarray(part)).max() == 0.0
    if tier == "interpret":
        assert {k: int(v) for k, v in cost.items()} == {
            "moe_rows_computed": 0, "moe_form_grouped": 1}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("counts", [
    [128, 128, 0, 0],       # a group that ends exactly on a tile's edge
    [127, 2, 100, 27],      # one that starts on a tile's last row
    [5, 0, 130, 3],         # an empty group; one across two tiles
    [0, 0, 0, 0], [0, 0, 0, 256]])
def test_the_grouped_kernel_on_tile_edges(counts, dtype):
    """kernels/grouped_experts.py alone (interpreted): each sorted row
    against its own expert's matrices wherever its group begins and ends;
    rows past the held total are nobody's and not compared."""
    from mxnet_tpu.kernels.grouped_experts import grouped_experts
    rng = np.random.default_rng(sum(counts))
    dt = jnp.dtype(dtype)
    x, wg, wu, wd = (jnp.asarray(rng.standard_normal(s) * a, dt)
                     for s, a in (((256, 64), 1.0), ((4, 64, 48), 0.2),
                                  ((4, 64, 48), 0.2), ((4, 48, 64), 0.2)))
    y, rows = grouped_experts(x, jnp.asarray(counts, jnp.int32), wg, wu, wd,
                              interpret=True)
    assert y.dtype == jnp.float32
    assert int(rows) == tiles_of(counts, 128) * 128
    of = np.repeat(np.arange(4), counts)
    f32 = lambda a: np.asarray(a, np.float32)               # noqa: E731
    xs = f32(x)[:len(of)]
    gate = np.einsum("rd,rdf->rf", xs, f32(wg)[of])
    h = gate / (1 + np.exp(-gate)) * np.einsum("rd,rdf->rf", xs, f32(wu)[of])
    want = np.einsum("rf,rfd->rd", f32(jnp.asarray(h, dt)), f32(wd)[of])
    if len(of):
        tol = 1e-5 if dtype == "float32" else 2e-2
        assert np.abs(np.asarray(y)[:len(of)] - want).max() < tol


def test_engine_aux_counters_equal_a_numpy_count(params):
    served, rec, stats = serve(params, "mlaaux", new_tokens=4)
    m = stats["model"]
    layers, k = 2, 2
    prompt_tokens = sum(PROMPTS)
    steps = stats["steps"]
    step_tokens = stats["tokens"] - stats["prefills"]
    # every expert held: each token of each expert layer makes k assignments
    assert m["prefill_moe_assignments"] == prompt_tokens * layers * k
    assert m["moe_assignments"] == step_tokens * layers * k
    assert m["moe_layer_steps"] == steps * layers
    pieces = sum(-(-n // 16) for n in PROMPTS)
    assert m["prefill_moe_layer_steps"] == pieces * layers
    assert m["moe_busiest"] * 8 >= m["moe_assignments"]
    assert 0 < m["moe_experts_touched"] <= steps * layers * 8
    # cached tokens attended over: a row at position p reads p + 1
    live = sum(len(p) + i + 1 for p, o in served for i in range(len(o) - 1))
    assert m["kv_live_tokens"] == live
    # what the prefill pieces attended over, and what their keys and values
    # were made over: a piece once, the tiny table (64) being one span
    ends = [min(s + 16, n) for n in PROMPTS for s in range(0, n, 16)]
    seen = prefill_spans(rec)
    assert sorted(e for e, _ in seen) == sorted(ends)
    assert all(expanded >= end for end, expanded in seen)
    assert m["prefill_kv_live_tokens"] == sum(ends)
    assert m["prefill_kv_expanded_tokens"] == 64 * pieces \
        == sum(x for _, x in seen)


@tiers
def test_routed_experts_over_an_ep_axis_sums_the_shares(params, tier):
    """The shard_map body: four shares of two experts each, the partial
    results summed across the `ep` axis (parallel/mesh.py); buckets of 2
    and 4 send the busier shares to the ragged form or the grouped kernel."""
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel import get_mesh
    mesh = get_mesh(dp=2, ep=4)
    assert mesh.axis_names[-1] == "ep" and mesh.shape["ep"] == 4
    lp, x = params["layers"][2], expert_inputs(seed=8)
    leaves = {k: lp[k] for k in ("router", "experts_gate", "experts_up",
                                 "experts_down")}
    specs = {"router": P(), "experts_gate": P("ep"), "experts_up": P("ep"),
             "experts_down": P("ep")}

    def body(p, x):
        part, counts, _ = routed_experts(p, x, held=(0, 2), top_k=2,
                                         scale=2.5, axis_name="ep",
                                         buckets=(2, 4), **TIERS[tier])
        return part, counts

    # (the Pallas INTERPRETER cannot slice a varying table by a grid index
    # under the varying-axes check; the compiled kernel states what its
    # result varies over, `test_tpu_compile.py`)
    part, counts = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(specs, P()), out_specs=(P(), P("ep")),
        check_vma=tier == "lax"))(leaves, x)
    want = ref.routed_part(TINY, lp, x, "float32")
    assert np.abs(np.asarray(part - want)).max() < 1e-5
    assert counts.shape == (8,) and int(counts.sum()) == 24 * 2


# ---------------------------------------------------------------------------
# the cache seam
# ---------------------------------------------------------------------------
def test_the_cache_is_one_latent_pool_described_and_donated(params):
    model = MoEMLADecodeModel(cfg_of(TINY), params=params, flash="0")
    eng = DecodeEngine(**model.engine_kwargs(), name="mlaseam", block_size=4,
                       num_blocks=16, batch_size=2, max_seq_len=32,
                       prefill_buckets=(8,), prefill_chunk=0,
                       default_deadline_ms=None, autostart=False)
    spec = eng._cache_spec
    assert list(spec) == ["latent"]
    assert isinstance(spec["latent"], jax.ShapeDtypeStruct)
    # 16 + 8 numbers a token a layer, padded to whole lanes
    assert spec["latent"].shape == (3, 16, 4, 128)
    assert eng._cache["latent"].dtype == jnp.float32
    assert eng.stats()["kv"]["pool_bytes"] == 3 * 16 * 4 * 128 * 4
    assert eng.program_counts() == (1, 1)       # AOT from the description
    # the whole pytree is the donated argument, and it comes back aliased
    from mxnet_tpu.serving.program_cache import _donate_supported
    want = (1,) if _donate_supported() else ()
    assert tuple(eng._step_b._donate_argnums) == want
    assert tuple(eng._prefill_b._donate_argnums) == want
    i32 = np.int32
    sd = jax.ShapeDtypeStruct
    text = jax.jit(model.step_fn, donate_argnums=(1,)).lower(
        eng._params, spec, sd((2,), i32), sd((2,), i32), sd((2, 8), i32),
        sd((2,), np.bool_)).as_text()
    assert "tf.aliasing_output" in text or "jax.buffer_donor" in text
    # bfloat16 parameters make a bfloat16 pool: the engine casts nothing
    p16 = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), params)
    m16 = MoEMLADecodeModel(cfg_of(TINY), params=p16, flash="0")
    e16 = DecodeEngine(**m16.engine_kwargs(), name="mlaseam16", block_size=4,
                       num_blocks=16, batch_size=2, max_seq_len=32,
                       prefill_buckets=(8,), prefill_chunk=0,
                       default_deadline_ms=None, warmup=False,
                       autostart=False)
    assert e16._cache["latent"].dtype == jnp.bfloat16
    assert all(x.dtype == jnp.bfloat16
               for x in jax.tree_util.tree_leaves(e16._params))
    assert e16.stats()["kv"]["pool_bytes"] == 3 * 16 * 4 * 128 * 2


def test_a_mesh_keeps_the_latent_pool_whole(params):
    from mxnet_tpu.parallel import get_mesh
    mesh = get_mesh(dp=2, tp=4)
    model = MoEMLADecodeModel(cfg_of(TINY), params=params, flash="0",
                              mesh=mesh)
    eng = DecodeEngine(**model.engine_kwargs(), name="mlamesh", block_size=4,
                       num_blocks=16, batch_size=2, max_seq_len=32,
                       prefill_buckets=(8,), prefill_chunk=0, mesh=mesh,
                       default_deadline_ms=None)
    # 128 divides by tp = 4, and page_sharding would split it: the model's
    # own statement (no head axis in a latent row) wins
    assert eng._cache["latent"].sharding.spec == \
        jax.sharding.PartitionSpec()
    prompt = list(tokens_of(30, (7,)))
    out = eng.generate(prompt, max_new_tokens=4)
    eng.stop()
    plain = DecodeEngine(**MoEMLADecodeModel(
        cfg_of(TINY), params=params, flash="0").engine_kwargs(),
        name="mlaplain", block_size=4, num_blocks=16, batch_size=2,
        max_seq_len=32, prefill_buckets=(8,), prefill_chunk=0,
        default_deadline_ms=None)
    assert plain.generate(prompt, max_new_tokens=4) == out
    plain.stop()
