"""The Motif-3 family (mxnet_tpu/models/motif.py, the ring kernel of
kernels/paged_attention.py, the PolyNorm path of kernels/grouped_experts.py)
against its plain reference's copy (motif_reference.py loads
benchmark/cells/references/motif3.py by path), at a tiny preset in float32
on the CPU: hidden 64, the cut's five-layer pattern (dense full, window,
window, full, window), a window of 8, 4 streams, 16 experts of which 4 are
held.

Tolerance 1e-4 on logits of magnitude about 6: program and reference run the
same float32 arithmetic in another association (absorbed against expanded
attention, signal and noise heads combined in the latent space against
after the value projection, a ring against a band mask over the whole
sequence, a grouped product against a masked dense one). The streams or
Sinkhorn in bfloat16 miss it by an order of magnitude and more.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import motif_reference as ref
from mxnet_tpu.kernels import paged_attention as paged
from mxnet_tpu.kernels.grouped_experts import activate, grouped_experts
from mxnet_tpu.models import moe_mla as MM
from mxnet_tpu.models import motif as MT
from mxnet_tpu.models.decode_model import SlotPool
from mxnet_tpu.parallel.moe import routed_experts
from mxnet_tpu.serving import DecodeEngine

TOL = 1e-4
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
TINY = {
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
    "initializer_range": 0.2, "param_dtype": "float32"}
ACT = ("polynorm", MT.POLYNORM_EPS, 0.5, 0.5)


def cfg_of(config, **kw):
    return MT.MotifConfig.from_dict(config, block_k=16, step_row_block=2,
                                    step_col_blocks=2, **kw)


@pytest.fixture(scope="module")
def params():
    return ref.init_params(TINY, jax.random.PRNGKey(1))


def tokens_of(seed, n):
    return list(np.random.default_rng(seed).integers(0, 128, n)
                .astype(np.int32))


def test_config_from_the_published_keys_and_the_cut():
    import json
    with open(os.path.join(REPO, "benchmark", "cells", "configs",
                           "motif3_ep8.json")) as f:
        published = json.load(f)
    assert os.path.samefile(
        ref.logits_at.__code__.co_filename,
        os.path.join(REPO, "benchmark", "cells", "references", "motif3.py"))
    cfg = MT.MotifConfig.from_dict(published)
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.groups,
            cfg.signal_heads, cfg.head_dim, cfg.qk_nope_head_dim,
            cfg.v_head_dim, cfg.kv_lora_rank, cfg.q_lora_rank,
            cfg.sliding_window, cfg.moe_intermediate_size,
            cfg.intermediate_size, cfg.num_experts, cfg.experts_top_k,
            cfg.mhc_expansion_rate) == (4096, 80, 16, 4, 192, 128, 128, 512,
                                        1024, 128, 1280, 12288, 384, 8, 4)
    assert cfg.experts_held == (0, 48) and cfg.layers_kept == (0, 9, 10, 11,
                                                               12)
    assert [cfg.is_window(l) for l in range(5)] == [False, True, True,
                                                    False, True]
    assert [cfg.is_dense(l) for l in range(5)] == [True] + [False] * 4
    assert [cfg.kind_index(l) for l in range(5)] == [0, 0, 1, 1, 2]
    # the whole model: 20 full layers of 53
    whole = MT.MotifConfig.from_dict(dict(published, num_hidden_layers=53,
                                          layers_kept=None))
    assert whole.full_layers == 20
    assert cfg.latent_width == 576 and cfg.cache_row_width == 640
    model = MT.MotifDecodeModel(
        cfg, params={"embed": jnp.zeros((1, 1), jnp.bfloat16)}, flash="0")
    spec = model.cache_spec(11, 16, 512)
    assert spec["latent"].shape == (2, 11, 16, 640)
    assert isinstance(spec["window"], SlotPool)
    assert spec["window"].shape == (3, 512, 128, 640)
    assert 3 * 128 * 640 * 2 == published["ring_bytes_per_slot"]
    shape = lambda t: jax.tree_util.tree_map(lambda x: x.shape, t)  # noqa: E731
    key = jax.random.PRNGKey(0)
    assert shape(jax.eval_shape(lambda k: MT.init_motif(cfg_of(TINY), k),
                                key)) \
        == shape(jax.eval_shape(lambda k: ref.init_params(TINY, k), key))
    for bad in (dict(TINY, route_norm=False), dict(TINY, diff_v2=False),
                dict(TINY, num_noise_heads=1), dict(TINY, layers_kept=[0]),
                dict(TINY, rope_scaling={"apply_yarn_scaling": True})):
        with pytest.raises(ValueError):
            MT.MotifConfig.from_dict(bad)


def test_sinkhorn_is_doubly_stochastic():
    m = jnp.exp(jax.random.normal(jax.random.PRNGKey(3), (64, 4, 4)))
    for fn in (MT.sinkhorn, ref.sinkhorn):
        h = np.asarray(fn(m, 20))
        assert np.abs(h.sum(-1) - 1).max() < 1e-5
        assert np.abs(h.sum(-2) - 1).max() < 1e-5
        assert (h > 0).all()


# ---------------------------------------------------------------------------
# the ring kernel and the PolyNorm experts, alone
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("W,row", [(8, 128), (32, 256)])
def test_the_window_kernel_equals_its_lax_form(W, row):
    """`mx_window_latent_attn` (interpreted) against its lax form: positions
    before the first wrap, at it and well past it; an inactive row's slot
    is written by neither, and no other slot or layer either; its result is
    exact zeros, written by the kernel. Both forms of the queries and the
    result: rows-major, and heads-major as Motif's step hands them."""
    B, H, L, width, dr = 6, 8, 3, 64, 32
    ks = jax.random.split(jax.random.PRNGKey(W), 4)
    q = jax.random.normal(ks[0], (B, H, width), jnp.float32)
    q_rope = jax.random.normal(ks[3], (B, H, dr), jnp.float32)
    new = jax.random.normal(ks[1], (B, row), jnp.float32)
    ring = jax.random.normal(ks[2], (L, B, W, row), jnp.float32)
    pos = jnp.array([0, 5, W - 1, W, 3 * W + 2, 2], jnp.int32)
    active = jnp.array([True, True, False, True, True, True])
    u, got = paged.window_latent_attention(q, q_rope, new, ring, 1, pos,
                                           active, sm_scale=0.1,
                                           interpret=True)
    u_lax, want = paged.window_latent_attention_lax(
        MM._pool_query(q, q_rope, ring), new, ring, 1, pos, active,
        sm_scale=0.1, width=width)
    assert u.dtype == ring.dtype
    assert np.abs(np.asarray(u - u_lax)).max() < 1e-5
    assert np.array_equal(np.asarray(got), np.asarray(want))
    changed = np.argwhere(np.asarray(got != ring).any(-1))
    assert sorted(map(tuple, changed)) == sorted(
        (1, b, int(pos[b]) % W) for b in range(B) if active[b])
    assert not np.asarray(u[2]).any()
    u_h, got_h = paged.window_latent_attention(
        jnp.swapaxes(q, 0, 1), q_rope, new, ring, 1, pos, active,
        sm_scale=0.1, heads_major=True, out_dtype=jnp.float32,
        interpret=True)
    assert u_h.shape == (H, B, width)
    assert np.abs(np.asarray(jnp.swapaxes(u_h, 0, 1) - u_lax)).max() < 1e-5
    assert np.array_equal(np.asarray(got_h), np.asarray(want))
    assert not np.asarray(u_h[:, 2]).any()


def heads_major_forms(model, B=4, mb=4):
    """``latent_heads_major`` counted over ONE trace of ``model``'s decode
    step (shapes only: nothing is computed)."""
    from mxnet_tpu import profiler
    sd = lambda s, d: jax.ShapeDtypeStruct(s, d)               # noqa: E731
    cache = jax.tree_util.tree_map(lambda a: sd(a.shape, a.dtype),
                                   model.cache_spec(16, 4, B))
    profiler.lowering_counters(reset=True)
    jax.eval_shape(model.step_fn, model.params, cache, sd((B,), jnp.int32),
                   sd((B,), jnp.int32), sd((B, mb), jnp.int32),
                   sd((B,), jnp.bool_))
    return profiler.lowering_counters()["latent_heads_major"]


@pytest.mark.parametrize("flash,forms", [("interpret", 5), ("0", 0)],
                         ids=["kernels", "lax"])
def test_the_step_hands_its_kernels_the_heads_major_form(params, flash,
                                                         forms):
    """On the kernels' tier every attention layer of a step (two full, three
    window) takes the heads-major form: ``latent_heads_major`` counts 5 a
    trace of the step; the lax tier's walks take rows and count none."""
    model = MT.MotifDecodeModel(cfg_of(TINY), params=params, flash=flash)
    assert heads_major_forms(model) == forms


def test_polynorm_experts_in_the_kernel_equal_the_lax_forms(params):
    """`mx_grouped_experts` with PolyNorm in its down call (interpreted)
    against the lax forms of `routed_experts` and a row-by-row product: the
    same roundings, the same sums; the SiLU path is unchanged beside it."""
    lp = params["layers"][1]
    x = jnp.asarray(np.random.default_rng(4).standard_normal((21, 64)),
                    jnp.float32)
    kw = dict(held=(4, 4), top_k=4, scale=2.0)
    lax_part, counts, _ = routed_experts(lp, x, activation=ACT, **kw)
    kern, counts_k, cost = routed_experts(lp, x, activation=ACT,
                                          interpret=True, **kw)
    assert np.array_equal(np.asarray(counts), np.asarray(counts_k))
    assert int(cost["moe_form_grouped"]) == 1 and int(counts.sum()) > 0
    assert np.abs(np.asarray(kern - lax_part)).max() < 1e-5
    silu, _, _ = routed_experts(lp, x, **kw)
    assert np.abs(np.asarray(silu - lax_part)).max() > 1e-2
    # the kernel alone, expert 2 sent no row
    c = jnp.asarray([5, 9, 0, 2], jnp.int32)
    rows = x[:16]
    y, _ = grouped_experts(rows, c, lp["experts_gate"], lp["experts_up"],
                           lp["experts_down"], interpret=True,
                           activation=ACT, coef=lp["experts_poly"])
    e = np.repeat(np.arange(4), np.asarray(c))
    for r in range(16):
        g = rows[r] @ lp["experts_gate"][e[r]]
        h = activate(ACT, g[None], (rows[r] @ lp["experts_up"][e[r]])[None],
                     lp["experts_poly"][e[r]][None])
        assert np.abs(np.asarray(y[r] - (h @ lp["experts_down"][e[r]])[0])
                      ).max() < 1e-5


def test_the_shares_add_up_to_the_uncut_layer(params):
    """The routed parts of the 4 shares of 4 held experts, the shared expert
    counted ONCE, add up to the uncut reference's expert layer."""
    lp = dict(params["layers"][1])
    full = ref.init_params(dict(TINY, experts_held={"first": 0,
                                                    "count": 16}),
                           jax.random.PRNGKey(5))["layers"][1]
    lp.update({k: full[k] for k in full if k.startswith("experts_")})
    x = jnp.asarray(np.random.default_rng(6).standard_normal((19, 64)),
                    jnp.float32)
    want = np.asarray(ref._mlp(TINY, x, lp["shared_gate"], lp["shared_up"],
                               lp["shared_down"], lp["shared_poly"],
                               "float32")
                      + ref.routed_part(TINY, lp, x, "float32", first=0))
    total = MT._poly_mlp(cfg_of(TINY), x, lp["shared_gate"], lp["shared_up"],
                         lp["shared_down"], lp["shared_poly"])
    for first in range(0, 16, 4):
        share = dict(lp, **{k: lp[k][first:first + 4] for k in
                            ("experts_gate", "experts_up", "experts_down",
                             "experts_poly")})
        part, counts, _ = routed_experts(share, x, held=(first, 4), top_k=4,
                                         scale=2.0, activation=ACT)
        assert int(counts.sum()) > 0
        total = total + part
    assert np.abs(np.asarray(total) - want).max() < 1e-5


# ---------------------------------------------------------------------------
# through a real DecodeEngine
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
        nid, cache, aux, logits = MT.motif_decode_prefill(
            params, m.cfg, cache, tokens, start, length, table, slot,
            use_pallas=m.use_pallas, interpret=m.interpret,
            with_logits=True)
        jax.debug.callback(self._keep("prefill"), start + length, logits)
        return nid, cache, aux

    def step_fn(self, params, cache, token_ids, positions, tables, active):
        m = self.model
        ids, cache, aux, logits = MT.motif_decode_step(
            params, m.cfg, cache, token_ids, positions, tables, active,
            use_pallas=m.use_pallas, interpret=m.interpret,
            with_logits=True)
        jax.debug.callback(self._keep("step"), positions, active, logits)
        return ids, cache, aux

    def engine_kwargs(self):
        return dict(self.model.engine_kwargs(), prefill_fn=self.prefill_fn,
                    step_fn=self.step_fn)


def serve(params, name, prompts, new_tokens, flash="0", **cfg):
    rec = Recorder(MT.MotifDecodeModel(cfg_of(TINY, **cfg), params=params,
                                       flash=flash))
    eng = DecodeEngine(**rec.engine_kwargs(), name=name,
                       default_deadline_ms=None, block_size=4,
                       num_blocks=80, batch_size=4, max_seq_len=64,
                       prefill_buckets=(16,), prefill_chunk=16)
    streams = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
    outs = [s.result_wait(300.0) for s in streams]
    jax.effects_barrier()
    stats = eng.stats()
    eng.stop()
    return list(zip(prompts, outs)), rec, stats


def worst_logit_gap(params, served, rec):
    """Largest |program logit - reference logit| over every call the
    recorder saw (a prefill piece's last position, every active row of
    every step), each held against the sequence whose reference logits at
    that position it agrees with best (a wrong row agrees with none)."""
    want = []
    for prompt, out in served:
        toks = np.asarray(list(prompt) + list(out), np.int32)[None]
        pos = np.arange(toks.shape[1], dtype=np.int32)[None]
        want.append(np.asarray(ref.logits_at(TINY, params, toks, pos))[0])
    worst, n = 0.0, 0
    for kind, *arrays in rec.seen:
        if kind == "prefill":
            end, logits = arrays
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


@pytest.mark.parametrize("flash", ["0", "interpret"],
                         ids=["lax", "kernels"])
def test_prefill_in_pieces_then_steps_match_the_one_full_forward(params,
                                                                 flash):
    """Logits of every piece and step through the engine against the
    reference's ONE full forward: prompts of one, two and three pieces, 14
    tokens after each, so every ring wraps twice and more (19 to 51
    positions through a window of 8); on the lax tier and on the kernels' tier (the
    ring and paged walks, the flash prefill and the grouped PolyNorm
    experts, interpreted), which serve the same tokens. The counters count
    what the attention layers read."""
    prompts = [tokens_of(10 + i, n) for i, n in enumerate(PROMPTS)]
    served, rec, stats = serve(params, "motif" + flash, prompts, 14,
                               flash=flash)
    assert all(len(o) == 14 for _, o in served)
    assert stats["prefill_chunks"] >= 5         # 23 -> 2 pieces, 37 -> 3
    worst, n = worst_logit_gap(params, served, rec)
    assert n >= 7 + 4 * 13
    assert worst < TOL, worst
    m, rows = stats["model"], stats["tokens"] - stats["prefills"]
    assert m["gdla_window_rows"] == 3 * 8 * rows
    assert m["gdla_context_positions"] == 5 * m["kv_live_tokens"]
    assert m["moe_layer_steps"] == 4 * stats["steps"]
    assert m["prefill_gdla_context_positions"] == 5 * sum(
        min(s + 16, n) for n in PROMPTS for s in range(0, n, 16))
    assert stats["kv"]["state_bytes"] == 3 * 4 * 8 * 128 * 4


def test_streams_in_bfloat16_miss_the_tolerance(params):
    """The control: the same program with the mHC streams, projections and
    Sinkhorn in bfloat16 misses the tolerance the float32 streams meet."""
    toks = jnp.asarray(tokens_of(7, 16), jnp.int32)
    pos = jnp.arange(16, dtype=jnp.int32)[None]
    want = np.asarray(ref.logits_at(TINY, params, toks[None], pos))[0, -1]

    def prefill(**kw):
        cfg = cfg_of(TINY, **kw)
        model = MT.MotifDecodeModel(cfg, params=params, flash="0")
        cache = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype),
                                       model.cache_spec(8, 4, 1))
        run = jax.jit(lambda p, c: MT.motif_decode_prefill(
            p, cfg, c, toks, 0, 16, jnp.arange(1, 5, dtype=jnp.int32), 0,
            with_logits=True)[-1])
        return np.abs(np.asarray(run(params, cache)) - want).max()
    assert prefill() < TOL
    assert prefill(mhc_dtype="bfloat16") > 10 * TOL
