"""The EvaByte family (mxnet_tpu/models/evabyte.py, the page function behind
serving/kvcache.py, kernels/paged_attention.py::paged_head_attention) against
its plain reference's copy (evabyte_reference.py loads
benchmark/cells/references/evabyte.py by path), at a tiny preset in float32 on
the CPU: hidden 64, 4 heads of 16, window 64, chunk = block 4, 2 layers.

Tolerance 1e-4 on logits of magnitude about 3: program and reference run the
same float32 arithmetic in another association (a running softmax over pages
against one softmax over a window's columns; a summary pooled from a page
against one pooled from the whole sequence's chunks). Cache rows in bfloat16
where float32 is stated miss it by two orders (`test_..._bfloat16_rows_fail`).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import evabyte_reference as ref
from mxnet_tpu.kernels import paged_attention as paged
from mxnet_tpu.models import evabyte as E
from mxnet_tpu.serving import DecodeEngine
from mxnet_tpu.serving.kvcache import (NULL_BLOCK, CacheOverflow,
                                       PagedKVCache)

TOL = 1e-4
TINY = {
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "intermediate_size": 160, "vocab_size": 96,
    "window_size": 64, "chunk_size": 4, "rope_theta": 100000,
    "rms_norm_eps": 1e-5, "init_std": 0.2, "param_dtype": "float32"}
W, C = 64, 4
WINDOW_PAGES, SUMMARY_PAGES = W // C, W // (C * C)


def cfg_of(**kw):
    return E.EvaByteConfig.from_dict(TINY, block_k=16, step_row_block=2,
                                     step_col_blocks=4, **kw)


@pytest.fixture(scope="module")
def params():
    p = ref.init_params(TINY, jax.random.PRNGKey(1))
    # gains off zero, so that the unit offset is in the comparison
    bump = lambda a: a + 0.1                                    # noqa: E731
    p["norm_f"] = bump(p["norm_f"])
    for lp in p["layers"]:
        lp["norm_attn_in"] = bump(lp["norm_attn_in"])
        lp["norm_ffn_in"] = bump(lp["norm_ffn_in"])
    return p


def tokens_of(seed, n):
    return np.random.default_rng(seed).integers(0, 96, n).astype(np.int32)


# ---------------------------------------------------------------------------
# the page function and the allocator behind it
# ---------------------------------------------------------------------------
def test_page_function_names_the_window_and_the_summaries():
    pages = cfg_of().cache_pages
    wp = WINDOW_PAGES
    assert pages(0) == ((0, 0), (wp, wp))
    assert pages(1) == ((0, 1), (wp, wp))
    # the step that writes position 3 completes chunk 0: its summary page
    assert pages(4) == ((0, 1), (wp, wp + 1))
    assert pages(5) == ((0, 2), (wp, wp + 1))
    # position 63 is still window 0's: all its pages; 64 opens window 1
    assert pages(64) == ((0, wp), (wp, wp + 4))
    assert pages(65) == ((0, 1), (wp, wp + 4))
    assert pages(68) == ((0, 1), (wp, wp + 5))
    # a row never holds more than a window and its summaries
    for n in range(1, 400):
        (a, b), (c, d) = pages(n)
        assert (a, c) == (0, wp) and b - a <= wp
        assert d - c == -(-(n // C) // C)
    # the widest table is the longest sequence's
    assert max(b for n in range(1, 257) for _, b in pages(n)) \
        == max(b for _, b in pages(256))


def test_allocator_backs_the_new_entries_and_releases_the_dropped():
    kv = PagedKVCache(num_blocks=60, block_size=C, pages=cfg_of().cache_pages)
    assert kv.table_width(256) == WINDOW_PAGES + 16
    kv.allocate("a", 60)
    assert kv.live_blocks == 15 + 4
    released = []
    for n in range(61, 190):
        before = set(kv.table("a")) - {NULL_BLOCK}
        assert kv.growth("a") == kv.blocks_for(n) - len(before) \
            or (n - 1) % W == 0
        kv.extend("a")
        kv.check()
        table = kv.table("a")
        after = set(table) - {NULL_BLOCK}
        assert len(after) == kv.blocks_for(n) \
            <= WINDOW_PAGES + -(-(n // C) // C)
        if (n - 1) % W == 0:    # the first position of a window: the
            # window before it is dead, all but the page written now
            assert len(before - after) == WINDOW_PAGES - 1
            released.append(before - after)
            assert table[1:WINDOW_PAGES] == [NULL_BLOCK] * (WINDOW_PAGES - 1)
            if len(released) == 1:
                # what "a" handed back another sequence takes, while "a"
                # lives
                got = set(kv.allocate("b", 20)) - {NULL_BLOCK}
                assert len(got) == 5 + 2 and got <= released[0]
                kv.check()
    assert len(released) == 2
    st = kv.stats()
    assert st["blocks_released_live"] == 2 * (WINDOW_PAGES - 1)
    # a prompt's earlier windows are written through pages its last piece
    # no longer needs: held from admission to its first step
    assert kv.blocks_for(130, via=(32, 64, 96, 128)) == WINDOW_PAGES + 8
    free = kv.free_blocks
    held = kv.allocate("c", 130, (32, 64, 96, 128))
    kv.check()
    assert len(set(held) - {NULL_BLOCK}) == WINDOW_PAGES + 8
    assert kv.free_blocks == free - (WINDOW_PAGES + 8)
    kv.extend("c")
    kv.check()
    assert len(set(kv.table("c")) - {NULL_BLOCK}) == 1 + 8
    assert kv.free_blocks == free - (1 + 8)
    with pytest.raises(CacheOverflow):
        kv.allocate("d", 64 * 4)
    kv.check()
    for s in "abc":
        kv.free(s)
    kv.check()
    st = kv.stats()
    assert st["blocks_free"] == st["blocks_total"]
    assert st["allocs"] == st["frees"]


def test_a_family_without_a_page_function_is_served_by_the_same_code():
    """One region from the front, ``ceil(n / block_size)`` entries, nothing
    ever released while the sequence lives."""
    kv = PagedKVCache(num_blocks=40, block_size=4)
    kv.allocate("a", 5)
    for n in range(6, 90):
        assert kv.growth("a") == (1 if (n - 1) % 4 == 0 else 0)
        assert len(kv.extend("a")) == -(-n // 4)
        kv.check()
    assert kv.allocate("b", 9, (4, 8)) == kv.table("b") \
        and len(kv.table("b")) == 3
    assert kv.blocks_for(89, via=(16, 32)) == kv.blocks_for(89) == 23
    assert kv.table_width(256) == 64
    assert kv.stats()["blocks_released_live"] == 0
    assert NULL_BLOCK not in kv.table("a")


# ---------------------------------------------------------------------------
# through the engine, against the reference's one full forward
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
        nid, cache, aux, logits = E.evabyte_decode_prefill(
            params, m.cfg, cache, tokens, start, length, table,
            use_pallas=False, interpret=m.interpret, with_logits=True)
        jax.debug.callback(self._keep("prefill"), start + length, logits)
        return nid, cache, aux

    def step_fn(self, params, cache, token_ids, positions, tables, active):
        m = self.model
        ids, cache, aux, logits = E.evabyte_decode_step(
            params, m.cfg, cache, token_ids, positions, tables, active,
            use_pallas=False, interpret=m.interpret, with_logits=True)
        jax.debug.callback(self._keep("step"), positions, active, logits)
        return ids, cache, aux

    def engine_kwargs(self):
        return dict(self.model.engine_kwargs(), prefill_fn=self.prefill_fn,
                    step_fn=self.step_fn)


def engine_of(params, name, flash="0", model=None, **engine):
    rec = Recorder(model or E.EvaByteDecodeModel(cfg_of(), params=params,
                                                 flash=flash))
    engine = dict(dict(block_size=C, num_blocks=160, batch_size=4,
                       max_seq_len=320, prefill_buckets=(16, 32),
                       prefill_chunk=32), **engine)
    eng = DecodeEngine(**rec.engine_kwargs(), name=name,
                       default_deadline_ms=None, **engine)
    assert eng.program_counts() == (len(engine["prefill_buckets"]), 1)
    return eng, rec


def watch_the_pool(eng):
    """Check the allocator after every change of a table, and keep what each
    release handed back and who took it next."""
    kv = eng._kv
    seen = {"released": {}, "reused": 0, "most": 0}

    def checked(change):
        def call(seq_id, *args):
            before = set(kv._tables.get(seq_id, ())) - {NULL_BLOCK}
            out = change(seq_id, *args)
            kv.check()
            after = set(kv._tables[seq_id]) - {NULL_BLOCK}
            for b in before - after:
                seen["released"][b] = seq_id
            seen["reused"] += sum(
                1 for b in after - before
                if seen["released"].get(b, seq_id) != seq_id)
            seen["most"] = max(seen["most"], len(after))
            return out
        return call
    kv.allocate, kv.extend = checked(kv.allocate), checked(kv.extend)
    return seen


def serve(params, name, prompts, new_tokens, sync=False, **kw):
    """Serve ``prompts`` together; (prompts with outputs, recorder, stats,
    what the pool did)."""
    eng, rec = engine_of(params, name, autostart=False, **kw)
    pool = watch_the_pool(eng)
    if sync:
        eng._follows = lambda step: False
    streams = [eng.submit(p, max_new_tokens=n)
               for p, n in zip(prompts, new_tokens)]
    eng.start()
    outs = [s.result_wait(600.0) for s in streams]
    jax.effects_barrier()
    stats = eng.stats()
    eng.stop()
    eng._kv.check()
    return list(zip(prompts, outs)), rec, stats, pool


def worst_logit_gap(params, served, rec):
    """Largest |program logit - reference logit| over every call the
    recorder saw (a prefill piece's last position, every active row of
    every step), each held against the sequence whose reference logits at
    that position it agrees with best (a wrong row agrees with none)."""
    want = []
    for prompt, out in served:
        toks = np.asarray(list(prompt) + list(out), np.int32)
        n = len(toks)
        toks = np.pad(toks, (0, -n % C))[None]
        pos = np.arange(n, dtype=np.int32)[None]
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


# prompts that close three windows, one, none; prompts whose last chunk (210,
# 70, 50 % 4 = 2) decode completes; outputs that close one and two more
# windows (the second row passes 128 and 192)
PROMPTS = [210, 70, 50, 9]
NEW = [110, 130, 30, 12]


@pytest.mark.parametrize("flash", ["0", "interpret"],
                         ids=["lax", "kernels"])
def test_prefill_in_pieces_then_steps_match_the_one_full_forward(params,
                                                                 flash):
    """Logits of every piece and step through the engine against the
    reference's ONE full forward, on both tiers: a prompt that closes three
    windows in prefill and a row that closes two in decode, prompts whose
    last chunk decode completes, rows in different windows in one step (the
    four start in windows 3, 1, 0 and 0), rows that end early (an inactive
    row beside active ones). The allocator is checked after every release,
    no row ever holds more than a window and its summaries, and released
    pages are taken by another sequence."""
    prompts = [list(tokens_of(20 + i, n)) for i, n in enumerate(PROMPTS)]
    served, rec, stats, pool = serve(params, "eva" + flash[:1], prompts, NEW,
                                     flash=flash)
    assert [len(o) for _, o in served] == [110, 130, 30, 12]
    worst, n = worst_logit_gap(params, served, rec)
    assert n >= 7 + 3 + 2 + 1 + 110 + 130 + 30 + 12 - 4
    assert worst < TOL, worst
    model = stats["model"]
    # windows closed by a prefill piece: 210 -> 3, 70 -> 1; by a step:
    # position 255 of the first row, 127 and 191 of the second, 63 of the
    # third
    assert model["prefill_eva_windows_closed"] == 4
    assert model["eva_windows_closed"] == 4
    # a row of P prompt and N output tokens caches P + N - 1 positions
    assert model["prefill_eva_chunks_pooled"] == sum(p // C for p in PROMPTS)
    assert model["eva_chunks_pooled"] == sum(
        (p + n - 1) // C - p // C for p, n in zip(PROMPTS, NEW))
    # what the step attended is less than the contexts hold
    assert model["eva_window_rows"] + model["eva_summary_rows"] \
        == model["kv_live_tokens"] < model["eva_context_positions"]
    kv = stats["kv"]
    assert kv["blocks_released_live"] > 4 * (WINDOW_PAGES - 1)
    assert pool["reused"] > 0
    assert pool["most"] <= WINDOW_PAGES + 320 // (C * C)
    assert kv["blocks_free"] == kv["blocks_total"]


def test_cache_rows_in_bfloat16_where_float32_is_stated_fail(params):
    """The tolerance is tight enough to see the precision: the same run
    with the cache rows (k, v and the summaries) kept in bfloat16 misses
    it."""
    model = E.EvaByteDecodeModel(cfg_of(), params=params, flash="0")
    model.cache_dtype = jnp.bfloat16
    prompts = [list(tokens_of(20, 210))]
    eng, rec = engine_of(params, "evabf", model=model)
    out = eng.generate(prompts[0], max_new_tokens=20, timeout=600.0)
    jax.effects_barrier()
    eng.stop()
    worst, _ = worst_logit_gap(params, [(prompts[0], out)], rec)
    assert worst > 20 * TOL, worst


def test_the_loop_one_step_ahead_serves_the_synchronous_loops_tokens(params):
    """Rows cross a window's edge (pages released in the growth pass of a
    step dispatched BEHIND the one in flight) and tokens stay the same as
    with every step landed before the next is formed."""
    prompts = [list(tokens_of(40 + i, n)) for i, n in enumerate([60, 125])]
    new = [40, 20]
    ahead, _, st_a, _ = serve(params, "evaahead", prompts, new)
    sync, _, st_s, _ = serve(params, "evasync", prompts, new, sync=True)
    assert [o for _, o in ahead] == [o for _, o in sync]
    assert st_a["steps_ahead"] > 30 and st_s["steps_ahead"] == 0
    assert st_a["kv"]["blocks_released_live"] \
        == st_s["kv"]["blocks_released_live"] > 0


def test_a_pool_too_small_for_a_prompts_windows_sheds_it_typed(params):
    """A prompt is admitted when the most its pieces need fits; one that can
    never fit is shed as a cache overflow, not failed."""
    eng, _ = engine_of(params, "evasmall", num_blocks=18)
    ok = eng.submit(list(tokens_of(1, 40)), max_new_tokens=4)
    never = eng.submit(list(tokens_of(2, 130)), max_new_tokens=4)
    assert len(ok.result_wait(600.0)) == 4
    with pytest.raises(CacheOverflow):
        never.result_wait(600.0)
    assert never.outcome == "shed"
    eng.stop()
    eng._kv.check()


def test_admission_takes_what_it_priced_for_a_prompts_earlier_windows(params):
    """Two prompts of more than a window wait together on a pool that covers
    ONE prompt's pieces (its window's pages beside its summaries'): the
    first takes all of that at admission, so the second waits for the first's
    first step to hand the surplus back, and neither is failed in prefill.
    Beside its own need a waiter leaves what the next step of the rows in the
    batch may take (a block a region of the table each)."""
    need = WINDOW_PAGES + 8
    eng, _ = engine_of(params, "evahold", autostart=False,
                       num_blocks=need + 14 + 1)
    pool = watch_the_pool(eng)
    assert eng._kv.blocks_for(130, eng._piece_ends(130)) == need
    a = eng.submit(list(tokens_of(3, 130)), max_new_tokens=24)
    b = eng.submit(list(tokens_of(4, 130)), max_new_tokens=4)
    with eng._cv:
        _, _, admitted = eng._form_batch_locked()
    assert [s.rid for s in admitted] == [a.rid] and eng._waiting == [b]
    assert eng._kv.free_blocks == 14 < need + eng._kv.regions
    with eng._cv:       # back as submitted, for the engine's own loop
        eng._kv.free(a.rid)
        eng._slots[a._slot] = None
        eng._waiting.insert(0, a)
    eng.start()
    assert len(a.result_wait(600.0)) == 24 and len(b.result_wait(600.0)) == 4
    st = eng.stats()
    eng.stop()
    eng._kv.check()
    assert st["failed"] == 0 and st["kv"]["alloc_failures"] == 0
    # the second was admitted beside the LIVING first, not after it
    assert st["kv"]["blocks_high_water"] >= need + 1 + 8
    assert pool["most"] == need


def test_geometry_the_pages_cannot_hold_is_refused(params):
    model = E.EvaByteDecodeModel(cfg_of(), params=params, flash="0")
    with pytest.raises(ValueError, match="block_size 8 must equal"):
        DecodeEngine(**model.engine_kwargs(), name="evabs", block_size=8,
                     num_blocks=16, batch_size=2, max_seq_len=128,
                     prefill_buckets=(16,))
    with pytest.raises(Exception, match="straddle a chunk or a window"):
        DecodeEngine(**model.engine_kwargs(), name="evabucket", block_size=C,
                     num_blocks=64, batch_size=2, max_seq_len=128,
                     prefill_buckets=(48,))
    with pytest.raises(ValueError, match="whole pages"):
        E.EvaByteConfig.from_dict(dict(TINY, window_size=72))
    with pytest.raises(ValueError, match="one key-value head"):
        E.EvaByteConfig.from_dict(dict(TINY, num_key_value_heads=2))


# ---------------------------------------------------------------------------
# the kernel against the lax walk
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("active", [[True, True, True, True, True, True],
                                    [True, False, True, False, False, True],
                                    [False] * 6])
def test_paged_head_attention_interpreted_is_the_lax_walk(active):
    """`mx_eva_paged_attn` (interpreted) against `live_walk` over the same
    pools, tables and last positions: rows of one page, of several chunks
    of the kernel's walk and of a whole table; inactive rows read 0."""
    cfg = cfg_of()
    rng = np.random.default_rng(3)
    B, D, bs, mb, blocks, L = 6, 64, C, 40, 260, 2
    k_pool, v_pool = (jnp.asarray(rng.normal(size=(L, blocks, bs, D)),
                                  jnp.float32) for _ in range(2))
    q, k, v = (jnp.asarray(rng.normal(size=(B, D)), jnp.float32)
               for _ in range(3))
    tables = jnp.asarray(rng.permutation(np.arange(1, blocks))[:B * mb]
                         .reshape(B, mb), jnp.int32)
    last = jnp.asarray([0, 3, 17, 63, 100, mb * bs - 1], jnp.int32)
    active = jnp.asarray(active)
    plan = paged.walk_plan(last, tables, bs, 2, 16)
    for layer in range(L):
        want = E._step_attention(cfg, q, k, v, k_pool, v_pool, layer, plan)
        got = E._step_attention(cfg, q, k, v, k_pool, v_pool, layer,
                                (last, tables, active, True))
        np.testing.assert_allclose(
            np.asarray(got), np.where(np.asarray(active)[:, None],
                                      np.asarray(want), 0.0),
            rtol=1e-5, atol=1e-5)
