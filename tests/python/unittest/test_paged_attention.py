"""kernels/paged_attention.py: the one walk over the live positions, held
against a whole-table masked softmax in float64, under a fold shaped like
each family's (GPT-2: twin pools, positions on axis 1, `HIGHEST`; latent:
one pool scored and summed, positions on axis 2); the addressing of both
seams; the plan's count; the spans a prefill chunk's keys and values are
made over; the latent family's kernel (`paged_latent_attention`, interpreted)
against that walk under the family's own fold."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.kernels.paged_attention import (
    NULL_BLOCK, PagedRows, chunk_addresses, chunk_pages, chunk_spans,
    live_walk, paged_latent_attention, paged_walked, softmax_fold,
    span_index, step_addresses, walk_plan, walk_sizes)
from mxnet_tpu.models import moe_mla
from mxnet_tpu.models.transformer import _live_attention

BS, H, DH, ROWS, SPAN = 4, 2, 8, 4, 8       # span: two table blocks a piece
LAYER = 1


def case(B, mb, seed=0, pools=2, width=H * DH):
    """``B`` rows, each its own ``mb`` blocks of random pools; lengths
    ragged, among them 1 and the table's full length."""
    rng = np.random.RandomState(seed)
    shape = (2, 1 + B * mb, BS, width)
    pools = tuple(rng.standard_normal(shape).astype(np.float32)
                  for _ in range(pools))
    tables = 1 + rng.permutation(B * mb).astype(np.int32).reshape(B, mb)
    positions = rng.randint(0, mb * BS, B).astype(np.int32)
    positions[:2] = [mb * BS - 1, 0]
    q = rng.standard_normal((B, width)).astype(np.float32)
    return pools, tables, positions, q


def whole_table(keys, values, tables, positions, score):
    """float64: row ``b`` attends over every position of its table, those
    past ``positions[b]`` masked; ``score(q_b, K) -> (T, heads)``."""
    out = []
    for b, (tab, pos) in enumerate(zip(tables, positions)):
        K = keys[LAYER, tab].reshape(-1, keys.shape[-1]).astype(np.float64)
        V = values[LAYER, tab].reshape(-1, values.shape[-1]) \
            .astype(np.float64)
        s = score(b, K)
        s[np.arange(len(K)) > pos] = -np.inf
        p = np.exp(s - s.max(0))
        out.append((p / p.sum(0)).T @ V)                # (heads, width)
    return np.stack(out)


def gpt2_shaped(pools, tables, positions, q):
    k, v = pools
    plan = walk_plan(positions, tables, BS, ROWS, SPAN)
    got = jax.jit(lambda *a: _live_attention(*a, LAYER, plan, H))(q, k, v)
    want = whole_table(
        k, v, tables, positions,
        lambda b, K: (K.reshape(-1, H, DH)
                      * q[b].reshape(H, DH).astype(np.float64)).sum(-1)
        / np.sqrt(DH))
    # head h keeps its own lanes
    want = np.concatenate([want[:, h, h * DH:(h + 1) * DH]
                           for h in range(H)], -1)
    return np.asarray(got), want


R = 12          # the latent-shaped fold sums the first R numbers of a row


def latent_attention(pool, tables, positions, qq):
    """As `moe_mla._absorbed_attention`'s walk: every head scores the whole
    pool row, the context is a sum of the rows' first ``R`` numbers."""
    def rows_block(qq_b, pos_b, walk):
        def fold(carry, pieces, tpos):
            lat, = pieces
            s = jnp.einsum("bhc,btc->bht", qq_b, lat,
                           preferred_element_type=jnp.float32)
            return softmax_fold(
                carry, s, tpos, pos_b, 2,
                lambda p: jnp.einsum("bht,btr->bhr", p, lat[..., :R]))
        _, den, acc = walk(fold, (qq_b.shape[0], H), R)
        return acc / den[..., None]

    plan = walk_plan(positions, tables, BS, ROWS, SPAN)
    return live_walk(plan, (pool,), LAYER, qq, rows_block)


def per_head(q):
    """``(B, width)`` -> ``(B, H, width)``: a query a head, all different."""
    return q[:, None] * np.arange(1, H + 1, dtype=np.float32)[None, :, None]


def latent_shaped(pools, tables, positions, q):
    pool, = pools
    qq = per_head(q)
    got = jax.jit(latent_attention)(pool, tables, positions, qq)
    want = whole_table(pool, pool[..., :R], tables, positions,
                       lambda b, K: K @ qq[b].astype(np.float64).T)
    return np.asarray(got), want


@pytest.mark.parametrize("mb", [8, 7], ids=["pieces", "whole_table"])
@pytest.mark.parametrize("B", [8, 6], ids=["row_blocks", "one_block"])
@pytest.mark.parametrize("family", ["gpt2", "latent"])
def test_walk_equals_whole_table_softmax(family, B, mb):
    """A batch that divides into row blocks and one that does not, a table
    that divides into pieces and one that does not, lengths from 1 to the
    table's end."""
    rb, cb = walk_sizes(B, mb, BS, ROWS, SPAN)
    assert (rb < B) == (B == 8) and (cb < mb) == (mb == 8)
    if family == "gpt2":
        got, want = gpt2_shaped(*case(B, mb))
    else:
        got, want = latent_shaped(*case(B, mb, pools=1, width=16))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_a_rows_result_is_the_same_whatever_rows_share_its_block():
    """Bit for bit: shuffle the rows and redraw the OTHER rows' lengths
    (long mates make the block walk on past this row's end, short ones
    stop it early); two rows keep their query, table and length."""
    B, mb = 8, 8
    (pool,), tables, positions, q = case(B, mb, pools=1, width=16)
    qq = per_head(q)
    attend = jax.jit(latent_attention)
    keep = [1, 5]                       # position 0 (one live), and a ragged
    positions[5] = 13
    ref = np.asarray(attend(pool, tables, positions, qq))[keep]
    assert np.abs(ref).min() > 0
    rng = np.random.RandomState(7)
    for trial in range(3):
        perm = rng.permutation(B)
        slots = [int(np.where(perm == k)[0][0]) for k in keep]
        pos2 = rng.randint(0, 4 if trial == 2 else mb * BS, B) \
            .astype(np.int32)
        pos2[slots] = positions[keep]
        got = np.asarray(attend(pool, tables[perm], pos2, qq[perm]))[slots]
        assert (got == ref).all(), "trial %d" % trial


@pytest.mark.parametrize("B,mb", [(8, 8), (6, 8), (8, 7)])
def test_the_plan_counts_rows_x_span_x_pieces(B, mb):
    _, tables, positions, _ = case(B, mb, seed=3)
    plan = walk_plan(positions, tables, BS, ROWS, SPAN)
    rb, cb = walk_sizes(B, mb, BS, ROWS, SPAN)
    assert plan.cb == cb and plan.tables.shape == (B // rb, rb, mb)
    blocks = np.sort(positions).reshape(B // rb, rb)
    pieces = blocks.max(1) // (cb * BS) + 1
    assert (np.asarray(plan.pieces) == pieces).all()
    assert int(plan.walked) == rb * cb * BS * int(pieces.sum())
    # sorted, and the way back is the way back
    assert (np.asarray(plan.positions) == blocks).all()
    assert (np.asarray(plan.order)[np.asarray(plan.inverse)]
            == np.arange(B)).all()


def test_addresses_send_padding_and_inactive_rows_to_the_null_block():
    table = np.asarray([5, 2, 7, NULL_BLOCK], np.int32)
    pos, valid, blk, slot = chunk_addresses(table, 6, 3, 8, BS)
    assert (np.asarray(pos) == 6 + np.arange(8)).all()
    assert np.asarray(valid).tolist() == [True] * 3 + [False] * 5
    assert np.asarray(blk).tolist() == [2, 2, 7] + [NULL_BLOCK] * 5
    assert np.asarray(slot)[:3].tolist() == [2, 3, 0]
    assert np.asarray(slot).max() < BS          # clipped inside the table
    tables = np.asarray([[5, 2], [7, 3], [0, 0]], np.int32)
    blk, slot = step_addresses(tables, np.asarray([5, 2, 0], np.int32),
                               np.asarray([True, True, False]), BS)
    assert np.asarray(blk).tolist() == [2, 7, NULL_BLOCK]
    assert np.asarray(slot).tolist() == [1, 2, 0]


@pytest.mark.parametrize("chunk,table_len,floor,want", [
    (256, 4096, 1024, (1024, 2048, 4096)),      # the latent cell's buckets
    (512, 4096, 1024, (1024, 2048, 4096)),
    (1024, 4096, 1024, (1024, 2048, 4096)),
    (2048, 4096, 1024, (2048, 4096)),           # the chunk lifts the floor
    (256, 3072, 1024, (1024, 2048, 3072)),      # a table no power of two
    (256, 1024, 1024, (1024,)),                 # the first span holds it all
    (16, 64, 1024, (64,)),                      # the tests' tiny shapes
    (64, 64, 8, (64,)),                         # T <= C: a full forward
    (128, 64, 8, (64,)),
    (8, 64, 8, (8, 16, 32, 64)),
    (8, 60, 16, (16, 32, 60)),
])
def test_chunk_spans_double_from_the_floor_and_end_at_the_table(
        chunk, table_len, floor, want):
    spans = chunk_spans(chunk, table_len, floor)
    assert spans == want
    if floor == 1024:                           # the default
        assert chunk_spans(chunk, table_len) == want
    assert list(spans) == sorted(set(spans)) and spans[-1] == table_len
    assert len(spans) == 1 or spans[0] == max(chunk, floor)
    assert all(b == 2 * a for a, b in zip(spans[:-2], spans[1:-1]))
    if table_len <= chunk:
        assert spans == (table_len,)


@pytest.mark.parametrize("chunk,table_len,floor", [
    (8, 64, 8), (4, 60, 16), (16, 64, 1024), (8, 8, 8)])
def test_span_index_is_the_smallest_span_that_holds_the_chunks_end(
        chunk, table_len, floor):
    """Over every ``start`` and ``length`` the table holds (and ``length``
    0, a chunk of padding alone)."""
    spans = chunk_spans(chunk, table_len, floor)
    start, length = np.meshgrid(np.arange(table_len + 1),
                                np.arange(chunk + 1), indexing="ij")
    held = start + length <= table_len
    start, length = start[held], length[held]
    got = np.asarray(jax.jit(jax.vmap(
        lambda s, n: span_index(spans, s + n)))(start, length))
    want = [min(i for i, s in enumerate(spans) if s >= e)
            for e in start + length]
    assert got.dtype == np.int32 and got.tolist() == want
    assert set(got.tolist()) == set(range(len(spans)))  # every span chosen
    assert int(span_index(spans, 3)) == 0               # host integers too


# ---------------------------------------------------------------------------
# the latent family's kernel against its lax walk
# ---------------------------------------------------------------------------
K_BS, K_MB = 16, 40             # tables of 640 positions: two chunks of 512
K_ROWS = {                      # name -> (position, active)
    "one_position": (0, True),
    "ends_on_the_first_page_edge": (15, True),
    "inactive_between": (200, False),
    "starts_a_second_page": (16, True),
    "ends_on_the_chunk_edge": (511, True),
    "starts_a_second_chunk": (512, True),
    "inactive_long": (639, False),
    "at_max_seq_len": (K_MB * K_BS - 1, True),
    "ragged": (300, True),
}
K_FAR = 3.0e4                   # what every block no table names holds


def latent_cfg(heads):
    """The latent family at small widths (a 192-number row in 256 lanes)
    and the cells' head counts: the kernel adapts to ``heads`` alone."""
    return moe_mla.MoEMLAConfig.from_dict({
        "hidden_size": 64, "num_hidden_layers": 2,
        "first_k_dense_replace": 1, "num_attention_heads": heads,
        "q_lora_rank": None, "kv_lora_rank": 128, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 64, "v_head_dim": 16, "intermediate_size": 64,
        "moe_intermediate_size": 16, "n_routed_experts": 4,
        "n_shared_experts": 1, "num_experts_per_tok": 2,
        "routed_scaling_factor": 1.0, "rms_norm_eps": 1e-5,
        "rope_theta": 1e4, "vocab_size": 32, "mla_use_nope": True})


@functools.lru_cache(maxsize=None)
def both_tiers(heads, dtype):
    """One step's attention of layer ``LAYER`` over the rows of `K_ROWS`,
    through `moe_mla._absorbed_attention` on the lax tier (`live_walk` and
    today's fold, over a pool that holds the step's new rows) and on the
    interpreted kernel tier (handed the pool BEFORE the write, `K_FAR` at
    the positions to be written, and the new rows beside it). Every block
    that no live page names, the null block among them, holds `K_FAR`.
    Returns the two results, the two counts, the pool the kernel handed
    back and the pool it should be."""
    cfg = latent_cfg(heads)
    dt = jnp.dtype(dtype)
    rng = np.random.RandomState(heads)
    positions = np.asarray([p for p, _ in K_ROWS.values()], np.int32)
    active = np.asarray([a for _, a in K_ROWS.values()])
    B = len(positions)
    pages = positions // K_BS + 1
    blocks = 1 + rng.permutation(int(pages.sum()) + 7)
    tables = np.full((B, K_MB), NULL_BLOCK, np.int32)
    at = 0
    for b in range(B):
        tables[b, :pages[b]] = blocks[at:at + pages[b]]
        at += pages[b]
    before = np.full((2, len(blocks) + 1, K_BS, cfg.cache_row_width), K_FAR,
                     np.float32)
    named = tables[tables != NULL_BLOCK]
    before[:, named] = rng.standard_normal(
        (2, len(named), K_BS, cfg.cache_row_width))
    new_rows = rng.standard_normal((B, cfg.cache_row_width)) \
        .astype(np.float32)
    blk, slot = tables[np.arange(B), positions // K_BS], positions % K_BS
    before[:, blk, slot] = K_FAR            # not yet written
    written = before.copy()
    written[LAYER, blk[active], slot[active]] = new_rows[active]
    before, written, new_rows = (jnp.asarray(a, dt)
                                 for a in (before, written, new_rows))
    lp = {"wkv_b": jnp.asarray(0.2 * rng.standard_normal(
        (cfg.kv_lora_rank, heads * 32)), dt)}
    q_nope = jnp.asarray(rng.standard_normal((B, heads, 16)), dt)
    q_rope = jnp.asarray(rng.standard_normal((B, heads, 64)), jnp.float32)
    pos, tab, act = (jnp.asarray(a) for a in (positions, tables, active))

    def attend(walk, pool):
        out, pool = jax.jit(
            lambda qn, qr, pool, rows: moe_mla._absorbed_attention(
                cfg, lp, qn, qr, pool, LAYER, walk, rows))(
                    q_nope, q_rope, pool, new_rows)
        return np.asarray(out, np.float32), np.asarray(pool, np.float32)
    lax_walk, walked = moe_mla._step_walk(cfg, pos, tab, act, K_BS, False,
                                          False)
    kernel_walk, copied = moe_mla._step_walk(cfg, pos, tab, act, K_BS, False,
                                             True)
    assert isinstance(kernel_walk, PagedRows) and kernel_walk.interpret
    want, _ = attend(lax_walk, written)
    got, pool = attend(kernel_walk, before)
    return (want, got, int(walked), int(copied), positions, active, pool,
            np.asarray(written, np.float32))


@pytest.mark.parametrize("row", list(K_ROWS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("heads", [128, 32])
def test_the_latent_kernel_equals_the_lax_walk(heads, dtype, row):
    """Ragged lengths in ONE step (`K_ROWS`), both cells' head counts, the
    float32 of the tests and the bfloat16 as served: the kernel's row is
    the lax walk's, and holds nothing of a block its table does not name
    (`K_FAR` would show at once)."""
    want, got, _, _, _, active, _, _ = both_tiers(heads, dtype)
    b = list(K_ROWS).index(row)
    assert np.isfinite(got).all()
    if not active[b]:
        assert (got[b] == 0).all()      # never read: stated, not garbage
        return
    assert np.abs(want[b]).max() > 1e-3
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(got[b], want[b], rtol=tol, atol=tol)
    assert np.abs(got[b]).max() < 100


@pytest.mark.parametrize("heads", [128, 32])
def test_the_kernel_copies_a_rows_own_pages_and_no_further(heads):
    """`kv_walked_tokens` of the two tiers: the kernel's is every active
    row's own pages, whole; the lax walk's a block's longest row for every
    row of the block."""
    _, _, walked, copied, positions, active, _, _ = both_tiers(heads,
                                                               "float32")
    own = (positions[active] // K_BS + 1) * K_BS
    assert copied == own.sum() == int(paged_walked(
        jnp.asarray(positions), jnp.asarray(active), K_BS))
    live = (positions[active] + 1).sum()
    assert live <= copied < live + K_BS * active.sum()
    assert walked > copied


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("heads", [128, 32])
def test_the_kernel_writes_the_active_rows_new_rows_and_nothing_else(
        heads, dtype):
    """The pool the kernel hands back: every active row's new latent row
    at its position of layer ``LAYER``, bit for bit, and every other
    number as it was: an inactive row's position, the other layer, the
    null block."""
    *_, pool, written = both_tiers(heads, dtype)
    assert (pool == written).all()


@pytest.mark.parametrize("heads,mb,want", [
    (128, 256, 32), (32, 512, 32), (128, 8, 8), (4, 16, 16)])
def test_chunk_pages_come_from_the_shapes(heads, mb, want):
    """The cells' shapes (128 and 32 heads over 640-wide bfloat16 rows,
    pages of 16) take chunks of 512 positions; a short table is one
    chunk."""
    assert chunk_pages(heads, mb, 16, 640, 2) == want


def test_the_kernel_serves_no_active_row_and_a_full_batch():
    """No row active: every result 0, no page read (the pool holds NaN
    everywhere) and none written; every row active and equal: equal
    results."""
    B, H, W = 4, 8, 128
    pool = jnp.full((1, 6, K_BS, W), jnp.nan, jnp.float32)
    q = jnp.ones((B, H, 64), jnp.float32)
    q_rope = jnp.ones((B, H, 32), jnp.float32)
    new = jnp.ones((B, W), jnp.float32)
    tables = jnp.zeros((B, 4), jnp.int32)
    pos = jnp.full((B,), 20, jnp.int32)
    none, kept = paged_latent_attention(q, q_rope, new, pool, 0, pos, tables,
                                        jnp.zeros((B,), bool), sm_scale=1.0,
                                        interpret=True)
    assert (np.asarray(none) == 0).all() and np.isnan(np.asarray(kept)).all()
    rng = np.random.RandomState(0)
    pool = jnp.asarray(rng.standard_normal((1, 6, K_BS, W)), jnp.float32)
    tables = jnp.tile(jnp.asarray([[3, 5, 0, 0]], jnp.int32), (B, 1))
    every, _ = paged_latent_attention(
        q, q_rope, new, pool, 0, pos, tables, jnp.ones((B,), bool),
        sm_scale=0.1, interpret=True)
    every = np.asarray(every)
    assert np.abs(every[0]).max() > 0 and (every == every[:1]).all()


def ragged_step(B, heads, dtype, seed):
    """A step of ``B`` rows over `latent_cfg`'s widths: lengths ragged up to
    the table's end, every third row inactive (interleaved with active
    ones), random pages. Returns the config, the pool BEFORE the step's
    write, the pool after it (as the lax tier reads it), the queries
    ``(q_lat [B, H, rkv], q_rope [B, H, dr])``, the new rows and the rows'
    ``(positions, tables, active)``."""
    cfg = latent_cfg(heads)
    dt = jnp.dtype(dtype)
    rng = np.random.RandomState(seed)
    positions = rng.randint(0, K_MB * K_BS, size=B).astype(np.int32)
    positions[:2] = (0, K_MB * K_BS - 1)
    active = np.arange(B) % 3 != 1
    blocks = 1 + rng.permutation(B * K_MB)
    tables = blocks.reshape(B, K_MB).astype(np.int32)
    row = cfg.cache_row_width
    before = rng.standard_normal((2, B * K_MB + 1, K_BS, row))
    new_rows = rng.standard_normal((B, row))
    pos, tab, act = (jnp.asarray(a) for a in (positions, tables, active))
    blk, slot = step_addresses(tab, pos, act, K_BS)
    before, new_rows = jnp.asarray(before, dt), jnp.asarray(new_rows, dt)
    written = before.at[LAYER, blk, slot].set(new_rows)
    q_lat = jnp.asarray(rng.standard_normal((B, heads, cfg.kv_lora_rank)),
                        jnp.float32)
    q_rope = jnp.asarray(rng.standard_normal((B, heads,
                                              cfg.qk_rope_head_dim)),
                         jnp.float32)
    return cfg, before, written, (q_lat, q_rope), new_rows, (pos, tab, act)


@pytest.mark.parametrize("B", [20, 16, 5])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_latent_kernel_takes_both_forms_and_zeroes_inactive_rows(B,
                                                                     dtype):
    """`mx_paged_latent_attn` (interpreted) against the lax walk
    (`moe_mla._latent_attend`'s, over the pool that already holds the new
    rows), inactive rows interleaved with active ones, in blocks of 16 rows
    (20: a last block of 4; 5: one block of the whole batch): rows-major
    and heads-major give the lax walk's ``u``, transposed for the latter,
    and every inactive row's result is exact zeros; the pool-dtype result
    (the rows-major default, what pangu and kimi read) is the float32 one
    rounded to the pool's dtype; every form writes the same pool."""
    cfg, before, written, (q_lat, q_rope), new, rows = ragged_step(
        B, 8, dtype, B)
    pos, tab, act = rows
    plan, _ = moe_mla._step_walk(cfg, pos, tab, act, K_BS, False, False)
    want, _ = moe_mla._latent_attend(cfg, q_lat, q_rope, written, LAYER,
                                     plan)
    want = np.asarray(want)
    sm = float(1.0 / np.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim))
    call = functools.partial(paged_latent_attention, new_rows=new,
                             pool=before, layer=LAYER, positions=pos,
                             tables=tab, active=act, sm_scale=sm,
                             interpret=True)
    rows_f32, pool = call(q_lat, q_rope, out_dtype=jnp.float32)
    heads, pool_h = call(jnp.swapaxes(q_lat, 0, 1), q_rope,
                         heads_major=True, out_dtype=jnp.float32)
    in_pool, _ = call(q_lat, q_rope)
    assert heads.shape == (8, B, cfg.kv_lora_rank)
    assert in_pool.dtype == before.dtype
    tol = 1e-5 if dtype == "float32" else 2e-2
    live = np.asarray(act)
    for got in (np.asarray(rows_f32), np.swapaxes(np.asarray(heads), 0, 1)):
        assert (got[~live] == 0).all()
        np.testing.assert_allclose(got[live], want[live], rtol=tol,
                                   atol=tol)
    # the cast the latent family made after the call, moved into it: the
    # float32 result rounded once, bit for bit
    assert np.array_equal(np.asarray(in_pool, np.float32), np.asarray(
        rows_f32.astype(before.dtype), np.float32))
    # the lax tier wrote the inactive rows' rows into the null block; the
    # kernel writes an active row's alone
    blk, slot = (np.asarray(a) for a in step_addresses(tab, pos, act, K_BS))
    kept = np.asarray(before).copy()
    kept[LAYER, blk[live], slot[live]] = np.asarray(new)[live]
    assert (np.asarray(pool) == kept).all()
    assert (np.asarray(pool_h) == kept).all()


@pytest.mark.parametrize("B", [20, 5])
def test_the_window_kernel_takes_both_forms_past_a_block_of_rows(B):
    """`mx_window_latent_attn` (interpreted) against its lax form over a
    ring of 32 rows, inactive rows interleaved, 20 rows (a last block of 4)
    and 5 (one block): both forms give the lax form's ``u``, exact zeros
    for an inactive row, and the same ring."""
    from mxnet_tpu.kernels.paged_attention import (
        window_latent_attention, window_latent_attention_lax)
    cfg = latent_cfg(8)
    rng = np.random.RandomState(B)
    W, row = 32, cfg.cache_row_width
    ring = jnp.asarray(rng.standard_normal((2, B, W, row)), jnp.float32)
    new = jnp.asarray(rng.standard_normal((B, row)), jnp.float32)
    pos = jnp.asarray(rng.randint(0, 4 * W, size=B), jnp.int32)
    act = jnp.asarray(np.arange(B) % 3 != 1)
    q_lat = jnp.asarray(rng.standard_normal((B, 8, cfg.kv_lora_rank)),
                        jnp.float32)
    q_rope = jnp.asarray(rng.standard_normal((B, 8, cfg.qk_rope_head_dim)),
                         jnp.float32)
    want, ring_want = window_latent_attention_lax(
        moe_mla._pool_query(q_lat, q_rope, ring), new, ring, 1, pos, act,
        sm_scale=0.1, width=cfg.kv_lora_rank)
    rows, ring_rows = window_latent_attention(q_lat, q_rope, new, ring, 1,
                                              pos, act, sm_scale=0.1,
                                              interpret=True)
    heads, ring_heads = window_latent_attention(
        jnp.swapaxes(q_lat, 0, 1), q_rope, new, ring, 1, pos, act,
        sm_scale=0.1, heads_major=True, out_dtype=jnp.float32,
        interpret=True)
    live = np.asarray(act)
    for got in (np.asarray(rows), np.swapaxes(np.asarray(heads), 0, 1)):
        assert (got[~live] == 0).all()
        assert np.abs(got - np.asarray(want)).max() < 1e-5
    for got in (ring_rows, ring_heads):
        assert np.array_equal(np.asarray(got), np.asarray(ring_want))


def test_the_latent_family_keeps_its_kernels_rows_major():
    """The latent family's step (pangu's) on the kernels' tier hands its
    kernel rows: ``latent_heads_major`` counts none over a trace of it."""
    from mxnet_tpu import profiler
    cfg = latent_cfg(8)
    model = moe_mla.MoEMLADecodeModel(cfg, seed=0, dtype=jnp.float32,
                                      flash="interpret")
    sd = lambda s, d: jax.ShapeDtypeStruct(s, d)               # noqa: E731
    cache = jax.tree_util.tree_map(lambda a: sd(a.shape, a.dtype),
                                   model.cache_spec(16, K_BS, 4))
    profiler.lowering_counters(reset=True)
    jax.eval_shape(model.step_fn, model.params, cache, sd((4,), jnp.int32),
                   sd((4,), jnp.int32), sd((4, K_MB), jnp.int32),
                   sd((4,), jnp.bool_))
    assert profiler.lowering_counters()["latent_heads_major"] == 0
