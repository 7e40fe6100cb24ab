"""kernels/paged_attention.py: the one walk over the live positions, held
against a whole-table masked softmax in float64, under a fold shaped like
each family's (GPT-2: twin pools, positions on axis 1, `HIGHEST`; latent:
one pool scored and summed, positions on axis 2); the addressing of both
seams; the plan's count; the spans a prefill chunk's keys and values are
made over."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.kernels.paged_attention import (
    NULL_BLOCK, chunk_addresses, chunk_spans, live_walk, softmax_fold,
    span_index, step_addresses, walk_plan, walk_sizes)
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
