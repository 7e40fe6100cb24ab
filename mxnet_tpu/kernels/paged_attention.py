"""The device side of the paged cache, written once: where a position
lives in a page pool, the decode step's walk over the live positions, and
the prefill chunk's attention.

`serving/kvcache.py` owns the HOST side (which blocks a sequence holds);
the model families of `models/` own their contractions and layouts. What
they share is here, and is pure `jax.lax` today: a paged-attention Pallas
kernel would go in behind `live_walk`.

**The page format.** A pool is ``(layers, num_blocks, block_size, width)``.
Position ``p`` of a sequence whose block table is ``table`` lives at
``pool[l, table[p // block_size], p % block_size]``. Block `NULL_BLOCK` is
never allocated: every padded or inactive write of a fixed-shape program
goes there, and reads mask by length, so such a write cannot alias a live
sequence.

**Masking.** Reads mask additively with `MASKED`: ``exp(-1e30 - m)`` is
exactly 0.0 in float32, so a position not yet written, or another
sequence's, cannot perturb a real row's bits. Chunked prefill is
bit-identical to whole-prompt prefill, and batched decode to solo decode,
because of it.
"""
from __future__ import annotations

import collections

import jax.numpy as jnp
from jax import lax

from .flash_attention import blockwise_attention, flash_attention_with_lse

#: Block id reserved for padding/inactive scatter targets. Never allocated.
NULL_BLOCK = 0

#: Additive attention mask: exp(MASKED - m) is exactly 0.0 in float32.
MASKED = -1e30


# ---------------------------------------------------------------------------
# addressing
# ---------------------------------------------------------------------------
def chunk_addresses(table, start, length, chunk, block_size):
    """Where a prefill chunk's rows go. ``table`` ``(mb,)`` is the sequence's
    block table, the chunk holds global positions ``start .. start + chunk -
    1`` of which the first ``length`` are real. Returns ``(pos, valid, blk,
    slot)``, each ``(chunk,)``: the global positions (not clipped), which
    rows are real, and the block and slot each row writes to; a padded row
    writes to the null block."""
    last = table.shape[0] * block_size - 1
    idx = jnp.arange(chunk, dtype=jnp.int32)
    pos = start + idx
    valid = idx < length
    at = jnp.clip(pos, 0, last)
    blk = jnp.where(valid, table[at // block_size], NULL_BLOCK)
    return pos, valid, blk, at % block_size


def step_addresses(tables, positions, active, block_size):
    """Where a decode step's rows go. ``tables`` ``(B, mb)``, ``positions``
    ``(B,)`` the position each row writes, ``active`` ``(B,)``. Returns
    ``(blk, slot)``, each ``(B,)``; an inactive row writes to the null
    block."""
    blk = jnp.take_along_axis(tables, (positions // block_size)[:, None],
                              axis=1)
    return (jnp.where(active, blk[:, 0], NULL_BLOCK),
            positions % block_size)


def gather_pages(pool, layer, tab):
    """The pages of layer ``layer`` that ``tab`` (any shape of block ids)
    names: ``tab.shape + (block_size, width)``. ONE gather over both
    leading axes: ``pool[layer][tab]`` would copy the layer's pool out
    first (2.8 ms a prefill at GPT-2's widths, PERF.md PR 27)."""
    return pool[layer, tab]


# ---------------------------------------------------------------------------
# the decode step's walk over the live positions
# ---------------------------------------------------------------------------
# What every layer's walk of one step shares: order (B,) the rows by
# ascending position and inverse the way back; positions (blocks, rb) and
# tables (blocks, rb, mb) sorted; pieces (blocks,) each block walks; cb
# table blocks a piece (static); walked () positions one layer covers.
WalkPlan = collections.namedtuple(
    "WalkPlan", "order inverse positions tables pieces cb walked")


def walk_sizes(B, mb, block_size, rows, span):
    """``(rows per block, table blocks per piece)`` of the walk for ``B``
    rows over tables of ``mb`` blocks, asked for ``rows`` rows a block and
    ``span`` positions a piece. One block over all rows where ``B`` does
    not divide into several; one piece over the whole table where ``mb``
    does not."""
    rb = rows if B > rows and B % rows == 0 else B
    cb = max(1, span // block_size)
    return rb, (mb if mb % cb else cb)


def walk_plan(positions, tables, block_size, rows, span):
    """The step's plan, made once and handed to every layer: the rows
    sorted by length (and the way back) and split into blocks of ``rows``,
    each block's tables and positions, and the number of ``span``-position
    pieces it walks: as far as its longest row reaches and no further.
    Sorting keeps a block's rows about equally long, so little of a piece
    is masked. ``rows`` x ``span`` trades walked-but-masked positions
    (larger) against loop iterations (smaller): a family passes what it
    settled on the chip."""
    B, mb = tables.shape
    rb, cb = walk_sizes(B, mb, block_size, rows, span)
    order = jnp.argsort(positions)
    pos_s = jnp.take(positions, order).reshape(B // rb, rb)
    tables_s = jnp.take(tables, order, axis=0).reshape(B // rb, rb, mb)
    pieces = jnp.max(pos_s, axis=1) // (cb * block_size) + 1
    return WalkPlan(order, jnp.argsort(order), pos_s, tables_s, pieces, cb,
                    jnp.sum(pieces) * (rb * cb * block_size))


def live_walk(plan, pools, layer, q, rows_block):
    """One layer's decode attention over the LIVE positions only. ``pools``
    is a tuple of page pools read at ``layer``; ``q`` ``(B, ...)`` holds the
    rows' queries. Returns ``rows_block``'s results, ``(B, ...)``, in the
    rows' own order.

    A block of rows walks its tables a piece at a time (a loop with a
    traced trip count: static shapes, one program whatever the lengths).
    The family's ``rows_block(q_b, pos_b, walk)`` gets a block's queries
    ``(rb, ...)`` and positions ``(rb,)`` and calls ``walk(fold, shape,
    width)``, which starts a running softmax ``(m, den, acc)`` for scores
    of ``shape`` (the positions' axis left out) and values ``width`` wide,
    folds every piece the block walks into it with ``fold(carry, pieces,
    tpos) -> carry`` and returns it: ``pieces`` are the gathered pages of
    each pool, ``(rb, span, width)``, and ``tpos`` ``(span,)`` their
    positions. The two contractions of a piece, and their layouts, are the
    family's (`softmax_fold` holds the recurrence around them)."""
    B = q.shape[0]
    nb, rb, _ = plan.tables.shape
    cb = plan.cb
    span = cb * pools[0].shape[2]

    def block(args):
        q_b, tables_b, pos_b, n = args

        def walk(fold, shape, width):
            def piece(j, carry):
                tab = lax.dynamic_slice_in_dim(tables_b, j * cb, cb, axis=1)
                pieces = tuple(
                    gather_pages(p, layer, tab).reshape(rb, span,
                                                        p.shape[-1])
                    for p in pools)
                tpos = j * span + jnp.arange(span, dtype=jnp.int32)
                return fold(carry, pieces, tpos)
            # position 0 is live for every row, so the first piece leaves
            # a finite running maximum and a masked one after it adds 0
            return lax.fori_loop(0, n, piece, (
                jnp.full(shape, MASKED, jnp.float32),
                jnp.zeros(shape, jnp.float32),
                jnp.zeros(tuple(shape) + (width,), jnp.float32)))

        return rows_block(q_b, pos_b, walk)

    q_s = jnp.take(q, plan.order, axis=0).reshape((nb, rb) + q.shape[1:])
    out = lax.map(block, (q_s, plan.tables, plan.positions, plan.pieces))
    return jnp.take(out.reshape((B,) + out.shape[2:]), plan.inverse, axis=0)


def softmax_fold(carry, s, tpos, pos_b, axis, weigh):
    """Fold one piece's float32 scores ``s`` into the running softmax
    ``(m, den, acc)``. ``s`` is ``(rows, positions, heads)`` with ``axis``
    1 or ``(rows, heads, positions)`` with ``axis`` 2; a position past its
    row's own (``tpos > pos_b``) is masked. ``weigh(p)`` contracts the
    weights ``p`` (shaped as ``s``) with the piece's values into ``acc``'s
    shape. A masked position contributes exact 0 and a piece past a row's
    end leaves that row's carry bit-for-bit (``alpha = 1``, ``p = 0``), so
    a row's result does not depend on which rows share its block."""
    m, den, acc = carry
    live = jnp.expand_dims(tpos[None, :] <= pos_b[:, None], 3 - axis)
    s = jnp.where(live, s, MASKED)
    m_new = jnp.maximum(m, jnp.max(s, axis=axis))
    p = jnp.exp(s - jnp.expand_dims(m_new, axis))
    alpha = jnp.exp(m - m_new)
    return (m_new, den * alpha + jnp.sum(p, axis=axis),
            acc * alpha[..., None] + weigh(p))


def chunk_spans(chunk, table_len, floor=1024):
    """The static widths a prefill chunk's keys and values may be made
    over: ``max(chunk, floor)`` doubled while under ``table_len``, then
    ``table_len`` itself, so every ``start + length`` the table holds is
    served and a program carries a few branches, not one a bucket. A
    causal chunk reads positions ``0 .. start + length - 1`` only (the
    flash kernel skips the tiles beyond, the lax tier masks them): what a
    family makes of the rows past its span nobody reads. One span, and no
    branch, where the table is no longer than the first."""
    spans = []
    s = max(chunk, floor)
    while s < table_len:
        spans.append(s)
        s *= 2
    return tuple(spans) + (table_len,)


def span_index(spans, end):
    """Which of `chunk_spans` serves a chunk whose last real position is
    ``end - 1`` (``end = start + length``, traced): the smallest span that
    holds ``end`` positions."""
    return jnp.sum(jnp.asarray(end, jnp.int32)
                   > jnp.asarray(spans[:-1], jnp.int32)).astype(jnp.int32)


def chunk_attention(q, k, v, start, sm_scale, block_k, use_pallas,
                    interpret, variant="grid"):
    """Causal attention of a prefill chunk over its sequence's gathered
    pages, head-major: ``q`` ``(H, C, d)`` at global positions ``start +
    i``, ``k`` / ``v`` ``(H, T, d)`` at positions ``0..T-1``; returns ``(H,
    C, d)``. Kernel tier (``use_pallas`` / ``interpret``): the offset-aware
    flash kernels of ``variant``; lax tier: `blockwise_attention`: identical
    masking, fp-tolerance numerics. Layout (transposes, head-width padding,
    dtype) is the caller's."""
    C, T = q.shape[1], k.shape[1]
    # block sizes must tile exactly: C is a prefill bucket (so C itself
    # always works), T = mb * block_size (so T itself always works)
    bq = C if C % min(block_k, C) else min(block_k, C)
    bk = T if T % min(block_k, T) else min(block_k, T)
    if use_pallas or interpret:
        offs = jnp.stack([jnp.asarray(start, jnp.int32), jnp.int32(0)])
        out, _ = flash_attention_with_lse(q[None], k[None], v[None], offs,
                                          sm_scale, True, bq, bk, interpret,
                                          variant)
    else:
        out, _ = blockwise_attention(q[None], k[None], v[None], causal=True,
                                     sm_scale=sm_scale, block_k=bk,
                                     q_offset=start, k_offset=0)
    return out[0]
