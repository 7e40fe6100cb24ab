"""The device side of the paged cache, written once: where a position
lives in a page pool, the decode step's walk over the live positions, and
the prefill chunk's attention.

`serving/kvcache.py` owns the HOST side (which blocks a sequence holds);
the model families of `models/` own their contractions and layouts. What
they share is here: addressing, `NULL_BLOCK`, `MASKED`, the lax walk
(`walk_plan`, `live_walk`, `softmax_fold`: every family's on the CPU, and
the GPT-2 family's everywhere) and, for the latent family whose 128 heads
share ONE pool row, the walk as a Pallas kernel that reads the pool in
place (`paged_latent_attention`, ``mx_paged_latent_attn``). A row a head
(twin pools, heads folded into the lanes) is another contraction: the same
compaction, page copies and running softmax as `paged_head_attention`
(``mx_eva_paged_attn``; `models/evabyte.py`: summaries and window, one walk).
A sliding window is no page range of one table: a window layer of the
latent family keeps a RING of its last ``W`` latent rows a decode slot
(position ``p`` at ring row ``p % W``, a per-slot pool), which
`window_latent_attention` (``mx_window_latent_attn``) attends and writes in
place; `models/motif.py` puts it beside paged full layers.

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
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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


# ---------------------------------------------------------------------------
# the latent family's walk as one kernel
# ---------------------------------------------------------------------------
# The rows of a decode step as `paged_latent_attention` takes them.
PagedRows = collections.namedtuple("PagedRows",
                                   "positions tables active interpret")

#: VMEM a chunk of the kernel's walk may take: its two page buffers and the
#: float32 scores, weights and mask of one product.
_CHUNK_VMEM = 4 << 20

#: Pages whose copies are issued straight-line, as one group: the kernel's
#: trace and lowering grow with it (0.55 s of set-up at 32, three sites),
#: its issue cost falls with it.
_COPY_GROUP = 8

#: Rows in a block of the two latent kernels' queries and results: a
#: heads-major block ``[H, rows, w]`` spans whole tiles of its rows' axis
#: (16 bfloat16 rows). The grid still takes one row a step.
_ROW_GROUP = 16

#: VMEM the two latent kernels may use: their chunk or ring buffers, the
#: double-buffered blocks of `_ROW_GROUP` rows' queries and results and, for
#: heads-major blocks, `_each_row`'s float32 copies of them (about 18 MB at
#: Motif's 80 heads, over the v5e's default of 16 MB).
_LATENT_VMEM = 48 << 20


def chunk_pages(heads, mb, block_size, width, itemsize):
    """Pages a chunk of `paged_latent_attention`'s walk: the largest power
    of two whose double-buffered rows and three ``[heads, positions]``
    float32 temporaries fit `_CHUNK_VMEM`, no more than the table."""
    pages = 1
    while pages * 2 <= mb and 4 * pages * block_size * (
            2 * width * itemsize + 3 * heads * 4) <= _CHUNK_VMEM:
        pages *= 2
    return pages


def _row_chain(active):
    """The rows as the two latent kernels walk them, for scalar memory:
    ``(act, first, after)``, int32: ``act`` ``(B,)`` 1 for an active row,
    ``first`` ``(1,)`` the first active row and ``after`` ``(B,)`` the next
    active row after each (``B`` where there is none). A row's copies for
    the next active row start while it still computes."""
    B = active.shape[0]
    at = jnp.where(active, jnp.arange(B, dtype=jnp.int32), B)
    ahead = lax.cummin(at, axis=0, reverse=True)    # first active from b on
    return (active.astype(jnp.int32), ahead[:1],
            jnp.concatenate([ahead[1:], jnp.full((1,), B, jnp.int32)]))


def _row_blocks(B, H, width, dr, heads_major):
    """``(R, q, q_rope, u)``: the rows of a block of the two latent kernels'
    queries and results, and their BlockSpecs over the grid ``(blocks,
    R)``. ``q`` and ``u`` come in blocks of ``R`` rows of ``[B, H, w]``, or
    heads-major ``[H, B, w]`` whose rows' axis a block spans whole tiles of
    (16 bfloat16 rows, twice 8 float32 ones) or the whole batch; ``q_rope``
    ``[B, H, dr]`` in blocks of rows either way (the rotary part leaves its
    projection rows-major). A block's index does not change over its ``R``
    grid steps: it is fetched once and written back once."""
    R = min(_ROW_GROUP, B)
    rows = lambda w: pl.BlockSpec((R, H, w),                # noqa: E731
                                  lambda g, j, *_: (g, 0, 0))
    if heads_major:
        heads = pl.BlockSpec((H, R, width), lambda g, j, *_: (0, g, 0))
        return R, heads, rows(dr), heads
    return R, rows(width), rows(dr), rows(width)


def _each_row(B, act_ref, q_ref, qr_ref, o_ref, forms, attend):
    """Grid step ``(g, j)`` of a latent kernel: row ``b = g R + j``, ``R``
    the rows of a block. ``attend(b, j, q, q_rope)`` gives an active row's
    result ``[H, width]`` float32 from its queries; an inactive row's result
    is exact zeros, and a row past ``B`` (a last block's padding) does
    nothing. ``forms`` is ``None`` for blocks of rows. For heads-major
    blocks it is two float32 VMEM buffers ``[width / 128, H R, 128]`` (a
    block's 128-lane columns, its heads and rows merged; a width under 128,
    one column): the block's latent queries go into the one at its first
    step, and a row's ``[H, 128]`` pieces are read from it with a stride of
    ``R`` rows (a sublane-strided load, which takes 32-bit numbers and a
    128-lane buffer); its results go into the other so, and to the block at
    its last step."""
    g, j = pl.program_id(0), pl.program_id(1)
    R = qr_ref.shape[0]
    b = g * R + j
    if forms is None:
        q_row = lambda: q_ref[j]                            # noqa: E731

        def put(u):
            o_ref[j] = u.astype(o_ref.dtype)
    else:
        qs, res = forms
        H = q_ref.shape[0]
        cols, lanes = range(qs.shape[0]), qs.shape[2]

        @pl.when(j == 0)
        def _():
            q = q_ref[...].astype(jnp.float32)
            for c in cols:
                qs[c] = q[:, :, c * lanes:(c + 1) * lanes].reshape(
                    H * R, lanes)

        def q_row():
            return jnp.concatenate([qs[c, pl.ds(j, H, stride=R)]
                                    for c in cols], 1).astype(q_ref.dtype)

        def put(u):
            for c in cols:
                res[c, pl.ds(j, H, stride=R)] = u[:, c * lanes:
                                                  (c + 1) * lanes]

    live = b < B
    active = act_ref[jnp.minimum(b, B - 1)] != 0

    @pl.when(live & active)
    def _():
        put(attend(b, j, q_row(), qr_ref[j]))

    @pl.when(live & jnp.logical_not(active))
    def _():
        put(jnp.zeros((qr_ref.shape[1], o_ref.shape[2]), jnp.float32))

    if forms is not None:
        @pl.when(j == R - 1)
        def _():
            for c in cols:
                o_ref[:, :, c * lanes:(c + 1) * lanes] = res[c].reshape(
                    H, R, lanes).astype(o_ref.dtype)


def _latent_scores(q, q_rope, lat, sm_scale):
    """``[H, positions]`` float32 scores of the latent queries ``q`` ``[H,
    rkv]`` and ``q_rope`` ``[H, dr]`` against rows ``lat`` ``[positions,
    row]`` = ``[c | k_rope | 0]``: two products, one a part of the row."""
    rkv, dr = q.shape[1], q_rope.shape[1]
    nt = (((1,), (1,)), ((), ()))
    return (lax.dot_general(q, lat[:, :rkv], nt,
                            preferred_element_type=jnp.float32)
            + lax.dot_general(q_rope, lat[:, rkv:rkv + dr], nt,
                              preferred_element_type=jnp.float32)) * sm_scale


def _latent_attn_kernel(layer_ref, act_ref, first_ref, after_ref, pos_ref,
                        tab_ref, q_ref, qr_ref, new_ref, pool_ref, o_ref,
                        pool_out_ref, buf, sems, back_sem, slot_ref, m_ref,
                        den_ref, acc_ref, *forms, sm_scale, mb):
    """Grid step ``g``: `_each_row` over rows ``g R ..``. An active row's
    pages arrive a chunk at a time in ``buf`` ``[2, pages, block_size,
    row]``, the next chunk's copies (the next ACTIVE row's first, at a
    row's last) started before this chunk's products; ``slot_ref`` carries
    which half is due from row to row. The row's NEW latent row
    (``new_ref``) is set into its last page as that page passes through
    VMEM, and the page is copied back to the pool (``pool_out_ref`` is
    ``pool_ref``'s own buffer). Inside a chunk: `softmax_fold`'s
    arithmetic."""
    B = act_ref.shape[0]
    _, pages, bs, row_width = buf.shape
    span = pages * bs
    dt = buf.dtype
    width = acc_ref.shape[1]

    group = min(pages, _COPY_GROUP)

    def chunk_copies(r, c, pages_from, one_page):
        """The copies of chunk ``c`` of row ``r``, the LIVE pages only (a
        row's pages end at its own position): ``pages_from(j)`` for every
        whole group of ``group`` pages from page ``j`` on, straight-line;
        ``one_page(j)`` for each page left over."""
        live = jnp.minimum(pos_ref[r] // bs + 1 - c * pages, pages)
        whole = live // group

        def groups(g, carry):
            pages_from(g * group)
            return carry

        def page(j, carry):
            one_page(j)
            return carry
        lax.fori_loop(0, whole, groups, 0)
        lax.fori_loop(whole * group, live, page, 0)

    def start(r, c, slot):
        def one_page(j):
            pltpu.make_async_copy(
                pool_ref.at[layer_ref[0], tab_ref[r * mb + c * pages + j]],
                buf.at[slot, j], sems.at[slot]).start()

        def pages_from(j):
            # issuing 20 KB copies is what the walk costs beside its
            # products: no loop and no branch inside a group
            for k in range(group):
                one_page(j + k)
        chunk_copies(r, c, pages_from, one_page)

    def wait(r, c, slot):
        # a wait takes its amount from the shape it names: a page, or a
        # group's pages in one
        def pages_at(j, count):
            at = buf.at[slot, pl.ds(j, count)]
            pltpu.make_async_copy(at, at, sems.at[slot]).wait()
        chunk_copies(r, c, lambda j: pages_at(j, group),
                     lambda j: pages_at(j, 1))

    def attend(r, j, q, q_rope):
        pos = pos_ref[r]
        chunks = pos // span + 1

        @pl.when(r == first_ref[0])
        def _():
            # a partly filled chunk leaves the rows of an earlier one
            # behind it (finite, masked to weight 0): never VMEM as found
            buf[...] = jnp.zeros_like(buf)
            slot_ref[0] = 0
            start(r, 0, 0)

        first_slot = slot_ref[0]
        m_ref[...] = jnp.full_like(m_ref, MASKED)
        den_ref[...] = jnp.zeros_like(den_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def fold(c, slot):
            lat = buf[slot].reshape(span, row_width)
            s = _latent_scores(q, q_rope, lat, sm_scale)
            tpos = c * span + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(tpos <= pos, s, MASKED)
            m = m_ref[...]
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            m_ref[...] = m_new
            den_ref[...] = den_ref[...] * alpha + jnp.sum(p, axis=1,
                                                          keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
                p.astype(dt), lat[:, :width],
                preferred_element_type=jnp.float32)

        def whole_chunk(c, carry):
            slot = (first_slot + c) % 2
            start(r, c + 1, 1 - slot)
            wait(r, c, slot)
            fold(c, slot)
            return carry

        lax.fori_loop(0, chunks - 1, whole_chunk, 0)

        # the row's last chunk holds the position this step writes: the new
        # row goes into its page here, and the page back to the pool ahead
        # of the next active row's first copies
        c = chunks - 1
        slot = (first_slot + c) % 2
        wait(r, c, slot)
        k = pos // bs - c * pages
        page = buf[slot, k].astype(jnp.float32)
        at = lax.broadcasted_iota(jnp.int32, page.shape, 0)
        buf[slot, k] = jnp.where(at == pos % bs,
                                 new_ref[j].astype(jnp.float32),
                                 page).astype(dt)
        back = pltpu.make_async_copy(
            buf.at[slot, k],
            pool_out_ref.at[layer_ref[0], tab_ref[r * mb + pos // bs]],
            back_sem)
        back.start()

        @pl.when(after_ref[r] < B)
        def _():
            start(after_ref[r], 0, 1 - slot)

        fold(c, slot)
        slot_ref[0] = (first_slot + chunks) % 2
        # before this half of ``buf`` is due again, and before the end
        back.wait()
        return acc_ref[...] / den_ref[...]

    _each_row(B, act_ref, q_ref, qr_ref, o_ref, forms or None, attend)


def _latent_call(kernel, name, layer, prefetch, q_lat, q_rope, new_rows,
                 pool, active, scratch, heads_major, out_dtype, interpret):
    """ONE ``pallas_call`` of a latent kernel, named ``name``, over the grid
    ``(blocks, R)`` of `_row_blocks`: its scalar-prefetch operands are
    ``layer``, `_row_chain`'s three and ``prefetch``; then ``q_lat``,
    ``q_rope`` and ``new_rows`` in blocks of rows, and ``pool`` in HBM,
    aliased to the second result. ``scratch`` goes before `_each_row`'s
    VMEM. Returns ``(u, pool)``."""
    dt = pool.dtype
    B, H = q_rope.shape[:2]
    width = q_lat.shape[-1]
    if q_lat.shape[:2] != ((H, B) if heads_major else (B, H)):
        raise ValueError("q_lat %s is not %s for q_rope %s"
                         % (q_lat.shape, "heads-major" if heads_major
                            else "rows-major", q_rope.shape))
    forms = []
    if heads_major:
        lanes = 128 if width % 128 == 0 else width
        forms = [pltpu.VMEM((width // lanes, H * min(_ROW_GROUP, B), lanes),
                            jnp.float32)] * 2
    R, q_spec, qr_spec, o_spec = _row_blocks(B, H, width, q_rope.shape[-1],
                                             heads_major)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 + len(prefetch), grid=(pl.cdiv(B, R), R),
        in_specs=[q_spec, qr_spec,
                  pl.BlockSpec((R, 1, pool.shape[-1]),
                               lambda g, j, *_: (g, 0, 0)),
                  in_hbm],
        out_specs=[o_spec, in_hbm],
        scratch_shapes=list(scratch) + forms)
    # (side effects: the call writes the pool; XLA neither drops it nor
    # makes it twice)
    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        has_side_effects=True, vmem_limit_bytes=_LATENT_VMEM)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(
            q_lat.shape, dt if out_dtype is None else out_dtype),
                   jax.ShapeDtypeStruct(pool.shape, dt)],
        # operands count the scalar-prefetch ones, then q_lat, q_rope and
        # new_rows
        input_output_aliases={7 + len(prefetch): 1},
        compiler_params=params, interpret=interpret, name=name)(
            jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
            *_row_chain(active), *prefetch, q_lat.astype(dt),
            q_rope.astype(dt), new_rows.astype(dt)[:, None], pool)


@functools.partial(jax.jit, static_argnames=("sm_scale", "heads_major",
                                             "out_dtype", "interpret"))
def paged_latent_attention(q_lat, q_rope, new_rows, pool, layer, positions,
                           tables, active, *, sm_scale, heads_major=False,
                           out_dtype=None, interpret=False):
    """One layer's cache write and decode attention of the latent family,
    the pool read and written in place: ONE ``pallas_call`` named
    ``mx_paged_latent_attn``. ``q_lat`` holds every head's latent query
    (``rkv`` wide, against the first ``rkv`` numbers of a pool row) and
    ``q_rope`` ``(B, H, dr)`` its rotary part (against the ``dr`` numbers
    after them), both cast to the pool's dtype; ``new_rows`` ``(B, row)``
    the rows' new latent rows; ``pool`` ``(L, blocks, block_size, row)``
    stays in HBM and is used at ``layer``. Row ``b`` writes position
    ``positions[b]`` of ``tables[b]`` and attends positions ``0 ..
    positions[b]``. Returns ``(u, pool)``: ``u`` the softmax-weighted sum of
    the rows' first ``rkv`` numbers in ``out_dtype`` (the pool's by
    default; an inactive row's is exact zeros, written by the kernel), and
    the pool with the active rows' new rows in it (an inactive row writes
    nothing).

    The CALLER chooses the form of ``q_lat`` and of ``u`` to be the one its
    neighbouring products make and take, so that no XLA pass sits between
    them and the kernel: rows-major ``(B, H, rkv)`` (the latent family's
    per-head ``bhn,rhn->bhr`` and ``bhr,rhv->bhv``), or with
    ``heads_major`` ``(H, B, rkv)`` (Motif's grouped ``gsbn,rgn->gsbr`` and
    ``gsbr,rgv->bgsv``); ``q_rope`` leaves its projection rows-major and is
    taken so in both. It chooses ``out_dtype`` as its consumer reads ``u``.

    The grid walks the rows in order, one a step, their queries and results
    in blocks of `_ROW_GROUP` rows; an inactive row skips its copies and
    products. A row copies its own pages ``0 ..
    positions[b] // block_size`` and no further, each one contiguous copy
    into VMEM, and no gathered piece is ever written to HBM; the next
    active row's first copies start under a row's last products. The pool
    is aliased to the kernel's output and written by it alone, a page a
    row: a chain of in-place updates in XLA's hands beside the kernel's
    reads was rematerialised at the Kimi-Linear cell's sizes (PERF.md §6,
    with what that can do). Arithmetic: `softmax_fold`'s, a chunk of
    `chunk_pages` pages a fold (operands in the pool's dtype, float32
    accumulation and softmax). Jitted, though it only ever runs inside a
    program: the layers share one trace."""
    H, row_width = q_rope.shape[1], pool.shape[3]
    bs, mb = pool.shape[2], tables.shape[1]
    pages = chunk_pages(H, mb, bs, row_width, pool.dtype.itemsize)
    return _latent_call(
        functools.partial(_latent_attn_kernel, sm_scale=sm_scale, mb=mb),
        "mx_paged_latent_attn", layer,
        (positions.astype(jnp.int32), tables.astype(jnp.int32).reshape(-1)),
        q_lat, q_rope, new_rows, pool, active,
        [pltpu.VMEM((2, pages, bs, row_width), pool.dtype),
         pltpu.SemaphoreType.DMA((2,)), pltpu.SemaphoreType.DMA(()),
         pltpu.SMEM((1,), jnp.int32), pltpu.VMEM((H, 1), jnp.float32),
         pltpu.VMEM((H, 1), jnp.float32),
         pltpu.VMEM((H, q_lat.shape[-1]), jnp.float32)],
        heads_major, out_dtype, interpret)


def paged_walked(positions, active, block_size):
    """Positions whose pages `paged_latent_attention` copies for one layer:
    every active row's own pages, whole."""
    return jnp.sum(jnp.where(active,
                             (positions // block_size + 1) * block_size, 0))


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


# ---------------------------------------------------------------------------
# a row a head: twin pools, heads folded into the lanes, as one kernel
# ---------------------------------------------------------------------------
#: Pages a chunk of `paged_head_attention`'s walk: two pools, two halves,
#: 128 KB a page at 4,096 lanes of bfloat16 (4 MB of VMEM).
_HEAD_CHUNK_PAGES = 8


def _head_attn_kernel(layer_ref, rows_ref, n_ref, last_ref, tab_ref, q_ref,
                      knew_ref, vnew_ref, k_pool, v_pool, o_ref, kbuf, vbuf,
                      sems, slot_ref, m_ref, den_ref, acc_ref, *, sm_scale,
                      mb):
    """Grid step ``i``: the ``i``-th ACTIVE row. Its pages arrive a chunk at
    a time in the halves of ``kbuf`` / ``vbuf`` ``[2, pages, block_size,
    D]``, the next chunk's copies (the next row's first, at a row's last)
    started before this chunk's products; ``slot_ref`` carries which half
    is due from row to row, as `_latent_attn_kernel`'s. The running softmax
    ``(m, den, acc)`` has a head a sublane row: it STARTS from the row's own
    new key and value (``knew_ref``, ``vnew_ref``: not in the pool yet), so
    no row is ever all masked. Head ``h`` owns lanes ``h * dh .. (h + 1) *
    dh - 1``: the query is laid out block-diagonally ``[H, D]`` so one
    product gives every head's scores, and of the ``[H, D]`` accumulator
    head ``h`` keeps its own lanes."""
    i = pl.program_id(0)
    n = n_ref[0]
    _, pages, bs, D = kbuf.shape
    H = m_ref.shape[0]
    span = pages * bs
    dt = kbuf.dtype

    def live_pages(r, c):
        return jnp.minimum(last_ref[r] // bs + 1 - c * pages, pages)

    def start(r, c, slot):
        def page(j, carry):
            blk = tab_ref[r * mb + c * pages + j]
            pltpu.make_async_copy(k_pool.at[layer_ref[0], blk],
                                  kbuf.at[slot, j], sems.at[slot, 0]).start()
            pltpu.make_async_copy(v_pool.at[layer_ref[0], blk],
                                  vbuf.at[slot, j], sems.at[slot, 1]).start()
            return carry
        lax.fori_loop(0, live_pages(r, c), page, 0)

    def wait(r, c, slot):
        def page(j, carry):
            for buf, kv in ((kbuf, 0), (vbuf, 1)):
                at = buf.at[slot, j]
                pltpu.make_async_copy(at, at, sems.at[slot, kv]).wait()
            return carry
        lax.fori_loop(0, live_pages(r, c), page, 0)

    @pl.when(i < n)
    def _():
        r = rows_ref[i]
        last = last_ref[r]
        chunks = last // span + 1

        @pl.when(i == 0)
        def _():
            # a partly filled chunk leaves the rows of an earlier one
            # behind it (finite, masked to weight 0): never VMEM as found
            kbuf[...] = jnp.zeros_like(kbuf)
            vbuf[...] = jnp.zeros_like(vbuf)
            slot_ref[0] = 0
            start(r, 0, 0)

        first_slot = slot_ref[0]
        own = (lax.broadcasted_iota(jnp.int32, (H, D), 1) // (D // H)
               == lax.broadcasted_iota(jnp.int32, (H, D), 0))
        # (masks are applied to float32: a bool of bfloat16's tiling is a
        # relayout the compiler refuses)
        q = q_ref[0].astype(jnp.float32)                    # [1, D]
        q_bd = jnp.where(own, q, 0.0).astype(dt)            # [H, D]
        m_ref[...] = jnp.sum(
            jnp.where(own, q * knew_ref[0].astype(jnp.float32), 0.0),
            axis=1, keepdims=True) * sm_scale
        den_ref[...] = jnp.ones_like(den_ref)
        acc_ref[...] = jnp.broadcast_to(vnew_ref[0].astype(jnp.float32),
                                        (H, D))

        def fold(c, slot):
            s = lax.dot_general(q_bd, kbuf[slot].reshape(span, D),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
            tpos = c * span + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(tpos <= last, s, MASKED)
            m = m_ref[...]
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            m_ref[...] = m_new
            den_ref[...] = den_ref[...] * alpha + jnp.sum(p, axis=1,
                                                          keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
                p.astype(dt), vbuf[slot].reshape(span, D),
                preferred_element_type=jnp.float32)

        def whole_chunk(c, carry):
            slot = (first_slot + c) % 2
            start(r, c + 1, 1 - slot)
            wait(r, c, slot)
            fold(c, slot)
            return carry

        lax.fori_loop(0, chunks - 1, whole_chunk, 0)
        c = chunks - 1
        slot = (first_slot + c) % 2

        @pl.when(i + 1 < n)
        def _():
            start(rows_ref[i + 1], 0, 1 - slot)

        wait(r, c, slot)
        fold(c, slot)
        slot_ref[0] = (first_slot + chunks) % 2
        o_ref[0] = jnp.sum(jnp.where(own, acc_ref[...] / den_ref[...], 0.0),
                           axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("num_heads", "sm_scale",
                                             "interpret"))
def paged_head_attention(q, k_new, v_new, k_pool, v_pool, layer, last,
                         tables, active, *, num_heads, sm_scale,
                         interpret=False):
    """One layer's decode attention over twin K and V pools with the heads
    folded into the lanes (the GPT-2 family's page format), the pools read
    in place: ONE ``pallas_call`` named ``mx_eva_paged_attn``. ``q``,
    ``k_new``, ``v_new`` ``(B, D)`` are the rows' queries and their own new
    keys and values, which the pools do not hold yet; ``k_pool`` /
    ``v_pool`` ``(L, blocks, block_size, D)`` stay in HBM and are read at
    ``layer``; row ``b`` attends the positions ``0 .. last[b]`` of
    ``tables[b]`` and itself, under one softmax. Returns ``(B, D)``
    float32 (an inactive row's is 0).

    What a position IS is the caller's: `models/evabyte.py` hands a table
    whose front holds a row's visible chunk summaries and whose back its
    open window, so one walk covers both spans. The grid walks the ACTIVE
    rows only (compacted through scalar prefetch); a row copies its own
    pages ``0 .. last[b] // block_size`` of both pools and no further, each
    one contiguous copy into VMEM, `_HEAD_CHUNK_PAGES` a chunk, double
    buffered. Arithmetic: `softmax_fold`'s a chunk (operands in the pools'
    dtype, float32 accumulation and softmax). Jitted, though it only ever
    runs inside a program: the layers share one trace."""
    B, D = q.shape
    bs = k_pool.shape[2]
    mb = tables.shape[1]
    pages = min(_HEAD_CHUNK_PAGES, mb)
    n = jnp.sum(active.astype(jnp.int32))
    order = jnp.argsort(jnp.logical_not(active), stable=True).astype(
        jnp.int32)
    at = jnp.minimum(jnp.arange(B, dtype=jnp.int32), jnp.maximum(n - 1, 0))
    rows = jnp.take(order, at)
    row_block = lambda i, layer, rows, *_: (rows[i], 0, 0)      # noqa: E731
    row_spec = pl.BlockSpec((1, 1, D), row_block)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5, grid=(B,),
        in_specs=[row_spec, row_spec, row_spec, in_hbm, in_hbm],
        out_specs=row_spec,
        scratch_shapes=[pltpu.VMEM((2, pages, bs, D), k_pool.dtype),
                        pltpu.VMEM((2, pages, bs, D), v_pool.dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((1,), jnp.int32),
                        pltpu.VMEM((num_heads, 1), jnp.float32),
                        pltpu.VMEM((num_heads, 1), jnp.float32),
                        pltpu.VMEM((num_heads, D), jnp.float32)])
    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("arbitrary",))
    dt = k_pool.dtype
    out = pl.pallas_call(
        functools.partial(_head_attn_kernel, sm_scale=sm_scale, mb=mb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, D), jnp.float32),
        compiler_params=params, interpret=interpret,
        name="mx_eva_paged_attn")(
            jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), rows,
            jnp.reshape(n, (1,)), last.astype(jnp.int32),
            tables.astype(jnp.int32).reshape(-1), q.astype(dt)[:, None],
            k_new.astype(dt)[:, None], v_new.astype(dt)[:, None], k_pool,
            v_pool)
    return jnp.where(active[:, None], out[:, 0], 0.0)


# ---------------------------------------------------------------------------
# a window of latent rows: a ring a slot, attended and written in place
# ---------------------------------------------------------------------------
#: Rows of a ring copied back to the pool at once: a bfloat16 tile's height.
_RING_TILE = 16


def ring_live(positions, width):
    """``[..., width]`` bool: which rows of a ``width``-row ring hold one of
    the positions ``p - width + 1 .. p`` (and none below 0) once the row at
    position ``p`` (``positions`` ``[...]``) has been written at ``p %
    width``."""
    w = jnp.arange(width, dtype=jnp.int32)
    p = jnp.asarray(positions, jnp.int32)[..., None]
    return (w <= p) | (p >= width - 1)


def _window_attn_kernel(layer_ref, act_ref, first_ref, after_ref, pos_ref,
                        q_ref, qr_ref, new_ref, ring_ref, o_ref, ring_out_ref,
                        buf, sems, back_sem, slot_ref, *forms, sm_scale):
    """Grid step ``g``: `_each_row` over rows (slots) ``g R ..``. An active
    row's ring arrives in one copy in a half of ``buf`` ``[2, W, row]``, the
    next ACTIVE row's started as soon as this one has landed; ``slot_ref``
    carries which half is due. The row's NEW latent row (``new_ref``) is set
    into its tile of the ring in VMEM, the tile goes back to the pool
    (``ring_out_ref`` is ``ring_ref``'s own buffer), and the row attends the
    whole ring under `ring_live`'s mask: one softmax, no chunks."""
    B = act_ref.shape[0]
    _, W, _ = buf.shape
    dt = buf.dtype
    tile_rows = min(_RING_TILE, W)

    def copy_in(r, slot):
        return pltpu.make_async_copy(ring_ref.at[layer_ref[0], r],
                                     buf.at[slot], sems.at[slot])

    def attend(r, j, q, q_rope):
        pos = pos_ref[r]

        @pl.when(r == first_ref[0])
        def _():
            slot_ref[0] = 0
            copy_in(r, 0).start()

        slot = slot_ref[0]
        copy_in(r, slot).wait()

        @pl.when(after_ref[r] < B)
        def _():
            copy_in(after_ref[r], 1 - slot).start()

        at = pos % W
        t = pl.multiple_of(at // tile_rows * tile_rows, tile_rows)
        tile = buf[slot, pl.ds(t, tile_rows)].astype(jnp.float32)
        row = lax.broadcasted_iota(jnp.int32, tile.shape, 0)
        buf[slot, pl.ds(t, tile_rows)] = jnp.where(
            row == at - t, new_ref[j].astype(jnp.float32), tile).astype(dt)
        back = pltpu.make_async_copy(
            buf.at[slot, pl.ds(t, tile_rows)],
            ring_out_ref.at[layer_ref[0], r, pl.ds(t, tile_rows)], back_sem)
        back.start()
        lat = buf[slot]                                     # [W, row]
        s = _latent_scores(q, q_rope, lat, sm_scale)
        w = lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((w <= pos) | (pos >= W - 1), s, MASKED)
        p = jnp.exp(s - jnp.max(s, axis=1, keepdims=True))
        u = jnp.dot(p.astype(dt), lat[:, :q.shape[1]],
                    preferred_element_type=jnp.float32) \
            / jnp.sum(p, axis=1, keepdims=True)
        slot_ref[0] = 1 - slot
        # before this half of ``buf`` is due again, and before the end
        back.wait()
        return u

    _each_row(B, act_ref, q_ref, qr_ref, o_ref, forms or None, attend)


@functools.partial(jax.jit, static_argnames=("sm_scale", "heads_major",
                                             "out_dtype", "interpret"))
def window_latent_attention(q_lat, q_rope, new_rows, ring, layer, positions,
                            active, *, sm_scale, heads_major=False,
                            out_dtype=None, interpret=False):
    """One window layer's cache write and decode attention of the latent
    family, the ring pool read and written in place: ONE ``pallas_call``
    named ``mx_window_latent_attn``. ``q_lat`` and ``q_rope`` are the
    latent and rotary queries as `paged_latent_attention` takes them:
    ``q_lat`` in the form the CALLER chooses (rows-major ``(B, H, rkv)`` or,
    with ``heads_major``, ``(H, B, rkv)``), ``q_rope`` ``(B, H, dr)``; ``u``
    comes back in ``q_lat``'s form, in ``out_dtype`` (the ring's by
    default). ``new_rows`` ``(B, row)`` holds the rows' new latent rows,
    ``ring`` ``(L, slots, W, row)`` stays in HBM and is used at ``layer``;
    row ``b`` is slot ``b``, writes position
    ``positions[b]`` at ring row ``positions[b] % W`` and attends the ring
    rows `ring_live` names. Returns ``(u, ring)``: ``u`` the
    softmax-weighted sum of the rows' first ``rkv`` numbers (an inactive
    row's is exact zeros, written by the kernel), and the ring with the
    active rows' new rows in it (an inactive row writes nothing).

    The grid walks the rows in order as `paged_latent_attention`'s does;
    an active row copies its slot's ring
    into VMEM in one piece and copies back the one tile of `_RING_TILE`
    rows (a ring under that, whole) it changed. The pool is aliased to the
    kernel's output and written by it alone: three window layers' XLA
    scatters into one pool beside the kernels' reads would be the chain of
    in-place updates the compiler may rematerialise (PERF.md §6).
    Arithmetic: `softmax_fold`'s over one piece (operands in the pool's
    dtype, float32 softmax). Jitted, though it only ever runs inside a
    program: the layers share one trace."""
    W, row_width = ring.shape[2:]
    if W % min(_RING_TILE, W):
        raise ValueError("a ring of %d rows is no whole number of %d-row "
                         "tiles" % (W, _RING_TILE))
    return _latent_call(
        functools.partial(_window_attn_kernel, sm_scale=sm_scale),
        "mx_window_latent_attn", layer, (positions.astype(jnp.int32),),
        q_lat, q_rope, new_rows, ring, active,
        [pltpu.VMEM((2, W, row_width), ring.dtype),
         pltpu.SemaphoreType.DMA((2,)), pltpu.SemaphoreType.DMA(()),
         pltpu.SMEM((1,), jnp.int32)],
        heads_major, out_dtype, interpret)


def window_latent_attention_lax(q, new_rows, ring, layer, positions, active,
                                *, sm_scale, width):
    """`window_latent_attention` in ``jax.numpy``: the lax tier's, and the
    kernel's reference. The same roundings (operands in the pool's dtype,
    float32 scores and softmax); the ring comes back with the active rows'
    new rows set by one scatter."""
    B = q.shape[0]
    W = ring.shape[2]
    dt = ring.dtype
    at = positions % W
    b = jnp.arange(B)
    lat = ring[layer]                                       # [B, W, row]
    lat = jnp.where((jnp.arange(W)[None, :] == at[:, None])[..., None],
                    new_rows.astype(dt)[:, None], lat)
    s = jnp.einsum("bhc,bwc->bhw", q.astype(dt), lat,
                   preferred_element_type=jnp.float32) * sm_scale
    s = jnp.where(ring_live(positions, W)[:, None, :], s, MASKED)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    u = jnp.einsum("bhw,bwr->bhr", p.astype(dt), lat[..., :width],
                   preferred_element_type=jnp.float32) \
        / jnp.sum(p, axis=-1)[..., None]
    keep = ring[layer, b, at]
    ring = ring.at[layer, b, at].set(
        jnp.where(active[:, None], new_rows.astype(dt), keep))
    return jnp.where(active[:, None, None], u, 0.0), ring
