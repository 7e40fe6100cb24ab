"""Paged KV cache: block-allocated device-resident decode state (ISSUE 18).

The naive KV cache for autoregressive decode reserves
``max_length x batch`` of HBM up front — almost all of it dead weight,
because most sequences finish early and the batch is rarely full. This
module is the vLLM-style alternative: the cache is a fixed pool of
fixed-size **token blocks** (``(num_blocks, block_size, dim)`` per
layer-side; what a row holds — K and V, or one latent row — is the
model's ``cache_spec`` to say), a sequence owns a **block table** (list of block ids, one
per ``block_size`` tokens of its history), and blocks come from a
free-list allocator. HBM then scales with *live tokens*, not with the
worst case, and the accounting counters below prove it.

**Which entries of its table a sequence backs** is a pure function of the
positions it holds, ``pages(n)``: one ``(start, stop)`` span of table
entries per REGION of the table (a region's ``start`` is fixed, its
``stop`` moves, by at most one entry a position; the largest ``stop`` never
falls as ``n`` grows, so a table is as wide as ``pages(max_seq_len)``
says). The default is the one region
``(0, ceil(n / block_size))``: entry ``p // block_size`` holds position
``p``, and a block is its sequence's until the sequence ends. A family
whose cache forgets (`models/evabyte.py`: exact rows die with their window,
their summaries stay) hands its own function over; as a sequence grows the
allocator backs the entries that are new and RELEASES those that dropped
out, while the sequence lives (``stats()["blocks_released_live"]``). An
entry that is not backed reads `NULL_BLOCK`.

The layout contract (where position ``p`` lives, and **block 0, the null
block**: never allocated, the target of every inactive or padding write)
is `kernels/paged_attention.py`'s, which the models' programs use. The
allocator hands out ids ``1..num_blocks-1``.

Allocation failure raises the typed :class:`CacheOverflow` — a
:class:`~.batcher.DeadlineExceeded` subclass, so every existing shed
path (server outcome classification, frontdoor accounting, client
``result_wait``) treats cache pressure as a shed, not a crash.

Pure host-side bookkeeping: no device calls, no locks (the decode loop
is the single owner; cross-thread reads go through ``stats()`` which
only copies ints).
"""
from __future__ import annotations

from ..kernels.paged_attention import NULL_BLOCK
from .batcher import DeadlineExceeded

__all__ = ["PagedKVCache", "CacheOverflow", "NULL_BLOCK", "page_sharding"]


def page_sharding(mesh, page_shape, axis_name="tp"):
    """NamedSharding for a KV page pool on ``mesh``: shard the trailing
    model dim over ``axis_name`` when the axis exists, is wider than one
    device, and divides the dim — else fully replicated. This is the
    DEFAULT for a pool whose trailing dim is heads folded together; a
    pool with no head axis (a latent row,
    :class:`~..models.moe_mla.MoEMLADecodeModel`) states its own
    sharding on its ``cache_spec`` leaf and never comes here.

    The transformer page layout folds heads into the trailing
    ``d_model`` dim (``(num_layers, num_blocks, block_size, d_model)``),
    so tp-sharding the trailing dim is head sharding: each tp shard
    holds every sequence's block table but only its own heads' K/V —
    the standard tensor-parallel attention split, with block tables and
    the layers/blocks/slots axes replicated so host-side paging stays
    tier-agnostic."""
    from jax.sharding import NamedSharding, PartitionSpec
    spec = PartitionSpec()
    if axis_name in getattr(mesh, "axis_names", ()):
        size = int(mesh.shape[axis_name])
        if size > 1 and int(page_shape[-1]) % size == 0:
            spec = PartitionSpec(*([None] * (len(page_shape) - 1)
                                   + [axis_name]))
    return NamedSharding(mesh, spec)


class CacheOverflow(DeadlineExceeded):
    """Typed shed raised when the block pool cannot satisfy an
    allocation. Subclasses ``DeadlineExceeded`` deliberately: cache
    pressure is load shedding (retryable, bounded), not a failure, and
    the whole serving stack already classifies sheds by that type."""


class PagedKVCache:
    """Free-list block allocator + per-sequence block tables.

    ``blocks_for(n)`` tokens need as many blocks as ``pages(n)`` has
    entries (``ceil(n / block_size)`` unless a family says otherwise). The
    usable pool is ``num_blocks - 1`` (block 0 is the null block).
    """

    def __init__(self, num_blocks, block_size, pages=None):
        if num_blocks < 2:
            raise ValueError("PagedKVCache needs >= 2 blocks "
                             "(block 0 is reserved as the null block)")
        if block_size < 1:
            raise ValueError("block_size must be >= 1")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        # LIFO free list: recently-freed blocks are reused first, which
        # keeps the touched working set small. Ids 1..num_blocks-1.
        self._free = list(range(self.num_blocks - 1, 0, -1))
        bs = self.block_size
        self._pages = pages or (lambda n: ((0, -(-n // bs)),))
        self.regions = len(self._pages(1))
        self._tables = {}           # seq_id -> [block ids], NULL_BLOCK holes
        self._spans = {}            # seq_id -> the spans its table backs
        self._lengths = {}          # seq_id -> token count
        # watermark / accounting counters
        self._allocs = 0
        self._frees = 0
        self._released_live = 0     # of _frees: from sequences still live
        self._alloc_failures = 0
        self._high_water = 0        # max blocks simultaneously live
        # the device state this manager accounts, of two kinds: bytes of
        # the paged pools these blocks index (all of them together), and
        # bytes of the per-slot pools beside them (recurrent state: a row a
        # decode slot, nothing a block). Whoever builds the pools
        # (DecodeEngine, from the model's cache_spec) writes both here;
        # the manager only reports them
        self.pool_bytes = 0
        self.state_bytes = 0

    # -- capacity queries ------------------------------------------------
    @property
    def capacity_blocks(self):
        """Usable pool size (excludes the null block)."""
        return self.num_blocks - 1

    @property
    def free_blocks(self):
        return len(self._free)

    @property
    def live_blocks(self):
        return self.capacity_blocks - len(self._free)

    def _spans_for(self, n_tokens, via=()):
        """``pages(n_tokens)``, each region widened to what any length of
        ``via`` needs as well."""
        spans = self._pages(int(n_tokens))
        for m in via:
            spans = tuple((a, max(b, d)) for (a, b), (_, d)
                          in zip(spans, self._pages(int(m))))
        return spans

    def blocks_for(self, n_tokens, via=()):
        """Blocks needed to hold ``n_tokens`` tokens (and, with ``via``,
        every length of it on the way there)."""
        return sum(b - a for a, b in self._spans_for(n_tokens, via))

    def can_fit(self, n_tokens, via=()):
        return self.blocks_for(n_tokens, via) <= len(self._free)

    def table_width(self, max_tokens):
        """Entries a table must have for sequences of up to ``max_tokens``."""
        return max(b for _, b in self._pages(int(max_tokens)))

    def growth(self, seq_id, n_tokens=1):
        """Blocks ``extend(seq_id, n_tokens)`` would take from the pool."""
        spans = self._pages(self._lengths[seq_id] + int(n_tokens))
        old = self._spans[seq_id]
        return 0 if spans == old else self._added(spans, old)

    @staticmethod
    def _added(spans, old):
        """Entries ``spans`` backs that ``old`` does not."""
        return sum(max(0, b - d) for (_, b), (_, d) in zip(spans, old))

    # -- sequence lifecycle ---------------------------------------------
    def _resize(self, seq_id, spans, what):
        """Make ``seq_id``'s table back exactly ``spans``: take the new
        entries' blocks from the pool, hand the dropped entries' back.
        Raises :class:`CacheOverflow` without mutating anything when the
        pool cannot cover what is new."""
        old, table = self._spans[seq_id], self._tables[seq_id]
        need = self._added(spans, old)
        if need > len(self._free):
            self._alloc_failures += 1
            raise CacheOverflow(
                "KV cache overflow: sequence %r %s %d blocks, %d free "
                "(%d live of %d)" % (seq_id, what, need, len(self._free),
                                     self.live_blocks, self.capacity_blocks))
        width = max(b for _, b in spans)
        if width > len(table):
            table.extend([NULL_BLOCK] * (width - len(table)))
        dropped = self._added(old, spans)
        for (_, b), (_, d) in zip(spans, old):
            for i in range(b, d):           # dropped out: back to the pool
                self._free.append(table[i])
                table[i] = NULL_BLOCK
        for (_, b), (_, d) in zip(spans, old):
            for i in range(d, b):
                table[i] = self._free.pop()
        self._spans[seq_id] = spans
        self._frees += dropped
        self._released_live += dropped
        if need:
            self._allocs += need
            self._high_water = max(self._high_water, self.live_blocks)

    def allocate(self, seq_id, n_tokens, via=()):
        """Register ``seq_id`` with blocks for ``n_tokens`` of history and,
        beside them, what every length of ``via`` needs (the ends of its
        prefill pieces: a family whose table shrinks writes a prompt's
        earlier windows through pages its last piece no longer needs) until
        its first ``extend`` hands the surplus back. ``via`` adds nothing
        to a table that only grows.

        Raises :class:`CacheOverflow` (and allocates nothing) when the
        free list cannot cover it.
        """
        if seq_id in self._tables:
            raise ValueError("sequence %r already allocated" % (seq_id,))
        spans = self._spans_for(n_tokens, via)
        self._tables[seq_id] = []
        self._spans[seq_id] = tuple((a, a) for a, _ in spans)
        self._lengths[seq_id] = int(n_tokens)
        try:
            self._resize(seq_id, spans, "needs")
        except CacheOverflow:
            self.free(seq_id)
            raise
        return list(self._tables[seq_id])

    def extend(self, seq_id, n_tokens=1):
        """Grow ``seq_id`` by ``n_tokens``: back the entries its new length
        adds, release those it no longer needs. Raises
        :class:`CacheOverflow` without mutating anything when the pool
        cannot cover the growth."""
        new_len = self._lengths[seq_id] + int(n_tokens)
        spans = self._pages(new_len)
        if spans != self._spans[seq_id]:
            self._resize(seq_id, spans, "grew by")
        self._lengths[seq_id] = new_len
        return list(self._tables[seq_id])

    def free(self, seq_id):
        """Retire ``seq_id`` and return its blocks to the free list."""
        table = self._tables.pop(seq_id, None)
        if table is None:
            return 0
        self._lengths.pop(seq_id, None)
        held = [table[i] for a, b in self._spans.pop(seq_id)
                for i in range(a, b)]
        self._free.extend(held)
        self._frees += len(held)
        return len(held)

    def table(self, seq_id):
        return list(self._tables[seq_id])

    def length(self, seq_id):
        return self._lengths[seq_id]

    def sequences(self):
        return list(self._tables)

    # -- invariant check (tests, smoke gates) ---------------------------
    def check(self):
        """Assert allocator invariants; returns True or raises AssertionError.

        - conservation: free + live tables == capacity, no block lost,
          and every block ever taken is live or was handed back (at the
          sequence's end or, released, while it lived);
        - a table backs the entries its spans name and no other, and the
          spans hold what ``pages`` asks for at the sequence's length (more
          only between an ``allocate`` through ``via`` and the first ``extend``);
        - no aliasing: a block id appears in at most one table, never in
          both a table and the free list, and never the null block.
        """
        seen = {}
        for sid, table in self._tables.items():
            spans = self._spans[sid]
            assert all(b >= d for (_, b), (_, d) in zip(
                spans, self._pages(self._lengths[sid]))), \
                "table size mismatch for %r" % (sid,)
            backed = {i for a, b in spans for i in range(a, b)}
            for i, b in enumerate(table):
                if i not in backed:
                    assert b == NULL_BLOCK, \
                        "entry %d of %r backed outside its spans" % (i, sid)
                    continue
                assert b != NULL_BLOCK, "null block leaked into %r" % (sid,)
                assert 0 < b < self.num_blocks, "block %d out of range" % b
                assert b not in seen, \
                    "block %d aliased by %r and %r" % (b, seen[b], sid)
                seen[b] = sid
        free_set = set(self._free)
        assert len(free_set) == len(self._free), "duplicate free blocks"
        assert not (free_set & set(seen)), "block both live and free"
        assert NULL_BLOCK not in free_set, "null block in free list"
        assert len(free_set) + len(seen) == self.capacity_blocks, \
            "block conservation violated: %d free + %d live != %d" % (
                len(free_set), len(seen), self.capacity_blocks)
        assert self._allocs - self._frees == len(seen), \
            "blocks taken and handed back do not add up to those live"
        assert self._released_live <= self._frees
        return True

    def stats(self):
        return {"block_size": self.block_size,
                "blocks_total": self.capacity_blocks,
                "blocks_free": len(self._free),
                "blocks_live": self.live_blocks,
                "blocks_high_water": self._high_water,
                "sequences": len(self._tables),
                "tokens_live": sum(self._lengths.values()),
                "pool_bytes": int(self.pool_bytes),
                "state_bytes": int(self.state_bytes),
                "allocs": self._allocs, "frees": self._frees,
                "blocks_released_live": self._released_live,
                "alloc_failures": self._alloc_failures}
