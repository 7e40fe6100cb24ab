"""The decode-model seam: what a model family hands `DecodeEngine`, which
holds no model itself: ``params``, ``cache_spec``, ``prefill_fn`` and
``step_fn`` (`serving/decode.py`, "The cache seam", has the contract).

``cache_spec(num_blocks, block_size, slots)`` returns a pytree whose leaves
are of two kinds: a plain ``jax.ShapeDtypeStruct`` is a *paged pool*
(indexed by block tables: a row a cached token), a `SlotPool` a *per-slot
pool* (``[layers, slots, ...]``: a row a decode slot, for state that does
not grow with the sequence). ``prefill_fn(params, cache, tokens, start,
length, table, slot)`` is told the slot its prompt was admitted to; row
``i`` of ``step_fn(params, cache, token_ids, positions, tables, active)``
is slot ``i``. A family with paged pools only takes ``slots`` and ``slot``
and ignores them: one signature for every family.

A family may also say WHICH entries of its block table a sequence of ``n``
positions backs: ``cache_pages(n)``, a pure host function (the contract is
`serving/kvcache.py`'s: a ``(start, stop)`` span a region of the table).
`PagedKVCache` backs the entries that are new and releases those that
dropped out while the sequence lives; the engine's admission, growth and
step-ahead test reckon with the same function. A family without one gets
``ceil(n / block_size)`` entries from the front, through the same code.

The bodies take the page format, the step's walk over the live positions
and the prefill chunk's attention from `kernels/paged_attention.py`. The
methods keep the names ``prefill_fn`` and ``step_fn``: the benchmark finds
the programs by the XLA module names ``jit_prefill_fn`` / ``jit_step_fn``.
Clients: `transformer.TransformerDecodeModel`, `moe_mla.MoEMLADecodeModel`,
`kimi_linear.KimiLinearDecodeModel` (the one with per-slot state),
`evabyte.EvaByteDecodeModel` (the one with ``cache_pages``: a window of
exact rows that are handed back, chunk summaries that stay),
`motif.MotifDecodeModel` (paged full layers beside window layers whose
latent rows are a per-slot ring, four residual streams a token) and
`tiny_lm.TinyLMDecodeModel` (the tests' single-layer fixture).
"""
from __future__ import annotations

import os

import jax

__all__ = ["DecodeModel", "SlotPool"]


class SlotPool(jax.ShapeDtypeStruct):
    """A ``cache_spec`` leaf with one row a decode slot (``[layers, slots,
    ...]``). The engine treats it as any other leaf; `PagedKVCache` accounts
    its bytes as ``state_bytes``, apart from the paged ``pool_bytes``."""


class DecodeModel:
    """Base of the adapters: ``DecodeEngine(**model.engine_kwargs(), ...)``.
    A subclass sets ``params`` and defines ``cache_spec``, ``prefill_fn``
    and ``step_fn``, and ``cache_pages`` where its table is not one block
    a ``block_size`` positions."""

    cache_pages = None

    def resolve_flash(self, flash):
        """Pick the kernel tier of the prefill attention (and of a family's
        other kernels): ``flash`` None reads ``MXNET_SERVING_DECODE_FLASH``
        (auto | 1/on | 0/off | interpret, the `resolve_kernel_tier`
        vocabulary)."""
        from ..parallel.mesh_kernels import resolve_kernel_tier
        if flash is None:
            flash = os.environ.get("MXNET_SERVING_DECODE_FLASH", "auto")
        self.use_pallas, self.interpret = resolve_kernel_tier(flash)
        self.flash_engaged = bool(self.use_pallas or self.interpret)

    def engine_kwargs(self):
        return {"params": self.params, "cache_spec": self.cache_spec,
                "cache_pages": self.cache_pages,
                "prefill_fn": self.prefill_fn, "step_fn": self.step_fn}
