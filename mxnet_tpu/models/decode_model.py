"""The decode-model seam: what a model family hands `DecodeEngine`, which
holds no model itself: ``params``, ``cache_spec``, ``prefill_fn`` and
``step_fn`` (`serving/decode.py`, "The cache seam", has the signatures).

The bodies take the page format, the step's walk over the live positions
and the prefill chunk's attention from `kernels/paged_attention.py`. The
methods keep the names ``prefill_fn`` and ``step_fn``: the benchmark finds
the programs by the XLA module names ``jit_prefill_fn`` / ``jit_step_fn``.
Clients: `transformer.TransformerDecodeModel`, `moe_mla.MoEMLADecodeModel`
and `tiny_lm.TinyLMDecodeModel` (the tests' single-layer fixture).
"""
from __future__ import annotations

import os

__all__ = ["DecodeModel"]


class DecodeModel:
    """Base of the adapters: ``DecodeEngine(**model.engine_kwargs(), ...)``.
    A subclass sets ``params`` and defines ``cache_spec``, ``prefill_fn``
    and ``step_fn``."""

    def resolve_flash(self, flash):
        """Pick the prefill attention tier (a step body runs no kernel):
        ``flash`` None reads ``MXNET_SERVING_DECODE_FLASH`` (auto | 1/on |
        0/off | interpret, the `resolve_kernel_tier` vocabulary)."""
        from ..parallel.mesh_kernels import resolve_kernel_tier
        if flash is None:
            flash = os.environ.get("MXNET_SERVING_DECODE_FLASH", "auto")
        self.use_pallas, self.interpret = resolve_kernel_tier(flash)
        self.flash_engaged = bool(self.use_pallas or self.interpret)

    def engine_kwargs(self):
        return {"params": self.params, "cache_spec": self.cache_spec,
                "prefill_fn": self.prefill_fn, "step_fn": self.step_fn}
