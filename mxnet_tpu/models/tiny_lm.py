"""A deliberately tiny single-layer attention LM on the decode-model seam
(`decode_model.py`): embed -> K/V into the paged cache -> masked attention
over the sequence's own blocks -> greedy argmax.

Small enough for the CPU test mesh yet history-dependent and
row-independent, so "continuous-batched decode is bit-identical to solo
decode" is a real statement about the engine's cache and batching. Its step
attends over the WHOLE table: the tests' plain oracle, not a served path.
"""
from __future__ import annotations

import math

import numpy as _np
import jax
import jax.numpy as jnp

from ..kernels.paged_attention import (MASKED, chunk_addresses,
                                       step_addresses)
from .decode_model import DecodeModel

__all__ = ["TinyLMDecodeModel"]


class TinyLMDecodeModel(DecodeModel):
    """Parameters ``emb (V, D)``, ``w_k (D, D)``, ``w_v (D, D)``, ``w_out
    (D, V)``, float32 from a seeded RandomState, so every process (tests,
    smoke clients) derives the same model. The cache: twin float32 pools
    ``{"k", "v"}`` of ``(num_blocks, block_size, dim)``, no layer axis."""

    def __init__(self, vocab=32, dim=16, seed=0):
        rng = _np.random.RandomState(seed)
        s = 1.0 / math.sqrt(dim)
        self.dim = dim
        self.params = {
            "emb": rng.standard_normal((vocab, dim)).astype(_np.float32),
            "w_k": (rng.standard_normal((dim, dim)) * s).astype(_np.float32),
            "w_v": (rng.standard_normal((dim, dim)) * s).astype(_np.float32),
            "w_out": (rng.standard_normal((dim, vocab)) * s).astype(
                _np.float32),
        }

    def cache_spec(self, num_blocks, block_size, slots):
        pool = jax.ShapeDtypeStruct((num_blocks, block_size, self.dim),
                                    jnp.float32)
        return {"k": pool, "v": pool}

    def prefill_fn(self, params, cache, tokens, start, length, table, slot):
        """Writes K/V for global positions ``start..start+length-1`` of
        the bucket-padded chunk ``tokens (L,)``, attends the chunk's last
        real token over ``pos < start + length``."""
        emb, w_k, w_v, w_out = (params["emb"], params["w_k"],
                                params["w_v"], params["w_out"])
        k_pages, v_pages = cache["k"], cache["v"]
        bs = k_pages.shape[1]
        mb = table.shape[0]
        x = emb[tokens]                                     # (L, D)
        _, _, blk, slot = chunk_addresses(table, start, length,
                                          tokens.shape[0], bs)
        k_pages = k_pages.at[blk, slot].set(x @ w_k)
        v_pages = v_pages.at[blk, slot].set(x @ w_v)
        x_last = jnp.take(x, length - 1, axis=0)            # (D,)
        ks = k_pages[table].reshape(mb * bs, self.dim)
        vs = v_pages[table].reshape(mb * bs, self.dim)
        tpos = jnp.arange(mb * bs, dtype=jnp.int32)
        scores = (ks @ x_last) * (1.0 / math.sqrt(self.dim))
        scores = jnp.where(tpos < start + length, scores, MASKED)
        ctx = jax.nn.softmax(scores) @ vs
        next_id = jnp.argmax(ctx @ w_out).astype(jnp.int32)
        return next_id, {"k": k_pages, "v": v_pages}, {}

    def step_fn(self, params, cache, token_ids, positions, tables, active):
        """Every per-row computation contracts only over that row's own
        gathered blocks: rows cannot observe each other."""
        emb, w_k, w_v, w_out = (params["emb"], params["w_k"],
                                params["w_v"], params["w_out"])
        k_pages, v_pages = cache["k"], cache["v"]
        bs = k_pages.shape[1]
        b, mb = tables.shape
        x = emb[token_ids]                                  # (B, D)
        blk, slot = step_addresses(tables, positions, active, bs)
        k_pages = k_pages.at[blk, slot].set(x @ w_k)
        v_pages = v_pages.at[blk, slot].set(x @ w_v)
        ks = k_pages[tables].reshape(b, mb * bs, self.dim)  # (B, T, D)
        vs = v_pages[tables].reshape(b, mb * bs, self.dim)
        tpos = jnp.arange(mb * bs, dtype=jnp.int32)[None, :]
        scores = jnp.einsum("bd,btd->bt", x, ks) \
            * (1.0 / math.sqrt(self.dim))
        scores = jnp.where(tpos <= positions[:, None], scores, MASKED)
        ctx = jnp.einsum("bt,btd->bd", jax.nn.softmax(scores, axis=-1), vs)
        next_ids = jnp.argmax(ctx @ w_out, axis=-1).astype(jnp.int32)
        return next_ids, {"k": k_pages, "v": v_pages}, {}
