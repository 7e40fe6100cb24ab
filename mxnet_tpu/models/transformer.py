"""Transformer LM — the long-context flagship model family.

The reference's sequence stack tops out at fused RNNs + bucketing (SURVEY.md
§5.7); transformers are the TPU-native capability that the parallel stack
(ring attention, tensor parallelism) is designed around. This module is
functional-first (params pytree + pure forward) so it composes with
`jax.jit`/`shard_map`/`jax.checkpoint`; a Gluon block wrapper can ride on top.

TPU design points:
- per-layer params are **stacked** on a leading axis and the layer loop is a
  `lax.scan` — one trace regardless of depth, and the leading axis doubles as
  the pipeline-stage shard axis (`parallel/pipeline.py`).
- attention runs inside a full-mesh `shard_map` island: heads shard over
  'tp', sequence over 'sp' (ring or Ulysses), batch over 'dp'. Everything
  else is plain jnp under jit — XLA inserts the TP collectives from the
  weight shardings (scaling-book recipe).
- `cfg.remat` wraps each block in `jax.checkpoint` (reference analog:
  MXNET_BACKWARD_DO_MIRROR, graph_executor.cc:277-300).
"""
from __future__ import annotations

import numpy as _np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..kernels.flash_attention import flash_attention
from ..parallel.collectives import shard_map
from ..parallel.ring_attention import sequence_parallel_attention

__all__ = ["TransformerConfig", "init_transformer", "transformer_forward",
           "transformer_loss", "transformer_sharding_rules",
           "transformer_decode_prefill", "transformer_decode_step",
           "TransformerDecodeModel"]


class TransformerConfig:
    """Decoder-only LM config (GPT-style, pre-LN)."""

    def __init__(self, vocab_size, num_layers=2, num_heads=4, d_model=128,
                 d_ff=None, max_len=512, dtype=jnp.float32, remat=False,
                 attn_impl="ring", block_k=512, dropout=0.0,
                 attn_variant="stream"):
        self.vocab_size = vocab_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.d_model = d_model
        self.d_ff = d_ff or 4 * d_model
        self.max_len = max_len
        self.dtype = dtype
        self.remat = remat
        self.attn_impl = attn_impl  # 'ring' | 'ulysses' | 'full'
        self.block_k = block_k
        self.dropout = dropout
        # Pallas kernel family for the attention core: 'stream' or 'grid'
        # (O(block) VMEM — long per-device sequence chunks)
        self.attn_variant = attn_variant
        assert attn_variant in ("stream", "grid"), attn_variant
        assert d_model % num_heads == 0


def init_transformer(cfg, key):
    """Params pytree; layer params stacked on axis 0 (scan/pipeline axis)."""
    d, f, L = cfg.d_model, cfg.d_ff, cfg.num_layers
    keys = jax.random.split(key, 8)
    s = 0.02

    def norm(k, shape):
        return (jax.random.normal(k, shape) * s).astype(cfg.dtype)

    params = {
        "embed": norm(keys[0], (cfg.vocab_size, d)),
        "pos_embed": norm(keys[1], (cfg.max_len, d)),
        "ln_f_scale": jnp.ones((d,), cfg.dtype),
        "ln_f_bias": jnp.zeros((d,), cfg.dtype),
        "layers": {
            "wq": norm(keys[2], (L, d, d)),
            "wk": norm(keys[3], (L, d, d)),
            "wv": norm(keys[4], (L, d, d)),
            "wo": norm(keys[5], (L, d, d)),
            "w1": norm(keys[6], (L, d, f)),
            "b1": jnp.zeros((L, f), cfg.dtype),
            "w2": norm(keys[7], (L, f, d)),
            "b2": jnp.zeros((L, d), cfg.dtype),
            "ln1_scale": jnp.ones((L, d), cfg.dtype),
            "ln1_bias": jnp.zeros((L, d), cfg.dtype),
            "ln2_scale": jnp.ones((L, d), cfg.dtype),
            "ln2_bias": jnp.zeros((L, d), cfg.dtype),
        },
    }
    return params


def transformer_sharding_rules(cfg, mesh):
    """PartitionSpec pytree matching init_transformer's structure.

    TP recipe: attention projections column-shard the head dim ('tp' on the
    output axis of wq/wk/wv, input axis of wo); MLP shards d_ff; embedding
    shards vocab. Layer-stacked leading axis stays unsharded here — the
    pipeline path shards it over 'pp' instead.
    """
    tp = "tp" if "tp" in mesh.axis_names else None
    # a vocabulary the tp degree does not divide (GPT-2's 50257) cannot be
    # split by rows: that table stays replicated
    vocab_tp = tp if tp and cfg.vocab_size % mesh.shape[tp] == 0 else None
    return {
        "embed": P(vocab_tp, None),
        "pos_embed": P(),
        "ln_f_scale": P(),
        "ln_f_bias": P(),
        "layers": {
            "wq": P(None, None, tp),
            "wk": P(None, None, tp),
            "wv": P(None, None, tp),
            "wo": P(None, tp, None),
            "w1": P(None, None, tp),
            "b1": P(None, tp),
            "w2": P(None, tp, None),
            "b2": P(),
            "ln1_scale": P(),
            "ln1_bias": P(),
            "ln2_scale": P(),
            "ln2_bias": P(),
        },
    }


def _layer_norm(x, scale, bias, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * scale + bias


def _attention(q, k, v, cfg, mesh):
    """[B, H, S, D] attention; shard_map island when a mesh is given.

    The kernel tier inside the island follows MXNET_TPU_MESH_KERNEL_TIER
    (`parallel.mesh_kernels.resolve_kernel_tier`, resolved at trace
    time): pallas_call is not auto-partitionable, but per-shard inside
    the manual region it is a plain local op, so the flash kernel stays
    engaged on dp×tp meshes instead of lax-falling-back."""
    from ..parallel.mesh_kernels import resolve_kernel_tier
    kt_pallas, kt_interpret = resolve_kernel_tier()
    if mesh is None:
        return flash_attention(q, k, v, causal=True, block_k=cfg.block_k,
                               use_pallas=kt_pallas, interpret=kt_interpret,
                               variant=cfg.attn_variant)
    names = mesh.axis_names
    bq = "dp" if "dp" in names else None
    hq = "tp" if "tp" in names else None
    impl = cfg.attn_impl
    # impl='full' keeps the sequence replicated (no SP): sharding it over 'sp'
    # without a ring/all-to-all would silently block-diagonalize attention
    sq = "sp" if ("sp" in names and impl != "full") else None
    spec = P(bq, hq, sq, None)

    def local(q, k, v):
        if sq is None or impl == "full":
            return flash_attention(q, k, v, causal=True, block_k=cfg.block_k,
                                   use_pallas=kt_pallas,
                                   interpret=kt_interpret,
                                   variant=cfg.attn_variant)
        return sequence_parallel_attention(q, k, v, sq, impl=impl,
                                           causal=True, block_k=cfg.block_k,
                                           variant=cfg.attn_variant)

    # pad sequence to a multiple of the sp degree: causal masking keeps
    # end-padding invisible to real query positions
    S = q.shape[2]
    n_sp = mesh.shape[sq] if sq is not None else 1
    pad = (-S) % n_sp
    if pad:
        padw = ((0, 0), (0, 0), (0, pad), (0, 0))
        q, k, v = (jnp.pad(t, padw) for t in (q, k, v))
    # the Pallas kernels declare no varying-mesh-axes type on their outputs
    # (and the interpret-mode evaluator cannot carry one), so the island
    # checks replication only on the lax tier — as flash_attention_mesh does
    out = shard_map(local, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
                    check_vma=not (kt_pallas or kt_interpret))(q, k, v)
    return out[:, :, :S] if pad else out


def _dropout(x, rate, key):
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0).astype(x.dtype)


def _block(x, lp, cfg, mesh, key=None):
    """One pre-LN decoder block. x: [B, S, D]; key enables dropout."""
    B, S, d = x.shape
    H, Dh = cfg.num_heads, cfg.d_model // cfg.num_heads
    h = _layer_norm(x, lp["ln1_scale"], lp["ln1_bias"])
    q = (h @ lp["wq"]).reshape(B, S, H, Dh).transpose(0, 2, 1, 3)
    k = (h @ lp["wk"]).reshape(B, S, H, Dh).transpose(0, 2, 1, 3)
    v = (h @ lp["wv"]).reshape(B, S, H, Dh).transpose(0, 2, 1, 3)
    a = _attention(q, k, v, cfg, mesh)
    a = a.transpose(0, 2, 1, 3).reshape(B, S, d)
    a = a @ lp["wo"]
    if key is not None:
        k1, k2 = jax.random.split(key)
        a = _dropout(a, cfg.dropout, k1)
    x = x + a
    h = _layer_norm(x, lp["ln2_scale"], lp["ln2_bias"])
    h = jax.nn.gelu(h @ lp["w1"] + lp["b1"])
    h = h @ lp["w2"] + lp["b2"]
    if key is not None:
        h = _dropout(h, cfg.dropout, k2)
    x = x + h
    return x


def transformer_forward(params, tokens, cfg, mesh=None, rng=None,
                        train=False):
    """tokens: [B, S] int32 -> logits [B, S, vocab].

    Dropout is applied only when `train` and `cfg.dropout > 0` and an `rng`
    key is given (per-layer keys derived inside the layer scan).
    """
    B, S = tokens.shape
    x = params["embed"][tokens].astype(cfg.dtype)
    x = x + params["pos_embed"][:S].astype(cfg.dtype)
    use_dropout = train and cfg.dropout > 0.0 and rng is not None

    block = lambda x, lp, key: _block(x, lp, cfg, mesh, key=key)
    if cfg.remat:
        block = jax.checkpoint(block)

    def body(carry, lp):
        x, key = carry
        if use_dropout:
            key, sub = jax.random.split(key)
        else:
            sub = None
        return (block(x, lp, sub), key), None

    if rng is None:
        rng = jax.random.PRNGKey(0)
    (x, _), _ = lax.scan(body, (x, rng), params["layers"])
    x = _layer_norm(x, params["ln_f_scale"], params["ln_f_bias"])
    logits = x @ params["embed"].T.astype(cfg.dtype)
    return logits


def transformer_loss(params, tokens, targets, cfg, mesh=None, rng=None,
                     train=True):
    """Mean next-token cross-entropy. targets: [B, S] int32 (-1 = ignore)."""
    logits = transformer_forward(params, tokens, cfg, mesh=mesh, rng=rng,
                                 train=train)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.maximum(targets, 0)
    gold = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
    nll = logz - gold
    mask = (targets >= 0).astype(jnp.float32)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


# ---------------------------------------------------------------------------
# Paged-KV decode bodies (serving/decode.py program family)
# ---------------------------------------------------------------------------
# The serving DecodeEngine is model-agnostic: it owns the paged KV pool,
# block tables and continuous batching, and calls a bucketed batch-1
# prefill program plus one fixed-shape batched step program. These are
# the real multi-layer multi-head transformer bodies for that seam —
# replacing the engine's built-in single-layer parity fixture with the
# model family the parallel stack is designed around.
#
# KV page layout: layer-major, ``(num_layers, num_blocks, block_size,
# d_model)`` for each of K and V, so ``pages[l]`` is one layer's
# contiguous pool and a layer gathers only its own pages. Heads are folded
# into d_model, so tp-sharding the trailing dim shards heads
# (`kvcache.page_sharding`). Per layer l, position p of a sequence lives
# at ``pages[l, table[p // bs], p % bs]``.
#
# Masking contract (shared with the built-in fixture): padding/inactive
# writes scatter into the null block, and every read masks additively
# with -1e30 — exp(-1e30 - m) is exactly 0.0 in f32, so not-yet-written
# or foreign page content can never perturb a real row's bits. This is
# what makes chunked prefill BIT-identical to whole-prompt prefill: a
# query at global position p gathers the same table-shaped page block
# either way, real keys (tpos <= p) hold identical bits by induction
# over layers/chunks, and masked lanes contribute exactly 0 regardless
# of content.

_NEG = -1e30


def _decode_attn_prefill(q, ks, vs, start, cfg, use_pallas, interpret):
    """Chunk attention over gathered pages. q: (C, H, Dh); ks/vs:
    (T, H, Dh) gathered from the sequence's block table. Causal at
    global offset `start` (query row i sits at position start + i).

    Kernel tier: the offset-aware flash kernels
    (`_flash_fwd_offs_kernel` block-table variant) with
    offs = [start, 0]; lax tier: `blockwise_attention` with q_offset —
    identical masking semantics, fp-tolerance numerics."""
    from ..kernels.flash_attention import (blockwise_attention,
                                           flash_attention_with_lse)
    C, H, Dh = q.shape
    T = ks.shape[0]
    sm = 1.0 / _np.sqrt(Dh)
    q4 = q.transpose(1, 0, 2)[None]                     # (1, H, C, Dh)
    k4 = ks.transpose(1, 0, 2)[None]
    v4 = vs.transpose(1, 0, 2)[None]
    # block sizes must tile exactly: C is a prefill bucket (so C itself
    # always works), T = mb * block_size (so block_size always works)
    bq = C if C % min(cfg.block_k, C) else min(cfg.block_k, C)
    bk = T if T % min(cfg.block_k, T) else min(cfg.block_k, T)
    if use_pallas or interpret:
        offs = jnp.asarray([start, 0], jnp.int32) \
            if not hasattr(start, "dtype") else \
            jnp.stack([start.astype(jnp.int32), jnp.int32(0)])
        out, _ = flash_attention_with_lse(q4, k4, v4, offs, sm, True,
                                          bq, bk, interpret,
                                          cfg.attn_variant)
    else:
        out, _ = blockwise_attention(q4, k4, v4, causal=True, sm_scale=sm,
                                     block_k=bk, q_offset=start, k_offset=0)
    return out[0].transpose(1, 0, 2)                    # (C, H, Dh)


@jax.named_scope("decode.prefill")      # the trace's device-side name
def transformer_decode_prefill(params, cfg, cache, tokens,
                               start, length, table, *, use_pallas=False,
                               interpret=False):
    """Bucketed batch-1 prefill chunk: write K/V for global positions
    ``start .. start+length-1`` into the paged cache, return the greedy
    next token after the chunk's last real position.

    Matches the DecodeEngine prefill seam
    ``(params, cache, tokens, start, length, table) -> (next_id, cache,
    aux)``; the cache is ``{"k": pages, "v": pages}``, ``aux`` empty.
    Whole-prompt prefill is the ``start=0`` call; chunked prefill is the
    SAME bucket program called repeatedly with advancing ``start`` —
    the program family stays at len(buckets)+1."""
    k_pages, v_pages = cache["k"], cache["v"]
    C = tokens.shape[0]
    bs = k_pages.shape[2]
    mb = table.shape[0]
    L = cfg.num_layers
    H, Dh = cfg.num_heads, cfg.d_model // cfg.num_heads
    T = mb * bs
    idx = jnp.arange(C, dtype=jnp.int32)
    pos = start + idx
    x = params["embed"][tokens].astype(cfg.dtype)
    x = x + params["pos_embed"][jnp.clip(pos, 0, cfg.max_len - 1)] \
        .astype(cfg.dtype)
    valid = idx < length
    blk = jnp.where(valid, table[jnp.clip(pos, 0, T - 1) // bs], 0)
    slot = jnp.clip(pos, 0, T - 1) % bs
    lp_all = params["layers"]
    for l in range(L):
        with jax.named_scope("layer"):
            lp = {k: v[l] for k, v in lp_all.items()}
            h = _layer_norm(x, lp["ln1_scale"], lp["ln1_bias"])
            q = (h @ lp["wq"]).reshape(C, H, Dh)
            kk = h @ lp["wk"]                               # (C, D)
            vv = h @ lp["wv"]
            k_pages = k_pages.at[l, blk, slot].set(kk)
            v_pages = v_pages.at[l, blk, slot].set(vv)
            ks = k_pages[l, table].reshape(T, H, Dh)
            vs = v_pages[l, table].reshape(T, H, Dh)
            a = _decode_attn_prefill(q, ks, vs, start, cfg, use_pallas,
                                     interpret)
            x = x + a.reshape(C, cfg.d_model) @ lp["wo"]
            h = _layer_norm(x, lp["ln2_scale"], lp["ln2_bias"])
            x = x + (jax.nn.gelu(h @ lp["w1"] + lp["b1"]) @ lp["w2"]
                     + lp["b2"])
    x = _layer_norm(x, params["ln_f_scale"], params["ln_f_bias"])
    x_last = jnp.take(x, jnp.clip(length - 1, 0, C - 1), axis=0)
    logits = x_last @ params["embed"].T.astype(cfg.dtype)
    return (jnp.argmax(logits).astype(jnp.int32),
            {"k": k_pages, "v": v_pages}, {})


# The decode step's walk over the live positions: how many rows a block
# of the walk holds and how many positions one piece of it covers. The
# trade is walked-but-masked positions (larger blocks and pieces: a block
# walks as far as its LONGEST row, rounded up to a piece) against loop
# iterations (smaller ones: layers x row blocks x pieces, each with a
# fixed cost). Settled on the chip at 64 rows over 64 blocks of 16
# (PERF.md, PR 30): 8 x 128 reads 7.2 ms a step, 8 x 32 and 2 x 128 9.5,
# 32 x 256 14.
_WALK_ROWS = 8
_WALK_SPAN = 128


def _walk_sizes(B, mb, bs):
    """``(rows per block, table blocks per piece)`` of the step's walk for
    ``B`` rows over tables of ``mb`` blocks of ``bs`` positions. One block
    over all rows where ``B`` does not divide into several; one piece over
    the whole table where ``mb`` does not."""
    rb = _WALK_ROWS if B > _WALK_ROWS and B % _WALK_ROWS == 0 else B
    cb = max(1, _WALK_SPAN // bs)
    return rb, (mb if mb % cb else cb)


def _walk_plan(positions, tables, bs):
    """What every layer's walk shares: the rows sorted by length (and the
    way back) and split into blocks, each block's tables and positions,
    and the number of pieces it walks: as far as its longest row reaches
    and no further. With it, the positions one layer's walk covers."""
    B, mb = tables.shape
    rb, cb = _walk_sizes(B, mb, bs)
    order = jnp.argsort(positions)
    pos_s = jnp.take(positions, order).reshape(B // rb, rb)
    tables_s = jnp.take(tables, order, axis=0).reshape(B // rb, rb, mb)
    pieces = jnp.max(pos_s, axis=1) // (cb * bs) + 1
    return ((order, jnp.argsort(order), pos_s, tables_s, pieces, cb),
            jnp.sum(pieces) * (rb * cb * bs))


def _live_attention(q, k_pages, v_pages, l, plan, num_heads):
    """One layer's decode attention over the LIVE positions only, heads
    kept in the lanes. ``q`` ``(B, d_model)``; pools ``(L, blocks, bs,
    d_model)`` read at layer ``l`` (``pages[l, tab]``, never
    ``pages[l][tab]``: the second copies the layer's pool first).

    A block of rows walks its tables a piece at a time (`_walk_plan`: a
    loop with a traced trip count, static shapes, one program) and folds
    each piece in with a running softmax in float32. A gathered piece stays
    ``(rows, span, d_model)``. The query is laid out block-diagonally,
    ``(rows, d_model, heads)`` with head ``h``'s 64 numbers in column ``h``,
    so the scores are ``piece @ q_bd`` and the context ``p^T @ piece``, of
    which head ``h`` keeps its own lanes: no positions-sized array ever
    has ``(heads, head_dim)`` as its minor pair (the (8,128) tile pads
    that pair from 768 lanes to 2,048). Both contractions carry
    ``HIGHEST`` precision: no key, value, score or weight is rounded on
    the way through the matrix unit.

    A masked position contributes exact 0 and a piece past a row's end
    leaves that row's carry bit-for-bit (``alpha = 1``, ``p = 0``), so a
    row's result does not depend on which rows share its block."""
    order, inverse, pos_s, tables_s, pieces, cb = plan
    B, D = q.shape
    nb, rb, _ = tables_s.shape
    span = cb * k_pages.shape[2]
    dh = D // num_heads
    sm = 1.0 / _np.sqrt(dh)
    hi = lax.Precision.HIGHEST
    # ind[d, h] = 1 where lane d belongs to head h
    ind = (jnp.arange(D)[:, None] // dh
           == jnp.arange(num_heads)[None, :]).astype(jnp.float32)

    def rows_block(args):
        q_b, tables_b, pos_b, n = args          # (rb, D), (rb, mb), (rb,), ()
        q_bd = q_b[:, :, None] * ind            # (rb, D, H), block-diagonal

        def piece(j, carry):
            m, den, acc = carry                 # (rb, H), (rb, H), (rb, H, D)
            tab = lax.dynamic_slice_in_dim(tables_b, j * cb, cb, axis=1)
            kp = k_pages[l, tab].reshape(rb, span, D)
            vp = v_pages[l, tab].reshape(rb, span, D)
            s = jnp.einsum("bsd,bdh->bsh", kp, q_bd, precision=hi) * sm
            tpos = j * span + jnp.arange(span, dtype=jnp.int32)
            s = jnp.where(tpos[None, :, None] <= pos_b[:, None, None], s,
                          _NEG)
            m_new = jnp.maximum(m, jnp.max(s, axis=1))
            p = jnp.exp(s - m_new[:, None, :])
            alpha = jnp.exp(m - m_new)
            return (m_new, den * alpha + jnp.sum(p, axis=1),
                    acc * alpha[..., None]
                    + jnp.einsum("bsh,bsd->bhd", p, vp, precision=hi))

        # position 0 is live for every row, so the first piece leaves a
        # finite running maximum and a masked piece after it adds exact 0
        carry = (jnp.full((rb, num_heads), _NEG, jnp.float32),
                 jnp.zeros((rb, num_heads), jnp.float32),
                 jnp.zeros((rb, num_heads, D), jnp.float32))
        _, den, acc = lax.fori_loop(0, n, piece, carry)
        # head h keeps its own lanes of row h of the (H, D) accumulator
        return jnp.sum(acc / den[..., None] * ind.T, axis=1)

    q_s = jnp.take(q, order, axis=0).reshape(nb, rb, D)
    ctx = lax.map(rows_block, (q_s, tables_s, pos_s, pieces))
    return jnp.take(ctx.reshape(B, D), inverse, axis=0)


@jax.named_scope("decode.step")      # the trace's device-side name
def transformer_decode_step(params, cfg, cache, token_ids,
                            positions, tables, active):
    """Fixed-shape batched decode step: one token per active row.

    Matches the DecodeEngine step seam ``(params, cache, token_ids,
    positions, tables, active) -> (next_ids, cache, aux)``. Attention reads
    and contracts only the LIVE positions of each row's table
    (`_live_attention`): the rows are sorted by length, a block of rows
    walks its tables a piece at a time as far as its longest row reaches,
    and the 768-wide row of the pool stays in the lane dimension
    throughout. One program whatever the lengths (the walk's trip counts
    are traced values). A row contracts only over its own gathered blocks
    and a walked-but-masked position adds exact 0, so rows cannot observe
    each other: batched decode stays bit-identical to solo decode. The
    model's products run at the default matmul precision; the attention's
    own contractions round nothing (float32, ``HIGHEST`` where they use
    the matrix unit). Prefill is where the flash tier earns its keep.

    ``aux`` counts what the walk did, summed into ``stats()["model"]``:
    ``kv_live_tokens`` (cached tokens the active rows attended over,
    ``positions + 1`` each) and ``kv_walked_tokens`` (positions gathered
    and contracted: rows x span x pieces over the row blocks, one layer's
    count); their ratio is the walk's efficiency."""
    k_pages, v_pages = cache["k"], cache["v"]
    bs = k_pages.shape[2]
    L = cfg.num_layers
    x = params["embed"][token_ids].astype(cfg.dtype)
    x = x + params["pos_embed"][jnp.clip(positions, 0, cfg.max_len - 1)] \
        .astype(cfg.dtype)
    blk = jnp.take_along_axis(tables, (positions // bs)[:, None], axis=1)
    blk = jnp.where(active, blk[:, 0], 0)
    slot = positions % bs
    plan, walked = _walk_plan(positions, tables, bs)
    lp_all = params["layers"]
    for l in range(L):
        with jax.named_scope("layer"):
            lp = {k: v[l] for k, v in lp_all.items()}
            h = _layer_norm(x, lp["ln1_scale"], lp["ln1_bias"])
            q = h @ lp["wq"]
            kk = h @ lp["wk"]
            vv = h @ lp["wv"]
            k_pages = k_pages.at[l, blk, slot].set(kk)
            v_pages = v_pages.at[l, blk, slot].set(vv)
            ctx = _live_attention(q, k_pages, v_pages, l, plan,
                                  cfg.num_heads)
            x = x + ctx @ lp["wo"]
            h = _layer_norm(x, lp["ln2_scale"], lp["ln2_bias"])
            x = x + (jax.nn.gelu(h @ lp["w1"] + lp["b1"]) @ lp["w2"]
                     + lp["b2"])
    x = _layer_norm(x, params["ln_f_scale"], params["ln_f_bias"])
    logits = x @ params["embed"].T.astype(cfg.dtype)
    aux = {"kv_live_tokens": jnp.sum(jnp.where(active, positions + 1, 0)),
           "kv_walked_tokens": walked}
    return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
            {"k": k_pages, "v": v_pages}, aux)


class TransformerDecodeModel:
    """Adapter: a multi-layer TransformerConfig wired for the
    DecodeEngine seam.

    >>> model = TransformerDecodeModel(TransformerConfig(vocab_size=256,
    ...     num_layers=2, num_heads=4, d_model=64, max_len=128))
    >>> eng = DecodeEngine(**model.engine_kwargs(), max_seq_len=128)

    ``flash`` picks the prefill attention tier (the step body runs no
    kernel: see `transformer_decode_step`): None reads
    ``MXNET_SERVING_DECODE_FLASH`` (auto | 1/on | 0/off | interpret,
    the `resolve_kernel_tier` vocabulary). Params default to
    `init_transformer` from a seeded key, so every process (engine,
    smoke clients, bench) derives the same model."""

    def __init__(self, cfg, params=None, seed=0, flash=None):
        from ..parallel.mesh_kernels import resolve_kernel_tier
        self.cfg = cfg
        if params is None:
            params = init_transformer(cfg, jax.random.PRNGKey(seed))
        self.params = params
        mode = flash
        if mode is None:
            import os
            mode = os.environ.get("MXNET_SERVING_DECODE_FLASH", "auto")
        self.use_pallas, self.interpret = resolve_kernel_tier(mode)
        self.flash_engaged = bool(self.use_pallas or self.interpret)

    def cache_spec(self, num_blocks, block_size):
        """The cache: twin float32 K and V pools, layer-major, so a
        layer reads and writes only ``pages[l]``."""
        pool = jax.ShapeDtypeStruct(
            (self.cfg.num_layers, num_blocks, block_size, self.cfg.d_model),
            jnp.float32)
        return {"k": pool, "v": pool}

    def prefill_fn(self, params, cache, tokens, start, length, table):
        return transformer_decode_prefill(
            params, self.cfg, cache, tokens, start, length,
            table, use_pallas=self.use_pallas, interpret=self.interpret)

    def step_fn(self, params, cache, token_ids, positions, tables, active):
        return transformer_decode_step(params, self.cfg, cache,
                                       token_ids, positions, tables, active)

    def engine_kwargs(self):
        """kwargs bundle for DecodeEngine(**model.engine_kwargs(), ...)."""
        return {"params": self.params, "cache_spec": self.cache_spec,
                "prefill_fn": self.prefill_fn, "step_fn": self.step_fn}
