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
from ..kernels import paged_attention as paged
from ..parallel.collectives import shard_map
from ..parallel.ring_attention import sequence_parallel_attention
from .decode_model import DecodeModel

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
# Paged-KV decode bodies (the DecodeEngine seam, models/decode_model.py)
# ---------------------------------------------------------------------------
# The page format, the masking contract, the step's walk over the live
# positions and the prefill chunk's attention are
# `kernels/paged_attention.py`'s; what is written here is this family's
# layout and its two contractions. K and V each have a layer-major pool
# ``(num_layers, num_blocks, block_size, d_model)``; heads are folded into
# d_model, so tp-sharding the trailing dim shards heads
# (`kvcache.page_sharding`).

# The decode step's walk: rows a block and positions a piece. Settled on
# the chip at 64 rows over 64 blocks of 16 (PERF.md, PR 30): 8 x 128 reads
# 7.2 ms a step, 8 x 32 and 2 x 128 9.5, 32 x 256 14.
_WALK_ROWS = 8
_WALK_SPAN = 128


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
    the program family stays at len(buckets)+1, and the result stays
    BIT-identical: real keys hold identical bits by induction over layers
    and chunks, and masked lanes contribute exactly 0 whatever they hold."""
    k_pages, v_pages = cache["k"], cache["v"]
    C = tokens.shape[0]
    bs = k_pages.shape[2]
    H, Dh = cfg.num_heads, cfg.d_model // cfg.num_heads
    T = table.shape[0] * bs
    pos, _, blk, slot = paged.chunk_addresses(table, start, length, C, bs)
    x = params["embed"][tokens].astype(cfg.dtype)
    x = x + params["pos_embed"][jnp.clip(pos, 0, cfg.max_len - 1)] \
        .astype(cfg.dtype)
    heads = lambda t, n: t.reshape(n, H, Dh).transpose(1, 0, 2)  # noqa: E731
    lp_all = params["layers"]
    for l in range(cfg.num_layers):
        with jax.named_scope("layer"):
            lp = {k: v[l] for k, v in lp_all.items()}
            h = _layer_norm(x, lp["ln1_scale"], lp["ln1_bias"])
            k_pages = k_pages.at[l, blk, slot].set(h @ lp["wk"])
            v_pages = v_pages.at[l, blk, slot].set(h @ lp["wv"])
            a = paged.chunk_attention(
                heads(h @ lp["wq"], C),
                heads(paged.gather_pages(k_pages, l, table), T),
                heads(paged.gather_pages(v_pages, l, table), T),
                start, 1.0 / _np.sqrt(Dh), cfg.block_k, use_pallas,
                interpret, cfg.attn_variant)
            x = x + a.transpose(1, 0, 2).reshape(C, cfg.d_model) @ lp["wo"]
            h = _layer_norm(x, lp["ln2_scale"], lp["ln2_bias"])
            x = x + (jax.nn.gelu(h @ lp["w1"] + lp["b1"]) @ lp["w2"]
                     + lp["b2"])
    x = _layer_norm(x, params["ln_f_scale"], params["ln_f_bias"])
    x_last = jnp.take(x, jnp.clip(length - 1, 0, C - 1), axis=0)
    logits = x_last @ params["embed"].T.astype(cfg.dtype)
    return (jnp.argmax(logits).astype(jnp.int32),
            {"k": k_pages, "v": v_pages}, {})


def _live_attention(q, k_pages, v_pages, l, plan, num_heads):
    """One layer's decode attention over the live positions
    (`paged_attention.live_walk`), heads kept in the lanes. ``q`` ``(B,
    d_model)``; pools ``(L, blocks, bs, d_model)`` read at layer ``l``.

    A gathered piece stays ``(rows, span, d_model)``. The query is laid
    out block-diagonally, ``(rows, d_model, heads)`` with head ``h``'s 64
    numbers in column ``h``, so the scores are ``piece @ q_bd`` and the
    context ``p^T @ piece``, of which head ``h`` keeps its own lanes: no
    positions-sized array ever has ``(heads, head_dim)`` as its minor pair
    (the (8,128) tile pads that pair from 768 lanes to 2,048). Both
    contractions carry ``HIGHEST`` precision: no key, value, score or
    weight is rounded on the way through the matrix unit."""
    D = q.shape[1]
    dh = D // num_heads
    sm = 1.0 / _np.sqrt(dh)
    hi = lax.Precision.HIGHEST
    # ind[d, h] = 1 where lane d belongs to head h
    ind = (jnp.arange(D)[:, None] // dh
           == jnp.arange(num_heads)[None, :]).astype(jnp.float32)

    def rows_block(q_b, pos_b, walk):
        q_bd = q_b[:, :, None] * ind            # (rb, D, H), block-diagonal

        def fold(carry, pieces, tpos):
            kp, vp = pieces                     # (rb, span, D) each
            s = jnp.einsum("bsd,bdh->bsh", kp, q_bd, precision=hi) * sm
            return paged.softmax_fold(
                carry, s, tpos, pos_b, 1,
                lambda p: jnp.einsum("bsh,bsd->bhd", p, vp, precision=hi))

        _, den, acc = walk(fold, (q_b.shape[0], num_heads), D)
        # head h keeps its own lanes of row h of the (H, D) accumulator
        return jnp.sum(acc / den[..., None] * ind.T, axis=1)

    return paged.live_walk(plan, (k_pages, v_pages), l, q, rows_block)


@jax.named_scope("decode.step")      # the trace's device-side name
def transformer_decode_step(params, cfg, cache, token_ids,
                            positions, tables, active):
    """Fixed-shape batched decode step: one token per active row.

    Matches the DecodeEngine step seam ``(params, cache, token_ids,
    positions, tables, active) -> (next_ids, cache, aux)``. Attention reads
    and contracts only the LIVE positions of each row's table
    (`_live_attention`). A row contracts only over its own gathered blocks
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
    x = params["embed"][token_ids].astype(cfg.dtype)
    x = x + params["pos_embed"][jnp.clip(positions, 0, cfg.max_len - 1)] \
        .astype(cfg.dtype)
    blk, slot = paged.step_addresses(tables, positions, active, bs)
    plan = paged.walk_plan(positions, tables, bs, _WALK_ROWS, _WALK_SPAN)
    lp_all = params["layers"]
    for l in range(cfg.num_layers):
        with jax.named_scope("layer"):
            lp = {k: v[l] for k, v in lp_all.items()}
            h = _layer_norm(x, lp["ln1_scale"], lp["ln1_bias"])
            k_pages = k_pages.at[l, blk, slot].set(h @ lp["wk"])
            v_pages = v_pages.at[l, blk, slot].set(h @ lp["wv"])
            ctx = _live_attention(h @ lp["wq"], k_pages, v_pages, l, plan,
                                  cfg.num_heads)
            x = x + ctx @ lp["wo"]
            h = _layer_norm(x, lp["ln2_scale"], lp["ln2_bias"])
            x = x + (jax.nn.gelu(h @ lp["w1"] + lp["b1"]) @ lp["w2"]
                     + lp["b2"])
    x = _layer_norm(x, params["ln_f_scale"], params["ln_f_bias"])
    logits = x @ params["embed"].T.astype(cfg.dtype)
    aux = {"kv_live_tokens": jnp.sum(jnp.where(active, positions + 1, 0)),
           "kv_walked_tokens": plan.walked}
    return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
            {"k": k_pages, "v": v_pages}, aux)


class TransformerDecodeModel(DecodeModel):
    """Adapter: a multi-layer TransformerConfig wired for the
    DecodeEngine seam.

    >>> model = TransformerDecodeModel(TransformerConfig(vocab_size=256,
    ...     num_layers=2, num_heads=4, d_model=64, max_len=128))
    >>> eng = DecodeEngine(**model.engine_kwargs(), max_seq_len=128)

    ``flash`` picks the prefill attention tier (`DecodeModel.resolve_flash`;
    the step body runs no kernel: see `transformer_decode_step`). Params
    default to `init_transformer` from a seeded key, so every process
    (engine, smoke clients, bench) derives the same model."""

    def __init__(self, cfg, params=None, seed=0, flash=None):
        self.cfg = cfg
        if params is None:
            params = init_transformer(cfg, jax.random.PRNGKey(seed))
        self.params = params
        self.resolve_flash(flash)

    def cache_spec(self, num_blocks, block_size, slots):
        """The cache: twin float32 K and V pools, layer-major, so a
        layer reads and writes only its own pages."""
        pool = jax.ShapeDtypeStruct(
            (self.cfg.num_layers, num_blocks, block_size, self.cfg.d_model),
            jnp.float32)
        return {"k": pool, "v": pool}

    def prefill_fn(self, params, cache, tokens, start, length, table, slot):
        return transformer_decode_prefill(
            params, self.cfg, cache, tokens, start, length,
            table, use_pallas=self.use_pallas, interpret=self.interpret)

    def step_fn(self, params, cache, token_ids, positions, tables, active):
        return transformer_decode_step(params, self.cfg, cache,
                                       token_ids, positions, tables, active)
