"""A fourth decoder family: EvaByte (config.json of EvaByte/EvaByte; EVA
attention is Zheng et al., "Efficient Attention via Control Variates", ICLR
2023), a byte-level model whose attention is EXACT inside an aligned window
of ``window_size`` positions and sees everything before the window through
``chunk_size``-to-1 summaries, under one softmax, assembled from the
published config's own keys (`EvaByteConfig.from_dict`).

**The layer** (the benchmark's plain reference,
``benchmark/cells/references/evabyte.py``, has the equations): pre-norm,
``norm(x) = x / rms(x) * (1 + g)``, rotary over the whole head, a gated SiLU
MLP, an untied head; per head two learned vectors ``mu``, ``phi`` pool each
chunk of ``C`` positions into one key and one value row (``kbar_c``,
``vbar_c``: two softmaxes over the chunk's positions). A query at ``t``, ``w
= t // W``, attends the keys ``w W .. t`` and the summaries of every chunk
of every CLOSED window (``C c < w W``).

**The cache forgets** (`EvaByteConfig.cache_pages`). A summary row has
the shape and dtype of a key row, so both live in the same twin pools
``k``, ``v`` ``[layers, blocks, block_size, hidden]`` and ``block_size``
must equal ``chunk_size``: a page holds 16 positions, or 16 chunks. A
sequence's table has two regions: entries ``0 .. W / C - 1`` are its WINDOW
pages (position ``p`` lives at entry ``(p % W) // C``), the entries after
them its SUMMARY pages (chunk ``c`` at entry ``W / C + c // C``, row ``c %
C``). When a row passes a multiple of ``W`` its window pages are dead: the
allocator takes them back while the sequence lives
(`serving/kvcache.py`), and the next window writes into pages backed anew.
A summary is written when its chunk completes and becomes visible when its
window closes: visibility is position arithmetic (`_virtual`), nothing is
copied at the boundary.

**One walk over both spans.** `_virtual` lines a row's table up as the
attention reads it: its visible summary pages first, its window pages
after them. Position ``p`` sits at virtual position ``(p // W) (W / C) + p
% W`` of that table, and what the query sees is every virtual position up
to its own: the prefill chunk's attention is the offset-causal one every
family uses (`paged_attention.chunk_attention`; the always-visible prefix
needs no mask of its own), and the decode step's is a paged attention over
the virtual table: `paged_attention.paged_head_attention`
(``mx_eva_paged_attn``) on the kernel tier, `paged_attention.live_walk` on
the lax tier.

**A donated pool is written once a program**: every layer reads the pools
as they came in (a prefill piece sets its own rows into the gathered
columns, a step folds its own new row in beside the walk) and the rows of
all layers go into each pool in ONE scatter at the program's end (the rule
of PERF.md PR 34: no chain of in-place updates for the compiler to
rematerialise).

**Precision**: weights and cache rows (k, v, kbar, vbar) in the parameters'
dtype; products accumulate in float32; norms, rotary, the three softmaxes,
the residual sums and the logits are float32.

**Parameter layout** (shared with the reference, which makes the weights):
``{"embed", "head", "norm_f", "layers"}``; a layer ``norm_attn_in``,
``norm_ffn_in`` (the gains ``g``), ``wq``, ``wk``, ``wv``, ``wo`` ``[d,
d]``, ``mu``, ``phi`` ``[H, d / H]``, ``w_gate``, ``w_up`` ``[d, I]``,
``w_down`` ``[I, d]``.

Device-side names: ``eva``, ``eva.pool``, ``mlp`` inside
``decode.step/layer`` and ``decode.prefill/layer``; the kernel
``mx_eva_paged_attn``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from ..kernels import paged_attention as paged
from . import moe_mla as M
from .decode_model import DecodeModel

__all__ = ["EvaByteConfig", "init_evabyte", "evabyte_decode_prefill",
           "evabyte_decode_step", "EvaByteDecodeModel"]


@dataclasses.dataclass(frozen=True)
class EvaByteConfig:
    """The published keys by their own names."""
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    intermediate_size: int
    vocab_size: int
    window_size: int
    chunk_size: int
    num_key_value_heads: int = None
    rope_theta: float = 100000.0
    rms_norm_eps: float = 1e-5
    init_std: float = 0.01275
    # decode-path knobs (not the model's): the flash kernels' key block and
    # the lax tier's walk (rows a block, pages a piece)
    block_k: int = 512
    step_row_block: int = 8
    step_col_blocks: int = 8

    def __post_init__(self):
        H = self.num_attention_heads
        if self.num_key_value_heads not in (None, H):
            raise ValueError("EvaByte has one key-value head a query head")
        if self.hidden_size % H or self.window_size % (self.chunk_size ** 2):
            raise ValueError(
                "hidden_size must divide into the heads, and a window's "
                "chunk summaries (%d) into whole pages of chunk_size rows"
                % (self.window_size // self.chunk_size))

    @classmethod
    def from_dict(cls, config, **overrides):
        """From a ``config.json`` as published (unknown keys ignored)."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in config.items() if k in names}
        kw.update(overrides)
        return cls(**kw)

    initializer_range = property(lambda self: self.init_std)
    head_dim = property(lambda self: self.hidden_size
                        // self.num_attention_heads)
    #: a sequence's window pages: the front region of its table
    window_pages = property(lambda self: self.window_size // self.chunk_size)
    #: summary pages a closed window leaves behind
    summary_pages = property(lambda self: self.window_size
                             // self.chunk_size ** 2)

    def cache_pages(self, n):
        """The table entries a sequence of ``n`` positions backs (the
        position a step is writing counted in): the pages of its open
        window ``(n - 1) // W`` up to that position, and a summary page a
        ``chunk_size`` completed chunks. Two regions, window pages first
        (`serving/kvcache.py` has the contract). A pure host function."""
        W, C, wp = self.window_size, self.chunk_size, self.window_pages
        if n <= 0:
            return ((0, 0), (wp, wp))
        return ((0, -(-(n - (n - 1) // W * W) // C)),
                (wp, wp + -(-(n // C) // C)))


def _layer_shapes(cfg):
    d, H, i = cfg.hidden_size, cfg.num_attention_heads, cfg.intermediate_size
    return {"norm_attn_in": (d,), "norm_ffn_in": (d,),
            "wq": (d, d), "wk": (d, d), "wv": (d, d), "wo": (d, d),
            "mu": (H, cfg.head_dim), "phi": (H, cfg.head_dim),
            "w_gate": (d, i), "w_up": (d, i), "w_down": (i, d)}


def init_evabyte(cfg, key, dtype=jnp.float32):
    """Seeded parameters in ONE jitted call: normal(0, ``init_std``)
    matrices and pooling vectors, norm gains 0. (The benchmark's reference
    makes its own; this one is the tests'.)"""
    zeros = lambda k, shape: jnp.zeros(shape, dtype)            # noqa: E731
    return M._init_tree(
        cfg, key, dtype, [_layer_shapes(cfg)] * cfg.num_hidden_layers,
        special={n: zeros for n in ("norm_attn_in", "norm_ffn_in", "norm_f")})


# ---------------------------------------------------------------------------
# pieces
# ---------------------------------------------------------------------------
def _norm(x, g, eps):
    return M._rms(x, 1.0 + g.astype(jnp.float32), eps)


def _project(cfg, lp, h, pos, dt):
    """Normed ``h`` ``[N, d]`` at positions ``pos`` ``[N]`` -> ``(q, k, v)``
    ``[N, d]``: q and k rotated, float32; k and v rounded as the cache
    stores them (``dt``) and handed back in float32."""
    N, H, dh = h.shape[0], cfg.num_attention_heads, cfg.head_dim
    q, k = (M._rope(M._mm(h, lp[n]).reshape(N, H, dh), pos,
                    cfg.rope_theta).reshape(N, H * dh) for n in ("wq", "wk"))
    as_cached = lambda t: t.astype(dt).astype(jnp.float32)      # noqa: E731
    return q, as_cached(k), as_cached(M._mm(h, lp["wv"]))


def _pool(cfg, lp, k, v):
    """Chunks ``k``, ``v`` ``[n, C, d]`` float32 -> their summaries ``(kbar,
    vbar)`` ``[n, d]``: per head ``softmax_m(mu . k_m)`` weighs the keys and
    ``softmax_m(phi . k_m / sqrt dh)`` the values, all float32."""
    n, C, H, dh = k.shape[0], k.shape[1], cfg.num_attention_heads, cfg.head_dim
    with jax.named_scope("eva.pool"):
        kh, vh = k.reshape(n, C, H, dh), v.reshape(n, C, H, dh)
        mu, phi = (lp[x].astype(jnp.float32) for x in ("mu", "phi"))
        a_k = jax.nn.softmax(jnp.sum(kh * mu, -1), axis=1)
        a_v = jax.nn.softmax(jnp.sum(kh * phi, -1) * dh ** -0.5, axis=1)
        return (jnp.sum(a_k[..., None] * kh, 1).reshape(n, H * dh),
                jnp.sum(a_v[..., None] * vh, 1).reshape(n, H * dh))


def _virtual(cfg, tables, window, width):
    """A row's table as its attention reads it: ``tables`` ``[..., mb]``, the
    row's open window ``window`` ``[...]`` -> ``[..., width]``: the summary
    pages of its closed windows, then its window pages, then the null
    block. Virtual position ``window * W / C + p % W`` is position ``p``."""
    mb = tables.shape[-1]
    wp = cfg.window_pages
    j = jnp.arange(width, dtype=jnp.int32)
    seen = (window * cfg.summary_pages)[..., None]      # visible summaries
    entry = jnp.where(j < seen, wp + j, j - seen)
    ok = (j < seen + wp) & (entry < mb)
    return jnp.where(ok, jnp.take_along_axis(
        tables, jnp.clip(entry, 0, mb - 1), axis=-1), paged.NULL_BLOCK)


def _write_once(pool, rows, blk, slot):
    """Every layer's ``rows`` ``[L, N, d]`` into ``pool`` at ``(blk, slot)``
    ``[N]`` of each layer: the program's ONE write of the pool."""
    layers = jnp.arange(pool.shape[0], dtype=jnp.int32)[:, None]
    return pool.at[layers, blk[None, :], slot[None, :]].set(
        rows.astype(pool.dtype))


def _mlp_block(cfg, lp, x, attn_out):
    """The residual sums around the attention's output and the MLP, float32."""
    h = x + attn_out
    with jax.named_scope("mlp"):
        f = _norm(h, lp["norm_ffn_in"], cfg.rms_norm_eps)
        return h + M._gated_mlp(f, lp["w_gate"], lp["w_up"], lp["w_down"])


def _check_geometry(cfg, pool, piece=None):
    if pool.shape[2] != cfg.chunk_size:
        raise ValueError(
            "EvaByte's pages hold a chunk's rows: block_size %d must equal "
            "chunk_size %d" % (pool.shape[2], cfg.chunk_size))
    if piece is not None and (piece % cfg.chunk_size
                              or cfg.window_size % piece):
        raise ValueError(
            "a prefill piece of %d positions would straddle a chunk or a "
            "window (chunk_size %d, window_size %d): every prefill bucket "
            "must be a multiple of the one and divide the other"
            % (piece, cfg.chunk_size, cfg.window_size))


# ---------------------------------------------------------------------------
# the DecodeEngine seam
# ---------------------------------------------------------------------------
@jax.named_scope("decode.prefill")      # the trace's device-side name
def evabyte_decode_prefill(params, cfg, cache, tokens, start, length, table,
                           *, use_pallas=False, interpret=False,
                           with_logits=False):
    """Bucketed batch-1 prefill piece: positions ``start .. start + length -
    1`` of one window (a bucket divides the window, so a piece never
    straddles one). Writes the piece's keys and values into its window pages
    and the summaries of the chunks it completes into its summary pages;
    attends over ``[visible summaries | window rows up to the piece's end]``
    with the offset-causal mask. The seam's ``(params, cache, tokens, start,
    length, table, slot) -> (next_id, cache, aux)``; ``with_logits`` (tests)
    appends the last real position's float32 logits."""
    k_pool, v_pool = cache["k"], cache["v"]
    N, bs, dt = tokens.shape[0], k_pool.shape[2], k_pool.dtype
    _check_geometry(cfg, k_pool, N)
    W, C, H, dh = (cfg.window_size, cfg.chunk_size, cfg.num_attention_heads,
                   cfg.head_dim)
    idx = jnp.arange(N, dtype=jnp.int32)
    pos, valid = start + idx, idx < length
    window = start // W
    at = start - window * W + idx                   # offsets in the window
    w_blk = jnp.where(valid, table[at // bs], paged.NULL_BLOCK)
    # the piece's chunks; one that its last real position completes is pooled
    ci = jnp.arange(N // C, dtype=jnp.int32)
    chunk = start // C + ci
    pooled = (ci + 1) * C <= length
    s_blk = jnp.where(pooled, table[jnp.clip(
        cfg.window_pages + chunk // bs, 0, table.shape[0] - 1)],
        paged.NULL_BLOCK)
    blk = jnp.concatenate([w_blk, s_blk])
    slot = jnp.concatenate([at % bs, chunk % bs])
    # the columns the piece attends, as pages: padded to whole key blocks
    per = max(1, cfg.block_k // bs)
    width = -(-table.shape[0] // per) * per if table.shape[0] > per \
        else table.shape[0]
    vt = _virtual(cfg, table, window, width)
    v_start = window * cfg.summary_pages * bs + (start - window * W)
    heads = lambda t: t.reshape(-1, H, dh).transpose(1, 0, 2)   # noqa: E731
    tier = dt if (use_pallas or interpret) else jnp.float32
    x = params["embed"][tokens].astype(jnp.float32)
    new_k, new_v = [], []
    for l, lp in enumerate(params["layers"]):
        with jax.named_scope("layer"):
            with jax.named_scope("eva"):
                h = _norm(x, lp["norm_attn_in"], cfg.rms_norm_eps)
                q, k, v = _project(cfg, lp, h, pos, dt)
                kbar, vbar = _pool(cfg, lp, k.reshape(-1, C, H * dh),
                                   v.reshape(-1, C, H * dh))
                new_k.append(jnp.concatenate([k, kbar]))
                new_v.append(jnp.concatenate([v, vbar]))
                # the pools as they came in, this piece's rows set in
                cols = [lax.dynamic_update_slice(
                    paged.gather_pages(p, l, vt).reshape(-1, H * dh),
                    t.astype(dt), (v_start, 0))
                    for p, t in ((k_pool, k), (v_pool, v))]
                o = paged.chunk_attention(
                    heads(q.astype(tier)), heads(cols[0].astype(tier)),
                    heads(cols[1].astype(tier)), v_start, dh ** -0.5,
                    cfg.block_k, use_pallas, interpret)
                a = M._mm(o.transpose(1, 0, 2).reshape(N, H * dh), lp["wo"])
            x = _mlp_block(cfg, lp, x, a)
    x_last = jnp.take(x, jnp.clip(length - 1, 0, N - 1), axis=0)
    logits = M._mm(_norm(x_last, params["norm_f"], cfg.rms_norm_eps),
                   params["head"])
    end = start + length
    aux = {"prefill_eva_chunks_pooled": jnp.sum(pooled.astype(jnp.int32)),
           "prefill_eva_windows_closed": (end % W == 0).astype(jnp.int32),
           "prefill_kv_live_tokens": (v_start + length).astype(jnp.int32)}
    cache = {"k": _write_once(k_pool, jnp.stack(new_k), blk, slot),
             "v": _write_once(v_pool, jnp.stack(new_v), blk, slot)}
    out = (jnp.argmax(logits).astype(jnp.int32), cache, aux)
    return out + (logits,) if with_logits else out


def _step_attention(cfg, q, k, v, k_pool, v_pool, l, walk):
    """One layer's decode attention: rows ``q``, ``k``, ``v`` ``[B, d]``
    float32 (the rows' own new key and value, not in the pools yet) over the
    positions ``0 .. last`` of the rows' virtual tables and themselves.
    ``walk`` is ``(last, tables, active, interpret)`` on the kernel tier, a
    `paged_attention.WalkPlan` over the same on the lax tier."""
    H, dh = cfg.num_attention_heads, cfg.head_dim
    sm = dh ** -0.5
    if not isinstance(walk, paged.WalkPlan):
        last, vt, active, interpret = walk
        return paged.paged_head_attention(
            q, k, v, k_pool, v_pool, l, last, vt, active, num_heads=H,
            sm_scale=float(sm), interpret=interpret)

    def rows_block(qkv_b, pos_b, pieces_of):
        rb = qkv_b.shape[0]
        q_b, k_b, v_b = (qkv_b[:, i].reshape(rb, H, dh) for i in range(3))

        def fold(carry, pieces, tpos):
            kp, vp = (p.astype(jnp.float32).reshape(rb, -1, H, dh)
                      for p in pieces)
            s = jnp.einsum("bshd,bhd->bsh", kp, q_b) * sm
            return paged.softmax_fold(
                carry, s, tpos, pos_b, 1,
                lambda p: jnp.einsum("bsh,bshd->bhd", p, vp))

        m, den, acc = pieces_of(fold, (rb, H), dh)
        # the row's own position, folded in beside the walk
        s0 = jnp.sum(q_b * k_b, -1) * sm
        m_new = jnp.maximum(m, s0)
        a, b = jnp.exp(m - m_new), jnp.exp(s0 - m_new)
        out = (acc * a[..., None] + b[..., None] * v_b) \
            / (den * a + b)[..., None]
        return out.reshape(rb, H * dh)

    return paged.live_walk(walk, (k_pool, v_pool), l,
                           jnp.stack([q, k, v], axis=1), rows_block)


@jax.named_scope("decode.step")      # the trace's device-side name
def evabyte_decode_step(params, cfg, cache, token_ids, positions, tables,
                        active, *, use_pallas=False, interpret=False,
                        with_logits=False):
    """Fixed-shape batched decode step, one byte per active row. A row at
    position ``p`` writes its key and value into its window page; one that
    completes a chunk (``(p + 1) % C == 0``) pools that page, its own new
    row set in, into the chunk's summary row; its attention walks its
    visible summary pages, then its window pages, then itself, in one pass
    (`_step_attention`). Rows sit in different windows in one step: what
    each sees is its own position's arithmetic. The seam's ``(params, cache,
    token_ids, positions, tables, active) -> (next_ids, cache, aux)``.

    ``aux`` (summed into ``stats()["model"]``): ``eva_window_rows`` and
    ``eva_summary_rows`` (cache rows the active rows attended, a layer
    once), ``eva_context_positions`` (the positions those rows stand for:
    ``p + 1`` each), ``eva_chunks_pooled``, ``eva_windows_closed``;
    ``kv_live_tokens`` and ``kv_walked_tokens`` as the other families give
    them (rows attended; positions whose pages the walk read)."""
    k_pool, v_pool = cache["k"], cache["v"]
    bs, dt = k_pool.shape[2], k_pool.dtype
    _check_geometry(cfg, k_pool)
    W, C, H, dh = (cfg.window_size, cfg.chunk_size, cfg.num_attention_heads,
                   cfg.head_dim)
    B, mb = tables.shape
    window = positions // W
    at = positions - window * W
    seen = window * cfg.summary_pages * bs          # visible summary rows
    # an active row's virtual position is >= 1 (its prompt is not empty, and
    # a later window has summaries before it): the walk covers 0 .. last
    last = jnp.maximum(seen + at - 1, 0)
    vt = _virtual(cfg, tables, window, mb)
    entry = lambda e: jnp.take_along_axis(                      # noqa: E731
        tables, jnp.clip(e, 0, mb - 1)[:, None], axis=1)[:, 0]
    page = entry(at // bs)
    completes = active & ((positions + 1) % C == 0)
    chunk = positions // C
    blk = jnp.concatenate([
        jnp.where(active, page, paged.NULL_BLOCK),
        jnp.where(completes, entry(cfg.window_pages + chunk // bs),
                  paged.NULL_BLOCK)])
    slot = jnp.concatenate([at % bs, chunk % bs])
    mine = (jnp.arange(bs, dtype=jnp.int32)[None, :]
            == (at % bs)[:, None])[..., None]
    if use_pallas or interpret:
        walk = (last, vt, active, interpret)
        walked = paged.paged_walked(last, active, bs)
    else:
        walk = paged.walk_plan(last, vt, bs, cfg.step_row_block,
                               cfg.step_col_blocks * bs)
        walked = walk.walked
    x = params["embed"][token_ids].astype(jnp.float32)
    new_k, new_v = [], []
    for l, lp in enumerate(params["layers"]):
        with jax.named_scope("layer"):
            with jax.named_scope("eva"):
                h = _norm(x, lp["norm_attn_in"], cfg.rms_norm_eps)
                q, k, v = _project(cfg, lp, h, positions, dt)
                # the row's page as the pool holds it, its new row set in
                kbar, vbar = _pool(cfg, lp, *(
                    jnp.where(mine, t[:, None],
                              p[l, page].astype(jnp.float32))
                    for p, t in ((k_pool, k), (v_pool, v))))
                new_k.append(jnp.concatenate([k, kbar]))
                new_v.append(jnp.concatenate([v, vbar]))
                o = _step_attention(cfg, q, k, v, k_pool, v_pool, l, walk)
                a = M._mm(o, lp["wo"])
            x = _mlp_block(cfg, lp, x, a)
    logits = M._mm(_norm(x, params["norm_f"], cfg.rms_norm_eps),
                   params["head"])
    count = lambda t: jnp.sum(jnp.where(active, t, 0))          # noqa: E731
    aux = {"eva_window_rows": count(at + 1),
           "eva_summary_rows": count(seen),
           "eva_context_positions": count(positions + 1),
           "eva_chunks_pooled": jnp.sum(completes.astype(jnp.int32)),
           "eva_windows_closed": count(((positions + 1) % W == 0)
                                       .astype(jnp.int32)),
           "kv_live_tokens": count(seen + at + 1),
           "kv_walked_tokens": walked}
    cache = {"k": _write_once(k_pool, jnp.stack(new_k), blk, slot),
             "v": _write_once(v_pool, jnp.stack(new_v), blk, slot)}
    out = (jnp.argmax(logits, axis=-1).astype(jnp.int32), cache, aux)
    return out + (logits,) if with_logits else out


class EvaByteDecodeModel(DecodeModel):
    """Adapter: an `EvaByteConfig` wired for the DecodeEngine seam.

    >>> model = EvaByteDecodeModel(cfg, params=params)      # or seed=
    >>> eng = DecodeEngine(**model.engine_kwargs(), block_size=16,
    ...                    prefill_buckets=(256, 512, 1024), ...)

    ``flash`` picks the kernel tier of the prefill attention AND of the
    step's walk (`DecodeModel.resolve_flash`). ``block_size`` must equal
    ``chunk_size`` and every prefill bucket divide ``window_size`` (refused
    when the programs are built)."""

    def __init__(self, cfg, params=None, seed=0, dtype=jnp.bfloat16,
                 flash=None):
        self.cfg = cfg
        if params is None:
            params = init_evabyte(cfg, jax.random.PRNGKey(seed), dtype)
        self.params = params
        self.cache_dtype = params["embed"].dtype
        self.resolve_flash(flash)

    def cache_spec(self, num_blocks, block_size, slots):
        """Twin pools ``k`` and ``v``, layer-major, heads folded into the
        lanes: a row is a position's key (value), or a chunk's summary."""
        pool = jax.ShapeDtypeStruct(
            (self.cfg.num_hidden_layers, num_blocks, block_size,
             self.cfg.hidden_size), self.cache_dtype)
        _check_geometry(self.cfg, pool)
        return {"k": pool, "v": pool}

    @property
    def cache_pages(self):
        """The family's page function (`EvaByteConfig.cache_pages`)."""
        return self.cfg.cache_pages

    def prefill_fn(self, params, cache, tokens, start, length, table, slot):
        return evabyte_decode_prefill(
            params, self.cfg, cache, tokens, start, length, table,
            use_pallas=self.use_pallas, interpret=self.interpret)

    def step_fn(self, params, cache, token_ids, positions, tables, active):
        return evabyte_decode_step(
            params, self.cfg, cache, token_ids, positions, tables, active,
            use_pallas=self.use_pallas, interpret=self.interpret)
