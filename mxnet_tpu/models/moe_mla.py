"""A second decoder family: latent attention (MLA) over a latent paged cache,
sparse experts beside a shared one, assembled from a published config.

What the GPT-2-style family of `models/transformer.py` does not have, each
built from the config's own keys (`MoEMLAConfig.from_dict`): RMSNorm and
sandwich norms (plain pre-norm where a layer lacks the outer gains), rotary
positions on part of the head (or none: ``mla_use_nope``), multi-head latent
attention (the query through a low rank, or directly: ``q_lora_rank``
null), a gated SiLU MLP, an untied head, leading dense layers and then
expert layers (`parallel/moe.py::routed_experts`: top-k over the router's
full width, the experts held HERE computed through a grouped matrix product,
a shared expert beside them).

**Two attention paths, one mathematics.** The cache holds ``[c | k_rope]``
for every token and layer: ``kv_lora_rank + qk_rope_head_dim`` numbers, not a
key and a value for every head. *Prefill* (``moe_mla_decode_prefill``, and the
full-sequence ``moe_mla_forward``) EXPANDS keys and values from the latent
rows, ``[k_nope | v]_h = c W_kvb``, and attends through the repo's flash
kernel or its blockwise tier; the full score matrix is never materialised.
The *decode step* (``moe_mla_decode_step``) ABSORBS the up-projections:
``q'_h = q_nope_h W_kvb,k,h^T`` is scored against the latent rows themselves,
``u_h = sum_j p c(j)`` is a sum of latent rows, and ``o_h = u_h W_kvb,v,h``.
All heads share one ``rkv + dr``-wide key a token, so a row's scores are one
``[H, rkv + dr] x [rkv + dr, T]`` matrix product.

**Precision.** Matrix products take operands in the parameters' dtype
(bfloat16 as served) and accumulate in float32; norms, router scores, softmax
and rotary run in float32. Parameters keep the dtype they arrive in and the
cache takes it too, so the float32 tests run the same code.

**Parameter layout** (shared with the benchmark's plain reference, which
makes the weights): ``{"embed" [V, d], "head" [d, V], "norm_f" [d],
"layers": [one dict a layer]}``; a layer holds ``norm_attn_in/out``,
``norm_ffn_in/out``, ``wq_a``, ``norm_q``, ``wq_b``, ``wkv_a``, ``norm_kv``,
``wkv_b``, ``wo`` and either ``w_gate/w_up/w_down`` (dense) or ``router``,
``shared_gate/up/down``, ``experts_gate/up/down`` (leading axis: the experts
held). Layers are separate leaves: nothing slices a stacked 6 GB array.

Device-side names (`jax.named_scope`): ``mla``, ``moe.route``,
``moe.experts``, ``moe.shared``, ``mlp`` inside ``decode.step/layer`` and
``decode.prefill/layer``.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as _np
import jax
import jax.numpy as jnp
from jax import lax

from ..kernels import paged_attention as paged
from ..parallel.moe import routed_experts
from .decode_model import DecodeModel

__all__ = ["MoEMLAConfig", "init_moe_mla", "moe_mla_forward",
           "moe_mla_decode_prefill", "moe_mla_decode_step",
           "MoEMLADecodeModel"]

_LANES = 128


def _held_experts(held, routed):
    """``experts_held`` as ``(first, count)``: ``None`` is all ``routed``
    experts, a dict is the configuration file's ``{"first", "count"}``."""
    if held is None:
        held = (0, routed)
    elif isinstance(held, dict):
        held = (held["first"], held["count"])
    held = (int(held[0]), int(held[1]))
    if not (0 <= held[0] and held[0] + held[1] <= routed and held[1] >= 1):
        raise ValueError("experts_held %r lies outside the %d routed "
                         "experts" % (held, routed))
    return held


@dataclasses.dataclass(frozen=True)
class MoEMLAConfig:
    """The published keys by their own names, plus ``experts_held``
    ``(first, count)``: the routed experts whose weights live here."""
    hidden_size: int
    num_hidden_layers: int
    first_k_dense_replace: int
    num_attention_heads: int
    q_lora_rank: int            # None: the query is projected directly
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    n_shared_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    rms_norm_eps: float
    rope_theta: float
    vocab_size: int
    experts_held: tuple = None
    initializer_range: float = 0.02
    mla_use_nope: bool = False  # True: the rope part is carried unrotated
    # decode-path knobs (not the model's): flash tile; rows, and blocks of
    # their tables, that a step's attention holds live at once
    block_k: int = 512
    step_row_block: int = 32
    step_col_blocks: int = 32

    def __post_init__(self):
        object.__setattr__(self, "experts_held", _held_experts(
            self.experts_held, self.n_routed_experts))

    @classmethod
    def from_dict(cls, config, **overrides):
        """From a ``config.json`` as published (unknown keys ignored)."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in config.items() if k in names}
        kw.update(overrides)
        return cls(**kw)

    @property
    def latent_width(self):
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_row_width(self):
        """A pool row: ``[c | k_rope]`` zero-padded to whole lanes. The
        TPU's tiled layout pads the trailing axis to a multiple of 128 in
        memory either way; left to itself it instead picks a layout with
        the BLOCK axis innermost for a 576-wide row and transposes the whole
        pool on the way in and out of every program (counted for a
        described v5e, PERF.md PR 28). Stating the padded width keeps
        logical and physical layout the same: layout only, the pad is
        never read as a number."""
        return -(-self.latent_width // _LANES) * _LANES

    def is_dense(self, layer):
        return layer < self.first_k_dense_replace

    @property
    def num_expert_layers(self):
        return self.num_hidden_layers - min(self.first_k_dense_replace,
                                            self.num_hidden_layers)


def _query_shapes(cfg):
    """The query projection's leaves: ``wq_a``, ``norm_q``, ``wq_b`` through
    the published low rank, or ONE matrix ``wq`` where ``q_lora_rank`` is
    null."""
    d = cfg.hidden_size
    wide = cfg.num_attention_heads * (cfg.qk_nope_head_dim
                                      + cfg.qk_rope_head_dim)
    if cfg.q_lora_rank is None:
        return {"wq": (d, wide)}
    return {"wq_a": (d, cfg.q_lora_rank), "norm_q": (cfg.q_lora_rank,),
            "wq_b": (cfg.q_lora_rank, wide)}


def _layer_shapes(cfg, dense):
    d, H = cfg.hidden_size, cfg.num_attention_heads
    out = {
        "norm_attn_in": (d,), "norm_attn_out": (d,),
        "norm_ffn_in": (d,), "norm_ffn_out": (d,),
        **_query_shapes(cfg),
        "wkv_a": (d, cfg.latent_width), "norm_kv": (cfg.kv_lora_rank,),
        "wkv_b": (cfg.kv_lora_rank,
                  H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "wo": (H * cfg.v_head_dim, d),
    }
    if dense:
        i = cfg.intermediate_size
        out.update({"w_gate": (d, i), "w_up": (d, i), "w_down": (i, d)})
    else:
        f, n = cfg.moe_intermediate_size, cfg.experts_held[1]
        fs = f * cfg.n_shared_experts
        out.update({"router": (d, cfg.n_routed_experts),
                    "shared_gate": (d, fs), "shared_up": (d, fs),
                    "shared_down": (fs, d),
                    "experts_gate": (n, d, f), "experts_up": (n, d, f),
                    "experts_down": (n, f, d)})
    return out


def init_moe_mla(cfg, key, dtype=jnp.float32):
    """Seeded parameters in ONE jitted call, every leaf made in ``dtype``
    directly: normal(0, ``initializer_range``) matrices, norm gains 1."""
    return _init_tree(cfg, key, dtype,
                      [_layer_shapes(cfg, cfg.is_dense(l))
                       for l in range(cfg.num_hidden_layers)])


def _init_tree(cfg, key, dtype, layers, special=None):
    """The parameter tree ``{"embed", "head", "norm_f", "layers"}`` over the
    ``layers``' shapes, made in ONE jitted call: a leaf named ``norm_*`` is
    ones, one named in ``special`` (``name -> fn(key, shape)``) is that
    function's, every other normal(0, ``initializer_range``) in ``dtype``."""
    shapes = {"embed": (cfg.vocab_size, cfg.hidden_size),
              "head": (cfg.hidden_size, cfg.vocab_size),
              "norm_f": (cfg.hidden_size,), "layers": layers}
    is_shape = lambda s: isinstance(s, tuple)           # noqa: E731
    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes,
                                                        is_leaf=is_shape)
    dt = jnp.dtype(dtype)
    special = special or {}

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (path, shape) in zip(keys, leaves):
            name = str(path[-1].key)
            if name in special:
                out.append(special[name](k, shape))
            elif name.startswith("norm_"):
                out.append(jnp.ones(shape, dt))
            else:
                out.append((jax.random.normal(k, shape, jnp.float32)
                            * cfg.initializer_range).astype(dt))
        return jax.tree_util.tree_unflatten(tree, out)

    return make(key)


# ---------------------------------------------------------------------------
# pieces
# ---------------------------------------------------------------------------
def _mm(a, b, out=jnp.float32):
    """``a @ b`` with operands in the parameters' dtype, accumulated in
    float32 and handed back in ``out`` (float32 unless the result is only
    ever another product's operand)."""
    return jnp.matmul(a.astype(b.dtype), b, preferred_element_type=out)


def _rms(x, g, eps):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _rope(x, pos, theta):
    """Half-split rotary in float32. ``x`` ``[N, ..., dr]``, ``pos`` ``[N]``:
    row ``n`` is rotated to position ``pos[n]``."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x = x.astype(jnp.float32)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _gated_mlp(x, wg, wu, wd):
    return _mm(jax.nn.silu(_mm(x, wg)) * _mm(x, wu), wd)


def _mla_project(cfg, lp, h, pos):
    """Normed activations ``h`` ``[N, d]`` at positions ``pos`` ``[N]`` ->
    ``(q_nope [N, H, dn], q_rope [N, H, dr] rotated, rows [N, rkv + dr])``:
    ``rows`` is what the cache holds, ``[c | k_rope]``, float32 here. With
    ``mla_use_nope`` nothing is rotated and ``pos`` is not read."""
    H, dn, dr = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                 cfg.qk_rope_head_dim)
    N = h.shape[0]
    # q is only ever an operand again (its rotary part after a float32 turn)
    if "wq" in lp:              # q_lora_rank null: no down-projection
        q = _mm(h, lp["wq"], lp["wq"].dtype)
    else:
        c_q = _rms(_mm(h, lp["wq_a"]), lp["norm_q"], cfg.rms_norm_eps)
        q = _mm(c_q, lp["wq_b"], lp["wq_b"].dtype)
    q = q.reshape(N, H, dn + dr)
    kv = _mm(h, lp["wkv_a"])
    c = _rms(kv[:, :cfg.kv_lora_rank], lp["norm_kv"], cfg.rms_norm_eps)
    k_rope, q_rope = kv[:, cfg.kv_lora_rank:], q[..., dn:]
    if not cfg.mla_use_nope:    # (NoPE: the shared part goes in as it is)
        k_rope = _rope(k_rope, pos, cfg.rope_theta)
        q_rope = _rope(q_rope, pos, cfg.rope_theta)
    return q[..., :dn], q_rope, jnp.concatenate([c, k_rope], -1)


def _cache_rows(rows, pool):
    """``[c | k_rope]`` rows as the pool stores them: its dtype, zero-padded
    to its row width."""
    return jnp.pad(rows.astype(pool.dtype),
                   ((0, 0), (0, pool.shape[-1] - rows.shape[-1])))


def _expansion_weights(cfg, wkv_b, k_width=None, v_width=None):
    """The up-projection ``wkv_b`` as two matrices that expand latent rows
    straight into the attention kernels' layout: ``w_k`` ``[rkv + dr, H, k_width]``
    gives a key ``[k_nope | k_rope | 0]`` (an identity block carries the
    row's rotary part, one for all heads, into its columns: a product with
    1.0, exact), ``w_v`` ``[rkv, H, v_width]`` a value ``[v | 0]``. The
    widths default to the published ``dn + dr`` and ``dv``. Splitting,
    broadcasting, concatenating and padding the product's RESULT instead
    costs four more passes over the keys and values (PERF.md, PR 28)."""
    H, dn, dr, dv, rkv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                          cfg.qk_rope_head_dim, cfg.v_head_dim,
                          cfg.kv_lora_rank)
    k_width = dn + dr if k_width is None else k_width
    v_width = dv if v_width is None else v_width
    dt = wkv_b.dtype                # operands of the attention products
    w_kvb = wkv_b.reshape(rkv, H, dn + dv)
    carry = jnp.pad(jnp.broadcast_to(jnp.eye(dr, dtype=dt)[:, None, :],
                                     (dr, H, dr)),
                    ((0, 0), (0, 0), (dn, k_width - dn - dr)))
    w_k = jnp.concatenate(
        [jnp.pad(w_kvb[..., :dn], ((0, 0), (0, 0), (0, k_width - dn))),
         carry], axis=0)
    w_v = jnp.pad(w_kvb[..., dn:], ((0, 0), (0, 0), (0, v_width - dv)))
    return w_k, w_v


def _mla_expand(cfg, rows, w_k, w_v):
    """Latent rows ``[T, >= rkv + dr]`` (pool rows keep their pad) -> keys
    and values ``[H, T, .]``, head-major, one matrix product each."""
    dt = w_k.dtype
    rows = rows.astype(dt)
    k = jnp.einsum("tc,chx->htx", rows[:, :cfg.latent_width], w_k,
                   preferred_element_type=dt)
    v = jnp.einsum("tr,rhx->htx", rows[:, :cfg.kv_lora_rank], w_v,
                   preferred_element_type=dt)
    return k, v


@functools.partial(jax.jit, static_argnums=(0, 5, 6))
def _attend_expanded(cfg, wkv_b, q, rows, start, use_pallas, interpret):
    """Causal attention of queries ``q`` ``[C, H, dn + dr]`` at global
    positions ``start + i`` over the keys and values that the latent
    ``rows`` ``[T, .]`` at positions ``0..T-1`` expand to through the
    layer's ``wkv_b`` (`paged_attention.chunk_attention`: the flash kernel
    or the blockwise lax tier). The kernels want ONE head width, a multiple
    of the lane count: q and k (192 wide as published) and v (128) are
    zero-padded to it and the output is cut back: layout only, a zero
    column adds nothing to a score or to a value. (Expanding only the live
    positions, a group at a time into buffers carried from layer to layer,
    was tried and lost: the loop's buffers take another layout than the
    kernel's and are copied into it, PERF.md PR 28.)

    Jitted, though it only ever runs inside a program: the layers of a
    program then share ONE trace and ONE lowering of the kernel for each
    ``T``, where every call of its own cost half a second of set-up on the
    chip's host (PERF.md PR 32). The compiler inlines the calls."""
    dqk, dv = q.shape[-1], cfg.v_head_dim
    q = q.transpose(1, 0, 2)
    if use_pallas or interpret:
        w = -(-max(dqk, dv) // _LANES) * _LANES if use_pallas \
            else max(dqk, dv)
        k, v = _mla_expand(cfg, rows, *_expansion_weights(cfg, wkv_b, w, w))
        q = jnp.pad(q.astype(k.dtype), ((0, 0), (0, 0), (0, w - dqk)))
    else:
        # the lax tier keeps its online softmax in its operands' dtype:
        # hand it float32 (the kernels accumulate in float32 themselves)
        k, v = _mla_expand(cfg, rows, *_expansion_weights(cfg, wkv_b))
        q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    out = paged.chunk_attention(q, k, v, start, 1.0 / _np.sqrt(dqk),
                                cfg.block_k, use_pallas, interpret)
    return out[..., :dv].transpose(1, 0, 2)             # [C, H, dv]


def _ffn(cfg, lp, h, valid=None, kernels=(False, False)):
    """The layer's feed-forward over normed ``h`` ``[N, d]``: the dense gated
    MLP, or shared expert + the held routed experts' part (``kernels``:
    ``(use_pallas, interpret)``, the tier of its grouped product). Returns
    ``(out, tally)``; ``tally`` (`routed_experts`'s ``(counts, cost)``) is
    ``None`` for a dense layer."""
    if "w_gate" in lp:
        with jax.named_scope("mlp"):
            return _gated_mlp(h, lp["w_gate"], lp["w_up"], lp["w_down"]), None
    hp = h.astype(lp["experts_gate"].dtype)
    with jax.named_scope("moe.shared"):
        shared = _gated_mlp(hp, lp["shared_gate"], lp["shared_up"],
                            lp["shared_down"])
    routed, counts, cost = routed_experts(
        lp, hp, held=cfg.experts_held, top_k=cfg.num_experts_per_tok,
        scale=cfg.routed_scaling_factor, valid=valid,
        use_pallas=kernels[0], interpret=kernels[1])
    return shared + routed, (counts, cost)


def _block(cfg, lp, x, attend, valid=None, scope="mla",
           kernels=(False, False)):
    """One residual block. ``attend(h)`` maps the normed input to the
    mixer's output BEFORE ``W_o`` (``[N, H * dv]``): the paths (full
    sequence, prefill chunk, absorbed step; another family's mixer under
    its own ``scope``) differ only there. Sandwich-normed where the layer
    has the two outer gains (``norm_attn_out``, ``norm_ffn_out``), else
    plain pre-norm: ``x + mix(norm(x))``, ``h + ffn(norm(h))``. Returns
    ``(x, tally)``, `_ffn`'s."""
    eps = cfg.rms_norm_eps
    with jax.named_scope(scope):
        a = _mm(attend(_rms(x, lp["norm_attn_in"], eps)), lp["wo"])
    sandwich = "norm_attn_out" in lp
    h = x + (_rms(a, lp["norm_attn_out"], eps) if sandwich else a)
    f, tally = _ffn(cfg, lp, _rms(h, lp["norm_ffn_in"], eps), valid, kernels)
    return h + (_rms(f, lp["norm_ffn_out"], eps) if sandwich else f), tally


def _aux(cfg, tallies, prefix=""):
    """The expert layers' tallies of one call as the engine's ``aux``."""
    if not tallies:
        return {}
    c = jnp.stack([counts for counts, _ in tallies])    # [layers, held]
    aux = {"moe_assignments": jnp.sum(c),
           "moe_busiest": jnp.sum(jnp.max(c, axis=1)),
           "moe_experts_touched": jnp.sum(c > 0),
           "moe_layer_steps": jnp.int32(len(tallies))}
    for name in tallies[0][1]:          # what the forms that ran cost
        aux[name] = sum(cost[name] for _, cost in tallies)
    return {prefix + k: v for k, v in aux.items()}


def _logits(cfg, params, x):
    return _mm(_rms(x, params["norm_f"], cfg.rms_norm_eps), params["head"])


# ---------------------------------------------------------------------------
# full sequence (tests; the expanded path, no cache)
# ---------------------------------------------------------------------------
def moe_mla_forward(params, cfg, tokens, *, use_pallas=False,
                    interpret=False):
    """Logits ``[B, S, V]`` (float32) of a full causal forward over
    ``tokens`` ``[B, S]``: the expanded attention path with no cache, the
    same block the prefill runs."""
    def one(toks):
        S = toks.shape[0]
        pos = jnp.arange(S, dtype=jnp.int32)
        x = params["embed"][toks].astype(jnp.float32)
        for lp in params["layers"]:
            def attend(h, lp=lp):
                q_nope, q_rope, rows = _mla_project(cfg, lp, h, pos)
                q = jnp.concatenate([q_nope, q_rope], -1)
                o = _attend_expanded(cfg, lp["wkv_b"], q, rows, 0,
                                     use_pallas, interpret)
                return o.reshape(S, -1)
            x, _ = _block(cfg, lp, x, attend,
                          kernels=(use_pallas, interpret))
        return _logits(cfg, params, x)
    return lax.map(one, tokens)     # a sequence at a time (no vmap of the
    #                                 grouped product, no batch of scores)


# ---------------------------------------------------------------------------
# the DecodeEngine seam
# ---------------------------------------------------------------------------
def _prefill_attend(cfg, lp, h, pool, l, pos, blk, slot, table, start,
                    spans, which, use_pallas, interpret):
    """One layer's latent attention over a prefill chunk: write the chunk's
    rows into layer ``l`` of ``pool``, gather the table's rows, expand keys
    and values over the span ``which`` names (``lax.switch``: one span calls
    it, and builds no branch) and attend. ``(out [C, H * dv], pool)``. The
    write and the gather stay OUTSIDE the branches: the pool is never a
    branch's operand."""
    C = h.shape[0]
    q_nope, q_rope, rows = _mla_project(cfg, lp, h, pos)
    pool = pool.at[l, blk, slot].set(_cache_rows(rows, pool))
    q = jnp.concatenate([q_nope, q_rope], -1)
    seen = paged.gather_pages(pool, l, table).reshape(-1, pool.shape[3])

    def over(span):
        return lambda q, seen: _attend_expanded(
            cfg, lp["wkv_b"], q, seen[:span], start, use_pallas,
            interpret).reshape(C, -1)
    return lax.switch(which, [over(s) for s in spans], q, seen), pool


@jax.named_scope("decode.prefill")      # the trace's device-side name
def moe_mla_decode_prefill(params, cfg, cache, tokens, start, length, table,
                           *, use_pallas=False, interpret=False,
                           with_logits=False):
    """Bucketed batch-1 prefill chunk: write the latent rows of global
    positions ``start .. start+length-1`` into ``cache["latent"]``, expand
    keys and values from the sequence's latent rows over the chunk's live
    span, attend causally, return the greedy next token after the chunk's
    last real position. The DecodeEngine prefill seam ``(params, cache,
    tokens, start, length, table) -> (next_id, cache, aux)``;
    ``with_logits`` (tests) appends that position's float32 logits.

    The chunk reads positions ``0 .. start + length - 1`` of a table that
    holds ``max_seq_len``: keys and values are made over the smallest of
    `paged_attention.chunk_spans` that holds them, a static width chosen on
    the device (``lax.switch``; the last branch is the whole table). The
    write and the gather of the table's rows stay OUTSIDE the branches: the
    pool is never a branch's operand. ``aux`` counts what was asked for and
    what was made, ``prefill_kv_live_tokens`` and
    ``prefill_kv_expanded_tokens``, a piece once (not a layer)."""
    pool = cache["latent"]                  # [L, blocks, bs, rkv + dr]
    C = tokens.shape[0]
    pos, valid, blk, slot = paged.chunk_addresses(table, start, length, C,
                                                  pool.shape[2])
    end = start + length
    spans = paged.chunk_spans(C, table.shape[0] * pool.shape[2])
    which = paged.span_index(spans, end)
    x = params["embed"][tokens].astype(jnp.float32)
    all_counts = []
    for l, lp in enumerate(params["layers"]):
        with jax.named_scope("layer"):
            def attend(h, l=l, lp=lp):
                nonlocal pool
                out, pool = _prefill_attend(
                    cfg, lp, h, pool, l, pos, blk, slot, table, start,
                    spans, which, use_pallas, interpret)
                return out
            x, counts = _block(cfg, lp, x, attend, valid,
                               kernels=(use_pallas, interpret))
            if counts is not None:
                all_counts.append(counts)
    x_last = jnp.take(x, jnp.clip(length - 1, 0, C - 1), axis=0)
    logits = _logits(cfg, params, x_last)
    aux = _aux(cfg, all_counts, "prefill_")
    aux["prefill_kv_live_tokens"] = jnp.asarray(end, jnp.int32)
    aux["prefill_kv_expanded_tokens"] = jnp.asarray(spans, jnp.int32)[which]
    out = (jnp.argmax(logits).astype(jnp.int32), {"latent": pool}, aux)
    return out + (logits,) if with_logits else out


def _step_walk(cfg, positions, tables, active, block_size, use_pallas,
               interpret):
    """What the latent layers of one decode step share, made once:
    ``(walk, walked)``. Kernel tier: the rows as
    `paged_attention.paged_latent_attention` takes them; lax tier:
    `paged_attention.walk_plan` over the config's row block and span.
    ``walked`` counts the positions one layer's walk covers."""
    if use_pallas or interpret:
        return (paged.PagedRows(positions, tables, active, interpret),
                paged.paged_walked(positions, active, block_size))
    plan = paged.walk_plan(positions, tables, block_size, cfg.step_row_block,
                           cfg.step_col_blocks * block_size)
    return plan, plan.walked


def _absorbed_attention(cfg, lp, q_nope, q_rope, pool, l, walk,
                        new_rows=None):
    """The decode step's attention in the latent space, over the live
    positions only. ``q_nope`` ``[B, H, dn]``, ``q_rope`` ``[B, H, dr]``,
    ``pool`` ``[L, blocks, bs, row]`` used at layer ``l``; ``walk`` is
    `_step_walk`'s. Returns ``(out [B, H * dv], pool)``: `_latent_attend`
    between the up-projection moved onto the query and the one applied to
    its result."""
    H, dn, dv, rkv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                      cfg.v_head_dim, cfg.kv_lora_rank)
    dt = pool.dtype
    w_kvb = lp["wkv_b"].reshape(rkv, H, dn + dv)
    w_k, w_v = w_kvb[..., :dn], w_kvb[..., dn:]
    # q'_h = q_nope_h W_kvb,k,h^T: the up-projection moves onto the query
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope.astype(dt), w_k,
                       preferred_element_type=jnp.float32)
    u, pool = _latent_attend(cfg, q_lat, q_rope, pool, l, walk, new_rows)
    o = jnp.einsum("bhr,rhv->bhv", u.astype(dt), w_v,
                   preferred_element_type=jnp.float32)
    return o.reshape(-1, H * dv), pool


def _pool_query(q_lat, q_rope, pool):
    """``[q_lat | q_rope | 0]`` ``[B, H, row]`` in the pool's dtype: one
    query against a whole pool row, as the lax tier scores it."""
    qq = jnp.concatenate([q_lat, q_rope], -1).astype(pool.dtype)
    return jnp.pad(qq, ((0, 0), (0, 0), (0, pool.shape[-1] - qq.shape[-1])))


def _latent_attend(cfg, q_lat, q_rope, pool, l, walk, new_rows=None):
    """Queries in the latent space, ``q_lat`` ``[B, H, rkv]`` beside
    ``q_rope`` ``[B, H, dr]``, against the live latent rows of layer ``l``:
    ``(u [B, H, rkv], pool)``, ``u_h = sum_j p c(j)``.

    Lax tier (the CPU's, and the kernel's reference):
    `paged_attention.live_walk` over a ``pool`` that HOLDS the rows' new
    latent rows already; what is live at once is a piece's gathered latent
    rows and scores; ``u`` float32. Kernel tier:
    `paged_attention.paged_latent_attention` reads the pool's pages in
    place and sets ``new_rows`` ``[B, row]`` into it itself; it takes the
    queries rows-major as they are and writes ``u`` in the pool's dtype,
    what the per-head ``bhr,rhv->bhv`` after it reads."""
    H, rkv = q_lat.shape[1], cfg.kv_lora_rank
    dt = pool.dtype
    sm = 1.0 / _np.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)

    if isinstance(walk, paged.PagedRows):
        return paged.paged_latent_attention(
            q_lat, q_rope, new_rows, pool, l, walk.positions, walk.tables,
            walk.active, sm_scale=float(sm), interpret=walk.interpret)
    qq = _pool_query(q_lat, q_rope, pool)                   # [B, H, row]

    def rows_block(qq_b, pos_b, pieces_of):
        def fold(carry, pieces, tpos):
            lat, = pieces                       # [rb, span, row]
            s = jnp.einsum("bhc,btc->bht", qq_b, lat,
                           preferred_element_type=jnp.float32) * sm
            return paged.softmax_fold(
                carry, s, tpos, pos_b, 2,
                lambda p: jnp.einsum("bht,btr->bhr", p.astype(dt),
                                     lat[..., :rkv],
                                     preferred_element_type=jnp.float32))

        _, den, acc = pieces_of(fold, (qq_b.shape[0], H), rkv)
        return acc / den[..., None]

    return paged.live_walk(walk, (pool,), l, qq, rows_block), pool


def _step_attend(cfg, lp, h, pool, l, positions, blk, slot, walk):
    """One layer's latent attention of a decode step: write the rows' new
    latent row into layer ``l`` of ``pool`` (here on the lax tier, inside
    the kernel on its tier) and attend absorbed over the live positions.
    ``(out [B, H * dv], pool)``."""
    q_nope, q_rope, rows = _mla_project(cfg, lp, h, positions)
    rows = _cache_rows(rows, pool)
    if not isinstance(walk, paged.PagedRows):
        pool = pool.at[l, blk, slot].set(rows)
    return _absorbed_attention(cfg, lp, q_nope, q_rope, pool, l, walk, rows)


@jax.named_scope("decode.step")      # the trace's device-side name
def moe_mla_decode_step(params, cfg, cache, token_ids, positions, tables,
                        active, *, use_pallas=False, interpret=False,
                        with_logits=False):
    """Fixed-shape batched decode step, one token per active row, attention
    absorbed into the latent space. The DecodeEngine step seam ``(params,
    cache, token_ids, positions, tables, active) -> (next_ids, cache, aux)``.
    A row contracts only over its own blocks; an inactive row writes to the
    null block (on the kernel tier: nowhere), is routed to no expert and
    counted nowhere. ``aux`` counts
    the walk as the GPT-2 step does: ``kv_live_tokens`` (``positions + 1``,
    the active rows together) and ``kv_walked_tokens`` (positions the walk
    of ONE layer read: the kernel's own pages, or the lax tier's rows x
    span x pieces). ``with_logits`` (tests) appends the rows' float32
    logits."""
    pool = cache["latent"]
    bs = pool.shape[2]
    blk, slot = paged.step_addresses(tables, positions, active, bs)
    walk, walked = _step_walk(cfg, positions, tables, active, bs, use_pallas,
                              interpret)
    x = params["embed"][token_ids].astype(jnp.float32)
    all_counts = []
    for l, lp in enumerate(params["layers"]):
        with jax.named_scope("layer"):
            def attend(h, l=l, lp=lp):
                nonlocal pool
                out, pool = _step_attend(cfg, lp, h, pool, l, positions,
                                         blk, slot, walk)
                return out
            x, counts = _block(cfg, lp, x, attend, active,
                               kernels=(use_pallas, interpret))
            if counts is not None:
                all_counts.append(counts)
    logits = _logits(cfg, params, x)
    aux = _aux(cfg, all_counts)
    # cached tokens this step attended over, the active rows together
    aux["kv_live_tokens"] = jnp.sum(jnp.where(active, positions + 1, 0))
    aux["kv_walked_tokens"] = walked
    out = (jnp.argmax(logits, axis=-1).astype(jnp.int32), {"latent": pool},
           aux)
    return out + (logits,) if with_logits else out


class MoEMLADecodeModel(DecodeModel):
    """Adapter: a `MoEMLAConfig` wired for the DecodeEngine seam.

    >>> model = MoEMLADecodeModel(cfg, params=params)       # or seed=
    >>> eng = DecodeEngine(**model.engine_kwargs(), max_seq_len=4096, ...)

    ``flash`` picks the kernel tier of the prefill attention AND of the
    step's walk (`DecodeModel.resolve_flash`). The cache is ONE pool of
    latent rows in the parameters' dtype; with a ``mesh`` it is stated
    replicated: a latent row has no head axis to shard."""

    def __init__(self, cfg, params=None, seed=0, dtype=jnp.bfloat16,
                 flash=None, mesh=None):
        self.cfg = cfg
        if params is None:
            params = init_moe_mla(cfg, jax.random.PRNGKey(seed), dtype)
        self.params = params
        self.cache_dtype = params["embed"].dtype
        self.mesh = mesh
        self.resolve_flash(flash)

    def cache_spec(self, num_blocks, block_size, slots):
        """One pool: ``(layers, blocks, block_size, cache_row_width)`` in
        the parameters' dtype; a row is ``[c | k_rope]``, ``kv_lora_rank +
        qk_rope_head_dim`` numbers, padded to whole lanes."""
        sharding = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            sharding = NamedSharding(self.mesh, PartitionSpec())
        return {"latent": jax.ShapeDtypeStruct(
            (self.cfg.num_hidden_layers, num_blocks, block_size,
             self.cfg.cache_row_width), self.cache_dtype,
            sharding=sharding)}

    def prefill_fn(self, params, cache, tokens, start, length, table, slot):
        return moe_mla_decode_prefill(
            params, self.cfg, cache, tokens, start, length, table,
            use_pallas=self.use_pallas, interpret=self.interpret)

    def step_fn(self, params, cache, token_ids, positions, tables, active):
        return moe_mla_decode_step(
            params, self.cfg, cache, token_ids, positions, tables, active,
            use_pallas=self.use_pallas, interpret=self.interpret)
