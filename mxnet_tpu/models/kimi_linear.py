"""A third decoder family: gated delta-rule linear attention (KDA) layers
with a per-slot recurrent state, beside latent-attention (MLA) layers over
the latent paged cache, sparse experts after a leading dense layer: the
Kimi-Linear architecture (arXiv:2510.26692), assembled from the published
config's own keys (`KimiLinearConfig.from_dict`).

**Layer kind per layer, from the config** (``linear_attn_config``'s
``kda_layers`` / ``full_attn_layers``, 1-based as published). An MLA layer
runs `models/moe_mla.py`'s functions (``_mla_project`` with the query
projected directly and nothing rotated, ``_prefill_attend``,
``_step_attend``); an expert layer is ``_ffn`` over
`parallel/moe.py::routed_experts`; blocks are ``_block``, pre-norm here. This
file CALLS them: an optimisation of one family is measured on the other.

**The KDA mixer** (new here; the state programs are `kernels/kda.py`'s). For
the normed input ``x_t``: ``[q^, k^, v^] = x W_qkv``; a depthwise causal
convolution of ``short_conv_kernel_size`` taps and SiLU on each; per head
``q = q / |q| / sqrt(d_k)``, ``k = k / |k|``; log-decay a head and channel ``g
= -exp(A_log) softplus(W_f x + dt_bias)`` (``W_f`` through a rank of the
head width), write strength ``beta = sigmoid(W_b x)``; the delta rule
(`kernels/kda.py`); ``y = W_o [RMSNorm_head(o) * sigmoid(W_g x)]``.

**Two kinds of state** (``cache_spec``): the paged pool ``latent``
``[MLA layers, blocks, block_size, 640]`` as the other latent family's, and
two per-slot pools (`decode_model.SlotPool`): ``kda_state`` ``[KDA layers,
slots, H, d_k, d_v]`` (float32 as served: the sum of thousands of updates)
and ``kda_conv`` ``[KDA layers, slots, taps - 1, 3 H d_k]``, the
convolution's tail of pre-activation rows in the parameters' dtype.
*Lifetime*: a prefill piece with ``start == 0`` begins from zero state and
a zero tail whatever the slot held; a later piece continues from what the
piece before it left; a step updates ACTIVE rows only (row ``i`` is slot
``i``), so a slot in mid-prefill or vacant is neither read nor written.

**Precision**: as `models/moe_mla.py`; besides, decay, write strength, the
L2 norms and the recurrent state are float32 (``state_dtype``: the
benchmark's control keeps the state in bfloat16), the tail is the
parameters' dtype.

**Parameter layout** (shared with the benchmark's plain reference, which
makes the weights): ``{"embed", "head", "norm_f", "layers"}``; every layer
``norm_attn_in``, ``norm_ffn_in``, ``wo`` and its feed-forward leaves as
`models/moe_mla.py`'s; a KDA layer ``wqkv`` ``[d, 3 H d_k]``, ``conv``
``[taps, 3 H d_k]``, ``A_log`` ``[H]``, ``wf_a``, ``wf_b``, ``dt_bias``,
``wb``, ``wg_a``, ``wg_b``, ``norm_o`` ``[d_v]``; an MLA layer ``wq``,
``wkv_a``, ``norm_kv``, ``wkv_b``.

Device-side names: ``kda``, ``kda.conv``, ``kda.state`` beside ``mla``,
``moe.*``, ``mlp`` inside ``decode.step/layer`` and ``decode.prefill/layer``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..kernels import kda as _kda
from ..kernels import paged_attention as paged
from . import moe_mla as M
from .decode_model import DecodeModel, SlotPool

__all__ = ["KimiLinearConfig", "init_kimi_linear", "kimi_linear_decode_prefill",
           "kimi_linear_decode_step", "KimiLinearDecodeModel"]

_L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    """The published keys by their own names (``linear_attn_config`` is read
    into ``kda_layers``, ``full_attn_layers``, ``kda_num_heads``,
    ``kda_head_dim``, ``short_conv_kernel_size``), plus ``experts_held``
    ``(first, count)``: the routed experts whose weights live here."""
    hidden_size: int
    num_hidden_layers: int
    first_k_dense_replace: int
    num_attention_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    num_experts: int
    num_shared_experts: int
    num_experts_per_token: int
    routed_scaling_factor: float
    rms_norm_eps: float
    vocab_size: int
    kda_layers: tuple
    full_attn_layers: tuple
    kda_num_heads: int
    kda_head_dim: int
    short_conv_kernel_size: int
    q_lora_rank: int = None
    mla_use_nope: bool = True
    rope_theta: float = 10000.0
    moe_renormalize: bool = True
    moe_router_activation_func: str = "sigmoid"
    num_expert_group: int = 1
    topk_group: int = 1
    experts_held: tuple = None
    initializer_range: float = 0.02
    state_dtype: str = "float32"
    # decode-path knobs (not the model's): as MoEMLAConfig's, and the
    # chunked scan's chunk and sub-chunk lengths
    block_k: int = 512
    step_row_block: int = 32
    step_col_blocks: int = 32
    kda_chunk: int = 64
    kda_sub: int = 16

    def __post_init__(self):
        object.__setattr__(self, "experts_held", M._held_experts(
            self.experts_held, self.num_experts))
        kinds = tuple(int(l) for l in self.kda_layers), \
            tuple(int(l) for l in self.full_attn_layers)
        object.__setattr__(self, "kda_layers", kinds[0])
        object.__setattr__(self, "full_attn_layers", kinds[1])
        if sorted(kinds[0] + kinds[1]) != list(
                range(1, self.num_hidden_layers + 1)):
            raise ValueError(
                "kda_layers %r and full_attn_layers %r do not name each of "
                "the %d layers once" % (kinds + (self.num_hidden_layers,)))
        # what `routed_experts` computes: sigmoid scores, a plain top-k,
        # weights renormalised over the chosen
        if not (self.moe_renormalize and self.num_expert_group == 1
                and self.topk_group == 1
                and self.moe_router_activation_func == "sigmoid"):
            raise ValueError("only the sigmoid router with one expert group "
                             "and renormalised weights is built")

    @classmethod
    def from_dict(cls, config, **overrides):
        """From a ``config.json`` as published (unknown keys ignored)."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in config.items() if k in names}
        lin = config.get("linear_attn_config")
        if lin:
            kw.update(kda_layers=tuple(lin["kda_layers"]),
                      full_attn_layers=tuple(lin["full_attn_layers"]),
                      kda_num_heads=lin["num_heads"],
                      kda_head_dim=lin["head_dim"],
                      short_conv_kernel_size=lin["short_conv_kernel_size"])
        kw.update(overrides)
        return cls(**kw)

    # the names the shared functions of models/moe_mla.py read
    n_routed_experts = property(lambda self: self.num_experts)
    n_shared_experts = property(lambda self: self.num_shared_experts)
    num_experts_per_tok = property(lambda self: self.num_experts_per_token)
    latent_width = M.MoEMLAConfig.latent_width
    cache_row_width = M.MoEMLAConfig.cache_row_width
    is_dense = M.MoEMLAConfig.is_dense

    def is_kda(self, layer):
        """Layer ``layer`` (0-based) is a KDA layer."""
        return layer + 1 in self.kda_layers

    def kind_index(self, layer):
        """The layer's index among the layers of its own kind: its row in
        that kind's pools."""
        same = self.kda_layers if self.is_kda(layer) else self.full_attn_layers
        return sorted(same).index(layer + 1)

    @property
    def kda_width(self):
        return self.kda_num_heads * self.kda_head_dim


def _layer_shapes(cfg, l):
    d, Hd, dk = cfg.hidden_size, cfg.kda_width, cfg.kda_head_dim
    out = {"norm_attn_in": (d,), "norm_ffn_in": (d,)}
    if cfg.is_kda(l):
        out.update({
            "wqkv": (d, 3 * Hd), "conv": (cfg.short_conv_kernel_size, 3 * Hd),
            "A_log": (cfg.kda_num_heads,), "wf_a": (d, dk), "wf_b": (dk, Hd),
            "dt_bias": (Hd,), "wb": (d, cfg.kda_num_heads), "wg_a": (d, dk),
            "wg_b": (dk, Hd), "norm_o": (dk,), "wo": (Hd, d)})
    else:
        H = cfg.num_attention_heads
        out.update({
            **M._query_shapes(cfg),
            "wkv_a": (d, cfg.latent_width), "norm_kv": (cfg.kv_lora_rank,),
            "wkv_b": (cfg.kv_lora_rank,
                      H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            "wo": (H * cfg.v_head_dim, d)})
    ffn = M._layer_shapes(cfg, cfg.is_dense(l))
    out.update({k: v for k, v in ffn.items()
                if k.startswith(("w_", "router", "shared_", "experts_"))})
    return out


def init_kimi_linear(cfg, key, dtype=jnp.float32):
    """Seeded parameters in ONE jitted call: normal(0, ``initializer_range``)
    matrices, norm gains 1, ``A_log`` the log of uniform(1, 16), ``dt_bias``
    0. (The benchmark's reference makes its own; this one is the tests'.)"""
    return M._init_tree(
        cfg, key, dtype,
        [_layer_shapes(cfg, l) for l in range(cfg.num_hidden_layers)],
        special={
            "dt_bias": lambda k, shape: jnp.zeros(shape, jnp.float32),
            "A_log": lambda k, shape: jnp.log(jax.random.uniform(
                k, shape, jnp.float32, 1.0, 16.0))})


# ---------------------------------------------------------------------------
# the KDA mixer
# ---------------------------------------------------------------------------
def _conv(lp, window, taps):
    """``window`` ``[..., taps - 1 + N, 3 H dk]`` of pre-activation rows ->
    the ``N`` convolved, SiLU-ed rows, float32: row ``t`` sums ``taps`` rows
    ending at its own."""
    n = window.shape[-2] - (taps - 1)
    w = lp["conv"].astype(jnp.float32)
    y = sum(w[i] * window[..., i:i + n, :].astype(jnp.float32)
            for i in range(taps))
    return jax.nn.silu(y)


def _kda_gates(cfg, lp, h, y):
    """From the normed input ``h`` ``[N, d]`` and the convolved rows ``y``
    ``[N, 3 H dk]``: ``(q, k, v [N, H, dk], g [N, H, dk], beta [N, H])``, all
    float32, q and k L2-normalised a head, q scaled."""
    N, H, dk = h.shape[0], cfg.kda_num_heads, cfg.kda_head_dim
    q, k, v = (t.reshape(N, H, dk) for t in jnp.split(y, 3, axis=-1))
    unit = lambda t: t * jax.lax.rsqrt(                         # noqa: E731
        jnp.sum(t * t, -1, keepdims=True) + _L2_EPS)
    q, k = unit(q) * dk ** -0.5, unit(k)
    f = M._mm(M._mm(h, lp["wf_a"]), lp["wf_b"]) \
        + lp["dt_bias"].astype(jnp.float32)
    g = -jnp.exp(lp["A_log"].astype(jnp.float32))[None, :, None] \
        * jax.nn.softplus(f.reshape(N, H, dk))
    beta = jax.nn.sigmoid(M._mm(h, lp["wb"]))
    return q, k, v, g, beta


def _kda_out(cfg, lp, h, o):
    """``[RMSNorm_head(o; norm_o) * sigmoid(W_g h)]`` ``[N, H * dv]``: what
    ``W_o`` takes (``_block`` applies it)."""
    N, H, dk = h.shape[0], cfg.kda_num_heads, cfg.kda_head_dim
    gate = jax.nn.sigmoid(M._mm(M._mm(h, lp["wg_a"]), lp["wg_b"]))
    return (M._rms(o, lp["norm_o"], cfg.rms_norm_eps)
            * gate.reshape(N, H, dk)).reshape(N, H * dk)


def _kda_prefill(cfg, lp, h, state, tail, li, slot, start, length, valid):
    """A prefill piece through KDA layer ``li`` (its index among the KDA
    layers) for the sequence in ``slot``: from zero state and a zero tail
    when ``start == 0``, else from what the slot holds in the pools
    ``state`` and ``tail`` (read, never written here). ``(out [C, H * dv],
    the state [H, dk, dv] and the tail [taps - 1, 3 H dk] after the piece's
    last REAL token)``: the caller writes all layers' into the slot at
    once."""
    taps = cfg.short_conv_kernel_size
    fresh = start == 0
    with jax.named_scope("kda.conv"):
        rows = M._mm(h, lp["wqkv"], lp["wqkv"].dtype)           # [C, 3 H dk]
        held = jnp.where(fresh, 0, tail[li, slot]).astype(rows.dtype)
        window = jnp.concatenate([held, rows], axis=0)
        y = _conv(lp, window, taps)
        # the last taps - 1 REAL rows: rows length - taps + 1 .. length - 1
        new_tail = jax.lax.dynamic_slice_in_dim(window, length, taps - 1,
                                                axis=0)
    q, k, v, g, beta = _kda_gates(cfg, lp, h, y)
    # a padded token leaves the state as it is
    g = jnp.where(valid[:, None, None], g, 0.0)
    beta = jnp.where(valid[:, None], beta, 0.0)
    with jax.named_scope("kda.state"):
        s0 = jnp.where(fresh, 0, state[li, slot]).astype(jnp.float32)
        o, s = _kda.kda_chunk_scan(q, k, v, g, beta, s0, chunk=cfg.kda_chunk,
                                   sub=cfg.kda_sub,
                                   mm_dtype=lp["wqkv"].dtype)
    return _kda_out(cfg, lp, h, o), s, new_tail


def _kda_step(cfg, lp, h, state, tail, li, active, use_pallas, interpret):
    """One token of every ACTIVE row (row ``i`` is slot ``i``) through KDA
    layer ``li``: the state pool updated in place, the tail pool ``tail``
    read only. ``(out [B, H * dv], state, the rows' new tails [B, taps - 1,
    3 H dk])``: the caller writes all layers' tails at once."""
    taps = cfg.short_conv_kernel_size
    with jax.named_scope("kda.conv"):
        rows = M._mm(h, lp["wqkv"], lp["wqkv"].dtype)           # [B, 3 H dk]
        window = jnp.concatenate([tail[li].astype(rows.dtype),
                                  rows[:, None]], 1)
        y = _conv(lp, window, taps)[:, 0]
    q, k, v, g, beta = _kda_gates(cfg, lp, h, y)
    with jax.named_scope("kda.state"):
        o, state = _kda.kda_step(state, li, q, k, v, g, beta, active,
                                 use_pallas=use_pallas, interpret=interpret)
    return _kda_out(cfg, lp, h, o), state, window[:, 1:]


# ---------------------------------------------------------------------------
# the DecodeEngine seam
# ---------------------------------------------------------------------------
@jax.named_scope("decode.prefill")      # the trace's device-side name
def kimi_linear_decode_prefill(params, cfg, cache, tokens, start, length,
                               table, slot, *, use_pallas=False,
                               interpret=False, with_logits=False):
    """Bucketed batch-1 prefill chunk of the sequence admitted to ``slot``:
    the MLA layers write and read the latent pool as
    `moe_mla.moe_mla_decode_prefill`'s do, the KDA layers run the chunked
    scan from the slot's state (zero when ``start == 0``) and leave the
    state and the convolution's tail after the chunk's last REAL token in
    the slot. The seam's ``(params, cache, tokens, start, length, table,
    slot) -> (next_id, cache, aux)``; ``with_logits`` (tests) appends the
    last real position's float32 logits."""
    pool, state, tail = cache["latent"], cache["kda_state"], cache["kda_conv"]
    C = tokens.shape[0]
    pos, valid, blk, at = paged.chunk_addresses(table, start, length, C,
                                                pool.shape[2])
    end = start + length
    spans = paged.chunk_spans(C, table.shape[0] * pool.shape[2])
    which = paged.span_index(spans, end)
    x = params["embed"][tokens].astype(jnp.float32)
    all_counts, states, tails = [], [], []
    for l, lp in enumerate(params["layers"]):
        li = cfg.kind_index(l)
        with jax.named_scope("layer"):
            if cfg.is_kda(l):
                def mix(h, lp=lp, li=li):
                    out, s, t = _kda_prefill(cfg, lp, h, state, tail, li,
                                             slot, start, length, valid)
                    states.append(s)
                    tails.append(t)
                    return out
                x, counts = M._block(cfg, lp, x, mix, valid, scope="kda",
                                     kernels=(use_pallas, interpret))
            else:
                def attend(h, lp=lp, li=li):
                    nonlocal pool
                    out, pool = M._prefill_attend(
                        cfg, lp, h, pool, li, pos, blk, at, table, start,
                        spans, which, use_pallas, interpret)
                    return out
                x, counts = M._block(cfg, lp, x, attend, valid,
                                     kernels=(use_pallas, interpret))
            if counts is not None:
                all_counts.append(counts)
    x_last = jnp.take(x, jnp.clip(length - 1, 0, C - 1), axis=0)
    logits = M._logits(cfg, params, x_last)
    aux = M._aux(cfg, all_counts, "prefill_")
    aux["prefill_kv_live_tokens"] = jnp.asarray(end, jnp.int32)
    aux["prefill_kv_expanded_tokens"] = jnp.asarray(spans, jnp.int32)[which]
    # chunks of the scan that held a real token, the KDA layers together
    aux["prefill_kda_chunks"] = (len(states) * (
        (length + cfg.kda_chunk - 1) // cfg.kda_chunk)).astype(jnp.int32)
    if states:
        # every KDA layer's state and tail into the slot, ONE write a pool:
        # the layers read the pools as they came in, so no update of a pool
        # is chained on another (a chain of in-place updates that the
        # compiler rematerialised read a tail AFTER it was overwritten on
        # the chip, PERF.md PR 34)
        state = state.at[:, slot].set(jnp.stack(states).astype(state.dtype))
        tail = tail.at[:, slot].set(jnp.stack(tails).astype(tail.dtype))
    cache = {"latent": pool, "kda_state": state, "kda_conv": tail}
    out = (jnp.argmax(logits).astype(jnp.int32), cache, aux)
    return out + (logits,) if with_logits else out


@jax.named_scope("decode.step")      # the trace's device-side name
def kimi_linear_decode_step(params, cfg, cache, token_ids, positions, tables,
                            active, *, use_pallas=False, interpret=False,
                            with_logits=False):
    """Fixed-shape batched decode step, one token per active row. MLA
    layers: absorbed attention over the live latent rows
    (`moe_mla._step_attend`); KDA layers: `kernels/kda.py::kda_step` over
    the rows' own slots. An inactive row writes to the null block, leaves
    its slot's state and tail untouched, is routed to no expert and counted
    nowhere. The seam's ``(params, cache, token_ids, positions, tables,
    active) -> (next_ids, cache, aux)``."""
    pool, state, tail = cache["latent"], cache["kda_state"], cache["kda_conv"]
    bs = pool.shape[2]
    blk, at = paged.step_addresses(tables, positions, active, bs)
    walk, walked = M._step_walk(cfg, positions, tables, active, bs,
                                use_pallas, interpret)
    x = params["embed"][token_ids].astype(jnp.float32)
    all_counts, tails = [], []
    for l, lp in enumerate(params["layers"]):
        li = cfg.kind_index(l)
        with jax.named_scope("layer"):
            if cfg.is_kda(l):
                def mix(h, lp=lp, li=li):
                    nonlocal state
                    out, state, t = _kda_step(cfg, lp, h, state, tail, li,
                                              active, use_pallas, interpret)
                    tails.append(t)
                    return out
                x, counts = M._block(cfg, lp, x, mix, active, scope="kda",
                                     kernels=(use_pallas, interpret))
            else:
                def attend(h, lp=lp, li=li):
                    nonlocal pool
                    out, pool = M._step_attend(cfg, lp, h, pool, li,
                                               positions, blk, at, walk)
                    return out
                x, counts = M._block(cfg, lp, x, attend, active,
                                     kernels=(use_pallas, interpret))
            if counts is not None:
                all_counts.append(counts)
    logits = M._logits(cfg, params, x)
    aux = M._aux(cfg, all_counts)
    rows = jnp.sum(active.astype(jnp.int32))
    # cached tokens the MLA layers attended over, the active rows together
    aux["kv_live_tokens"] = jnp.sum(jnp.where(active, positions + 1, 0))
    aux["kv_walked_tokens"] = walked    # one MLA layer's walk
    aux["kda_rows_updated"] = rows * len(tails)
    aux["kda_layer_steps"] = jnp.int32(len(tails))
    if tails:
        # the active rows' tails of every KDA layer, ONE elementwise write
        # of the pool (see the prefill's note)
        tail = jnp.where(active[None, :, None, None],
                         jnp.stack(tails).astype(tail.dtype), tail)
    cache = {"latent": pool, "kda_state": state, "kda_conv": tail}
    out = (jnp.argmax(logits, axis=-1).astype(jnp.int32), cache, aux)
    return out + (logits,) if with_logits else out


class KimiLinearDecodeModel(DecodeModel):
    """Adapter: a `KimiLinearConfig` wired for the DecodeEngine seam.

    >>> model = KimiLinearDecodeModel(cfg, params=params)       # or seed=
    >>> eng = DecodeEngine(**model.engine_kwargs(), max_seq_len=8192, ...)

    ``flash`` picks the kernel tier of the prefill attention AND of the
    step's state update (`DecodeModel.resolve_flash`). With a ``mesh``
    every pool is stated replicated."""

    def __init__(self, cfg, params=None, seed=0, dtype=jnp.bfloat16,
                 flash=None, mesh=None):
        self.cfg = cfg
        if params is None:
            params = init_kimi_linear(cfg, jax.random.PRNGKey(seed), dtype)
        self.params = params
        self.cache_dtype = params["embed"].dtype
        self.mesh = mesh
        self.resolve_flash(flash)

    def cache_spec(self, num_blocks, block_size, slots):
        """``latent``: the paged pool of ``[c | k_pe]`` rows, a layer axis
        over the MLA layers only. ``kda_state`` and ``kda_conv``: per-slot
        pools over the KDA layers."""
        cfg = self.cfg
        sharding = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            sharding = NamedSharding(self.mesh, PartitionSpec())
        H, dk = cfg.kda_num_heads, cfg.kda_head_dim
        n_kda = len(cfg.kda_layers)
        return {
            "latent": jax.ShapeDtypeStruct(
                (len(cfg.full_attn_layers), num_blocks, block_size,
                 cfg.cache_row_width), self.cache_dtype, sharding=sharding),
            "kda_state": SlotPool((n_kda, slots, H, dk, dk),
                                  jnp.dtype(cfg.state_dtype),
                                  sharding=sharding),
            "kda_conv": SlotPool(
                (n_kda, slots, cfg.short_conv_kernel_size - 1,
                 3 * cfg.kda_width), self.cache_dtype, sharding=sharding)}

    def prefill_fn(self, params, cache, tokens, start, length, table, slot):
        return kimi_linear_decode_prefill(
            params, self.cfg, cache, tokens, start, length, table, slot,
            use_pallas=self.use_pallas, interpret=self.interpret)

    def step_fn(self, params, cache, token_ids, positions, tables, active):
        return kimi_linear_decode_step(
            params, self.cfg, cache, token_ids, positions, tables, active,
            use_pallas=self.use_pallas, interpret=self.interpret)
