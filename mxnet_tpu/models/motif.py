"""A fourth decoder family: four hyper-connected residual streams, grouped
differential attention over latent rows, window layers beside full ones and
PolyNorm experts: the Motif-3 architecture, assembled from the published
config's own keys (`MotifConfig.from_dict`).

**The residual path (mHC, arXiv:2512.24880).** The stream is ``x`` ``[n,
d]`` a token (``n = mhc_expansion_rate``), float32; the embedding is copied
into the ``n`` streams. Each sub-layer ``F`` (attention, then the MLP) reads
``x~ = RMS(vec x)`` (gainless, ``n d`` wide) and makes its three mixings
from ONE projection ``x~ phi`` ``[2 n + n^2]``::

    H_pre  = sigmoid(a_pre  x~ phi_pre  + b_pre)                    [n]
    H_post = 2 sigmoid(a_post x~ phi_post + b_post)                 [n]
    H_res  = Sinkhorn(exp(a_res mat(x~ phi_res) + b_res))           [n, n]
    x     <- H_res x + H_post F(RMS(H_pre x; g))

Sinkhorn normalises rows, then columns, ``mhc_sinkhorn_iters`` times; the
streams are clamped to ``+-hidden_clamp`` after each update. After the last
layer the streams are summed, then the final norm and the head.

**Grouped differential latent attention (GDLA).** Queries, the latent rows
``[c | k_rope]`` and rotary are `models/moe_mla.py`'s (``_mla_project``, a
head ``head_dim = qk_nope + qk_rope`` wide). The ``H`` query heads fall into
``G = num_key_value_heads`` groups of ``S + 1``: ``S`` signal heads and one
noise head (``num_noise_heads = G``), head ``h = g (S + 1) + j``, the noise
head last. Group ``g`` has ONE key ``[c W_UK,g | k_rope]`` and value ``c
W_UV,g`` (``wkv_b`` ``[rkv, G (dn + dv)]``). Every head attends; a signal
head's output is ``a_h - lambda_h a_noise(g)`` with ``lambda = sigmoid(u
W_lambda)`` a token and signal head, then ``* sigmoid(u W_G)`` (an
elementwise output gate) and ``W_O``. Both are linear after the softmax, so
the DECODE step combines signal and noise heads in the latent space, before
``W_UV,g``; the prefill expands keys and values a head and combines after.

**Window or full, a layer at a time.** Layer ``i`` (its PUBLISHED index:
``layers_kept`` names them) is full below ``max_window_layers`` and where
``(i + 1) % sliding_window_period == 0``, else it attends the last
``sliding_window`` positions, its own included. A full layer's latent rows
are a paged pool ``latent`` ``[full layers, blocks, block_size, row]`` as
the other latent families'; a window layer's are a per-slot RING
(`decode_model.SlotPool`) ``window`` ``[window layers, slots, W, row]``,
position ``p`` at ring row ``p % W``. *Lifetime*: a prefill piece with
``start == 0`` reads nothing of the ring it finds; every piece attends
``[ring | chunk]`` under a band mask and leaves the ring holding the last
``W`` positions, all window layers' rings in ONE write at the program's
end; a step writes ACTIVE rows only, inside
`paged_attention.window_latent_attention` (``mx_window_latent_attn``).

**Experts.** Every MLP is gated, ``down(PolyNorm(gate x) * up x)``
(`kernels/grouped_experts.py::polynorm`, four coefficients an MLP): the
dense MLP and the shared expert in XLA, the routed experts through
`parallel/moe.py::routed_experts` with the activation as a parameter
(PolyNorm in the grouped kernel's down call). Router: sigmoid over all
experts, top-k, renormalised, times ``route_scale``.

**Precision**: bfloat16 weights, latent cache and rings as served; matrix
products accumulate in float32; norms, the mHC streams, projections and
Sinkhorn (``mhc_dtype``: the tests' control runs them in bfloat16), router,
softmaxes, ``lambda``, gates and logits in float32.

**Parameter layout** (shared with the benchmark's plain reference, which
makes the weights): ``{"embed", "head", "norm_f", "layers"}``; every layer
``mhc_{attn,ffn}_{phi [n d, 2 n + n^2], alpha [3], bias [2 n + n^2]}``,
``norm_attn_in``, ``norm_ffn_in``, ``wq_a``, ``norm_q``, ``wq_b``,
``wkv_a``, ``norm_kv``, ``wkv_b``, ``w_lambda`` ``[d, G S]``, ``wg_o`` ``[d,
G S dv]``, ``wo`` ``[G S dv, d]``; a dense layer ``w_gate``, ``w_up``,
``w_down``, ``mlp_poly`` ``[4]``; an expert layer ``router``,
``shared_{gate,up,down}``, ``shared_poly``, ``experts_{gate,up,down}``,
``experts_poly`` ``[held, 4]``. ``alpha``, ``bias`` and the PolyNorm
coefficients are float32.

Device-side names: ``gdla``, ``gdla.window``, ``mhc``, ``moe.*``, ``mlp``
inside ``decode.step/layer`` and ``decode.prefill/layer``.
"""
from __future__ import annotations

import dataclasses

import numpy as _np
import jax
import jax.numpy as jnp
from jax import lax

from .. import profiler
from ..kernels import paged_attention as paged
from ..kernels.grouped_experts import polynorm
from ..parallel.moe import routed_experts
from . import moe_mla as M
from .decode_model import DecodeModel, SlotPool

__all__ = ["MotifConfig", "init_motif", "sinkhorn", "motif_decode_prefill",
           "motif_decode_step", "MotifDecodeModel"]

#: PolyNorm's own epsilon (the Motif-2.6B module's default).
POLYNORM_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class MotifConfig:
    """The published keys by their own names, plus ``layers_kept`` (the
    published indices of the layers held here, in order; default all) and
    ``experts_held`` ``(first, count)``."""
    hidden_size: int
    num_hidden_layers: int
    n_dense_first_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    num_noise_heads: int
    head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    q_lora_rank: int
    kv_lora_rank: int
    intermediate_size: int
    moe_intermediate_size: int
    num_experts: int
    num_shared_experts: int
    experts_top_k: int
    route_scale: float
    rms_norm_eps: float
    vocab_size: int
    sliding_window: int
    sliding_window_period: int
    max_window_layers: int
    mhc_expansion_rate: int
    mhc_sinkhorn_iters: int
    rope_theta: float = 10000.0
    polynorm_output_scale: float = 0.5
    polynorm_bias_clamp: float = 0.5
    hidden_clamp: float = 1e6
    layers_kept: tuple = None
    experts_held: tuple = None
    initializer_range: float = 0.02
    mhc_dtype: str = "float32"
    # decode-path knobs (not the model's): as MoEMLAConfig's
    block_k: int = 512
    step_row_block: int = 32
    step_col_blocks: int = 32

    def __post_init__(self):
        object.__setattr__(self, "experts_held", M._held_experts(
            self.experts_held, self.num_experts))
        kept = tuple(range(self.num_hidden_layers)) \
            if self.layers_kept is None else tuple(int(i) for i in
                                                   self.layers_kept)
        if len(kept) != self.num_hidden_layers:
            raise ValueError("layers_kept %r does not name %d layers"
                             % (kept, self.num_hidden_layers))
        object.__setattr__(self, "layers_kept", kept)
        G, H = self.num_key_value_heads, self.num_attention_heads
        if self.num_noise_heads != G or H % G:
            raise ValueError("each of the %d groups needs one noise head "
                             "among %d heads" % (G, H))

    @classmethod
    def from_dict(cls, config, **overrides):
        """From a ``config.json`` as published (unknown keys ignored). The
        readings this family is built for are refused otherwise: a sigmoid
        router renormalised over the chosen, the ``diff_v2`` lambda, an
        elementwise output gate, interleaved windows, mHC, no yarn scaling
        applied."""
        c = config
        rope = c.get("rope_scaling") or {}
        wanted = (c.get("score_func", "sigmoid") == "sigmoid"
                  and c.get("route_norm", True)
                  and c.get("diff_v2", True)
                  and c.get("elementwise_attn_output_gate", True)
                  and not c.get("headwise_attn_output_gate", False)
                  and c.get("use_sliding_window", True)
                  and c.get("sliding_window_pattern",
                            "interleave") == "interleave"
                  and c.get("mhc_enabled", True)
                  and not rope.get("apply_yarn_scaling", False))
        if not wanted:
            raise ValueError("only the published Motif-3 readings are built")
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in c.items() if k in names}
        kw.update(overrides)
        return cls(**kw)

    # the names the shared functions of models/moe_mla.py read
    qk_nope_head_dim = property(lambda self: self.head_dim
                                - self.qk_rope_head_dim)
    mla_use_nope = False
    latent_width = M.MoEMLAConfig.latent_width
    cache_row_width = M.MoEMLAConfig.cache_row_width

    @property
    def groups(self):
        return self.num_key_value_heads

    @property
    def signal_heads(self):
        """Signal heads a group."""
        return self.num_attention_heads // self.groups - 1

    def is_dense(self, layer):
        return self.layers_kept[layer] < self.n_dense_first_layers

    def is_window(self, layer):
        """Layer ``layer`` (0-based in the cut) attends a window."""
        i = self.layers_kept[layer]
        return i >= self.max_window_layers \
            and (i + 1) % self.sliding_window_period != 0

    def kind_index(self, layer):
        """The layer's index among the layers of its own kind: its row in
        that kind's pool."""
        return sum(self.is_window(l) == self.is_window(layer)
                   for l in range(layer))

    @property
    def window_layers(self):
        return sum(self.is_window(l) for l in range(self.num_hidden_layers))

    @property
    def full_layers(self):
        return self.num_hidden_layers - self.window_layers

    @property
    def activation(self):
        """The experts' activation as `routed_experts` takes it."""
        return ("polynorm", POLYNORM_EPS, self.polynorm_output_scale,
                self.polynorm_bias_clamp)


def layer_shapes(cfg, l):
    d, H, G, S = (cfg.hidden_size, cfg.num_attention_heads, cfg.groups,
                  cfg.signal_heads)
    n = cfg.mhc_expansion_rate
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    out = {"norm_attn_in": (d,), "norm_ffn_in": (d,)}
    for sub in ("attn", "ffn"):
        out.update({"mhc_%s_phi" % sub: (n * d, 2 * n + n * n),
                    "mhc_%s_alpha" % sub: (3,),
                    "mhc_%s_bias" % sub: (2 * n + n * n,)})
    out.update({
        "wq_a": (d, cfg.q_lora_rank), "norm_q": (cfg.q_lora_rank,),
        "wq_b": (cfg.q_lora_rank, H * (dn + dr)),
        "wkv_a": (d, cfg.latent_width), "norm_kv": (cfg.kv_lora_rank,),
        "wkv_b": (cfg.kv_lora_rank, G * (dn + dv)),
        "w_lambda": (d, G * S), "wg_o": (d, G * S * dv),
        "wo": (G * S * dv, d)})
    if cfg.is_dense(l):
        i = cfg.intermediate_size
        out.update({"w_gate": (d, i), "w_up": (d, i), "w_down": (i, d),
                    "mlp_poly": (4,)})
    else:
        f, e = cfg.moe_intermediate_size, cfg.experts_held[1]
        fs = f * cfg.num_shared_experts
        out.update({"router": (d, cfg.num_experts),
                    "shared_gate": (d, fs), "shared_up": (d, fs),
                    "shared_down": (fs, d), "shared_poly": (4,),
                    "experts_gate": (e, d, f), "experts_up": (e, d, f),
                    "experts_down": (e, f, d), "experts_poly": (e, 4)})
    return out


def _uniform(lo, hi):
    return lambda k, shape: jax.random.uniform(k, shape, jnp.float32, lo, hi)


#: The float32 leaves' seeds (the reference makes the served weights with
#: the same rules): mHC gains and biases, PolyNorm coefficients about the
#: published initial (1/3 each, bias 0).
SPECIAL = {
    "alpha": _uniform(0.05, 0.15),
    "bias": lambda k, shape: jax.random.normal(k, shape, jnp.float32),
    "poly": lambda k, shape: jnp.concatenate(
        [jax.random.uniform(k, shape[:-1] + (3,), jnp.float32, 1 / 6, 1 / 2),
         jax.random.uniform(jax.random.fold_in(k, 1), shape[:-1] + (1,),
                            jnp.float32, -0.5, 0.5)], -1),
}


def init_motif(cfg, key, dtype=jnp.float32):
    """Seeded parameters in ONE jitted call: normal(0,
    ``initializer_range``) matrices in ``dtype``, norm gains 1, the float32
    leaves by `SPECIAL` (the last word of a leaf's name). (The benchmark's
    reference makes its own; this one is the tests'.)"""
    layers = [layer_shapes(cfg, l) for l in range(cfg.num_hidden_layers)]
    names = {k for lp in layers for k in lp if k.rsplit("_", 1)[-1]
             in SPECIAL}
    return M._init_tree(cfg, key, dtype, layers, special={
        k: SPECIAL[k.rsplit("_", 1)[-1]] for k in names})


# ---------------------------------------------------------------------------
# the residual path
# ---------------------------------------------------------------------------
def sinkhorn(m, iters):
    """Rows, then columns, normalised to sum 1, ``iters`` times over the
    last two axes of the positive ``m``."""
    for _ in range(iters):
        m = m / jnp.sum(m, -1, keepdims=True)
        m = m / jnp.sum(m, -2, keepdims=True)
    return m


def _mhc_maps(cfg, lp, x, sub):
    """The stream ``x`` ``[N, n, d]`` -> ``(H_pre [N, n], H_post [N, n],
    H_res [N, n, n])`` of sub-layer ``sub`` (``attn`` | ``ffn``), in the
    streams' dtype."""
    N, n, d = x.shape
    sd = x.dtype
    flat = x.reshape(N, n * d)
    flat = flat * lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                            + cfg.rms_norm_eps).astype(sd)
    proj = jnp.matmul(flat, lp["mhc_%s_phi" % sub].astype(sd),
                      precision=lax.Precision.HIGHEST)
    a = lp["mhc_%s_alpha" % sub].astype(sd)
    b = lp["mhc_%s_bias" % sub].astype(sd)
    pre = jax.nn.sigmoid(a[0] * proj[:, :n] + b[:n])
    post = 2 * jax.nn.sigmoid(a[1] * proj[:, n:2 * n] + b[n:2 * n])
    res = jnp.exp(a[2] * proj[:, 2 * n:] + b[2 * n:]).reshape(N, n, n)
    return pre, post, sinkhorn(res, cfg.mhc_sinkhorn_iters)


def _sublayer(cfg, lp, x, sub, norm, scope, fn):
    """One sub-layer on the streams: ``x <- H_res x + H_post fn(RMS(H_pre
    x; norm))`` (``fn`` under ``scope``), clamped."""
    with jax.named_scope("mhc"):
        pre, post, res = _mhc_maps(cfg, lp, x, sub)
        h = M._rms(jnp.einsum("ni,nid->nd", pre, x), lp[norm],
                   cfg.rms_norm_eps)
    with jax.named_scope(scope):
        f = fn(h)
    with jax.named_scope("mhc"):
        x = jnp.einsum("nij,njd->nid", res, x) \
            + post[..., None] * f.astype(x.dtype)[:, None, :]
        return jnp.clip(x, -cfg.hidden_clamp, cfg.hidden_clamp)


# ---------------------------------------------------------------------------
# pieces
# ---------------------------------------------------------------------------
def _poly_mlp(cfg, x, wg, wu, wd, coef):
    gate, up = M._mm(x, wg), M._mm(x, wu)
    h = polynorm(gate, coef, POLYNORM_EPS, cfg.polynorm_output_scale,
                 cfg.polynorm_bias_clamp) * up
    return M._mm(h, wd)


def _ffn(cfg, lp, h, valid, kernels):
    """The layer's feed-forward over normed ``h`` ``[N, d]``: the dense
    PolyNorm MLP, or shared expert + the held routed experts' part.
    ``(out, tally)`` as `moe_mla._ffn`'s."""
    if "w_gate" in lp:
        with jax.named_scope("mlp"):
            return _poly_mlp(cfg, h, lp["w_gate"], lp["w_up"], lp["w_down"],
                             lp["mlp_poly"]), None
    hp = h.astype(lp["experts_gate"].dtype)
    with jax.named_scope("moe.shared"):
        shared = _poly_mlp(cfg, hp, lp["shared_gate"], lp["shared_up"],
                           lp["shared_down"], lp["shared_poly"])
    routed, counts, cost = routed_experts(
        lp, hp, held=cfg.experts_held, top_k=cfg.experts_top_k,
        scale=cfg.route_scale, valid=valid, use_pallas=kernels[0],
        interpret=kernels[1], activation=cfg.activation)
    return shared + routed, (counts, cost)


def _per_head(cfg, wkv_b):
    """``wkv_b`` ``[rkv, G (dn + dv)]`` a group -> ``[rkv, H (dn + dv)]`` a
    head: each head reads its group's columns (what `moe_mla`'s expansion
    takes)."""
    G, per = cfg.groups, cfg.signal_heads + 1
    w = wkv_b.reshape(wkv_b.shape[0], G, 1, -1)
    w = jnp.broadcast_to(w, (w.shape[0], G, per, w.shape[-1]))
    return w.reshape(wkv_b.shape[0], -1)


def _gates(cfg, lp, h):
    """``(lambda [N, G, S], gate [N, G S dv])``, float32, of the normed
    input ``h``."""
    lam = jax.nn.sigmoid(M._mm(h, lp["w_lambda"]))
    return (lam.reshape(h.shape[0], cfg.groups, cfg.signal_heads),
            jax.nn.sigmoid(M._mm(h, lp["wg_o"])))


def _differential(cfg, o, lam, axis=2):
    """Per-head results -> the signal heads' ``a_h - lambda_h a_noise(g)``,
    float32. ``o`` is ``[N, H, w]`` (taken as ``[N, G, S + 1, w]``) or
    already grouped with its ``S + 1`` heads on ``axis``; ``lam`` has the
    result's shape less ``w``."""
    S = cfg.signal_heads
    if o.ndim == 3:
        o = o.reshape(o.shape[0], cfg.groups, S + 1, -1)
    o = o.astype(jnp.float32)
    return lax.slice_in_dim(o, 0, S, axis=axis) \
        - lam[..., None] * lax.slice_in_dim(o, S, S + 1, axis=axis)


def _out(cfg, lp, o, gate):
    """Signal heads' outputs ``[N, G, S, dv]`` -> ``[N, d]``: the gate, then
    ``W_O``."""
    return M._mm(o.reshape(o.shape[0], -1) * gate, lp["wo"])


def _absorbed(cfg, lp, h, q_nope, q_rope, attend, heads_major=False):
    """The decode step's GDLA after the projections: queries moved into the
    latent space a group, ``attend(q_lat, q_rope) -> u``, signal and noise
    combined THERE in float32, then ``W_UV,g``, the gate and ``W_O``.

    The caller's ``heads_major`` chooses the form of ``q_lat`` and ``u``:
    rows-major ``[B, H, rkv]`` (the lax tier's walks), or heads-major ``[H,
    B, rkv]`` (the kernels'), head ``h = g (S + 1) + j`` in either;
    ``q_rope`` ``[B, H, dr]`` is handed on as it is. Heads-major is the form
    the grouped products emit and read: ``q_lat`` comes out of
    ``gsbn,rgn->gsbr`` and ``u`` goes into ``gsbr,rgv->bgsv`` with no
    relayout between them and the kernel. ``u`` may come back in any
    float dtype; the combination is float32."""
    G, S, dn, dv, rkv = (cfg.groups, cfg.signal_heads, cfg.qk_nope_head_dim,
                         cfg.v_head_dim, cfg.kv_lora_rank)
    B = q_nope.shape[0]
    dt = lp["wkv_b"].dtype
    w_kvb = lp["wkv_b"].reshape(rkv, G, dn + dv)
    w_k, w_v = w_kvb[..., :dn], w_kvb[..., dn:]
    q_nope = q_nope.astype(dt).reshape(B, G, S + 1, dn)
    lam, gate = _gates(cfg, lp, h)                        # lam [B, G, S]
    form, flat = "bgsr", (B, -1, rkv)
    if heads_major:
        # the queries turned BEFORE the product, which then emits
        # [G][S+1][B][r] as the kernels take it (turned after it, the
        # product emits B minor and XLA copies it)
        profiler.record_lowering("latent_heads_major")
        form, flat = "gsbr", (-1, B, rkv)
        q_nope = jnp.transpose(q_nope, (1, 2, 0, 3))
        lam = jnp.transpose(lam, (1, 2, 0))               # [G, S, B]
    q_lat = jnp.einsum(form[:3] + "n,rgn->" + form, q_nope, w_k,
                       preferred_element_type=jnp.float32)
    u = attend(q_lat.reshape(flat), q_rope).reshape(q_lat.shape)
    sig = _differential(cfg, u, lam, form.index("s"))
    o = jnp.einsum(form + ",rgv->bgsv", sig.astype(dt), w_v,
                   preferred_element_type=jnp.float32)
    return _out(cfg, lp, o, gate)


def _band_attention(cfg, lp, q, seen, start):
    """A prefill piece's window attention: queries ``q`` ``[C, H, dn + dr]``
    at positions ``start + i`` over ``seen`` ``[W + C, row]``, the latent
    rows of positions ``start - W .. start + C - 1`` in order (the ring's,
    then the piece's), query ``i`` seeing ``seen[i + 1 .. i + W]`` that lie
    at position 0 or later. Keys and values expanded a head, a query block
    of ``W`` against the ``2 W`` rows its band can reach: ``[C, H, dv]``."""
    W, H = cfg.sliding_window, cfg.num_attention_heads
    C = q.shape[0]
    dt = lp["wkv_b"].dtype
    k, v = M._mla_expand(cfg, seen,
                         *M._expansion_weights(cfg, _per_head(cfg,
                                                              lp["wkv_b"])))
    q = q.astype(dt).transpose(1, 0, 2)                     # [H, C, dqk]
    sm = 1.0 / _np.sqrt(cfg.head_dim)
    qb = min(C, W)
    outs = []
    for b in range(0, C, qb):
        s = jnp.einsum("hqd,htd->hqt", q[:, b:b + qb], k[:, b:b + qb + W],
                       preferred_element_type=jnp.float32) * sm
        i = b + jnp.arange(qb)[:, None]
        t = b + jnp.arange(qb + W)[None, :]
        live = (t > i) & (t <= i + W) & (start - W + t >= 0)
        s = jnp.where(live[None], s, paged.MASKED)
        p = jnp.exp(s - jnp.max(s, -1, keepdims=True))
        o = jnp.einsum("hqt,htv->hqv", p.astype(dt), v[:, b:b + qb + W],
                       preferred_element_type=jnp.float32)
        outs.append(o / jnp.sum(p, -1)[..., None])
    return jnp.concatenate(outs, 1).transpose(1, 0, 2)


def _full_prefill_attend(cfg, lp, h, pool, li, pos, valid, table, start,
                         spans, which, use_pallas, interpret):
    """A prefill piece's attention in full layer ``li``: the table's pages
    as the pool came in, the piece's own rows set over them in the gathered
    copy (the pool itself is written once, at the program's end), keys and
    values expanded a head over the span ``which`` names as
    `moe_mla._prefill_attend` does. ``(out [C, H, dv], the piece's pool
    rows [C, row])``."""
    C, H = h.shape[0], cfg.num_attention_heads
    q_nope, q_rope, rows = M._mla_project(cfg, lp, h, pos)
    rows = M._cache_rows(rows, pool)
    q = jnp.concatenate([q_nope, q_rope], -1)
    seen = paged.gather_pages(pool, li, table).reshape(-1, pool.shape[3])
    seen = seen.at[jnp.where(valid, pos, seen.shape[0])].set(rows,
                                                             mode="drop")
    wkv_b = _per_head(cfg, lp["wkv_b"])

    def over(span):
        return lambda q, seen: M._attend_expanded(
            cfg, wkv_b, q, seen[:span], start, use_pallas, interpret)
    out = lax.switch(which, [over(s) for s in spans], q, seen)
    return out.reshape(C, H, -1), rows


# ---------------------------------------------------------------------------
# the DecodeEngine seam
# ---------------------------------------------------------------------------
def _stream(cfg, params, tokens):
    x = params["embed"][tokens].astype(jnp.dtype(cfg.mhc_dtype))
    return jnp.broadcast_to(x[:, None, :], (x.shape[0],
                                            cfg.mhc_expansion_rate,
                                            x.shape[1]))


def _logits(cfg, params, x):
    """Streams summed, the final norm, the head."""
    return M._logits(cfg, params, jnp.sum(x.astype(jnp.float32), axis=1))


def _layer(cfg, lp, x, scope, attend, valid, kernels, tallies):
    """One layer on the streams: attention ``attend(h) -> [N, d]`` under
    ``scope``, then the feed-forward (its tally into ``tallies``)."""
    x = _sublayer(cfg, lp, x, "attn", "norm_attn_in", scope, attend)

    def ffn(h):
        out, tally = _ffn(cfg, lp, h, valid, kernels)
        if tally is not None:
            tallies.append(tally)
        return out
    return _sublayer(cfg, lp, x, "ffn", "norm_ffn_in", "ffn", ffn)


@jax.named_scope("decode.prefill")      # the trace's device-side name
def motif_decode_prefill(params, cfg, cache, tokens, start, length, table,
                         slot, *, use_pallas=False, interpret=False,
                         with_logits=False):
    """Bucketed batch-1 prefill piece of the sequence admitted to ``slot``.
    Full layers write and read the latent pool as
    `moe_mla.moe_mla_decode_prefill`'s do (keys and values expanded over
    the piece's live span); window layers attend ``[ring | piece]`` under a
    band mask (`_band_attention`) and leave the ring holding the last ``W``
    positions after the piece's last REAL token. The seam's ``(params,
    cache, tokens, start, length, table, slot) -> (next_id, cache, aux)``;
    ``with_logits`` (tests) appends that position's float32 logits."""
    pool, ring = cache["latent"], cache["window"]
    C = tokens.shape[0]
    W = cfg.sliding_window
    pos, valid, blk, at = paged.chunk_addresses(table, start, length, C,
                                                pool.shape[2])
    end = start + length
    spans = paged.chunk_spans(C, table.shape[0] * pool.shape[2])
    which = paged.span_index(spans, end)
    # row w of the ring after the piece: the last position p < end with
    # p % W == w, read from [ring in position order | piece] at p - start + W
    w = jnp.arange(W, dtype=jnp.int32)
    last = end - 1 - (end - 1 - w) % W
    x = _stream(cfg, params, tokens)
    tallies, rings, written = [], [], []
    kernels = (use_pallas, interpret)
    for l, lp in enumerate(params["layers"]):
        li = cfg.kind_index(l)
        with jax.named_scope("layer"):
            if cfg.is_window(l):
                def attend(h, lp=lp, li=li):
                    q_nope, q_rope, rows = M._mla_project(cfg, lp, h, pos)
                    q = jnp.concatenate([q_nope, q_rope], -1)
                    held = jnp.roll(ring[li, slot], -start, axis=0)
                    seen = jnp.concatenate([held, M._cache_rows(rows, ring)])
                    rings.append(jnp.take(seen, last - start + W, axis=0))
                    o = _band_attention(cfg, lp, q, seen, start)
                    lam, gate = _gates(cfg, lp, h)
                    return _out(cfg, lp, _differential(cfg, o, lam), gate)
                scope = "gdla.window"
            else:
                def attend(h, lp=lp, li=li):
                    o, rows = _full_prefill_attend(
                        cfg, lp, h, pool, li, pos, valid, table, start,
                        spans, which, use_pallas, interpret)
                    written.append(rows)
                    lam, gate = _gates(cfg, lp, h)
                    return _out(cfg, lp, _differential(cfg, o, lam), gate)
                scope = "gdla"
            x = _layer(cfg, lp, x, scope, attend, valid, kernels, tallies)
    x_last = jnp.take(x, jnp.clip(length - 1, 0, C - 1), axis=0)
    logits = _logits(cfg, params, x_last[None])[0]
    aux = M._aux(cfg, tallies, "prefill_")
    aux.update(_attention_counts(
        cfg, "prefill_", full=jnp.asarray(spans, jnp.int32)[which],
        window=W + C, context=end))
    aux["prefill_kv_live_tokens"] = jnp.asarray(end, jnp.int32)
    aux["prefill_kv_expanded_tokens"] = jnp.asarray(spans, jnp.int32)[which]
    # the pools are written after every layer has read them as they came
    # in (the rule `test_tpu_compile.py` holds; a write inside each layer
    # was rematerialised at the cell's size): each full layer's rows into
    # its pages, and every window layer's ring into the slot in ONE write
    for li, rows in enumerate(written):
        pool = pool.at[li, blk, at].set(rows)
    if rings:
        ring = ring.at[:, slot].set(jnp.stack(rings).astype(ring.dtype))
    cache = {"latent": pool, "window": ring}
    out = (jnp.argmax(logits).astype(jnp.int32), cache, aux)
    return out + (logits,) if with_logits else out


def _attention_counts(cfg, prefix, full, window, context):
    """The attention layers' counters: latent rows the full layers walked
    (``full`` one layer's), ring rows the window layers read (``window``
    one layer's) and context positions over all attention layers
    (``context`` one layer's)."""
    i32 = lambda v: jnp.asarray(v, jnp.int32)       # noqa: E731
    return {prefix + "gdla_full_rows": i32(full) * cfg.full_layers,
            prefix + "gdla_window_rows": i32(window) * cfg.window_layers,
            prefix + "gdla_context_positions":
                i32(context) * cfg.num_hidden_layers}


@jax.named_scope("decode.step")      # the trace's device-side name
def motif_decode_step(params, cfg, cache, token_ids, positions, tables,
                      active, *, use_pallas=False, interpret=False,
                      with_logits=False):
    """Fixed-shape batched decode step, one token per active row, GDLA
    absorbed into the latent space. Full layers walk the live latent rows
    (`moe_mla._latent_attend`: `paged_attention.paged_latent_attention` on
    the kernel tier); window layers attend their slot's ring
    (`paged_attention.window_latent_attention`, or its lax form). An
    inactive row writes to the null block and to no ring, is routed to no
    expert and counted nowhere. The seam's ``(params, cache, token_ids,
    positions, tables, active) -> (next_ids, cache, aux)``."""
    pool, ring = cache["latent"], cache["window"]
    bs = pool.shape[2]
    blk, at = paged.step_addresses(tables, positions, active, bs)
    walk, walked = M._step_walk(cfg, positions, tables, active, bs,
                                use_pallas, interpret)
    sm = float(1.0 / _np.sqrt(cfg.head_dim))
    kernel_tier = bool(use_pallas or interpret)
    x = _stream(cfg, params, token_ids)
    tallies = []
    kernels = (use_pallas, interpret)
    for l, lp in enumerate(params["layers"]):
        li = cfg.kind_index(l)
        with jax.named_scope("layer"):
            if cfg.is_window(l):
                def attend(h, lp=lp, li=li):
                    nonlocal ring
                    q_nope, q_rope, rows = M._mla_project(cfg, lp, h,
                                                          positions)
                    rows = M._cache_rows(rows, ring)

                    def latent(q_lat, q_rope):
                        nonlocal ring
                        if kernel_tier:
                            u, ring = paged.window_latent_attention(
                                q_lat, q_rope, rows, ring, li, positions,
                                active, sm_scale=sm, heads_major=True,
                                out_dtype=jnp.float32, interpret=interpret)
                        else:
                            u, ring = paged.window_latent_attention_lax(
                                M._pool_query(q_lat, q_rope, ring), rows,
                                ring, li, positions, active, sm_scale=sm,
                                width=cfg.kv_lora_rank)
                        return u
                    return _absorbed(cfg, lp, h, q_nope, q_rope, latent,
                                     kernel_tier)
                scope = "gdla.window"
            else:
                def attend(h, lp=lp, li=li):
                    nonlocal pool
                    q_nope, q_rope, rows = M._mla_project(cfg, lp, h,
                                                          positions)
                    rows = M._cache_rows(rows, pool)
                    if not isinstance(walk, paged.PagedRows):
                        pool = pool.at[li, blk, at].set(rows)

                    def latent(q_lat, q_rope):
                        nonlocal pool
                        if kernel_tier:
                            u, pool = paged.paged_latent_attention(
                                q_lat, q_rope, rows, pool, li, positions,
                                tables, active, sm_scale=sm,
                                heads_major=True, out_dtype=jnp.float32,
                                interpret=interpret)
                        else:
                            u, pool = M._latent_attend(cfg, q_lat, q_rope,
                                                       pool, li, walk, rows)
                        return u
                    return _absorbed(cfg, lp, h, q_nope, q_rope, latent,
                                     kernel_tier)
                scope = "gdla"
            x = _layer(cfg, lp, x, scope, attend, active, kernels, tallies)
    logits = _logits(cfg, params, x)
    aux = M._aux(cfg, tallies)
    rows = jnp.sum(active.astype(jnp.int32))
    live = jnp.sum(jnp.where(active, positions + 1, 0))
    aux.update(_attention_counts(cfg, "", full=walked,
                                 window=rows * cfg.sliding_window,
                                 context=live))
    # cached tokens the full layers attended over, the active rows together
    aux["kv_live_tokens"] = live
    aux["kv_walked_tokens"] = walked    # one full layer's walk
    cache = {"latent": pool, "window": ring}
    out = (jnp.argmax(logits, axis=-1).astype(jnp.int32), cache, aux)
    return out + (logits,) if with_logits else out


class MotifDecodeModel(DecodeModel):
    """Adapter: a `MotifConfig` wired for the DecodeEngine seam.

    >>> model = MotifDecodeModel(cfg, params=params)        # or seed=
    >>> eng = DecodeEngine(**model.engine_kwargs(), max_seq_len=6144, ...)

    ``flash`` picks the kernel tier of the prefill attention, the step's
    walk and ring kernel and the grouped experts
    (`DecodeModel.resolve_flash`). With a ``mesh`` every pool is stated
    replicated."""

    def __init__(self, cfg, params=None, seed=0, dtype=jnp.bfloat16,
                 flash=None, mesh=None):
        self.cfg = cfg
        if params is None:
            params = init_motif(cfg, jax.random.PRNGKey(seed), dtype)
        self.params = params
        self.cache_dtype = params["embed"].dtype
        self.mesh = mesh
        self.resolve_flash(flash)

    def cache_spec(self, num_blocks, block_size, slots):
        """``latent``: the paged pool of ``[c | k_rope]`` rows, a layer axis
        over the full layers only. ``window``: a per-slot ring of
        ``sliding_window`` such rows a window layer."""
        cfg = self.cfg
        sharding = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            sharding = NamedSharding(self.mesh, PartitionSpec())
        return {
            "latent": jax.ShapeDtypeStruct(
                (cfg.full_layers, num_blocks, block_size,
                 cfg.cache_row_width), self.cache_dtype, sharding=sharding),
            "window": SlotPool(
                (cfg.window_layers, slots, cfg.sliding_window,
                 cfg.cache_row_width), self.cache_dtype, sharding=sharding)}

    def prefill_fn(self, params, cache, tokens, start, length, table, slot):
        return motif_decode_prefill(
            params, self.cfg, cache, tokens, start, length, table, slot,
            use_pallas=self.use_pallas, interpret=self.interpret)

    def step_fn(self, params, cache, token_ids, positions, tables, active):
        return motif_decode_step(
            params, self.cfg, cache, token_ids, positions, tables, active,
            use_pallas=self.use_pallas, interpret=self.interpret)
