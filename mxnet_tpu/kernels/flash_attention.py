"""Flash attention: Pallas TPU kernel + jnp blockwise fallback.

The reference framework (MXNet 1.2) predates transformers and has no attention
op at all (SURVEY.md §5.7) — this is TPU-native new capability that the
long-context stack (ring attention, `mxnet_tpu/parallel/ring_attention.py`)
builds on.

Design:
- `attention_with_lse`: plain-jnp softmax attention that also returns the
  log-sum-exp per query row. The lse is what makes streaming/ring composition
  possible (merge partial results from different KV chunks exactly).
- `blockwise_attention`: lax.scan over KV blocks with online-softmax
  accumulation — compiler-friendly (static shapes, no data-dependent control
  flow) and memory-linear in sequence length. Differentiable by jax.grad.
- `flash_attention`: public entry. On TPU backends it runs a Pallas kernel
  (fused QK^T -> online softmax -> PV in VMEM, grid over (batch*heads,
  q blocks)) wrapped in `jax.custom_vjp`; the backward pass recomputes
  attention blockwise from the saved lse (standard FlashAttention-2 recompute
  strategy). On CPU it falls back to the blockwise jnp path so tests and the
  driver's virtual-device runs behave identically.

Shapes follow [batch, heads, seq, head_dim] throughout.
"""
from __future__ import annotations

import functools

import numpy as _np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "blockwise_attention", "attention_with_lse",
           "default_use_pallas", "pallas_status"]


def default_use_pallas():
    """Single policy for kernel selection: the compiled Pallas kernels run
    where the default backend's platform is "tpu", and nowhere else. A
    backend that fails to come up raises here — it is never read as "use
    the lax path"."""
    return jax.devices()[0].platform == "tpu"


def pallas_status():
    """(use_pallas, reason) — WHY the kernel gate is open or closed, for
    bench/observability (`flash_attn_pallas_reason`): "tpu" (compiled
    Mosaic kernels run) or "no-tpu" (CPU/GPU backend: the jnp blockwise
    path serves; the kernels themselves only run interpret-mode, as in
    CI)."""
    platform = jax.devices()[0].platform
    if platform == "tpu":
        return True, "tpu"
    return False, ("no-tpu (platform=%s; Pallas kernels run "
                   "interpret-mode only off-TPU)" % platform)


_NEG_INF = -1e30


def _fold_scale(q, sm_scale):
    """q * sm_scale rounded back to q's dtype — ONE [block_q, d] multiply
    per program instead of a [block_q, block_k] multiply per KV iteration.
    All four kernels (fwd and bwd, plain and offset) must fold identically:
    the bwd recomputes p = exp(s - lse) from the fwd-computed lse, and the
    two stay bit-consistent only if s is produced from the same rounded q."""
    return (q.astype(jnp.float32) * sm_scale).astype(q.dtype)


def _mxu_qk(a, b):
    """[m, d] x [n, d] -> [m, n] contracting d WITHOUT materializing b.T —
    Mosaic feeds the MXU the transposed operand directly; an explicit
    `.T` costs a VMEM relayout first."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _mxu_tn(a, b):
    """[m, n] x [m, d] -> [n, d] contracting m (a.T @ b without the .T)."""
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _grid_parallel():
    """Both grid axes of every flash kernel write disjoint output blocks —
    tell Mosaic so it can pipeline/parallelize instead of assuming a
    sequential grid."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"))


def _causal_mask(q_len, k_len, q_offset, k_offset, dtype=jnp.float32):
    """Additive causal mask for a q block at global offset vs k block."""
    q_pos = q_offset + jnp.arange(q_len)[:, None]
    k_pos = k_offset + jnp.arange(k_len)[None, :]
    return jnp.where(q_pos >= k_pos, 0.0, _NEG_INF).astype(dtype)


def attention_with_lse(q, k, v, *, causal=False, sm_scale=None,
                       q_offset=0, k_offset=0, bias=None):
    """Softmax attention returning (out, lse).

    q: [..., Sq, D], k/v: [..., Sk, D]. `lse[..., Sq]` is logsumexp of the
    scaled (and masked) logits over the key axis — the quantity needed to
    merge partial attention over disjoint KV chunks (ring attention).
    """
    if sm_scale is None:
        sm_scale = 1.0 / _np.sqrt(q.shape[-1])
    logits = jnp.einsum("...qd,...kd->...qk", q, k) * sm_scale
    if bias is not None:
        logits = logits + bias
    if causal:
        logits = logits + _causal_mask(q.shape[-2], k.shape[-2],
                                       q_offset, k_offset, logits.dtype)
    lse = jax.nn.logsumexp(logits, axis=-1)
    weights = jnp.exp(logits - lse[..., None])
    # fully-masked rows (ring steps ahead of the causal frontier): all logits
    # are _NEG_INF so lse ~ _NEG_INF + log(Sk); zero the output and pin lse to
    # _NEG_INF so merge_attention gives such chunks no weight
    masked_out = lse > _NEG_INF / 2
    weights = jnp.where(masked_out[..., None], weights, 0.0)
    lse = jnp.where(masked_out, lse, _NEG_INF)
    out = jnp.einsum("...qk,...kd->...qd", weights, v)
    return out, lse


def merge_attention(out_a, lse_a, out_b, lse_b):
    """Exactly combine two partial attentions over disjoint key sets."""
    m = jnp.maximum(lse_a, lse_b)
    m = jnp.where(m > _NEG_INF / 2, m, 0.0)  # both chunks fully masked: avoid nan
    wa = jnp.exp(lse_a - m)
    wb = jnp.exp(lse_b - m)
    s = wa + wb
    denom = jnp.where(s == 0.0, 1.0, s)
    out = (out_a * wa[..., None] + out_b * wb[..., None]) / denom[..., None]
    # guarded log: s == 0 (both fully masked) stays at _NEG_INF without the
    # log(0) -> -inf that poisons gradients (0 * inf = nan in the vjp)
    lse = jnp.where(s > 0.0, m + jnp.log(denom), _NEG_INF)
    return out, lse


def blockwise_attention(q, k, v, *, causal=False, sm_scale=None,
                        block_k=256, q_offset=0, k_offset=0):
    """Memory-linear attention: lax.scan over KV blocks w/ online softmax.

    Equivalent to full attention; peak memory O(Sq * block_k) instead of
    O(Sq * Sk). Differentiable via jax.grad (scan transposes cleanly).
    """
    if sm_scale is None:
        sm_scale = 1.0 / _np.sqrt(q.shape[-1])
    sk = k.shape[-2]
    block_k = min(block_k, sk)
    if sk % block_k != 0:  # fall back to one block if not divisible
        block_k = sk
    nblk = sk // block_k
    # [nblk, ..., block_k, D]
    ksplit = jnp.moveaxis(
        k.reshape(k.shape[:-2] + (nblk, block_k, k.shape[-1])), -3, 0)
    vsplit = jnp.moveaxis(
        v.reshape(v.shape[:-2] + (nblk, block_k, v.shape[-1])), -3, 0)

    sq = q.shape[-2]
    # zero that *depends on* q/k/v: keeps shard_map varying-axis (vma) types
    # of the scan carry consistent when this runs inside a manual region
    zdep = (q.sum() * 0 + k.sum() * 0 + v.sum() * 0).astype(jnp.float32)
    out0 = jnp.zeros(q.shape[:-1] + (v.shape[-1],), q.dtype) + zdep.astype(q.dtype)
    lse0 = jnp.full(q.shape[:-1], _NEG_INF, jnp.float32) + zdep

    def body(carry, blk):
        out, lse, idx = carry
        kb, vb = blk
        ob, lb = attention_with_lse(
            q, kb, vb, causal=causal, sm_scale=sm_scale,
            q_offset=q_offset, k_offset=k_offset + idx * block_k)
        out, lse = merge_attention(out, lse, ob, lb)
        return (out, lse, idx + 1), None

    (out, lse, _), _ = lax.scan(body, (out0, lse0, jnp.int32(0)),
                                (ksplit, vsplit))
    del sq
    return out, lse


# ---------------------------------------------------------------------------
# Pallas TPU kernel (forward) — FlashAttention-2 layout
# ---------------------------------------------------------------------------


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                      sm_scale, causal, block_k, kv_len):
    """One (batch*head, q-block) program: stream KV blocks through VMEM.

    Matmuls run in the input dtype (bf16 inputs -> full-rate MXU passes)
    with fp32 accumulation; softmax statistics are fp32 throughout.

    VPU-load design (the softmax/elementwise work between MXU passes is
    what bounds this kernel, not the matmuls): sm_scale is folded into q
    once per program instead of a [block_q, block_k] multiply per KV
    iteration, and the causal loop is SPLIT into an unmasked prefix (no
    iotas/compare/select at all) plus the few boundary blocks that
    actually straddle the diagonal.
    """
    q = q_ref[0]  # [block_q, d], input dtype
    block_q, d = q.shape
    qi = pl.program_id(1)
    q_off = qi * block_q
    qs = _fold_scale(q, sm_scale)

    nblk = kv_len // block_k

    def body(i, carry, masked):
        acc, m_i, l_i = carry
        k_blk = k_ref[0, pl.ds(i * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(i * block_k, block_k), :]
        s = _mxu_qk(qs, k_blk)
        if masked:
            q_pos = q_off + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = i * block_k + lax.broadcasted_iota(jnp.int32,
                                                       (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_i - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l_i * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[:, None] + jnp.dot(p.astype(v_blk.dtype), v_blk,
                                             preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    if causal:
        # blocks < full_hi lie entirely below the diagonal (no masking);
        # blocks in [full_hi, hi) straddle it; blocks >= hi are dead
        full_hi = jnp.minimum(lax.div(q_off, block_k), nblk)
        hi = jnp.minimum(lax.div(q_off + block_q + block_k - 1, block_k),
                         nblk)
        carry = lax.fori_loop(0, full_hi,
                              functools.partial(body, masked=False),
                              (acc0, m0, l0))
        acc, m_i, l_i = lax.fori_loop(full_hi, hi,
                                      functools.partial(body, masked=True),
                                      carry)
    else:
        acc, m_i, l_i = lax.fori_loop(0, nblk,
                                      functools.partial(body, masked=False),
                                      (acc0, m0, l0))
    l_safe = jnp.where(l_i == 0.0, 1.0, l_i)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    # lse ref carries a trailing lane dim of 1: TPU block shapes must be
    # (8,128)-tileable or match the array dims in the last two axes
    lse_ref[0] = (m_i + jnp.log(l_safe))[:, None]


# ---------------------------------------------------------------------------
# offset-aware forward kernel (ring attention): q/k global offsets arrive as
# scalar-prefetch values, output includes the lse so ring steps can merge
# ---------------------------------------------------------------------------


def _flash_fwd_offs_kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                           sm_scale, causal, block_k, kv_len):
    q = q_ref[0]  # [block_q, d], input dtype (matmuls accumulate in fp32)
    block_q, d = q.shape
    qi = pl.program_id(1)
    q_off = offs_ref[0] + qi * block_q   # global query offset
    k_base = offs_ref[1]                 # global key offset
    nblk = kv_len // block_k
    qs = _fold_scale(q, sm_scale)

    def body(i, carry, masked):
        acc, m_i, l_i = carry
        k_blk = k_ref[0, pl.ds(i * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(i * block_k, block_k), :]
        s = _mxu_qk(qs, k_blk)
        if masked:
            q_pos = q_off + lax.broadcasted_iota(jnp.int32,
                                                 (block_q, block_k), 0)
            k_pos = k_base + i * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=-1))
        # rows with every key masked keep m == -inf; substituting a per-row
        # SAFE maximum makes exp underflow to exact 0 for them (and for
        # masked entries), replacing two full-tile where()s with one
        # per-row select
        m_safe = jnp.where(m_new > _NEG_INF / 2, m_new, 0.0)
        p = jnp.exp(s - m_safe[:, None])
        alpha = jnp.exp(m_i - m_safe)
        l_new = l_i * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[:, None] + jnp.dot(
            p.astype(v_blk.dtype), v_blk, preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    if causal:
        # ring chunks put this q shard at a dynamic global offset: blocks
        # fully below the diagonal need no mask, blocks fully above it
        # (ahead of the causal frontier) contribute nothing and are
        # skipped outright
        full_hi = jnp.clip(lax.div(q_off - k_base + 1, block_k), 0, nblk)
        hi = jnp.clip(lax.div(q_off + block_q - k_base + block_k - 1,
                              block_k), full_hi, nblk)
        carry = lax.fori_loop(0, full_hi,
                              functools.partial(body, masked=False),
                              (acc0, m0, l0))
        acc, m_i, l_i = lax.fori_loop(full_hi, hi,
                                      functools.partial(body, masked=True),
                                      carry)
    else:
        acc, m_i, l_i = lax.fori_loop(0, nblk,
                                      functools.partial(body, masked=False),
                                      (acc0, m0, l0))
    l_safe = jnp.where(l_i == 0.0, 1.0, l_i)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[0] = jnp.where(l_i > 0.0, m_i + jnp.log(l_safe),
                           _NEG_INF)[:, None]


def _flash_fwd_offs_pallas(q, k, v, offs, sm_scale, causal, block_q, block_k,
                           interpret=False):
    """(out, lse) with dynamic global offsets; offs = int32[2]."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError("block sizes must divide the seq lengths")
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    kernel = functools.partial(_flash_fwd_offs_kernel, sm_scale=sm_scale,
                               causal=causal, block_k=block_k, kv_len=sk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * h, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, offs: (i, j, 0)),
            pl.BlockSpec((1, sk, d), lambda i, j, offs: (i, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda i, j, offs: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, offs: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j, offs: (i, j, 0)),
        ],
    )
    # inside shard_map, outputs inherit the inputs' varying-mesh-axes type
    vma = jax.typeof(q).vma
    out_shapes = [
        jax.ShapeDtypeStruct((b * h, sq, d), q.dtype, vma=vma),
        jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32, vma=vma),
    ]
    out, lse = pl.pallas_call(
        kernel,
        name="mx_flash_fwd_offs",
        grid_spec=grid_spec,
        out_shape=out_shapes,
        compiler_params=None if interpret else _grid_parallel(),
        interpret=interpret,
    )(offs.astype(jnp.int32), qf, kf, vf)
    return out.reshape(b, h, sq, d), lse.reshape(b, h, sq)


# --- offset-aware backward kernels (ring inner step) -----------------------
# Ring chunks can be FULLY masked (lse pinned to _NEG_INF), so p must be
# guarded against exp(-inf - -inf) = 1; and the lse output feeds
# merge_attention, so its cotangent is real: d lse_i/d s_ij = p_ij folds
# into the per-row scalar as delta_eff = delta - dlse.


def _flash_bwd_dq_offs_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref,
                              lse_ref, deff_ref, dq_ref, *, sm_scale,
                              causal, block_k, kv_len):
    q = q_ref[0]
    lse = lse_ref[0][:, 0]
    deff = deff_ref[0][:, 0]
    block_q, d = q.shape
    qi = pl.program_id(1)
    q_off = offs_ref[0] + qi * block_q
    k_base = offs_ref[1]
    nblk = kv_len // block_k
    qs = _fold_scale(q, sm_scale)
    do = do_ref[0].astype(v_ref.dtype)  # cast once, not per KV iteration
    # fully-masked ring rows carry lse == -inf; a +BIG substitute makes
    # exp(s - lse_safe) underflow to exact 0 for them, so no per-element
    # guard is needed (masked entries have s == -inf and underflow too)
    lse_safe = jnp.where(lse > _NEG_INF / 2, lse, -_NEG_INF)

    def body(i, dq, masked):
        k_blk = k_ref[0, pl.ds(i * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(i * block_k, block_k), :]
        s = _mxu_qk(qs, k_blk)
        if masked:
            q_pos = q_off + lax.broadcasted_iota(jnp.int32,
                                                 (block_q, block_k), 0)
            k_pos = k_base + i * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse_safe[:, None])
        dp = _mxu_qk(do, v_blk)
        ds = p * (dp - deff[:, None])   # sm_scale folded in after the loop
        return dq + jnp.dot(ds.astype(k_blk.dtype), k_blk,
                            preferred_element_type=jnp.float32)

    dq0 = jnp.zeros((block_q, d), jnp.float32)
    if causal:
        # blocks below the diagonal need no mask; blocks entirely past
        # the causal frontier contribute nothing
        full_hi = jnp.clip(lax.div(q_off - k_base + 1, block_k), 0, nblk)
        hi = jnp.clip(lax.div(q_off + block_q - k_base + block_k - 1,
                              block_k), full_hi, nblk)
        dq = lax.fori_loop(0, full_hi,
                           functools.partial(body, masked=False), dq0)
        dq = lax.fori_loop(full_hi, hi,
                           functools.partial(body, masked=True), dq)
    else:
        dq = lax.fori_loop(0, nblk,
                           functools.partial(body, masked=False), dq0)
    dq_ref[0] = (dq * sm_scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_offs_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref,
                               lse_ref, deff_ref, dk_ref, dv_ref, *,
                               sm_scale, causal, block_q, q_len):
    k = k_ref[0]
    v = v_ref[0]
    block_k, d = k.shape
    ki = pl.program_id(1)
    k_off = offs_ref[1] + ki * block_k
    q_base = offs_ref[0]
    nblk = q_len // block_q

    def body(i, carry, masked):
        dk, dv = carry
        # q pre-scaled by sm_scale: s comes out scaled, AND accumulating
        # dk against the scaled q folds the ds * sm_scale multiply away
        # (dk = sm_scale * sum ds'^T q  ==  sum ds'^T (q * sm_scale))
        q_blk = q_ref[0, pl.ds(i * block_q, block_q), :]
        qs_blk = _fold_scale(q_blk, sm_scale)
        do_blk = do_ref[0, pl.ds(i * block_q, block_q), :]
        lse_blk = lse_ref[0, pl.ds(i * block_q, block_q), 0]
        deff_blk = deff_ref[0, pl.ds(i * block_q, block_q), 0]
        s = _mxu_qk(qs_blk, k)
        if masked:
            q_pos = q_base + i * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_off + lax.broadcasted_iota(jnp.int32,
                                                 (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        # per-row safe lse (see dq kernel): exp underflows to exact 0 for
        # masked entries and for fully-masked ring rows — no tile-wide guard
        lse_safe = jnp.where(lse_blk > _NEG_INF / 2, lse_blk, -_NEG_INF)
        p = jnp.exp(s - lse_safe[:, None])
        dv = dv + _mxu_tn(p.astype(do_blk.dtype), do_blk)
        dp = _mxu_qk(do_blk.astype(v.dtype), v)
        ds = p * (dp - deff_blk[:, None])
        dk = dk + _mxu_tn(ds.astype(qs_blk.dtype), qs_blk)
        return dk, dv

    zeros = (jnp.zeros((block_k, d), jnp.float32),
             jnp.zeros((block_k, d), jnp.float32))
    if causal:
        # q blocks entirely before this kv block never attend to it;
        # blocks entirely past the diagonal need no mask
        lo = jnp.clip(lax.div(k_off - q_base, block_q), 0, nblk)
        mask_end = jnp.clip(lax.div(k_off + block_k - q_base + block_q - 1,
                                    block_q), lo, nblk)
        carry = lax.fori_loop(lo, mask_end,
                              functools.partial(body, masked=True), zeros)
        dk, dv = lax.fori_loop(mask_end, nblk,
                               functools.partial(body, masked=False), carry)
    else:
        dk, dv = lax.fori_loop(0, nblk,
                               functools.partial(body, masked=False), zeros)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd_staging(q, k, v, do, dlse, out, lse):
    """Flatten (b, h) and fold the lse cotangent into the per-row scalar
    delta_eff = delta - dlse (see note above). ONE definition shared by
    the streaming and grid backends: the deff contract is what keeps the
    two variants' gradients interchangeable."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    dof = do.reshape(b * h, sq, d)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    deff = (delta - dlse.astype(jnp.float32)).reshape(b * h, sq, 1)
    lsef = lse.reshape(b * h, sq, 1)
    return qf, kf, vf, dof, lsef, deff


def _flash_bwd_offs_pallas(q, k, v, offs, do, dlse, out, lse, sm_scale,
                           causal, block_q, block_k, interpret=False):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    qf, kf, vf, dof, lsef, deff = _bwd_staging(q, k, v, do, dlse, out, lse)
    offs = offs.astype(jnp.int32)

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_offs_kernel, sm_scale=sm_scale,
                          causal=causal, block_k=block_k, kv_len=sk),
        name="mx_flash_bwd_dq_offs",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * h, sq // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda i, j, o: (i, j, 0)),
                pl.BlockSpec((1, sk, d), lambda i, j, o: (i, 0, 0)),
                pl.BlockSpec((1, sk, d), lambda i, j, o: (i, 0, 0)),
                pl.BlockSpec((1, block_q, d), lambda i, j, o: (i, j, 0)),
                pl.BlockSpec((1, block_q, 1), lambda i, j, o: (i, j, 0)),
                pl.BlockSpec((1, block_q, 1), lambda i, j, o: (i, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, block_q, d),
                                   lambda i, j, o: (i, j, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        compiler_params=None if interpret else _grid_parallel(),
        interpret=interpret,
    )(offs, qf, kf, vf, dof, lsef, deff)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_offs_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=block_q, q_len=sq),
        name="mx_flash_bwd_dkv_offs",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * h, sk // block_k),
            in_specs=[
                pl.BlockSpec((1, sq, d), lambda i, j, o: (i, 0, 0)),
                pl.BlockSpec((1, block_k, d), lambda i, j, o: (i, j, 0)),
                pl.BlockSpec((1, block_k, d), lambda i, j, o: (i, j, 0)),
                pl.BlockSpec((1, sq, d), lambda i, j, o: (i, 0, 0)),
                pl.BlockSpec((1, sq, 1), lambda i, j, o: (i, 0, 0)),
                pl.BlockSpec((1, sq, 1), lambda i, j, o: (i, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_k, d), lambda i, j, o: (i, j, 0)),
                pl.BlockSpec((1, block_k, d), lambda i, j, o: (i, j, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, sk, d), v.dtype),
        ],
        compiler_params=None if interpret else _grid_parallel(),
        interpret=interpret,
    )(offs, qf, kf, vf, dof, lsef, deff)
    return (dq.reshape(b, h, sq, d), dk.reshape(b, h, sk, d),
            dv.reshape(b, h, sk, d))


# --- grid-variant offset forward (ring inner step): the grid fwd kernel
# with dynamic global offsets from scalar prefetch, plus the pinned-lse
# convention for fully-masked rows that merge_attention depends on.


def _flash_fwd_offs_grid_kernel(offs_ref, q_ref, k_ref, v_ref, o_ref,
                                lse_ref, acc_ref, m_ref, l_ref, *,
                                sm_scale, causal, block_q, block_k):
    j = pl.program_id(1)
    kb = pl.program_id(2)
    n_kb = pl.num_programs(2)
    q_off = offs_ref[0] + j * block_q
    k_off = offs_ref[1] + kb * block_k

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def tile(masked):
        s = _mxu_qk(_fold_scale(q_ref[0], sm_scale), k_ref[0])
        if masked:
            q_pos = q_off + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_off + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # per-row safe max: exp underflows to exact 0 for masked entries
        # and fully-masked ring rows (see the streaming offs kernel)
        m_safe = jnp.where(m_new > _NEG_INF / 2, m_new, 0.0)
        alpha = jnp.exp(m_prev - m_safe)
        p = jnp.exp(s - m_safe[:, :1])
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = (acc_ref[...] * alpha[:, :1]
                        + jnp.dot(p.astype(v_ref.dtype), v_ref[0],
                                  preferred_element_type=jnp.float32))

    if causal:
        is_dead = k_off > q_off + block_q - 1
        is_full = k_off + block_k - 1 <= q_off

        @pl.when(jnp.logical_not(is_dead) & is_full)
        def _full():
            tile(masked=False)

        @pl.when(jnp.logical_not(is_dead) & jnp.logical_not(is_full))
        def _boundary():
            tile(masked=True)
    else:
        tile(masked=False)

    @pl.when(kb == n_kb - 1)
    def _flush():
        l_col = l_ref[:, :1]
        l_safe = jnp.where(l_col == 0.0, 1.0, l_col)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = jnp.where(l_col > 0.0,
                               m_ref[:, :1] + jnp.log(l_safe), _NEG_INF)


def _flash_fwd_offs_grid_pallas(q, k, v, offs, sm_scale, causal, block_q,
                                block_k, interpret=False):
    """(out, lse) with dynamic global offsets — grid variant."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError("block sizes must divide the seq lengths")
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    n_qb, n_kb = sq // block_q, sk // block_k
    if causal:
        def kv_ix(i, j, kb, o):
            last_live = lax.div(o[0] + j * block_q + block_q - 1 - o[1],
                                block_k)
            return (i, jnp.minimum(kb, jnp.clip(last_live, 0, n_kb - 1)), 0)
    else:
        def kv_ix(i, j, kb, o):
            return (i, kb, 0)
    vma = jax.typeof(q).vma
    out_shapes = [
        jax.ShapeDtypeStruct((b * h, sq, d), q.dtype, vma=vma),
        jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32, vma=vma),
    ]
    out, lse = pl.pallas_call(
        functools.partial(_flash_fwd_offs_grid_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=block_q, block_k=block_k),
        name="mx_flash_fwd_offs_grid",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * h, n_qb, n_kb),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda i, j, kb, o: (i, j, 0)),
                pl.BlockSpec((1, block_k, d), kv_ix),
                pl.BlockSpec((1, block_k, d), kv_ix),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, d), lambda i, j, kb, o: (i, j, 0)),
                pl.BlockSpec((1, block_q, 1), lambda i, j, kb, o: (i, j, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
            ],
        ),
        out_shape=out_shapes,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(offs.astype(jnp.int32), qf, kf, vf)
    return out.reshape(b, h, sq, d), lse.reshape(b, h, sq)


# --- grid-variant backward: the arbitrary grid dimension replaces the
# in-kernel fori_loop, with dq (resp. dk/dv) accumulating in VMEM scratch.
# Same O(block) VMEM story as the grid forward — K/V (resp. Q/do) no
# longer stage whole-sequence blocks per program, so single-chip training
# scales to sequences the streaming backward cannot hold.


def _flash_bwd_dq_grid_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref,
                              lse_ref, deff_ref, dq_ref, dq_acc, *,
                              sm_scale, causal, block_q, block_k):
    j = pl.program_id(1)
    kb = pl.program_id(2)
    n_kb = pl.num_programs(2)
    q_off = offs_ref[0] + j * block_q
    k_off = offs_ref[1] + kb * block_k

    @pl.when(kb == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def tile(masked):
        qs = _fold_scale(q_ref[0], sm_scale)
        lse = lse_ref[0][:, 0]
        deff = deff_ref[0][:, 0]
        lse_safe = jnp.where(lse > _NEG_INF / 2, lse, -_NEG_INF)
        s = _mxu_qk(qs, k_ref[0])
        if masked:
            q_pos = q_off + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_off + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse_safe[:, None])
        dp = _mxu_qk(do_ref[0].astype(v_ref.dtype), v_ref[0])
        ds = p * (dp - deff[:, None])
        dq_acc[...] += jnp.dot(ds.astype(k_ref.dtype), k_ref[0],
                               preferred_element_type=jnp.float32)

    if causal:
        is_dead = k_off > q_off + block_q - 1
        is_full = k_off + block_k - 1 <= q_off

        @pl.when(jnp.logical_not(is_dead) & is_full)
        def _full():
            tile(masked=False)

        @pl.when(jnp.logical_not(is_dead) & jnp.logical_not(is_full))
        def _boundary():
            tile(masked=True)
    else:
        tile(masked=False)

    @pl.when(kb == n_kb - 1)
    def _flush():
        dq_ref[0] = (dq_acc[...] * sm_scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_grid_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref,
                               lse_ref, deff_ref, dk_ref, dv_ref,
                               dk_acc, dv_acc, *, sm_scale, causal,
                               block_q, block_k):
    kb = pl.program_id(1)
    qb = pl.program_id(2)
    n_qb = pl.num_programs(2)
    k_off = offs_ref[1] + kb * block_k
    q_off = offs_ref[0] + qb * block_q

    @pl.when(qb == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def tile(masked):
        qs_blk = _fold_scale(q_ref[0], sm_scale)
        lse = lse_ref[0][:, 0]
        deff = deff_ref[0][:, 0]
        lse_safe = jnp.where(lse > _NEG_INF / 2, lse, -_NEG_INF)
        s = _mxu_qk(qs_blk, k_ref[0])
        if masked:
            q_pos = q_off + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_off + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse_safe[:, None])
        do_blk = do_ref[0]
        dv_acc[...] += _mxu_tn(p.astype(do_blk.dtype), do_blk)
        dp = _mxu_qk(do_blk.astype(v_ref.dtype), v_ref[0])
        ds = p * (dp - deff[:, None])
        # dk against the pre-scaled q folds the sm_scale multiply away
        dk_acc[...] += _mxu_tn(ds.astype(qs_blk.dtype), qs_blk)

    if causal:
        is_dead = q_off + block_q - 1 < k_off
        is_full = q_off >= k_off + block_k - 1

        @pl.when(jnp.logical_not(is_dead) & is_full)
        def _full():
            tile(masked=False)

        @pl.when(jnp.logical_not(is_dead) & jnp.logical_not(is_full))
        def _boundary():
            tile(masked=True)
    else:
        tile(masked=False)

    @pl.when(qb == n_qb - 1)
    def _flush():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_offs_grid_pallas(q, k, v, offs, do, dlse, out, lse,
                                sm_scale, causal, block_q, block_k,
                                interpret=False):
    """Grid-variant backward (see _flash_bwd_offs_pallas for the math)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError("block sizes must divide the seq lengths")
    qf, kf, vf, dof, lsef, deff = _bwd_staging(q, k, v, do, dlse, out, lse)
    offs = offs.astype(jnp.int32)
    n_qb, n_kb = sq // block_q, sk // block_k

    def sem3():
        return (None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")))

    if causal:
        # clamp dead tiles' block index to the last/first LIVE one so the
        # index doesn't change across dead steps and Mosaic skips their
        # HBM copies (compute is skipped by pl.when in the kernel)
        def kv_ix(i, j, kb, o):
            last_live = lax.div(o[0] + j * block_q + block_q - 1 - o[1],
                                block_k)
            return (i, jnp.minimum(kb, jnp.clip(last_live, 0, n_kb - 1)), 0)

        def q_ix(i, kb, qb, o):
            first_live = lax.div(o[1] + kb * block_k - o[0], block_q)
            return (i, jnp.maximum(qb, jnp.clip(first_live, 0, n_qb - 1)),
                    0)
    else:
        def kv_ix(i, j, kb, o):
            return (i, kb, 0)

        def q_ix(i, kb, qb, o):
            return (i, qb, 0)

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_grid_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=block_q, block_k=block_k),
        name="mx_flash_bwd_dq_offs_grid",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * h, n_qb, n_kb),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda i, j, kb, o: (i, j, 0)),
                pl.BlockSpec((1, block_k, d), kv_ix),
                pl.BlockSpec((1, block_k, d), kv_ix),
                pl.BlockSpec((1, block_q, d), lambda i, j, kb, o: (i, j, 0)),
                pl.BlockSpec((1, block_q, 1), lambda i, j, kb, o: (i, j, 0)),
                pl.BlockSpec((1, block_q, 1), lambda i, j, kb, o: (i, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, block_q, d),
                                   lambda i, j, kb, o: (i, j, 0)),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        compiler_params=sem3(),
        interpret=interpret,
    )(offs, qf, kf, vf, dof, lsef, deff)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_grid_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=block_q, block_k=block_k),
        name="mx_flash_bwd_dkv_offs_grid",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * h, n_kb, n_qb),
            in_specs=[
                pl.BlockSpec((1, block_q, d), q_ix),
                pl.BlockSpec((1, block_k, d), lambda i, kb, qb, o: (i, kb, 0)),
                pl.BlockSpec((1, block_k, d), lambda i, kb, qb, o: (i, kb, 0)),
                pl.BlockSpec((1, block_q, d), q_ix),
                pl.BlockSpec((1, block_q, 1), q_ix),
                pl.BlockSpec((1, block_q, 1), q_ix),
            ],
            out_specs=[
                pl.BlockSpec((1, block_k, d), lambda i, kb, qb, o: (i, kb, 0)),
                pl.BlockSpec((1, block_k, d), lambda i, kb, qb, o: (i, kb, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, d), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, sk, d), v.dtype),
        ],
        compiler_params=sem3(),
        interpret=interpret,
    )(offs, qf, kf, vf, dof, lsef, deff)
    return (dq.reshape(b, h, sq, d), dk.reshape(b, h, sk, d),
            dv.reshape(b, h, sk, d))


def _bwd_dispatch(variant):
    return {"stream": _flash_bwd_offs_pallas,
            "grid": _flash_bwd_offs_grid_pallas}[variant]


def _fwd_offs_dispatch(variant):
    return {"stream": _flash_fwd_offs_pallas,
            "grid": _flash_fwd_offs_grid_pallas}[variant]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def flash_attention_with_lse(q, k, v, offs, sm_scale, causal, block_q,
                             block_k, interpret, variant="stream"):
    """Pallas fused (out, lse) attention with dynamic global offsets —
    the ring-attention inner step. Backward runs the offset-aware
    FlashAttention-2 Pallas kernels (lse cotangent included). `variant`
    selects both directions: "stream" (whole sequence in VMEM per
    program) or "grid" (blocks as an arbitrary grid dim, O(block) VMEM)."""
    return _fwd_offs_dispatch(variant)(q, k, v, offs, sm_scale, causal,
                                       block_q, block_k, interpret)


def _flash_lse_fwd_rule(q, k, v, offs, sm_scale, causal, block_q, block_k,
                        interpret, variant="stream"):
    out, lse = _fwd_offs_dispatch(variant)(q, k, v, offs, sm_scale, causal,
                                           block_q, block_k, interpret)
    return (out, lse), (q, k, v, offs, out, lse)


def _flash_lse_bwd_rule(sm_scale, causal, block_q, block_k, interpret,
                        variant, res, cts):
    q, k, v, offs, out, lse = res
    do, dlse = cts
    dq, dk, dv = _bwd_dispatch(variant)(q, k, v, offs, do, dlse, out, lse,
                                        sm_scale, causal, block_q, block_k,
                                        interpret)
    return dq, dk, dv, jnp.zeros_like(offs)


flash_attention_with_lse.defvjp(_flash_lse_fwd_rule, _flash_lse_bwd_rule)


def _flash_fwd_pallas(q, k, v, sm_scale, causal, block_q, block_k,
                      interpret=False):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError("block sizes must divide the seq lengths")
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    kernel = functools.partial(_flash_fwd_kernel, sm_scale=sm_scale,
                               causal=causal, block_k=block_k, kv_len=sk)
    out, lse = pl.pallas_call(
        kernel,
        name="mx_flash_fwd",
        grid=(b * h, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, sk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32),
        ],
        compiler_params=None if interpret else _grid_parallel(),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sq, d), lse.reshape(b, h, sq)


# ---------------------------------------------------------------------------
# Grid-variant forward: KV as a third ("arbitrary") grid dimension with
# VMEM scratch accumulators — the canonical TPU flash structure. Versus
# the streaming kernel above (whole K/V resident in VMEM, fori_loop over
# blocks) this keeps the FORWARD's VMEM at O(block_k) and hands the
# KV-block pipeline to Mosaic's grid-level double buffering. (The shared
# backward still stages full K/V per program, so the long-sequence VMEM
# ceiling moves only for inference until a grid backward exists; ring
# attention is the framework's answer for long-sequence training.)
# Which forward is faster is an empirical, shape-dependent question —
# tools/flash_tune.py sweeps both variants on-chip.
# ---------------------------------------------------------------------------


def _flash_fwd_grid_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                           acc_ref, m_ref, l_ref, *, sm_scale, causal,
                           block_q, block_k):
    """One (batch*head, q-block, kv-block) program.

    m/l scratch is [block_q, 128] with all lanes equal (lane-broadcast
    state avoids sublane-strided column writes); acc is [block_q, d]
    fp32. Output is flushed at the last KV step from scratch."""
    j = pl.program_id(1)
    kb = pl.program_id(2)
    n_kb = pl.num_programs(2)
    q_off = j * block_q
    k_off = kb * block_k

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def tile(masked):
        q = q_ref[0]
        s = _mxu_qk(_fold_scale(q, sm_scale), k_ref[0])
        if masked:
            q_pos = q_off + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_off + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_prev = m_ref[...]                         # [bq, 128], lanes equal
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)             # lanes equal
        p = jnp.exp(s - m_new[:, :1])
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = (acc_ref[...] * alpha[:, :1]
                        + jnp.dot(p.astype(v_ref.dtype), v_ref[0],
                                  preferred_element_type=jnp.float32))

    if causal:
        # dead tile (entirely past the diagonal): skip all compute;
        # boundary tile: masked; below-diagonal tile: mask-free
        is_dead = k_off > q_off + block_q - 1
        is_full = k_off + block_k - 1 <= q_off

        @pl.when(jnp.logical_not(is_dead) & is_full)
        def _full():
            tile(masked=False)

        @pl.when(jnp.logical_not(is_dead) & jnp.logical_not(is_full))
        def _boundary():
            tile(masked=True)
    else:
        tile(masked=False)

    @pl.when(kb == n_kb - 1)
    def _flush():
        l_col = l_ref[:, :1]
        l_safe = jnp.where(l_col == 0.0, 1.0, l_col)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:, :1] + jnp.log(l_safe)


def _flash_fwd_grid_pallas(q, k, v, sm_scale, causal, block_q, block_k,
                           interpret=False):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError("block sizes must divide the seq lengths")
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    kernel = functools.partial(_flash_fwd_grid_kernel, sm_scale=sm_scale,
                               causal=causal, block_q=block_q,
                               block_k=block_k)
    if interpret:
        params = None
    else:
        params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    if causal:
        # dead tiles (kb past the causal frontier of q block j) skip
        # compute via pl.when; clamping their KV index to the last LIVE
        # block means the block index doesn't change across dead steps,
        # so Mosaic skips their HBM->VMEM copies too (~2x KV traffic
        # saved at sq == sk)
        def kv_index(i, j, kb):
            last_live = (j * block_q + block_q - 1) // block_k
            return (i, jnp.minimum(kb, last_live), 0)
    else:
        def kv_index(i, j, kb):
            return (i, kb, 0)
    out, lse = pl.pallas_call(
        kernel,
        name="mx_flash_fwd_grid",
        grid=(b * h, sq // block_q, sk // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j, kb: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),     # acc
            pltpu.VMEM((block_q, 128), jnp.float32),   # m (lane-broadcast)
            pltpu.VMEM((block_q, 128), jnp.float32),   # l (lane-broadcast)
        ],
        compiler_params=params,
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sq, d), lse.reshape(b, h, sq)


# ---------------------------------------------------------------------------
# Pallas backward kernels (FlashAttention-2): dq gridded over q blocks,
# dk/dv gridded over kv blocks; both recompute P from the saved lse.
# ---------------------------------------------------------------------------


def _flash_bwd_pallas(q, k, v, do, out, lse, sm_scale, causal, block_q,
                      block_k, interpret=False, variant="stream"):
    """Backward for the non-offset path: the offset-aware kernels with
    offs = [0, 0] and no lse cotangent (one kernel pair per variant to
    maintain)."""
    offs = jnp.zeros((2,), jnp.int32)
    dlse = jnp.zeros(lse.shape, jnp.float32)
    return _bwd_dispatch(variant)(q, k, v, offs, do, dlse, out, lse,
                                  sm_scale, causal, block_q, block_k,
                                  interpret)


def _fwd_dispatch(variant):
    return {"stream": _flash_fwd_pallas,
            "grid": _flash_fwd_grid_pallas}[variant]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_attention_tpu(q, k, v, sm_scale, causal, block_q, block_k,
                         interpret, variant="stream"):
    out, _ = _fwd_dispatch(variant)(q, k, v, sm_scale, causal,
                                    block_q, block_k, interpret)
    return out


def _flash_fwd_rule(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                    variant="stream"):
    out, lse = _fwd_dispatch(variant)(q, k, v, sm_scale, causal,
                                      block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(sm_scale, causal, block_q, block_k, interpret,
                    variant, res, do):
    # Pallas FlashAttention-2 backward (dq kernel + dk/dv kernel), P
    # recomputed from the saved lse — no S materialization, no jnp
    # fallback graph. Both variants share the out/lse contract.
    q, k, v, out, lse = res
    return _flash_bwd_pallas(q, k, v, do, out, lse, sm_scale, causal,
                             block_q, block_k, interpret, variant)


_flash_attention_tpu.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, *, causal=False, sm_scale=None,
                    block_q=512, block_k=512, use_pallas=None,
                    interpret=False, variant="stream"):
    """Fused attention over [B, H, S, D] tensors.

    `use_pallas=None` auto-selects: the Pallas kernel on TPU backends,
    blockwise jnp elsewhere (identical numerics up to fp tolerance).
    `interpret=True` forces the Pallas kernel in interpret mode — the
    off-TPU kernel tier used by the mesh-parity suite and the multichip
    dryrun (same kernel body, executed op-by-op on the host backend).
    `variant` picks the Pallas kernels (fwd and bwd): "stream" (whole
    sequence resident in VMEM, fori_loop over blocks) or "grid" (blocks
    as an arbitrary grid dimension with scratch accumulators — O(block)
    VMEM, required for very long sequences).
    """
    if sm_scale is None:
        sm_scale = 1.0 / _np.sqrt(q.shape[-1])
    if use_pallas is None:
        use_pallas = default_use_pallas()
    run_kernel = use_pallas or interpret
    ok_shapes = (q.shape[2] % min(block_q, q.shape[2]) == 0
                 and k.shape[2] % min(block_k, k.shape[2]) == 0)
    if run_kernel and ok_shapes:
        return _flash_attention_tpu(q, k, v, sm_scale, causal,
                                    block_q, block_k, interpret, variant)
    out, _ = blockwise_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                                 block_k=block_k)
    return out
