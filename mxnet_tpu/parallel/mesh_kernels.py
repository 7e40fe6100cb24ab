"""Mesh dispatch for the Pallas kernel tier: shard_map islands that keep
the hand-written kernels load-bearing on multi-device meshes.

`pallas_call` is not auto-partitionable — GSPMD cannot split a kernel
invocation across devices, which is why the kernel tier historically fell
back to lax the moment a mesh had more than one device. But *inside* a
`shard_map` manual region there is nothing to partition: each device owns
a plain local block, and a pallas_call over that block is just another op
on one device. These wrappers put the two hot kernels behind exactly that
seam:

- `flash_attention_mesh` — flash attention with batch rows sharded over
  the dp axis and heads sharded over the tp axis. Every shard sees the
  full sequence, so causal masking and the online-softmax math are
  untouched; sharded-vs-unsharded is bitwise identical *within* a tier
  (kernel↔lax stays fp-tolerance, same as the single-device contract).
- `fused_update_mesh` — the fused optimizer update over transient
  (dp, chunk) param blocks: each dp replica updates its 1/dp chunk with
  `fused_update_step` (kernel tier engaging per eligible chunk) and
  all-gathers fresh params AND slots back to full shape. Unlike the ZeRO
  layout (`optim_update.apply_update_sharded`) the slots stay full-shaped
  outside the island, so this drops into the non-ZeRO fused path with no
  checkpoint-layout change. Bitwise identical to the replicated
  `fused_update_step` by construction (elementwise math on chunks of the
  same elements; the kernel and lax tiers already share one prologue).

Tier selection is centralized in `resolve_kernel_tier`, driven by the
`MXNET_TPU_MESH_KERNEL_TIER` env knob:

    auto       kernel tier on TPU backends, lax elsewhere  (default)
    1 / on     force the compiled kernel tier
    0 / off    force the lax tier
    interpret  Pallas interpret mode — the off-TPU kernel tier the
               parity suite and the multichip dryrun engage

The knob is read when a step/program is BUILT (trace time), never per
step.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as _np
from jax.sharding import PartitionSpec as P

from ..base import MXNetError
from ..kernels.flash_attention import (default_use_pallas, flash_attention,
                                       pallas_status)
from .collectives import shard_map

__all__ = ["resolve_kernel_tier", "kernel_tier_mode", "flash_attention_mesh",
           "fused_update_mesh", "flash_mesh_roofline",
           "optupdate_mesh_roofline", "flash_mesh_comm_plan",
           "optupdate_mesh_comm_plan"]

_ENV_TIER = "MXNET_TPU_MESH_KERNEL_TIER"

_tm = jax.tree_util.tree_map

# Chunk padding granularity for fused_update_mesh: 128 keeps every chunk
# lane-aligned so the (1, chunk) blocks stay eligible for the fused
# kernel's [rows, 128] layout. Waste is < dp*128 elements per leaf and
# the padding is transient (sliced off at regather).
_CHUNK_ALIGN = 128


def kernel_tier_mode():
    """Raw MXNET_TPU_MESH_KERNEL_TIER value (default 'auto')."""
    return os.environ.get(_ENV_TIER, "auto").strip().lower() or "auto"


def resolve_kernel_tier(mode=None):
    """-> (use_pallas, interpret) for kernel dispatch inside mesh islands.

    `mode=None` reads `MXNET_TPU_MESH_KERNEL_TIER`. Raises on unknown
    values — a typo'd tier knob silently falling back to lax is exactly
    the failure mode this module exists to kill.
    """
    if mode is None:
        mode = kernel_tier_mode()
    mode = str(mode).strip().lower()
    if mode in ("auto", ""):
        return bool(default_use_pallas()), False
    if mode in ("1", "on", "pallas", "kernel"):
        return True, False
    if mode in ("0", "off", "lax"):
        return False, False
    if mode == "interpret":
        return False, True
    raise MXNetError(
        "%s=%r not understood (auto | 1/on | 0/off | interpret)"
        % (_ENV_TIER, mode))


def _tier_requested(use_pallas, interpret):
    """Normalize the (use_pallas, interpret) pair like flash_attention:
    None means env-resolved auto."""
    if use_pallas is None and interpret is None:
        return resolve_kernel_tier()
    if use_pallas is None:
        use_pallas = default_use_pallas()
    return bool(use_pallas), bool(interpret or False)


def _mesh_axis_size(mesh, name):
    try:
        return int(mesh.shape[name]) if name in mesh.shape else 1
    except TypeError:
        return 1


# ---------------------------------------------------------------------------
# Flash attention on the mesh
# ---------------------------------------------------------------------------

def flash_attention_mesh(q, k, v, mesh, *, causal=False, sm_scale=None,
                         block_q=512, block_k=512, use_pallas=None,
                         interpret=None, variant="stream",
                         batch_axis="dp", head_axis="tp",
                         require_kernel=False):
    """Flash attention over [B, H, S, D] with a dp×tp shard_map island.

    Batch rows shard over `batch_axis`, heads over `head_axis`; axes the
    mesh doesn't have (or that don't divide B/H) are kept replicated.
    Each shard runs the SAME single-device `flash_attention` dispatch —
    kernel tier per (use_pallas, interpret), lax blockwise otherwise — so
    sharding never changes which tier runs or what bits it produces.

    `require_kernel=True` turns silent lax-fallback into a hard
    MXNetError: the CI engagement gate (multichip dryrun, decode smoke)
    uses it to prove the kernel tier is actually load-bearing on the
    mesh rather than quietly degrading.
    """
    if sm_scale is None:
        sm_scale = 1.0 / _np.sqrt(q.shape[-1])
    use_pallas, interpret = _tier_requested(use_pallas, interpret)
    run_kernel = use_pallas or interpret

    B, H, S, D = q.shape
    bq = batch_axis if (batch_axis in mesh.shape
                        and B % _mesh_axis_size(mesh, batch_axis) == 0
                        and _mesh_axis_size(mesh, batch_axis) > 1) else None
    hq = head_axis if (head_axis in mesh.shape
                       and H % _mesh_axis_size(mesh, head_axis) == 0
                       and _mesh_axis_size(mesh, head_axis) > 1) else None

    eff_bq = min(block_q, S)
    eff_bk = min(block_k, k.shape[2])
    ok_shapes = (S % eff_bq == 0 and k.shape[2] % eff_bk == 0)
    if require_kernel:
        if not run_kernel:
            ok, why = pallas_status()
            raise MXNetError(
                "mesh kernel tier required but not engaged: tier resolved "
                "to lax (%s; pallas_status=%s). Set "
                "MXNET_TPU_MESH_KERNEL_TIER=interpret for the off-TPU "
                "kernel tier." % (kernel_tier_mode(), why))
        if not ok_shapes:
            raise MXNetError(
                "mesh kernel tier required but shapes fall back to lax: "
                "S=%d %% block_q=%d or Sk=%d %% block_k=%d != 0"
                % (S, eff_bq, k.shape[2], eff_bk))

    def body(q, k, v):
        return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                               block_q=block_q, block_k=block_k,
                               use_pallas=use_pallas, interpret=interpret,
                               variant=variant)

    if bq is None and hq is None:
        # degenerate mesh (or indivisible shapes): no island needed
        return body(q, k, v)

    spec = P(bq, hq, None, None)
    fn = shard_map(body, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
                   check_vma=False)
    return fn(q, k, v)


def flash_mesh_roofline(q_shape, mesh, *, batch_axis="dp", head_axis="tp",
                        itemsize=4, causal=False):
    """Analytic HBM bytes for one flash fwd over [B,H,S,D], total and per
    mesh axis.

    Ideal bytes = read q,k,v + write out (the flash thesis: no S×S
    materialization). Per-axis entries give the bytes each shard moves
    when the island splits over that axis — the number the dryrun banks
    next to the ZeRO byte ratios so per-axis scaling is visible.
    """
    B, H, S, D = q_shape
    total = 4 * B * H * S * D * itemsize  # q,k,v in + out
    if causal:
        # causal halves the score work but not the qkv/out traffic
        pass
    per_axis = {}
    for name in (batch_axis, head_axis):
        n = _mesh_axis_size(mesh, name)
        if n > 1:
            per_axis[name] = {"size": n, "bytes_per_shard": total // n}
    both = max(1, _np.prod([v["size"] for v in per_axis.values()])
               if per_axis else 1)
    return {"ideal_bytes": int(total),
            "bytes_per_device": int(total // both),
            "per_axis": per_axis}


# ---------------------------------------------------------------------------
# Fused optimizer update on the mesh
# ---------------------------------------------------------------------------

def _chunk_size(n, dp):
    chunk = -(-n // dp)
    return -(-chunk // _CHUNK_ALIGN) * _CHUNK_ALIGN


def _chunkable(x):
    # float slots/params shard; adam's integer step counter (and sgd's
    # None momentum slot) ride replicated
    return (x is not None and getattr(x, "ndim", 0) >= 1
            and jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating))


def fused_update_mesh(optimizer, hp, params, opt_state, grads, mesh,
                      axis_name="dp", *, rescale=1.0, clip=None, wd=0.0,
                      use_pallas=None, interpret=None, cast_grads=None):
    """Fused optimizer update as a dp-sharded shard_map island, keeping
    full-shaped (non-ZeRO) params/slots outside the island.

    Per replica the body views every float leaf as a zero-padded
    (dp, chunk) block (chunk lane-aligned to _CHUNK_ALIGN=128 so eligible
    leaves keep the Pallas kernel), slices its own row, runs `fused_update_step` on
    the chunks — kernel tier per (use_pallas, interpret), fused-lax
    otherwise — and all-gathers fresh params AND slots back to full
    shape. The update math is elementwise per element, the padding
    updates to values that are sliced off, and the kernel/lax tiers
    share one prologue: the result is BITWISE identical to the
    replicated `fused_update_step` on every tier (the mesh-parity suite
    asserts it).

    Grads enter the island with spec P() — the partitioner materializes
    the same all-reduce the replicated step runs, in the same place, so
    the summed bits match by construction (the apply_update_sharded
    recipe). `cast_grads` applies the bf16→fp32 master cast to the
    chunks inside the island, mirroring the ZeRO path.
    """
    from ..kernels.opt_update import fused_update_step

    use_pallas, interpret = _tier_requested(use_pallas, interpret)
    dp = _mesh_axis_size(mesh, axis_name)
    if dp <= 1:
        if cast_grads is not None:
            grads = _tm(lambda g: g.astype(cast_grads), grads)
        return fused_update_step(optimizer, hp, params, opt_state, grads,
                                 rescale=rescale, clip=clip, wd=wd,
                                 use_pallas=use_pallas, interpret=interpret)

    hp_static = {k: v for k, v in hp.items() if k != "lr"}

    def body(params, opt_state, grads, lr):
        idx = jax.lax.axis_index(axis_name)

        def chunk_of(x):
            if not _chunkable(x):
                return x
            n = int(_np.prod(x.shape)) if x.ndim else 1
            chunk = _chunk_size(n, dp)
            flat = jnp.pad(x.reshape(-1), (0, dp * chunk - n))
            return jax.lax.dynamic_slice_in_dim(
                flat.reshape(dp, chunk), idx, 1, axis=0)

        p_sh = _tm(chunk_of, params)
        g_sh = _tm(chunk_of, grads)
        if cast_grads is not None:
            g_sh = _tm(lambda g: g.astype(cast_grads), g_sh)
        s_sh = _tm(chunk_of, opt_state)
        hp_l = dict(hp_static, lr=lr)
        new_p_sh, new_s_sh = fused_update_step(
            optimizer, hp_l, p_sh, s_sh, g_sh,
            rescale=rescale, clip=clip, wd=wd,
            use_pallas=use_pallas, interpret=interpret)

        def regather(chunk, ref):
            if not _chunkable(ref):
                return chunk
            n = int(_np.prod(ref.shape)) if ref.ndim else 1
            full = jax.lax.all_gather(
                chunk.reshape(chunk.shape[-1]), axis_name, tiled=True)
            return full[:n].reshape(ref.shape)

        new_params = _tm(regather, new_p_sh, params)
        new_state = _tm(regather, new_s_sh, opt_state)
        return new_params, new_state

    fn = shard_map(body, mesh=mesh, in_specs=(P(), P(), P(), P()),
                   out_specs=(P(), P()), check_vma=False)
    return fn(params, opt_state, grads, jnp.asarray(hp["lr"], jnp.float32))


def optupdate_mesh_roofline(optimizer, params, mesh, axis_name="dp",
                            opt_state=None):
    """Ideal fused-update bytes, total and per dp shard (padding
    included), banked by the dryrun next to the ZeRO byte ratios."""
    from ..kernels.opt_update import optupdate_ideal_bytes
    total = int(optupdate_ideal_bytes(optimizer, params, opt_state))
    dp = _mesh_axis_size(mesh, axis_name)
    leaves = [x for x in jax.tree_util.tree_leaves(params) if _chunkable(x)]
    padded = sum(dp * _chunk_size(int(_np.prod(x.shape)), dp)
                 for x in leaves)
    n_elems = sum(int(_np.prod(x.shape)) for x in leaves)
    scale = padded / max(1, n_elems)
    per_shard = int(total * scale) // max(1, dp)
    return {"ideal_bytes": total,
            "per_axis": {axis_name: {"size": dp,
                                     "bytes_per_shard": per_shard}}}


# ---------------------------------------------------------------------------
# Declared comm plans (TPL3xx program audit — analysis/program_audit.py)
# ---------------------------------------------------------------------------

def flash_mesh_comm_plan(mesh, batch_axis="dp", head_axis="tp"):
    """The flash-attention island's comm contract: ZERO collectives.
    Every shard owns full rows (batch over dp, heads over tp, sequence
    unsharded), so any collective the audit sees in this program is
    partitioner-injected — exactly the TPL301 failure mode."""
    from ..analysis.program_audit import CommPlan
    return CommPlan(site="mesh.flash_attention", allowed=(),
                    max_programs=1)


def optupdate_mesh_comm_plan(optimizer, params, mesh, axis_name="dp",
                             opt_state=None):
    """The fused-update island's comm contract: all-gathers over the dp
    axis regathering fresh params AND float slots from their transient
    (dp, chunk) blocks. The analytic ideal is exact — per chunkable leaf
    the gathered buffer is ``dp * chunk * itemsize`` bytes (lane padding
    included), the same accounting `optupdate_mesh_roofline` banks —
    so drift beyond tolerance is TPL302, not noise. Grads enter the
    island replicated (spec P()), so an all-reduce is allowed only for
    the embedded (step-fused) form, never counted in the ideal."""
    from ..analysis.program_audit import CommPlan
    dp = _mesh_axis_size(mesh, axis_name)
    if dp <= 1:
        return CommPlan(site="mesh.fused_update", allowed=(),
                        max_programs=1)
    gather = 0
    leaves = list(jax.tree_util.tree_leaves(params))
    if opt_state is not None:
        leaves += list(jax.tree_util.tree_leaves(opt_state))
    for x in leaves:
        # abstract-friendly _chunkable: plans are built from
        # ShapeDtypeStructs as often as from live arrays
        if x is None or getattr(x, "ndim", 0) < 1:
            continue
        dt = getattr(x, "dtype", None)
        dt = jnp.dtype(dt if dt is not None else jnp.asarray(x).dtype)
        if not jnp.issubdtype(dt, jnp.floating):
            continue
        gather += dp * _chunk_size(int(_np.prod(x.shape)), dp) * dt.itemsize
    return CommPlan(
        site="mesh.fused_update",
        allowed=[("all-gather", axis_name, None),
                 ("all-reduce", axis_name, None)],
        ideal_bytes_per_axis={axis_name: gather},
        max_programs=1)
