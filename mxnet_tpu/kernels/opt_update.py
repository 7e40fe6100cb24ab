"""Fused optimizer-update kernel: grad preprocessing + sgd/momentum/adam in
ONE memory-bound sweep per parameter block.

The fused train step's update today is a chain of tree_maps
(`parallel/optim_update.apply_update` plus the rescale/clip/weight-decay
prologue `tpu_step` builds around it): per parameter XLA sees 5-9 separate
elementwise HLOs and has to rediscover the fusion. The TPU-pod scaling
playbook (arXiv 1909.09756 §4.3) puts the weight update squarely in the
memory-bound regime — the only lever is touching each byte once. This
module provides that as a Pallas kernel (one grid sweep per parameter
block: read p/g/state, write p/state, nothing else), with the same
three-tier availability story as `kernels/flash_attention.py`:

* Pallas compiled (TPU) — `default_use_pallas()` true;
* Pallas interpret mode — tests exercise the kernel body anywhere;
* pure-lax fallback — one fused jnp expression per leaf, used on CPU and
  for leaves whose layout doesn't suit the kernel (tiny/ragged params).

**Parity contract**: every tier evaluates EXACTLY the expression sequence of
`tpu_step`'s prologue + `apply_update` — same operations, same order, same
f32 scalar handling. What that buys depends on the code generator:

* on the TPU the compiled kernel (Mosaic) and the lax leaf (XLA:TPU) are
  **bit-identical**: 0 ulp over the whole ResNet-50 parameter tree, 51 M
  (sgd-momentum) and 77 M (adam) elements (chip_smoke.py kernels phase,
  TPU v5e, PR 22) — so `MXNET_TPU_FUSED_OPTUPDATE=1` changes no trained
  weight there;
* the lax tier is bit-identical to the tree-map route under jit on every
  backend (one fused expression either way; test_opt_update.py);
* the kernel body in INTERPRET mode runs on XLA:CPU, which contracts
  `a*b + c` into a fused multiply-add per fusion, differently for the
  interpreter's block loop than for the reference's flat sweep: results
  agree to **4 ulp at the magnitude of the largest operand** (one rounding
  per contraction, three contractions in the adam chain; observed 2 ulp in
  3 of 2048 elements on jaxlib 0.9.0), not bitwise.
"""
from __future__ import annotations

import functools

import numpy as _np
import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import default_use_pallas

__all__ = ["fused_update_step", "fused_update_available",
           "optupdate_ideal_bytes", "optupdate_kernel_bytes"]

_LANES = 128
# rows per grid step: 512 x 128 f32 = 256 KB per operand block; adam's 7
# live blocks stay well under VMEM
_BLOCK_ROWS = 512
# leaves below this don't amortize a pallas_call dispatch; lax handles them
_MIN_KERNEL_ELEMS = 8 * _LANES


def fused_update_available():
    """Kernel-tier gate: same policy as the flash kernels."""
    return default_use_pallas()


def _scal2(x):
    """(1, 2) f32 scalar carrier for the kernels' SMEM block (lane-pair:
    a (1, 1) SMEM window is fine on hardware but the duplicate lane keeps
    interpret-mode layouts trivial)."""
    x = jnp.asarray(x, jnp.float32)
    return jnp.stack([x, x]).reshape(1, 2)


def _lazy_scal(x):
    """Build the SMEM scalar block only if a kernel-tier leaf consumes it:
    on the pure-lax tier the carrier would otherwise trace as a dead
    stack/reshape chain in the step program (tpulint TPL202)."""
    cache = []

    def get():
        if not cache:
            cache.append(_scal2(x))
        return cache[0]
    return get


def _prologue(p, g, rescale, clip, wd):
    """tpu_step's reference optimizer order: rescale -> clip -> + wd*w.
    One definition shared by the lax tier and the kernel bodies — parity
    by construction."""
    g = g * rescale
    if clip is not None:
        g = jnp.clip(g, -clip, clip)
    return g + wd * p


# ---------------------------------------------------------------------------
# Pallas kernel bodies — scalars ride in SMEM ((1, 2) f32: lr or lr*corr);
# static hyperparameters (momentum/betas/eps/rescale/clip/wd) are baked as
# Python floats exactly like the tree-map path bakes them
# ---------------------------------------------------------------------------


def _sgd_kernel(scal_ref, p_ref, g_ref, o_ref, *, rescale, clip, wd):
    lr = scal_ref[0, 0]
    p = p_ref[...]
    g = _prologue(p, g_ref[...], rescale, clip, wd)
    o_ref[...] = p - lr * g


def _sgd_mom_kernel(scal_ref, p_ref, g_ref, mom_ref, po_ref, mo_ref, *,
                    momentum, rescale, clip, wd):
    lr = scal_ref[0, 0]
    p = p_ref[...]
    g = _prologue(p, g_ref[...], rescale, clip, wd)
    mom = momentum * mom_ref[...] - lr * g
    mo_ref[...] = mom
    po_ref[...] = p + mom


def _adam_kernel(scal_ref, p_ref, g_ref, m_ref, v_ref, po_ref, mo_ref,
                 vo_ref, *, b1, b2, eps, rescale, clip, wd):
    lc = scal_ref[0, 0]  # lr * corr, folded outside exactly as apply_update
    p = p_ref[...]
    g = _prologue(p, g_ref[...], rescale, clip, wd)
    m = b1 * m_ref[...] + (1 - b1) * g
    v = b2 * v_ref[...] + (1 - b2) * g * g
    mo_ref[...] = m
    vo_ref[...] = v
    po_ref[...] = p - lc * m / (jnp.sqrt(v) + eps)


def _kernel_eligible(leaf):
    return (leaf.dtype == jnp.float32 and leaf.size >= _MIN_KERNEL_ELEMS
            and leaf.size % _LANES == 0)


def _run_leaf_kernel(kernel, scal, arrays, n_out, interpret):
    """One pallas_call over a leaf reshaped to [rows, 128] lanes.

    Param/state inputs alias their outputs (in-place update — the whole
    point of a memory-bound fused sweep): input order is (scal, p, g,
    state...), output order (p, state...), so input i+1 aliases output i
    for every non-grad operand."""
    shape = arrays[0].shape
    rows = arrays[0].size // _LANES
    flat = [a.reshape(rows, _LANES) for a in arrays]
    block_rows = min(rows, _BLOCK_ROWS)
    grid = (pl.cdiv(rows, block_rows),)
    tens_spec = pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0))
    if interpret:
        scal_spec = pl.BlockSpec((1, 2), lambda i: (0, 0))
    else:
        scal_spec = pl.BlockSpec((1, 2), lambda i: (0, 0),
                                 memory_space=pltpu.SMEM)
    aliases = {1: 0}                    # p -> new p
    for k in range(1, n_out):
        aliases[k + 2] = k              # state k (after scal, p, g) -> out k
    out = pl.pallas_call(
        kernel,
        name="mx_opt_update",
        grid=grid,
        in_specs=[scal_spec] + [tens_spec] * len(flat),
        out_specs=[tens_spec] * n_out,
        out_shape=[jax.ShapeDtypeStruct((rows, _LANES), jnp.float32)] * n_out,
        input_output_aliases=aliases,
        interpret=interpret,
    )(scal, *flat)
    return [o.reshape(shape) for o in out]


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------


def fused_update_step(optimizer, hp, params, opt_state, grads, *,
                      rescale=1.0, clip=None, wd=0.0, use_pallas=None,
                      interpret=False):
    """(params, opt_state, raw grads) -> (new_params, new_opt_state).

    Drop-in fusion of tpu_step's grad prologue (rescale -> clip -> +wd*w)
    with `optim_update.apply_update` — the same expression sequence (see
    the module's parity contract), one sweep per parameter block. `hp` carries lr (traced ok) and the optimizer's
    static scalars (momentum / beta1 / beta2 / eps).
    """
    if use_pallas is None:
        use_pallas = fused_update_available()
    run_kernel = use_pallas or interpret
    lr = hp["lr"]
    tm = jax.tree_util.tree_map

    if optimizer == "adam":
        b1, b2, eps = hp["beta1"], hp["beta2"], hp["eps"]
        t = opt_state["t"] + 1
        tf = t.astype(jnp.float32)
        corr = jnp.sqrt(1 - b2 ** tf) / (1 - b1 ** tf)
        lc = lr * corr  # apply_update's ((lr*corr)*m) association
        scal = _lazy_scal(lc)
        kernel = functools.partial(_adam_kernel, b1=b1, b2=b2, eps=eps,
                                   rescale=rescale, clip=clip, wd=wd)

        def leaf(p, g, m, v):
            if run_kernel and _kernel_eligible(p):
                return _run_leaf_kernel(kernel, scal(), (p, g, m, v), 3,
                                        interpret)
            g = _prologue(p, g, rescale, clip, wd)
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            return p - lc * m / (jnp.sqrt(v) + eps), m, v

        new = {n: leaf(params[n], grads[n], opt_state["m"][n],
                       opt_state["v"][n]) for n in params}
        return ({n: new[n][0] for n in params},
                {"m": {n: new[n][1] for n in params},
                 "v": {n: new[n][2] for n in params}, "t": t})

    if optimizer == "sgd":
        momentum = hp.get("momentum", 0.0)
        scal = _lazy_scal(lr)
        if opt_state.get("mom") is not None:
            kernel = functools.partial(_sgd_mom_kernel, momentum=momentum,
                                       rescale=rescale, clip=clip, wd=wd)

            def leaf(p, g, mom):
                if run_kernel and _kernel_eligible(p):
                    return _run_leaf_kernel(kernel, scal(), (p, g, mom), 2,
                                            interpret)
                g = _prologue(p, g, rescale, clip, wd)
                mom = momentum * mom - lr * g
                return p + mom, mom

            new = {n: leaf(params[n], grads[n], opt_state["mom"][n])
                   for n in params}
            return ({n: new[n][0] for n in params},
                    {"mom": {n: new[n][1] for n in params}})

        kernel = functools.partial(_sgd_kernel, rescale=rescale, clip=clip,
                                   wd=wd)

        def leaf(p, g):
            if run_kernel and _kernel_eligible(p):
                return _run_leaf_kernel(kernel, scal(), (p, g), 1,
                                        interpret)[0]
            return p - lr * _prologue(p, g, rescale, clip, wd)

        return tm(leaf, params, grads), opt_state

    raise ValueError("unknown optimizer %r" % optimizer)


def _opt_rw_counts(optimizer, opt_state):
    """(reads, writes) of p-sized operands per update sweep."""
    if optimizer == "adam":
        return 4, 3              # r: p,g,m,v  w: p,m,v
    mom = (opt_state or {}).get("mom") if optimizer == "sgd" else None
    if mom:
        return 3, 2              # r: p,g,mom  w: p,mom
    return 2, 1                  # r: p,g      w: p


def optupdate_ideal_bytes(optimizer, params, opt_state=None):
    """Roofline floor for one update sweep: bytes that MUST cross HBM —
    read p+g(+state), write p(+state). The profiler/bench `optupdate_*`
    counters gate the fused kernel against this number."""
    p_bytes = sum(_np.prod(v.shape) * _np.dtype(v.dtype).itemsize
                  for v in params.values())
    r, w = _opt_rw_counts(optimizer, opt_state)
    return int((r + w) * p_bytes)


def optupdate_kernel_bytes(optimizer, params, opt_state=None):
    """HBM traffic of the KERNEL tier's DMA schedule — computed from the
    same grid/BlockSpec arithmetic `_run_leaf_kernel` hands `pallas_call`
    (each index map visits every block exactly once, so traffic = grid
    steps x block bytes + the SMEM scalar per step). This is the byte
    count the TPU program executes, derivable on any host; leaves the
    kernel rejects (`_kernel_eligible`) are counted at the lax tier's
    post-fusion floor, i.e. the same r/w sweep XLA emits for them."""
    r, w = _opt_rw_counts(optimizer, opt_state)
    total = 0
    for v in params.values():
        elems = int(_np.prod(v.shape))
        leaf_bytes = elems * _np.dtype(v.dtype).itemsize
        if _kernel_eligible(v):
            rows = elems // _LANES
            block_rows = min(rows, _BLOCK_ROWS)
            steps = -(-rows // block_rows)              # pl.cdiv
            block_b = block_rows * _LANES * 4
            total += steps * ((r + w) * block_b + 8)    # + (1,2) f32 scal
        else:
            total += (r + w) * leaf_bytes
    return int(total)
