"""The expert layer's grouped matrix product over SORTED rows, in row tiles.

``rows`` ``[cap, d]`` are the held (token, expert) assignments of
`parallel/moe.py::routed_experts`, sorted by expert: the first
``counts[0]`` rows are expert 0's, the next ``counts[1]`` expert 1's, and
so on; rows past ``sum(counts)`` belong to nobody. `grouped_experts` runs a
gated expert over them, each row against its own expert's matrices::

    h = act(rows W_gate[e]) * (rows W_up[e])         [cap, f]
    y = h W_down[e]                                  [cap, d] float32

**The activation is a parameter** (``activation``): ``"silu"``, or
``("polynorm", eps, scale, clamp)`` with each expert's four coefficients
``coef`` ``[G, 4]`` (`polynorm`). SiLU is elementwise, so the gate-and-up
call applies it and hands the down call ``h``. PolyNorm normalises over the
WHOLE ``f``-wide row, which the gate-and-up call holds only a column tile of
(`column_tile`: 640 of 1,280 at the published widths), while the down call
holds the whole row as its contraction: so the gate-and-up call hands on the
gate and the up products, and PolyNorm is the down call's prologue.

Two ``pallas_call``s named ``mx_grouped_experts`` (gate and up, then down),
the shape of ``jax.experimental.pallas.ops.tpu.megablox.gmm``: the rows are
cut into tiles of `ROW_TILE`; a grid step is one (tile, expert) pair that
share rows, found through scalar-prefetched tables (`tile_plan`), so an
expert sent 20 rows costs one step and one sent 300 three, whatever ``cap``
is: the grid's length is the traced number of such pairs. A tile that
several experts share is visited once for each, consecutively, and each
visit stores its own rows only. What such a product has to cost is its
WEIGHTS (an expert's three matrices once), so the contraction is never
tiled: a weight block is ``[k, tn]``, the steps of one expert follow each
other and name the same block, and the pipeline fetches it once. ``tn``
comes from the shapes and `_WEIGHT_VMEM` (`column_tile`). Operands in the
rows' dtype, float32 accumulation, what passes between the calls (``h``, or
PolyNorm's gate and up products) rounded to the rows' dtype as the lax
forms do.

Rows past ``sum(counts)`` (and whole tiles past it) are NEVER written:
what the caller reads there is whatever the buffer held, and it masks them.

Jitted though it only ever runs inside a program, so that the layers of a
program share ONE trace and lowering of each call (PERF.md, PR 32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ROW_TILE", "activate", "column_tile", "grouped_experts",
           "polynorm", "tile_plan"]

ROW_TILE = 128          # the matrix unit's own height: fewer rows cost the same
_LANES = 128
_WEIGHT_VMEM = 24 << 20     # the double-buffered weight blocks of one call


def column_tile(k, n, mats, itemsize):
    """Columns of a weight block ``[k, tn]``: the widest divisor of ``n``
    in whole lane groups whose ``mats`` double-buffered blocks fit
    `_WEIGHT_VMEM` (one lane group at least; ``n`` itself where it is no
    multiple of the lane count, as at the tests' widths)."""
    if n % _LANES:
        return n
    fits = [tn for tn in range(_LANES, n + 1, _LANES)
            if n % tn == 0 and 2 * mats * k * tn * itemsize <= _WEIGHT_VMEM]
    return max(fits, default=_LANES)


def polynorm(z, coef, eps, scale, clamp):
    """PolyNorm over the last axis, float32: ``scale * (w0 N(z^3) + w1 N(z^2)
    + w2 N(z) + clip(b, -clamp, clamp))``, ``N`` a gainless RMS norm over the
    row. ``coef`` is ``(w0, w1, w2, b)``: four scalars, or an array whose
    last axis holds them and whose leading axes broadcast against
    ``z``'s."""
    z = z.astype(jnp.float32)
    if not isinstance(coef, tuple):
        coef = coef.astype(jnp.float32)
        coef = tuple(coef[..., i:i + 1] for i in range(4))
    w0, w1, w2, b = coef

    def norm(t):
        return t * jax.lax.rsqrt(jnp.mean(t * t, -1, keepdims=True) + eps)
    z2 = z * z
    return scale * (w0 * norm(z2 * z) + w1 * norm(z2) + w2 * norm(z)
                    + jnp.clip(b, -clamp, clamp))


def activate(activation, gate, up, coef=None):
    """``act(gate) * up`` in float32 (module docstring's ``activation``)."""
    if activation == "silu":
        return jax.nn.silu(gate) * up
    _, eps, scale, clamp = activation
    return polynorm(gate, coef, eps, scale, clamp) * up


def tile_plan(counts, cap, tm):
    """The grid's tables for group sizes ``counts`` ``[G]`` over ``cap``
    sorted rows in tiles of ``tm``: ``(group_of, tile_of, bounds, steps)``.
    Step ``s < steps`` pairs expert ``group_of[s]`` with row tile
    ``tile_of[s]``; ``bounds`` ``[G + 1]`` are the experts' first rows
    (``bounds[g + 1]`` is one past ``g``'s last). An expert with no row has
    no step; the tables' static length is ``cap // tm + G - 1``, the most
    pairs there can be."""
    G = counts.shape[0]
    ends = jnp.cumsum(counts)
    starts = ends - counts
    first = starts // tm
    tiles = jnp.where(counts > 0, (ends - 1) // tm - first + 1, 0)
    at = jnp.cumsum(tiles) - tiles              # an expert's first step
    steps = jnp.sum(tiles)
    length = cap // tm + G - 1
    group_of = jnp.repeat(jnp.arange(G, dtype=jnp.int32), tiles,
                          total_repeat_length=length)
    s = jnp.arange(length, dtype=jnp.int32)
    tile_of = jnp.take(first, group_of) + s - jnp.take(at, group_of)
    tile_of = jnp.clip(tile_of, 0, cap // tm - 1).astype(jnp.int32)
    bounds = jnp.concatenate([jnp.zeros(1, jnp.int32),
                              ends.astype(jnp.int32)])
    return group_of, tile_of, bounds, steps.astype(jnp.int32)


def _own_rows(group_of, tile_of, bounds, s, shape):
    """The rows of step ``s``'s tile that are its expert's."""
    g = group_of[s]
    row = tile_of[s] * shape[0] + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return (row >= bounds[g]) & (row < bounds[g + 1])


def _gate_up_kernel(group_of, tile_of, bounds, x_ref, wg_ref, wu_ref, h_ref):
    s = pl.program_id(1)
    x = x_ref[...]
    gate = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    up = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    h = (jax.nn.silu(gate) * up).astype(h_ref.dtype)
    own = _own_rows(group_of, tile_of, bounds, s, h.shape)
    h_ref[...] = jnp.where(own, h, h_ref[...])


def _down_kernel(group_of, tile_of, bounds, h_ref, wd_ref, y_ref):
    s = pl.program_id(1)
    y = jnp.dot(h_ref[...], wd_ref[...], preferred_element_type=jnp.float32)
    own = _own_rows(group_of, tile_of, bounds, s, y.shape)
    y_ref[...] = jnp.where(own, y, y_ref[...])


def _gate_and_up_kernel(group_of, tile_of, bounds, x_ref, wg_ref, wu_ref,
                        g_ref, u_ref):
    """A PolyNorm expert's first call: the two products, no activation."""
    s = pl.program_id(1)
    x = x_ref[...]
    own = _own_rows(group_of, tile_of, bounds, s, g_ref.shape)
    for w_ref, o_ref in ((wg_ref, g_ref), (wu_ref, u_ref)):
        o = jnp.dot(x, w_ref[...], preferred_element_type=jnp.float32)
        o_ref[...] = jnp.where(own, o.astype(o_ref.dtype), o_ref[...])


def _poly_down_kernel(group_of, tile_of, bounds, coef_ref, g_ref, u_ref,
                      wd_ref, y_ref, *, activation):
    """A PolyNorm expert's down call: ``h = PolyNorm(gate) * up`` over the
    whole row (the prologue), rounded to the rows' dtype, then ``h W_down``.
    The expert's four coefficients come from scalar memory."""
    s = pl.program_id(1)
    g = group_of[s]
    coef = tuple(coef_ref[4 * g + i] for i in range(4))
    h = activate(activation, g_ref[...].astype(jnp.float32),
                 u_ref[...].astype(jnp.float32), coef)
    y = jnp.dot(h.astype(g_ref.dtype), wd_ref[...],
                preferred_element_type=jnp.float32)
    own = _own_rows(group_of, tile_of, bounds, s, y.shape)
    y_ref[...] = jnp.where(own, y, y_ref[...])


def _product(kernel, plan, tm, rows, weights, out_dtype, interpret, outs=1,
             coef=None):
    """One grouped projection: ``rows`` ``[cap, k]`` (or a tuple of such,
    read side by side) in tiles of ``tm`` against ``weights`` (each ``[G,
    k, n]``) -> ``outs`` arrays ``[cap, n]`` in ``out_dtype`` (one array
    where ``outs`` is 1). ``coef`` (flat float32) goes to scalar memory
    whole. Grid: column tiles outside, (tile, expert) steps inside, so that
    a row tile's output block stays put while the experts that share it
    pass."""
    group_of, tile_of, bounds, steps = plan
    rows = rows if isinstance(rows, tuple) else (rows,)
    cap, k = rows[0].shape
    n = weights[0].shape[2]
    size = rows[0].dtype.itemsize
    tn = column_tile(k, n, len(weights), size)
    rows_spec = pl.BlockSpec((tm, k), lambda j, s, g, t, b: (t[s], 0))
    w_spec = pl.BlockSpec((None, k, tn), lambda j, s, g, t, b: (g[s], 0, j))
    out_spec = pl.BlockSpec((tm, tn), lambda j, s, g, t, b: (t[s], j))
    blocks = 2 * (len(rows) * tm * k * size + len(weights) * k * tn * size
                  + outs * tm * tn * jnp.dtype(out_dtype).itemsize)
    # inside a `shard_map` body (the `ep` share) the result varies over the
    # axes its operands vary over
    varies = frozenset().union(*(jax.typeof(a).vma
                                 for a in (bounds,) + rows + tuple(weights)))
    out_shape = jax.ShapeDtypeStruct((cap, n), out_dtype, vma=varies)
    in_specs = [rows_spec] * len(rows) + [w_spec] * len(weights)
    operands = rows + tuple(weights)
    if coef is not None:
        in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] + in_specs
        operands = (coef,) + operands
    return pl.pallas_call(
        kernel,
        out_shape=out_shape if outs == 1 else [out_shape] * outs,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=in_specs,
            out_specs=out_spec if outs == 1 else [out_spec] * outs,
            grid=(n // tn, steps)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(blocks + (16 << 20))),
        cost_estimate=pl.CostEstimate(
            flops=2 * len(weights) * cap * k * n, transcendentals=0,
            bytes_accessed=sum(w.size for w in weights) * size
            + (n // tn) * sum(r.size for r in rows) * size
            + outs * cap * n * jnp.dtype(out_dtype).itemsize),
        name="mx_grouped_experts", interpret=interpret,
    )(group_of, tile_of, bounds, *operands)


@functools.partial(jax.jit, static_argnames=("interpret", "activation"))
def grouped_experts(rows, counts, w_gate, w_up, w_down, *, interpret=False,
                    activation="silu", coef=None):
    """``[cap, d]`` float32: the gated experts' output for each sorted row
    (module docstring), and the row-equivalents it cost (steps x
    `ROW_TILE`, int32). ``cap`` is a multiple of `ROW_TILE`, or under it
    (one tile). ``activation`` and ``coef`` ``[G, 4]``: the module
    docstring's."""
    cap = rows.shape[0]
    tm = min(ROW_TILE, cap)
    if cap % tm:
        raise ValueError(f"{cap} sorted rows are no whole tiles of {tm}")
    plan = tile_plan(counts, cap, tm)
    if activation == "silu":
        h = _product(_gate_up_kernel, plan, tm, rows, (w_gate, w_up),
                     rows.dtype, interpret)
        y = _product(_down_kernel, plan, tm, h, (w_down,), jnp.float32,
                     interpret)
    else:
        gate_up = _product(_gate_and_up_kernel, plan, tm, rows,
                           (w_gate, w_up), rows.dtype, interpret, outs=2)
        y = _product(functools.partial(_poly_down_kernel,
                                       activation=activation),
                     plan, tm, tuple(gate_up), (w_down,), jnp.float32,
                     interpret, coef=coef.astype(jnp.float32).reshape(-1))
    return y, plan[3] * tm
