"""The gated delta rule's two programs: the decode step's one-token state
update over a per-slot state pool, and the prefill's chunked scan.

One head's recurrent state is ``S`` ``[d_k, d_v]`` (float32 as served). A
token with key ``k``, value ``v``, query ``q`` (both L2-normalised by the
caller, ``q`` scaled), log-decay ``g <= 0`` a head and CHANNEL and write
strength ``beta`` a head updates and reads it as::

    S' = Diag(exp g) S;   S_new = S' + beta k (v - S'^T k)^T;   o = S_new^T q

**The step** (`kda_step`) does that for one token of every live row, in
place over ``state`` ``[layers, slots, H, d_k, d_v]``: row ``i`` is slot
``i``. Rearranged so that the old state is read ONCE and both reductions
run over it (``S'^T x = S^T (exp g * x)``)::

    r  = S^T (beta exp(g) k)        o1 = S^T (exp(g) q)
    ub = beta v - r                 S_new = Diag(exp g) S + k ub^T
    o  = o1 + (q . k) ub

Kernel tier: ONE ``pallas_call`` named ``mx_kda_step`` a layer, the state
pool aliased to its output (``input_output_aliases``). The grid walks the
ACTIVE rows only (compacted through scalar prefetch: a grid step past the
last active row maps to the block already held, so nothing is moved for
it); a row's whole state, all heads, is one block: read once, written once.
The four vectors a head needs as COLUMNS over ``d_k`` arrive as rows of one
packed tile and are transposed in the kernel. The lax tier is the same
arithmetic over the whole layer in `jax.numpy`; ``interpret`` runs the
kernel through the Pallas interpreter (CPU tests).

**The chunked scan** (`kda_chunk_scan`) runs a prompt piece as chunks of
``chunk`` tokens: inside a chunk everything is matrix products, and only the
state crosses from chunk to chunk (``lax.scan``). With ``G_t`` the
cumulative log-decay inside the chunk and ``S_0`` the incoming state::

    A_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)   (j < i)
    [U_v | W_k] = (I + A)^-1 [beta V | beta K exp(G)]
    U   = U_v - W_k S_0
    o_t = (q_t exp G_t)^T S_0 + sum_{i<=t} [sum_c q_tc k_ic exp(G_tc - G_ic)] u_i
    S_C = Diag(exp G_C) S_0 + sum_i (k_i exp(G_C - G_i)) u_i^T

Every decay ratio is formed as the exponential of a DIFFERENCE of cumulative
log-decays that is ``<= 0`` (`_decay_scores`: directly inside sub-chunks of
``sub`` tokens; across sub-chunks as the product of two such factors taken
against the later sub-chunk's first token): no quotient of exponentials, no
overflow however fast a channel decays. Plain `jax.lax` matrix products (no
kernel of its own yet: its time is seen through the prefill program's).

Both are jitted though they only ever run inside a program, so that the
layers of a program share ONE trace and lowering (PERF.md, PR 32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["kda_step", "kda_chunk_scan", "kda_recurrence"]

_HI = lax.Precision.HIGHEST
_LANES = 128


# ---------------------------------------------------------------------------
# the recurrence itself (the tests' oracle for both programs)
# ---------------------------------------------------------------------------
def kda_recurrence(q, k, v, g, beta, s0):
    """Token by token, float32: ``q, k, g`` ``[T, H, dk]``, ``v`` ``[T, H,
    dv]``, ``beta`` ``[T, H]``, ``s0`` ``[H, dk, dv]`` -> ``(o [T, H, dv],
    S_T)``."""
    def one(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[..., None] * S
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t,
                                             precision=_HI))
        S = S + k_t[..., None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t, precision=_HI)

    f32 = lambda t: t.astype(jnp.float32)               # noqa: E731
    S, o = lax.scan(one, f32(s0), tuple(f32(t) for t in (q, k, v, g, beta)))
    return o, S


# ---------------------------------------------------------------------------
# the decode step's state update
# ---------------------------------------------------------------------------
def _step_kernel(lr_ref, rows_ref, n_ref, packed_ref, s_ref, s_out_ref,
                 o1_ref, ub_ref, *, heads):
    """One active row: ``packed`` ``[1, 5H, dk]`` holds, a head a row,
    ``beta exp(g) k``, ``exp(g) q``, ``exp(g)``, ``k`` (needed as columns
    over ``dk``: the first ``4H`` rows are transposed) and ``beta v`` (a
    row over ``dv``)."""
    del lr_ref, rows_ref
    i = pl.program_id(0)
    n = n_ref[0]
    H = heads

    @pl.when(i < n)
    def _():
        cols = packed_ref[0, :4 * H, :].astype(jnp.float32).T   # [dk, 4H]
        for h in range(H):
            S = s_ref[0, 0, h].astype(jnp.float32)              # [dk, dv]
            kba = cols[:, h:h + 1]
            qa = cols[:, H + h:H + h + 1]
            a = cols[:, 2 * H + h:2 * H + h + 1]
            kk = cols[:, 3 * H + h:3 * H + h + 1]
            r = jnp.sum(kba * S, axis=0, keepdims=True)         # [1, dv]
            o1 = jnp.sum(qa * S, axis=0, keepdims=True)
            ub = packed_ref[0, 4 * H + h:4 * H + h + 1, :].astype(
                jnp.float32) - r
            s_out_ref[0, 0, h] = (a * S + kk * ub).astype(s_out_ref.dtype)
            o1_ref[0, h:h + 1, :] = o1
            ub_ref[0, h:h + 1, :] = ub

    # no row is active: the one block every grid step maps to is written
    # back at the end, so it has to hold what it held
    @pl.when(jnp.logical_and(n == 0, i == 0))
    def _():
        s_out_ref[...] = s_ref[...]


def _step_pallas(state, layer, packed, active, interpret):
    L, B, H, dk, dv = state.shape
    # the active rows first, in slot order; a grid step past the last of
    # them maps to the block of the last (held already: nothing moves)
    n = jnp.sum(active.astype(jnp.int32))
    order = jnp.argsort(jnp.logical_not(active), stable=True).astype(
        jnp.int32)
    at = jnp.minimum(jnp.arange(B, dtype=jnp.int32), jnp.maximum(n - 1, 0))
    rows = jnp.take(order, at)
    lr = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    row_block = lambda i, lr, rows, n: (rows[i], 0, 0)          # noqa: E731
    state_block = lambda i, lr, rows, n: (lr[0], rows[i], 0, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(B,),
        in_specs=[pl.BlockSpec((1, 5 * H, dk), row_block),
                  pl.BlockSpec((1, 1, H, dk, dv), state_block)],
        out_specs=[pl.BlockSpec((1, 1, H, dk, dv), state_block),
                   pl.BlockSpec((1, H, dv), row_block),
                   pl.BlockSpec((1, H, dv), row_block)])
    block_bytes = H * dk * dv * state.dtype.itemsize
    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        # a row's state in and out, both double-buffered, and the vectors
        vmem_limit_bytes=int(4 * block_bytes + (16 << 20)))
    state, o1, ub = pl.pallas_call(
        functools.partial(_step_kernel, heads=H),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((B, H, dv), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, dv), jnp.float32)],
        # operands count the scalar-prefetch ones: 3 of them, packed, state
        input_output_aliases={4: 0},
        compiler_params=params, interpret=interpret,
        name="mx_kda_step")(lr, rows, jnp.reshape(n, (1,)), packed, state)
    return state, o1, ub


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def kda_step(state, layer, q, k, v, g, beta, active, *, use_pallas=False,
             interpret=False):
    """One token of every ACTIVE row through layer ``layer`` of ``state``
    ``[layers, slots, H, dk, dv]``: ``q, k, g`` ``[B, H, dk]``, ``v`` ``[B,
    H, dv]``, ``beta`` ``[B, H]``, ``active`` ``[B]``. Returns ``(o [B, H,
    dv] float32, state)``; an inactive row's state is neither read nor
    written and its ``o`` is 0."""
    f32 = jnp.float32
    q, k, v, g, beta = (t.astype(f32) for t in (q, k, v, g, beta))
    a = jnp.exp(g)
    qk = jnp.sum(q * k, axis=-1)                                # [B, H]
    kba, qa, vb = beta[..., None] * a * k, a * q, beta[..., None] * v
    if use_pallas or interpret:
        packed = jnp.concatenate([kba, qa, a, k, vb], axis=1)   # [B, 5H, .]
        state, o1, ub = _step_pallas(state, layer, packed, active, interpret)
    else:
        S = state[layer].astype(f32)
        r = jnp.einsum("bhkv,bhk->bhv", S, kba, precision=_HI)
        o1 = jnp.einsum("bhkv,bhk->bhv", S, qa, precision=_HI)
        ub = vb - r
        new = a[..., None] * S + k[..., None] * ub[:, :, None, :]
        keep = active[:, None, None, None]
        state = state.at[layer].set(
            jnp.where(keep, new, S).astype(state.dtype))
    o = o1 + qk[..., None] * ub
    # (the kernel leaves an inactive row's outputs unwritten)
    return jnp.where(active[:, None, None], o, 0.0), state


# ---------------------------------------------------------------------------
# the prefill's chunked scan
# ---------------------------------------------------------------------------
def _decay_scores(x, y, G, sub, strict):
    """``M_ij = sum_c x_ic y_jc exp(G_ic - G_jc)`` for ``j < i`` (``strict``)
    or ``j <= i``, else 0. ``x, y, G`` ``[..., c, d]`` float32, ``G``
    non-increasing along ``c``; returns ``[..., c, c]``. No exponent is ever
    positive: inside a sub-chunk of ``sub`` tokens the difference is taken
    directly; a pair in different sub-chunks goes through the later one's
    reference ``R`` (the cumulative log-decay before its first token):
    ``exp(G_i - R) exp(R - G_j)``, both factors at most 1, as one matrix
    product."""
    lead, (c, d) = x.shape[:-2], x.shape[-2:]
    a = c // sub
    split = lambda t: t.reshape(lead + (a, sub, d))             # noqa: E731
    xs, ys, Gs = split(x), split(y), split(G)
    ref = jnp.concatenate([jnp.zeros(lead + (1, d), G.dtype),
                           Gs[..., :-1, -1, :]], axis=-2)       # [., a, d]
    # diagonal blocks
    idx = jnp.arange(sub)
    keep = (idx[None, :] < idx[:, None]) if strict \
        else (idx[None, :] <= idx[:, None])
    diff = Gs[..., :, None, :] - Gs[..., None, :, :]        # [., a, i, j, d]
    diag = jnp.sum(xs[..., :, None, :] * ys[..., None, :, :]
                   * jnp.exp(jnp.where(keep[..., None], diff, -jnp.inf)),
                   axis=-1)                                     # [., a, i, j]
    full = (jnp.eye(a, dtype=x.dtype)[:, None, :, None]
            * diag[..., :, :, None, :]).reshape(lead + (c, c))
    if a == 1:
        return full
    # earlier sub-chunks, through the later one's reference
    xt = xs * jnp.exp(Gs - ref[..., :, None, :])                # [., a, s, d]
    early = (jnp.arange(c)[None, :] // sub) < jnp.arange(a)[:, None]  # [a, c]
    expo = ref[..., :, None, :] - G[..., None, :, :]            # [., a, c, d]
    yt = jnp.where(early[..., None],
                   y[..., None, :, :]
                   * jnp.exp(jnp.where(early[..., None], expo, 0.0)), 0.0)
    off = jnp.einsum("...asd,...acd->...asc", xt, yt, precision=_HI)
    return full + off.reshape(lead + (c, c))


@functools.partial(jax.jit, static_argnames=("chunk", "sub", "mm_dtype"))
def kda_chunk_scan(q, k, v, g, beta, s0, *, chunk=64, sub=16,
                   mm_dtype=jnp.float32):
    """A piece of ``T`` tokens from incoming state ``s0`` ``[H, dk, dv]``:
    ``q, k, g`` ``[T, H, dk]``, ``v`` ``[T, H, dv]``, ``beta`` ``[T, H]``.
    Returns ``(o [T, H, dv] float32, S_T float32)``: equal to
    `kda_recurrence`. A padded token carries ``g = 0`` and ``beta = 0``
    (the state passes it unchanged); ``T`` is padded so to whole chunks
    here. The triangular system and the decay scores run in float32; the
    products against the state take operands in ``mm_dtype`` and accumulate
    in float32."""
    f32 = jnp.float32
    T, H, dk = q.shape
    dv = v.shape[-1]
    chunk = min(chunk, -(-T // sub) * sub)
    sub = min(sub, chunk)
    if chunk % sub:
        raise ValueError("chunk %d is no multiple of sub %d" % (chunk, sub))
    n = -(-T // chunk)
    pad = n * chunk - T

    def heads_first(t):                                 # [T, H, .] -> [n, H, c, .]
        t = jnp.pad(t.astype(f32), ((0, pad),) + ((0, 0),) * (t.ndim - 1))
        t = t.reshape((n, chunk) + t.shape[1:])
        return jnp.moveaxis(t, 2, 1)

    q, k, v, g = (heads_first(t) for t in (q, k, v, g))
    beta = heads_first(beta[..., None])                         # [n,H,c,1]
    G = jnp.cumsum(g, axis=2)
    eG = jnp.exp(G)
    A = beta * _decay_scores(k, k, G, sub, strict=True)         # [n,H,c,c]
    rhs = jnp.concatenate([beta * v, beta * k * eG], axis=-1)
    sol = jax.scipy.linalg.solve_triangular(
        jnp.eye(chunk, dtype=f32) + A, rhs, lower=True, unit_diagonal=True)
    Uv, Wk = sol[..., :dv], sol[..., dv:]
    M = _decay_scores(q, k, G, sub, strict=False)               # [n,H,c,c]
    Gc = G[:, :, -1:, :]                                        # [n,H,1,dk]
    Kd = k * jnp.exp(Gc - G)
    Qg = q * eG
    prec = _HI if jnp.dtype(mm_dtype) == jnp.dtype(f32) else None

    def mm(spec, a, b):
        return jnp.einsum(spec, a.astype(mm_dtype), b.astype(mm_dtype),
                          preferred_element_type=f32, precision=prec)

    def one(S, x):
        Uv_c, Wk_c, M_c, Kd_c, Qg_c, eGc = x
        U = Uv_c - mm("hck,hkv->hcv", Wk_c, S)
        o = mm("hck,hkv->hcv", Qg_c, S) + mm("hci,hiv->hcv", M_c, U)
        S = eGc[..., None] * S + mm("hck,hcv->hkv", Kd_c, U)
        return S, o

    S, o = lax.scan(one, s0.astype(f32),
                    (Uv, Wk, M, Kd, Qg, jnp.exp(Gc[:, :, 0, :])))
    o = jnp.moveaxis(o, 1, 2).reshape(n * chunk, H, dv)         # [T+pad,H,dv]
    return o[:T], S
