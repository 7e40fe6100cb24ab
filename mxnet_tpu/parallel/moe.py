"""Mixture-of-Experts with expert parallelism over an 'ep' mesh axis.

Two expert layers live here (ROADMAP.md C names the duplication):
`routed_experts`, the served one (models/moe_mla.py) — top-k over the
router's full width, told which experts it holds, ONE grouped matrix
product a projection over the held assignments sorted by expert, no
capacity and no dropped token. The product has a form a tier: on the
kernel tier `kernels/grouped_experts.py` (``mx_grouped_experts``) over the
first ``cap`` sorted rows in row tiles of 128, each row then summed into its
token by a one-hot product, so that a call costs what its HELD rows cost;
on the lax tier (the CPU's path and the kernel's reference) a packed
bucket an expert or ``jax.lax.ragged_dot``, each token gathering its own
rows back. And `moe_ffn`, the older top-1 switch with capacity buffers,
reachable from no model.

`moe_ffn`:

Absent in the reference (SURVEY.md §2.8: no EP/MoE); TPU-native capability.
Design: switch (top-1) routing with capacity buffers, expressed as dense
einsums with one-hot dispatch/combine masks — static shapes throughout, so
XLA can tile everything onto the MXU — and `lax.all_to_all` over 'ep' to move
token buffers to the devices that own their experts (the canonical
expert-parallel exchange; rides ICI).

All functions are shard_map bodies: call inside `jax.shard_map` with the
token axis sharded over 'ep' and expert weights sharded on their leading
expert axis over 'ep'. (For an additional 'dp' token axis, pmean the aux
loss over 'dp' yourself — it is only reduced over `axis_name` here.)
"""
from __future__ import annotations

import functools

import numpy as _np
import jax
import jax.numpy as jnp
from jax import lax

from ..kernels.grouped_experts import ROW_TILE, activate, grouped_experts

__all__ = ["init_moe_ffn", "moe_ffn", "routed_experts"]


def init_moe_ffn(key, num_experts, d_model, d_ff, dtype=jnp.float32):
    """Params for a switch-FFN layer. Leading expert axis shards over 'ep'."""
    k1, k2, k3 = jax.random.split(key, 3)
    s = 0.02
    return {
        "wg": (jax.random.normal(k1, (d_model, num_experts)) * s).astype(dtype),
        "w1": (jax.random.normal(k2, (num_experts, d_model, d_ff)) * s).astype(dtype),
        "w2": (jax.random.normal(k3, (num_experts, d_ff, d_model)) * s).astype(dtype),
    }


def moe_ffn(params, x, axis_name="ep", capacity_factor=2.0):
    """Switch-routed expert FFN; shard_map body.

    params: {'wg': [d, E] replicated, 'w1': [e_local, d, f], 'w2':
        [e_local, f, d]} — expert leaves pre-sharded over `axis_name`.
    x: [T_local, d] local token slab.
    Returns ([T_local, d], aux_loss) — aux_loss is the switch load-balancing
    loss, E * sum_e(load_e * importance_e) (Switch Transformer eq. 4),
    pmean-ed over `axis_name`.
    """
    n = lax.psum(1, axis_name)
    e_local = params["w1"].shape[0]
    E = e_local * n
    T, d = x.shape
    C = int(_np.ceil(capacity_factor * T / E))

    gate_logits = x @ params["wg"]                   # [T, E]
    probs = jax.nn.softmax(gate_logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)              # [T]
    gate = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]

    # position of each token within its expert's capacity buffer
    onehot = jax.nn.one_hot(expert, E, dtype=jnp.int32)          # [T, E]
    pos = (jnp.cumsum(onehot, axis=0) - 1) * onehot              # [T, E]
    pos_tok = jnp.sum(pos, axis=1)                               # [T]
    keep = pos_tok < C
    # dispatch/combine one-hots (dropped tokens vanish)
    disp = (jax.nn.one_hot(expert, E)[:, :, None] *
            jax.nn.one_hot(jnp.clip(pos_tok, 0, C - 1), C)[:, None, :] *
            keep[:, None, None])                                 # [T, E, C]
    comb = disp * gate[:, None, None]

    # load-balancing loss (Switch Transformer eq. 4)
    load = jnp.mean(jax.nn.one_hot(expert, E, dtype=jnp.float32), axis=0)
    importance = jnp.mean(probs, axis=0)
    aux_loss = lax.pmean(E * jnp.sum(load * importance), axis_name)

    buf = jnp.einsum("tec,td->ecd", disp, x)                     # [E, C, d]
    # exchange: send each expert's buffer to its owner device
    buf = buf.reshape(n, e_local, C, d)
    buf = lax.all_to_all(buf, axis_name, split_axis=0, concat_axis=0,
                         tiled=False)                            # [n, e_local, C, d]
    buf = jnp.moveaxis(buf, 0, 1).reshape(e_local, n * C, d)

    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", buf, params["w1"]))
    out = jnp.einsum("ecf,efd->ecd", h, params["w2"])            # [e_local, n*C, d]

    # reverse exchange
    out = jnp.moveaxis(out.reshape(e_local, n, C, d), 1, 0)
    out = lax.all_to_all(out, axis_name, split_axis=0, concat_axis=0,
                         tiled=False)
    out = out.reshape(E, C, d)
    y = jnp.einsum("tec,ecd->td", comb, out)
    return y.astype(x.dtype), aux_loss


def _held_caps(rows, share):
    """Sorted-row capacities of the grouped form for ``rows`` assignments
    of which a router that spreads evenly sends ``share`` here: that
    expectation and a quarter, twice the expectation, and all rows (so
    nothing is ever dropped), each in whole row tiles (under a tile: whole
    sublane groups of 16)."""
    expected = max(int(rows * share), 1)
    unit = lambda c: ROW_TILE if c >= ROW_TILE else 16          # noqa: E731
    return sorted({-(-c // unit(c)) * unit(c)
                   for c in (min(expected + expected // 4, rows),
                             min(2 * expected, rows), rows)})


def _sum_by_token(ys, token, T):
    """``[T, d]`` float32: row ``r`` of ``ys`` ``[cap, d]`` (float32) added
    into token ``token[r]`` (a ``token`` outside ``0 .. T-1`` adds nowhere).
    A float32 product with the ``[T, cap]`` one-hot matrix: the matrix unit
    sums ``cap`` rows where a gather to (token, choice) order moves ``T *
    top_k`` of them and a scatter-add costs 8 microseconds a row."""
    onehot = (token[None, :] == jnp.arange(T)[:, None]).astype(jnp.float32)
    return jnp.matmul(onehot, ys, precision=lax.Precision.HIGHEST)


def routed_experts(params, x, *, held, top_k, scale, axis_name=None,
                   valid=None, buckets=(64, 256, 512), use_pallas=False,
                   interpret=False, activation="silu"):
    """The routed part of an expert layer, for the experts held HERE.

    ``held = (first, count)`` names the experts whose weights ``params``
    carries: ``params["router"]`` is ``[d, E]`` over ALL ``E`` experts,
    ``params["experts_gate"]`` / ``["experts_up"]`` are ``[count, d, f]`` and
    ``["experts_down"]`` ``[count, f, d]`` for experts ``first .. first +
    count - 1``. ``x`` is ``[T, d]``; ``valid`` (``[T]`` bool, optional) marks
    the real tokens: a padding row is routed nowhere and counted nowhere.

    Routing runs over the router's full width in float32: ``sigma =
    sigmoid(x W_r)``, the ``top_k`` largest, weights ``scale * sigma_e /
    (sum over the chosen + 1e-20)`` — normalised over all ``top_k`` whether
    held here or not. The (token, expert) assignments that fall in ``held``
    are sorted by expert (the others sort behind them), and each projection
    is ONE grouped matrix product over the held experts: the rows of expert
    ``e`` meet ``e``'s matrix and no other. No one-hot dispatch tensor, no
    capacity, no dropped token: every assignment to a held expert is
    computed, whatever the routing. The traced counts choose the product's
    form among those the tier has (``lax.switch``: static shapes, one
    program); every form ends in its own combine and hands back ``[T, d]``
    float32.

    **The kernel tier** (``use_pallas``; ``interpret`` for the CPU's tests)
    has one form, *grouped*: the first ``cap`` SORTED rows, ``cap`` the
    smallest of `_held_caps` that holds every held assignment (what an even
    router sends ``count`` of its ``E`` experts and a quarter, twice that,
    all ``T * top_k`` rows), through
    `kernels/grouped_experts.py` (``mx_grouped_experts``: row tiles of 128
    paired with their experts, an expert's weights fetched once). Each row
    is then weighted and summed into its token by a one-hot product
    (`_sum_by_token`). What it costs follows the rows that are HELD, not
    ``count x bucket`` and not ``T * top_k``: a prefill piece's and a decode
    step's form alike (PERF.md, PR 37).

    **The lax tier** (the CPU's path, and the kernel's reference) has two,
    and the busiest expert's count chooses:

    * *packed* — when no held expert was sent more than a bucket's rows
      (the smallest of ``buckets`` that fits), each expert's sorted rows
      fill a bucket of that many and the product is one batched ``[count,
      bucket, d] x [count, d, f]``: ``count x bucket`` row-equivalents;
    * *ragged* — otherwise ``jax.lax.ragged_dot`` over all ``T * top_k``
      sorted rows (on a TPU its row tile is ``min(rows, 512)``, so every
      group costs a tile of 512 however few its rows; PERF.md, PR 28).

    Each token then gathers its own ``top_k`` weighted rows back and sums
    them (the same additions as a scatter-add, in a fixed order).

    ``activation`` is the experts' (`kernels/grouped_experts.py`):
    ``"silu"``, or ``("polynorm", eps, scale, clamp)`` with each held
    expert's coefficients in ``params["experts_poly"]`` ``[count, 4]``; the
    lax forms round the PolyNorm expert's gate and up products to ``x``'s
    dtype before it, as the kernel hands them on.

    Returns ``(partial, counts, cost)``: ``partial`` ``[T, d]`` float32 is
    the part of ``sum_e w_e Expert_e(x)`` that the held experts give — what
    the absent experts would add is left out, not stood in for; ``counts``
    ``[count]`` int32 the assignments each held expert received; ``cost``
    two int32 counters of the form that ran: ``moe_rows_computed`` (the
    row-equivalents its products covered: row tiles x 128, ``count x
    bucket``, or all ``T * top_k``; over ``sum(counts)`` it is the form's
    padding) and ``moe_form_grouped`` (1 where the grouped form ran).

    With ``axis_name`` this is a ``shard_map`` body: ``x`` and the router are
    replicated over the axis, the expert leaves are this share's, the share
    with index ``i`` holds experts ``first + i * count ..``, and the partial
    results are summed across the shares (``psum``); ``counts`` and ``cost``
    stay this share's. On one chip it runs without the exchange.
    """
    first, count = held
    if axis_name is not None:
        first = first + lax.axis_index(axis_name) * count
    T, d = x.shape
    rows = T * top_k
    kernel_tier = bool(use_pallas or interpret)
    buckets = sorted({min(int(b), rows) for b in buckets})
    with jax.named_scope("moe.route"):
        logits = jnp.matmul(x.astype(jnp.float32),
                            params["router"].astype(jnp.float32),
                            precision=lax.Precision.HIGHEST)
        sigma = jax.nn.sigmoid(logits)                       # [T, E]
        top_s, top_e = lax.top_k(sigma, top_k)               # [T, k]
        weight = scale * top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)
        local = top_e - first
        here = (local >= 0) & (local < count)
        if valid is not None:
            here = here & valid[:, None]
        # held assignments first, grouped by expert; the rest behind them
        key = jnp.where(here, local, count).reshape(rows)
        order = jnp.argsort(key, stable=True)
        rank = jnp.argsort(order)           # an assignment's sorted position
        counts = jnp.sum(key[:, None] == jnp.arange(count)[None, :],
                         axis=0, dtype=jnp.int32)            # [count]
        starts = jnp.cumsum(counts) - counts
        token_of = order // top_k
        # a row that is not held here is never computed (or is another
        # assignment's): masked, not trusted to read zero
        w = jnp.where(here, weight, 0.0)
    wg, wu, wd = (params["experts_gate"], params["experts_up"],
                  params["experts_down"])
    coef = params.get("experts_poly")
    mm = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)

    def act(gate, up, c):
        """The lax forms' ``act(gate) * up``; ``c``: the rows'
        coefficients."""
        if activation == "silu":
            return jax.nn.silu(gate) * up
        f32 = lambda t: t.astype(x.dtype).astype(jnp.float32)   # noqa: E731
        return activate(activation, f32(gate), f32(up), c)

    def whole(n):
        """A form's static cost as the traced ones are typed: in a
        `shard_map` body the branches' results must vary over the same
        axes, and a count does."""
        return counts[0] * 0 + n

    def own(ys):
        """Each token's own ``top_k`` weighted rows, summed."""
        return jnp.sum(jnp.where(here[:, :, None], ys, 0.0) * w[:, :, None],
                       axis=1)

    def packed(bucket):
        def run(_):
            # bucket b of expert e: e's sorted rows starts[e] .. + counts[e]
            idx = starts[:, None] + jnp.arange(bucket)[None, :]
            xe = jnp.take(x, jnp.take(token_of, jnp.clip(idx, 0, rows - 1)),
                          axis=0)                            # [count, b, d]
            h = act(mm("ebd,edf->ebf", xe, wg), mm("ebd,edf->ebf", xe, wu),
                    None if coef is None else coef[:, None, :])
            ye = mm("ebf,efd->ebd", h.astype(x.dtype), wd)
            at = jnp.clip(local, 0, count - 1)
            slot = rank.reshape(T, top_k) - jnp.take(starts, at)
            ys = jnp.take(ye.reshape(count * bucket, d),
                          at * bucket + jnp.clip(slot, 0, bucket - 1), axis=0)
            return own(ys), whole(count * bucket)
        return run

    def ragged(_):
        xs = jnp.take(x, token_of, axis=0)                   # [T*k, d]
        dot = functools.partial(lax.ragged_dot, group_sizes=counts,
                                preferred_element_type=jnp.float32)
        h = act(dot(xs, wg), dot(xs, wu), None if coef is None else
                jnp.take(coef, jnp.minimum(jnp.take(key, order), count - 1),
                         axis=0))
        ys = dot(h.astype(x.dtype), wd)                      # [T*k, d] f32
        return (own(jnp.take(ys, rank, axis=0).reshape(T, top_k, d)),
                whole(rows))

    def grouped(cap):
        def run(_):
            r = jnp.arange(cap)
            at = jnp.take(order, jnp.minimum(r, rows - 1))   # assignments
            token = at // top_k
            ys, cost = grouped_experts(jnp.take(x, token, axis=0), counts,
                                       wg, wu, wd, interpret=interpret,
                                       activation=activation, coef=coef)
            # sorted rows past the held ones were never written
            live = r < total
            ys = jnp.where(live[:, None], ys, 0.0) \
                * jnp.take(w.reshape(rows), at)[:, None]
            return _sum_by_token(ys, jnp.where(live, token, T), T), cost
        return run

    with jax.named_scope("moe.experts"):
        if kernel_tier:
            # the first capacity that holds all held rows
            total = jnp.sum(counts)
            caps = _held_caps(rows, count / params["router"].shape[1])
            form = sum((total > c).astype(jnp.int32) for c in caps[:-1])
            forms = [grouped(c) for c in caps]
        else:
            # the first bucket that holds the busiest expert's rows, else ...
            busiest = jnp.max(counts)
            form = sum((busiest > b).astype(jnp.int32) for b in buckets)
            forms = [packed(b) for b in buckets] + [ragged]
        partial, computed = lax.switch(form, forms, None)
    cost = {"moe_rows_computed": computed,
            "moe_form_grouped": whole(int(kernel_tier))}
    if axis_name is not None:
        partial = lax.psum(partial, axis_name)
    return partial, counts, cost
