"""Mixture-of-Experts with expert parallelism over an 'ep' mesh axis.

Two expert layers live here (ROADMAP.md C names the duplication):
`routed_experts`, the served one — top-k over the router's full width, told
which experts it holds, a grouped matrix product over those, no capacity and
no dropped token (models/moe_mla.py) — and `moe_ffn`, the older top-1 switch
with capacity buffers, reachable from no model.

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


def routed_experts(params, x, *, held, top_k, scale, axis_name=None,
                   valid=None, buckets=(64, 256, 512)):
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
    computed, whatever the routing. The grouped product has two forms and
    the busiest expert's count chooses between them (``lax.switch``: static
    shapes, one program):

    * *packed* — when no held expert was sent more than a bucket's rows
      (the smallest of ``buckets`` that fits), each expert's sorted rows
      fill a bucket of that many and the product is one batched ``[count,
      bucket, d] x [count, d, f]``. Up to 256 rows an expert it costs
      little more than reading the weights: what a decode step (a bucket of
      64) and most prefill pieces (256) take; a bucket of 512 costs twice
      that and still a third less than the ragged form;
    * *ragged* — otherwise ``jax.lax.ragged_dot`` over all ``T * top_k``
      sorted rows (what a routing that sends every token here needs). On a
      TPU its row tile is ``min(rows, 512)``, so every group costs a tile of
      512 however few its rows: right, never dropping, and twice the packed
      form at a decode step's sizes (PERF.md, PR 28).

    Each token then sums its own ``top_k`` weighted rows (a gather back to
    (token, choice) order: the same additions as a scatter-add, in a fixed
    order; a scatter-add of rows costs the TPU 8 microseconds a row).

    Returns ``(partial, counts)``: ``partial`` ``[T, d]`` float32 is the part
    of ``sum_e w_e Expert_e(x)`` that the held experts give — what the absent
    experts would add is left out, not stood in for — and ``counts``
    ``[count]`` int32 the assignments each held expert received.

    With ``axis_name`` this is a ``shard_map`` body: ``x`` and the router are
    replicated over the axis, the expert leaves are this share's, the share
    with index ``i`` holds experts ``first + i * count ..``, and the partial
    results are summed across the shares (``psum``); ``counts`` stays this
    share's. On one chip it runs without the exchange.
    """
    first, count = held
    if axis_name is not None:
        first = first + lax.axis_index(axis_name) * count
    T, d = x.shape
    rows = T * top_k
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
    wg, wu, wd = (params["experts_gate"], params["experts_up"],
                  params["experts_down"])
    mm = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)

    def packed(bucket):
        def run(_):
            # bucket b of expert e: e's sorted rows starts[e] .. + counts[e]
            idx = starts[:, None] + jnp.arange(bucket)[None, :]
            xe = jnp.take(x, jnp.take(token_of, jnp.clip(idx, 0, rows - 1)),
                          axis=0)                            # [count, b, d]
            h = jax.nn.silu(mm("ebd,edf->ebf", xe, wg)) * mm("ebd,edf->ebf", xe, wu)
            ye = mm("ebf,efd->ebd", h.astype(x.dtype), wd)
            at = jnp.clip(local, 0, count - 1)
            slot = rank.reshape(T, top_k) - jnp.take(starts, at)
            return jnp.take(ye.reshape(count * bucket, d),
                            at * bucket + jnp.clip(slot, 0, bucket - 1),
                            axis=0)
        return run

    def ragged(_):
        xs = jnp.take(x, token_of, axis=0)                   # [T*k, d]
        dot = functools.partial(lax.ragged_dot, group_sizes=counts,
                                preferred_element_type=jnp.float32)
        h = jax.nn.silu(dot(xs, wg)) * dot(xs, wu)
        ys = dot(h.astype(x.dtype), wd)                      # [T*k, d] f32
        return jnp.take(ys, rank, axis=0).reshape(T, top_k, d)

    with jax.named_scope("moe.experts"):
        busiest = jnp.max(counts)
        form = sum((busiest > b).astype(jnp.int32) for b in buckets)
        ys = lax.switch(form, [packed(b) for b in buckets] + [ragged], None)
        # a row that is not held here was never computed (or is another
        # assignment's): masked, not trusted to read zero
        w = jnp.where(here, weight, 0.0)
        partial = jnp.sum(jnp.where(here[:, :, None], ys, 0.0)
                          * w[:, :, None], axis=1)
    if axis_name is not None:
        partial = lax.psum(partial, axis_name)
    return partial, counts
