"""Tree-level fused optimizer updates shared by the sharded/pipelined/DP
train steps.

One definition of the in-program update math (the reference runs this on the
PS server / in optimizer_op.cc kernels; here it fuses into the jitted step).

Every function is shape-agnostic over its leaves: the same expressions run
on full per-param leaves (replicated update) and on ZeRO ``(dp, chunk)``
shard blocks (`zero.ZeroShardLayout`) — which is what makes the sharded
weight update bit-identical to the replicated one.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["init_opt_state", "apply_update", "apply_update_sharded",
           "grad_prologue"]

_tm = jax.tree_util.tree_map


def init_opt_state(optimizer, params, momentum=0.0, layout=None):
    """Optimizer-state pytree for 'sgd' (momentum optional) or 'adam'.

    With ``layout`` (a `zero.ZeroShardLayout`), per-param slots are
    allocated in the cross-replica sharded form — one ``(dp, chunk)``
    block per parameter instead of a param-shaped leaf — so per-replica
    slot memory is O(params/dp) from the first step. Scalar state (adam's
    ``t``) stays replicated either way.
    """
    def slot_named(name):
        m = layout.meta_by_name[name]
        return jnp.zeros((layout.dp, m["chunk"]), m["dtype"])
    if optimizer == "adam":
        if layout is None:
            return {"m": _tm(jnp.zeros_like, params),
                    "v": _tm(jnp.zeros_like, params),
                    "t": jnp.zeros((), jnp.int32)}
        return {"m": {n: slot_named(n) for n in params},
                "v": {n: slot_named(n) for n in params},
                "t": jnp.zeros((), jnp.int32)}
    if optimizer == "sgd":
        if not momentum:
            return {"mom": None}
        if layout is None:
            return {"mom": _tm(jnp.zeros_like, params)}
        return {"mom": {n: slot_named(n) for n in params}}
    raise ValueError("unknown optimizer %r" % optimizer)


def grad_prologue(params, grads, rescale=1.0, clip=None, wd=0.0):
    """Reference optimizer order (optimizer_op.cc): rescale -> clip ->
    + wd*weight. Shape-agnostic; shared by the replicated and sharded
    update paths so parity is by construction."""
    grads = {n: g * rescale for n, g in grads.items()}
    if clip is not None:
        grads = {n: jnp.clip(g, -clip, clip) for n, g in grads.items()}
    # unconditional like the kernel-tier _prologue: `g + 0.0*p` and `g`
    # differ in the non-finite edge cases bit-parity tests cover
    return {n: g + wd * params[n] for n, g in grads.items()}


def apply_update(optimizer, hp, params, opt_state, grads):
    """(params, opt_state) -> (new_params, new_opt_state).

    hp: dict with lr and, per optimizer, momentum / beta1 / beta2 / eps.
    Pure and jit-safe; weight decay and clipping are the caller's concern.
    """
    lr = hp["lr"]
    if optimizer == "adam":
        b1, b2, eps = hp["beta1"], hp["beta2"], hp["eps"]
        t = opt_state["t"] + 1
        m = _tm(lambda m, g: b1 * m + (1 - b1) * g, opt_state["m"], grads)
        v = _tm(lambda v, g: b2 * v + (1 - b2) * g * g, opt_state["v"], grads)
        tf = t.astype(jnp.float32)
        corr = jnp.sqrt(1 - b2 ** tf) / (1 - b1 ** tf)
        params = _tm(lambda p, m, v: p - lr * corr * m / (jnp.sqrt(v) + eps),
                     params, m, v)
        return params, {"m": m, "v": v, "t": t}
    if optimizer == "sgd":
        momentum = hp.get("momentum", 0.0)
        if opt_state["mom"] is not None:
            mom = _tm(lambda mo, g: momentum * mo - lr * g,
                      opt_state["mom"], grads)
            params = _tm(lambda p, mo: p + mo, params, mom)
            return params, {"mom": mom}
        return _tm(lambda p, g: p - lr * g, params, grads), opt_state
    raise ValueError("unknown optimizer %r" % optimizer)


def apply_update_sharded(optimizer, hp, params, opt_state, grads, layout,
                         mesh, rescale=1.0, clip=None, wd=0.0,
                         fused=False, cast_grads=None,
                         use_pallas=None, interpret=False):
    """ZeRO form of prologue + `apply_update` (arxiv 2004.13336): runs
    INSIDE the jitted step, as a `shard_map` island over the dp axis.

    The manual region is the load-bearing choice: a GSPMD sharding
    constraint on the (dp, chunk) blocks PROPAGATES — through
    optimization barriers, reshapes, everything — back into the forward/
    backward, and the partitioner happily re-partitions the model
    tensor-parallel around it (full-rematerialization warnings, batch
    sums re-grouped, grads off by 1e-6 from the replicated program).
    Inside shard_map nothing propagates: the forward/backward stays the
    exact graph the replicated step compiles.

    Per replica, the body slices its own 1/dp chunk of the (replicated,
    already all-reduced) grads and params, runs the prologue + update on
    just that chunk against its resident slot shard, and `all_gather`s
    the fresh param chunks back to full shape. Grads enter with spec
    ``P()`` — the partitioner materializes the SAME all-reduce the
    replicated program runs, in the same place, so the summed bits are
    identical by construction. The update math is the shared shape-
    agnostic expressions above, so the whole step is BITWISE equal to
    the replicated update (test_zero_update.py asserts it across
    optimizers x precision x fused tiers). Trade-off vs the paper's
    reduce-scatter: grad comm stays at the baseline all-reduce volume
    (a reduce-scatter re-groups the partial sums and costs bit parity);
    the O(params/dp) persistent slot memory and the 1/dp update
    FLOPs/bytes — the memory wall ZeRO exists for — are fully realized.

    ``opt_state`` per-param slots must already be in the layout's block
    form (`init_opt_state(..., layout=)`); scalar state (adam's ``t``)
    rides replicated. Returns ``(new_params_full, new_opt_state_blocks)``.

    ``fused=True`` routes the chunk update through
    `kernels/opt_update.fused_update_step`. `pallas_call` is not
    auto-partitionable, but INSIDE this manual region there is nothing to
    partition — each replica's chunk is a plain local array — so the
    Pallas kernel tier dispatches per chunk like anywhere else:
    ``use_pallas``/``interpret`` thread straight through (None =
    auto-gate on TPU; ``interpret=True`` is the off-TPU kernel tier the
    parity suite runs). Chunks keep the kernel's eligibility rules —
    (1, chunk) f32 blocks with chunk a multiple of 128 and >= 1024
    elements take the kernel, the rest take the fused-lax path — and the
    tiers are bitwise-identical by the shared-prologue construction.

    ``cast_grads`` applies the multi-precision (bf16-compute/fp32-master)
    grad cast to the chunk INSIDE the body: same numbers as casting
    before the slice, but the cast lands in the same fused loop as the
    update math, mirroring the replicated path's loop composition.
    """
    from jax.sharding import PartitionSpec as P
    from .collectives import shard_map

    axis = layout.axis_name
    block_spec = P(axis, None)
    # lr is a traced scalar — it must enter the manual region as an
    # argument, never a closure; the rest of hp is static Python floats
    hp_static = {k: v for k, v in hp.items() if k != "lr"}

    def spec_of(x):
        # (dp, chunk) slot blocks ride sharded; scalars (adam's t) replicated
        return block_spec if getattr(x, "ndim", 0) >= 1 else P()

    state_specs = jax.tree_util.tree_map(spec_of, opt_state)

    def body(params, opt_state, grads, lr):
        idx = jax.lax.axis_index(axis)

        def chunk_of(x, name):
            # ONE definition of the flatten/pad/block layout (scatter);
            # checkpoint restore depends on the same invariant via
            # pack_host/unpack_host
            return jax.lax.dynamic_slice_in_dim(
                layout.scatter(x, name), idx, 1, axis=0)

        g_sh = {n: chunk_of(grads[n], n) for n in params}
        p_sh = {n: chunk_of(params[n], n) for n in params}
        if cast_grads is not None:
            g_sh = {n: g.astype(cast_grads) for n, g in g_sh.items()}
        hp_l = dict(hp_static, lr=lr)
        if fused:
            from ..kernels.opt_update import fused_update_step
            new_p_sh, new_state = fused_update_step(
                optimizer, hp_l, p_sh, opt_state, g_sh,
                rescale=rescale, clip=clip, wd=wd,
                use_pallas=use_pallas, interpret=interpret)
        else:
            g_sh = grad_prologue(p_sh, g_sh, rescale=rescale, clip=clip,
                                 wd=wd)
            new_p_sh, new_state = apply_update(optimizer, hp_l, p_sh,
                                               opt_state, g_sh)

        def regather(chunk, name):
            m = layout.meta_by_name[name]
            full = jax.lax.all_gather(chunk.reshape(m["chunk"]), axis,
                                      tiled=True)
            return full[:m["size"]].reshape(m["shape"])

        new_params = {n: regather(new_p_sh[n], n) for n in params}
        return new_params, new_state

    fn = shard_map(
        body, mesh=mesh,
        in_specs=({n: P() for n in params}, state_specs,
                  {n: P() for n in params}, P()),
        out_specs=({n: P() for n in params}, state_specs),
        check_vma=False)
    return fn(params, opt_state, grads, jnp.asarray(hp["lr"], jnp.float32))
