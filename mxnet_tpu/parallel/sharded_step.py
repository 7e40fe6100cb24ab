"""Multi-axis sharded train step: dp x tp x sp in ONE jitted program.

Generalizes `tpu_step.DataParallelTrainStep` beyond pure DP: parameters carry
arbitrary `PartitionSpec`s (tensor parallelism), the batch shards over 'dp',
the sequence axis over 'sp' (ring attention inside the model), and XLA derives
every collective from the sharding annotations — the scaling-book recipe,
replacing the reference's explicit KVStore push/pull + ps-lite/NCCL comm
(SURVEY.md §2.4, §3.2).

Optimizers run inside the same program with buffer donation ("update on
kvstore" semantics — the reference runs the optimizer on the PS server,
kvstore_dist_server.h:282; here it fuses into the step).
"""
from __future__ import annotations

import numpy as _np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..base import MXNetError

__all__ = ["ShardedTrainStep"]


class ShardedTrainStep:
    """jit(loss -> grads -> optimizer) over an arbitrary mesh.

    Parameters
    ----------
    loss_fn : callable(params, batch) -> scalar loss
        Pure; `batch` is a pytree of arrays with leading batch dim.
    mesh : jax.sharding.Mesh
    param_specs : pytree of PartitionSpec matching params
    batch_spec : PartitionSpec for batch leaves (default: shard dim 0 on 'dp')
    optimizer : 'sgd' | 'adam'
    """

    def __init__(self, loss_fn, mesh, param_specs, batch_spec=None,
                 optimizer="adam", lr=1e-3, momentum=0.9, wd=0.0,
                 beta1=0.9, beta2=0.999, eps=1e-8, grad_clip=None,
                 shard_update=None, zero=None, skip_nonfinite=False,
                 fused_optupdate=None):
        self.loss_fn = loss_fn
        # supervised numeric containment (resilience/supervisor.py's
        # pillar 1, composed-mesh form): the step computes an in-graph
        # all-finite verdict over loss + global grad norm and carries
        # params/opt_state unchanged on a bad step. The verdict device
        # scalar lands in `last_good` — readers fold it into whatever
        # readback they already do.
        self.skip_nonfinite = bool(skip_nonfinite)
        self.last_good = None
        self.mesh = mesh
        self.param_specs = param_specs
        if batch_spec is None:
            batch_spec = P("dp" if "dp" in mesh.axis_names else
                           mesh.axis_names[0])
        self.batch_spec = batch_spec
        self.optimizer = optimizer
        self.hp = dict(lr=lr, momentum=momentum, wd=wd, beta1=beta1,
                       beta2=beta2, eps=eps, grad_clip=grad_clip)
        # ZeRO-1 across the dp axis (see tpu_step): optimizer state for a
        # param replicated over 'dp' additionally shards its first free
        # divisible axis over 'dp' — composes with the tp shardings.
        # `zero` (or MXNET_TPU_ZERO=1) is the cross-step-consistent alias
        # for the same transform in the composed dp x tp case: here the
        # state keeps the param's own tp sharding per axis, so the
        # flatten/pad block layout tpu_step uses cannot apply — 'dp'
        # rides a free divisible axis instead, and the grads are
        # explicitly reduce-scattered onto that layout (see _build).
        dp_ok = "dp" in mesh.axis_names and mesh.shape["dp"] > 1
        if zero is None and shard_update is None:
            from ..base import env_flag
            if env_flag("MXNET_TPU_ZERO"):
                # env opt-in is opportunistic: without a real dp axis
                # there is nothing to shard over, keep the default
                zero = dp_ok or None
        flag_name = "shard_update"
        if zero is not None and shard_update is not None and \
                bool(zero) != bool(shard_update):
            raise MXNetError(
                "contradictory flags: zero=%r but shard_update=%r — in "
                "ShardedTrainStep zero IS the shard_update transform; "
                "pass only one" % (zero, shard_update))
        if shard_update is None and zero:
            # only a TRUTHY zero maps onto shard_update: zero=False means
            # "no ZeRO opinion" and keeps the auto-on default, matching
            # DataParallelTrainStep's semantics for the same flag
            shard_update = True
            flag_name = "zero"  # blame the flag the caller actually set
        if shard_update and not dp_ok:
            raise MXNetError(
                "%s=True needs a 'dp' mesh axis of size > 1; "
                "mesh axes are %r" % (flag_name, dict(mesh.shape)))
        self.shard_update = dp_ok if shard_update is None \
            else bool(shard_update)
        # Fused optimizer tier (kernels/opt_update) on the composed mesh.
        # Off the annotation-sharded (shard_update) path the update runs
        # as a fused_update_mesh shard_map island, where the Pallas
        # kernel tier engages per dp chunk; combined WITH shard_update
        # the state keeps its annotation layout and the update takes the
        # fused-lax sweep (pallas_call is not auto-partitionable inside
        # GSPMD-partitioned regions — only manual regions run it).
        if fused_optupdate is None:
            from ..base import env_flag
            fused_optupdate = env_flag("MXNET_TPU_FUSED_OPTUPDATE")
        self.fused_optupdate = bool(fused_optupdate)
        self._step_fn = None
        self.step_count = 0

    def _state_spec(self, param, spec):
        """State spec for one param: its own spec, plus 'dp' on the first
        unsharded, dp-divisible axis when weight-update sharding is on."""
        if not self.shard_update:
            return spec
        entries = tuple(spec)
        flat = [e for ent in entries if ent is not None
                for e in (ent if isinstance(ent, tuple) else (ent,))]
        if "dp" in flat:
            return spec  # already dp-sharded; an axis can't be reused
        dp = self.mesh.shape["dp"]
        ndim = getattr(param, "ndim", 0)
        entries = entries + (None,) * (ndim - len(entries))
        for i in range(ndim):
            if entries[i] is None and param.shape[i] % dp == 0 \
                    and param.shape[i] >= dp:
                return P(*entries[:i], "dp", *entries[i + 1:])
        return spec

    # ------------------------------------------------------------------
    def _shard(self, tree, specs):
        return jax.tree_util.tree_map(
            lambda x, s: jax.device_put(jnp.asarray(x),
                                        NamedSharding(self.mesh, s)),
            tree, specs)

    def init(self, params):
        """Place params on the mesh per spec; allocate optimizer state."""
        from .optim_update import init_opt_state
        self.params = self._shard(params, self.param_specs)
        if self.optimizer not in ("adam", "sgd"):
            raise MXNetError("unknown optimizer %r" % self.optimizer)
        self.opt_state = init_opt_state(self.optimizer, self.params,
                                        momentum=self.hp["momentum"])
        self._build()
        return self

    def _build(self):
        hp = self.hp
        opt = self.optimizer
        loss_fn = self.loss_fn
        mesh = self.mesh
        shard_update = self.shard_update
        # optimizer state shards like its param, PLUS 'dp' on a free axis
        # when weight-update sharding is on (state spec, not param spec)
        # two-tree tree_map flattens only up to the FIRST tree's leaves,
        # so each P arrives whole (same contract _shard relies on)
        state_specs = jax.tree_util.tree_map(
            self._state_spec, self.params, self.param_specs)

        skip_nonfinite = self.skip_nonfinite
        fused_opt = self.fused_optupdate
        dp_axis = "dp" if "dp" in mesh.axis_names else mesh.axis_names[0]
        from .mesh_kernels import resolve_kernel_tier
        kt_pallas, kt_interpret = resolve_kernel_tier()  # build-time knob

        def step(params, opt_state, batch):
            # device-side names for the trace (metadata only)
            with jax.named_scope("fwd_bwd"):
                loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            if skip_nonfinite:
                gsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                          for g in jax.tree_util.tree_leaves(grads))
                good = jnp.isfinite(loss) & jnp.isfinite(gsq)
            if hp["grad_clip"]:
                gnorm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                                     for g in jax.tree_util.tree_leaves(grads)))
                scale = jnp.minimum(1.0, hp["grad_clip"] / (gnorm + 1e-6))
                grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
            if hp["wd"]:
                grads = jax.tree_util.tree_map(
                    lambda g, p: g + hp["wd"] * p, grads, params)
            if shard_update:
                # explicit ZeRO scatter (arxiv 2004.13336): pin the grads
                # to the STATE layout (param spec + 'dp' on a free axis)
                # so the partitioner folds the pending cross-replica sum
                # into a reduce-scatter and the update below runs on 1/dp
                # of every slot-carrying tensor per replica; the param
                # out_shardings all-gather the fresh weights. Composes
                # with tp: the grad keeps its tensor-parallel axes.
                with jax.named_scope("grad_sync"):
                    grads = jax.tree_util.tree_map(
                        lambda g, s: jax.lax.with_sharding_constraint(
                            g, NamedSharding(mesh, s)),
                        grads, state_specs)
            with jax.named_scope("update"):
                if fused_opt and not shard_update:
                    # fused kernel tier as a dp shard_map island: transient
                    # (dp, chunk) blocks, kernel per eligible chunk, fresh
                    # params/slots all-gathered — bitwise equal to
                    # apply_update by the shared-prologue construction
                    from .mesh_kernels import fused_update_mesh
                    new_params, new_state = fused_update_mesh(
                        opt, hp, params, opt_state, grads, mesh, dp_axis,
                        use_pallas=kt_pallas, interpret=kt_interpret)
                elif fused_opt:
                    # annotation-sharded state (ZeRO layout) keeps its specs;
                    # one fused-lax sweep per leaf — the partitioner splits
                    # the elementwise update along the state layout
                    from ..kernels.opt_update import fused_update_step
                    new_params, new_state = fused_update_step(
                        opt, hp, params, opt_state, grads, use_pallas=False)
                else:
                    from .optim_update import apply_update
                    new_params, new_state = apply_update(opt, hp, params,
                                                         opt_state, grads)
            if skip_nonfinite:
                # carry the pre-step state through a bad update (the
                # donation-safe skip idiom shared with tpu_step)
                new_params = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(good, new, old),
                    new_params, params)
                new_state = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(good, new, old),
                    new_state, opt_state)
                return new_params, new_state, loss, good
            return new_params, new_state, loss

        if self.optimizer == "adam":
            opt_specs = {"m": state_specs, "v": state_specs, "t": P()}
        else:
            opt_specs = {"mom": state_specs
                         if self.opt_state["mom"] is not None else None}
        param_sh = jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), self.param_specs,
            is_leaf=lambda x: isinstance(x, P))
        opt_sh = jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), opt_specs,
            is_leaf=lambda x: isinstance(x, P))
        self._batch_sharding = NamedSharding(self.mesh, self.batch_spec)
        # the ONE lower/compile/cache path (compile/builder.py): same
        # dispatch semantics as the bare jit, plus warmup() AOT and the
        # per-site compile counters
        out_sh = (param_sh, opt_sh, NamedSharding(self.mesh, P()))
        if skip_nonfinite:
            out_sh = out_sh + (NamedSharding(self.mesh, P()),)
        from ..compile.builder import ProgramBuilder
        self._step_fn = ProgramBuilder(
            step, site="train.sharded_step",
            in_shardings=(param_sh, opt_sh, None),
            out_shardings=out_sh,
            donate_argnums=(0, 1))
        self.opt_state = self._shard(self.opt_state, opt_specs)

    # ------------------------------------------------------------------
    def comm_plan(self):
        """Declared comm contract for the TPL3xx program audit
        (analysis/program_audit.py). Gradient sums may land on any
        single mesh axis or axis combination (GSPMD is free to reduce
        per-axis or jointly, e.g. one all-reduce over ``dp+tp``);
        weight-update sharding additionally allows the ZeRO pair
        (reduce-scatter of grads onto the state layout, all-gather of
        fresh params) over dp. Anything else — a collective over an
        unexpected axis, or comm on a no-comm program — is TPL301."""
        from ..analysis.program_audit import CommPlan
        axes = [a for a in self.mesh.axis_names if self.mesh.shape[a] > 1]
        if not axes:
            return CommPlan(site="train.sharded_step", allowed=(),
                            max_programs=1)
        allowed = []
        for a in axes:
            allowed.append(("all-reduce", a, None))
        if len(axes) > 1:
            # joint-group reductions label as "ax1+ax2" (in mesh order)
            allowed.append(("all-reduce", "+".join(axes), None))
        if self.shard_update:
            dp_axis = "dp" if "dp" in self.mesh.axis_names \
                else self.mesh.axis_names[0]
            allowed += [("reduce-scatter", dp_axis, None),
                        ("all-gather", dp_axis, None)]
        elif self.fused_optupdate:
            allowed.append(("all-gather",
                            "dp" if "dp" in self.mesh.axis_names
                            else self.mesh.axis_names[0], None))
        return CommPlan(site="train.sharded_step", allowed=allowed,
                        max_programs=1)

    # ------------------------------------------------------------------
    def warmup(self, batch):
        """Ahead-of-time compile the sharded step from abstract shapes.
        ``batch`` is a pytree of arrays OR ShapeDtypeStruct-likes shaped
        like one GLOBAL batch; params/opt state shapes come from init().
        First step then pays dispatch only (and mostly disk with
        MXNET_TPU_COMPILE_CACHE set). Returns self."""
        if self._step_fn is None:
            raise MXNetError("call init() first")

        def sds(tree, sharding=None):
            # the batch arg has no jit-level in_sharding (unlike params/
            # state), so its abstract leaves must carry the dispatch-time
            # sharding explicitly or the executable would expect
            # unsharded inputs
            return jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    tuple(getattr(x, "shape", _np.shape(x))),
                    getattr(x, "dtype", _np.dtype(_np.float32)),
                    sharding=sharding),
                tree)

        self._step_fn.aot(sds(self.params), sds(self.opt_state),
                          sds(batch, sharding=self._batch_sharding))
        return self

    def __call__(self, batch):
        """One step on a global batch (pytree of numpy/jax arrays)."""
        if self._step_fn is None:
            raise MXNetError("call init() first")
        batch = jax.tree_util.tree_map(
            lambda x: jax.device_put(jnp.asarray(x), self._batch_sharding),
            batch)
        if self.skip_nonfinite:
            self.params, self.opt_state, loss, self.last_good = \
                self._step_fn(self.params, self.opt_state, batch)
        else:
            self.params, self.opt_state, loss = self._step_fn(
                self.params, self.opt_state, batch)
        self.step_count += 1
        return loss
