"""Sharded, fused train step — the heart of the `tpu_sync` design.

Reference path (SURVEY.md §3.1-3.2): forward → backward → kvstore.push(grad) →
server optimizer → kvstore.pull(weight), each a separate engine/network op
(reference python/mxnet/model.py:126-136). TPU-native: ONE jitted program:
forward + backward + gradient allreduce + optimizer update. Sharding
annotations (batch over 'dp', params replicated) let XLA insert the ICI
collectives — no hand-written comm. Module wires this in when
`kvstore='tpu_sync'` (module/module.py), so `fit` is one XLA dispatch/step.
"""
from __future__ import annotations

import numpy as _np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec, NamedSharding

from ..base import MXNetError

__all__ = ["DataParallelTrainStep"]


class DataParallelTrainStep:
    """Compile a Symbol's forward+backward+optimizer-update into one sharded
    XLA program.

    Parameters live as a dict of jax arrays (replicated over the mesh); each
    call consumes a global batch sharded along 'dp' and returns outputs plus
    updated params — buffer donation makes the update in-place on device.

    `lr` is a runtime argument of the jitted program, so lr schedules never
    trigger recompilation.
    """

    def __init__(self, symbol, mesh, lr=0.01, momentum=0.0, wd=0.0,
                 data_names=("data",), label_names=("softmax_label",),
                 sharding_config=None, rescale_grad=None, optimizer="sgd",
                 opt_hp=None, fixed_param_names=(), clip_gradient=None,
                 compute_dtype=None, shard_update=None,
                 fused_optupdate=None, zero=None, supervise=False):
        self.symbol = symbol
        # supervised numeric containment (resilience/supervisor.py): the
        # step takes a runtime loss-scale argument, seeds the backward
        # pass with it, unscales grads in-graph, and returns an
        # all-finite verdict; a bad step CARRIES params/opt_state/aux
        # unchanged through jnp.where. Off by default — the unsupervised
        # program is byte-identical to before (zero-overhead contract).
        self.supervise = bool(supervise)
        self.last_flag = None  # device verdict of the latest supervised step
        # stochastic-op scan decides whether steps draw fresh keys or reuse
        # one cached replicated key (see __call__)
        self._needs_rng = symbol._needs_rng()
        self._fixed_rng = None  # device-put copy of random.fixed_key()
        # MXNET_TPU_LINT jaxpr sweep armed by _lint_step, run on the first
        # __call__ (batch dtypes are only known then)
        self._lint_sweep_pending = False
        self.mesh = mesh
        self.lr = lr
        self.momentum = momentum
        self.wd = wd
        self.data_names = list(data_names)
        self.label_names = list(label_names)
        self.sharding_config = sharding_config
        self.optimizer = optimizer
        # static hyperparams baked into the program (momentum/beta1/beta2/eps)
        self.opt_hp = dict(opt_hp or {})
        if optimizer == "sgd":
            self.opt_hp.setdefault("momentum", momentum)
        self.fixed_param_names = frozenset(fixed_param_names or ())
        self.clip_gradient = clip_gradient
        # Mixed precision, TPU-native form of the reference's fp16 +
        # mp_sgd_update path (src/operator/optimizer_op.cc MP_SGD: fp16
        # weights with an fp32 master copy on the kvstore): master params
        # and the optimizer update stay fp32; the jitted program casts
        # params+batch to `compute_dtype` (bf16 on TPU) for fwd+bwd, and
        # grads are cast back to fp32 before the update. BN aux state
        # remains fp32 throughout.
        self.compute_dtype = (jnp.dtype(compute_dtype)
                              if compute_dtype is not None else None)

        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.param_names = [n for n in self.arg_names
                            if n not in self.data_names + self.label_names]
        self._rescale = rescale_grad

        self._repl = NamedSharding(mesh, PartitionSpec())
        self._dp_axis = "dp" if "dp" in mesh.axis_names else mesh.axis_names[0]
        self._batch_shard = NamedSharding(mesh, PartitionSpec(self._dp_axis))
        # Cross-replica weight-update sharding (Xu et al.,
        # arxiv 2004.13336 — the GSPMD weight-update-sharding transform,
        # ZeRO-1's TPU form): optimizer state shards over the dp axis, so
        # per-chip optimizer memory and update FLOPs drop by dp; the
        # annotation leaves any all-reduce/all-gather placement to XLA.
        # Auto-on when the dp axis is real (>1).
        dp_size = mesh.shape[self._dp_axis]
        self.shard_update = (dp_size > 1 if shard_update is None
                             else bool(shard_update))
        # ZeRO-style EXPLICIT update sharding (MXNET_TPU_ZERO=1 or ctor
        # arg): every param flattens/pads into a (dp, chunk) block
        # (parallel/zero.py), each replica slices and updates its 1/dp
        # shard of the all-reduced grads, params + slots (fp32 masters
        # included in the bf16 multi-precision path), and the fresh
        # params all-gather in-graph — a shard_map island; see
        # optim_update.apply_update_sharded for the comm/bitwise trade.
        # Strictly stronger than `shard_update`'s
        # annotation form: bias vectors and dp-indivisible shapes shard
        # too, so per-replica slot memory is exactly O(params/dp).
        # Supersedes shard_update when on.
        if zero is None:
            from ..base import env_flag
            # env opt-in is opportunistic (same policy as ShardedTrainStep):
            # with a 1-way dp axis there is nothing to shard — the layout
            # would only cost the single-device Pallas fused-optupdate tier
            # and the slot donation for zero benefit
            zero = env_flag("MXNET_TPU_ZERO") and dp_size > 1
        self.zero = bool(zero)
        self._zero_layout = None  # built with the params in _init_opt_state
        # fused optimizer-update kernel (kernels/opt_update.py): one
        # memory-bound Pallas sweep per param block instead of the
        # apply_update tree-map chain — bit-parity either way. Opt-in via
        # MXNET_TPU_FUSED_OPTUPDATE=1 (or the ctor arg).
        if fused_optupdate is None:
            from ..base import env_flag
            fused_optupdate = env_flag("MXNET_TPU_FUSED_OPTUPDATE")
        self.fused_optupdate = bool(fused_optupdate)
        self._step = None

    def _state_sharding_leaf(self, x):
        """dp-shard a state leaf on axis 0 when divisible; else replicate."""
        dp = self.mesh.shape[self._dp_axis]
        if (self.shard_update and getattr(x, "ndim", 0) >= 1
                and x.shape[0] >= dp and x.shape[0] % dp == 0):
            return NamedSharding(
                self.mesh, PartitionSpec(self._dp_axis,
                                         *([None] * (x.ndim - 1))))
        return self._repl

    def _state_shardings(self):
        if self.zero:
            zsh = self._zero_layout.sharding(self.mesh)
            # per-param slots are (dp, chunk) blocks sharded over dp;
            # scalar state (adam's t) stays replicated
            return jax.tree_util.tree_map(
                lambda x: zsh if getattr(x, "ndim", 0) >= 1 else self._repl,
                self.opt_state)
        return jax.tree_util.tree_map(self._state_sharding_leaf,
                                      self.opt_state)

    # ------------------------------------------------------------------
    def init(self, batch_shapes, dtype=_np.float32, seed=0):
        """Infer shapes, initialize replicated params + opt state, build the step."""
        arg_shapes, _, aux_shapes = self.symbol.infer_shape(**batch_shapes)
        shapes = dict(zip(self.arg_names, arg_shapes))
        key = jax.random.PRNGKey(seed)
        params = {}
        for name in self.param_names:
            key, sub = jax.random.split(key)
            shape = shapes[name]
            if name.endswith("_bias") or name.endswith("_beta") or \
                    name.endswith("_gamma"):
                init = (jnp.ones(shape, dtype) if name.endswith("_gamma")
                        else jnp.zeros(shape, dtype))
            else:
                fan_in = _np.prod(shape[1:]) if len(shape) > 1 else shape[0]
                scale = _np.sqrt(2.0 / max(fan_in, 1))
                init = jax.random.normal(sub, shape, dtype) * scale
            params[name] = jax.device_put(init, self._repl)
        aux = {name: jax.device_put(
                   jnp.ones(s, dtype) if "var" in name else jnp.zeros(s, dtype),
                   self._repl)
               for name, s in zip(self.aux_names, aux_shapes)}
        self.params, self.aux = params, aux
        self._init_opt_state()
        self._build_step(batch_shapes)
        return self

    def init_from(self, arg_params, aux_params, batch_shapes):
        """Adopt existing parameter values (dict name -> NDArray/ndarray) —
        the Module path: init_params already ran, this step becomes the
        device-side authority for them during fit."""
        self.params = {n: jax.device_put(jnp.asarray(
                           arg_params[n].asnumpy()  # tpulint: allow-host-sync one-time param adoption at build, not per-step
                           if hasattr(arg_params[n], "asnumpy")
                           else arg_params[n]), self._repl)
                       for n in self.param_names}
        self.aux = {n: jax.device_put(jnp.asarray(
                        aux_params[n].asnumpy()  # tpulint: allow-host-sync one-time param adoption at build, not per-step
                        if hasattr(aux_params[n], "asnumpy")
                        else aux_params[n]), self._repl)
                    for n in self.aux_names}
        self._init_opt_state()
        self._build_step(batch_shapes)
        return self

    def reload_params(self, arg_params, aux_params):
        """Overwrite device param/aux values in place, PRESERVING optimizer
        state and the compiled program (no re-jit, no momentum reset)."""
        self.params = {n: jax.device_put(jnp.asarray(
                           arg_params[n].asnumpy()  # tpulint: allow-host-sync checkpoint-restore reload, off the step path
                           if hasattr(arg_params[n], "asnumpy")
                           else arg_params[n]), self._repl)
                       for n in self.param_names}
        self.aux = {n: jax.device_put(jnp.asarray(
                        aux_params[n].asnumpy()  # tpulint: allow-host-sync checkpoint-restore reload, off the step path
                        if hasattr(aux_params[n], "asnumpy")
                        else aux_params[n]), self._repl)
                    for n in self.aux_names}

    def _init_opt_state(self):
        from .optim_update import init_opt_state
        momentum = self.opt_hp.get("momentum", self.momentum)
        if self.zero:
            from .zero import ZeroShardLayout
            self._zero_layout = ZeroShardLayout.from_params(
                self.params, self.mesh.shape[self._dp_axis],
                axis_name=self._dp_axis)
            self.opt_state = init_opt_state(
                self.optimizer, self.params, momentum=momentum,
                layout=self._zero_layout)
            self._record_zero_counters()
        else:
            self.opt_state = init_opt_state(
                self.optimizer, self.params, momentum=momentum)
        # place state with its (possibly dp-sharded) layout up front so
        # the first step doesn't reshard
        self.opt_state = jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, s),
            self.opt_state, self._state_shardings())
        # keep legacy attribute for existing callers/tests
        self.moms = self.opt_state.get("mom") or {}

    def _record_zero_counters(self):
        """Always-on profiler accounting for the sharded update: what the
        MULTICHIP bench banks (per-replica slot bytes, scatter/gather
        volumes) comes straight from the layout arithmetic."""
        from .. import profiler
        lay = self._zero_layout
        momentum = self.opt_hp.get("momentum", self.momentum)
        comm = lay.comm_bytes()
        profiler.record_zero_sharding(
            dp=lay.dp,
            opt_state_bytes_per_replica=lay.per_replica_slot_bytes(
                self.optimizer, momentum),
            opt_state_bytes_replicated=lay.replicated_slot_bytes(
                self.optimizer, momentum),
            grad_allreduce_bytes=comm["grad_allreduce_bytes"],
            update_gather_bytes=comm["gather_bytes"],
            param_bytes=lay.param_bytes())

    def opt_state_layout_meta(self):
        """Checkpoint manifest entry describing the sharded slot layout
        (None when the update is replicated) — restore uses it to
        reassemble canonical slots, including under a different replica
        count (checkpoint/state.py)."""
        return self._zero_layout.meta() if self.zero else None

    def export_params(self):
        """Current (params, aux) as numpy dicts (host sync point)."""
        return ({n: _np.asarray(v) for n, v in self.params.items()},  # tpulint: allow-host-sync export_params IS the documented host sync point
                {n: _np.asarray(v) for n, v in self.aux.items()})  # tpulint: allow-host-sync export_params IS the documented host sync point

    def _build_step(self, batch_shapes):
        from ..executor import Executor
        from ..ndarray.ndarray import zeros as nd_zeros
        from ..context import cpu
        # an executor instance only for its traced pure _run_graph
        arg_shapes, _, aux_shapes = self.symbol.infer_shape(**batch_shapes)
        shapes = dict(zip(self.arg_names, arg_shapes))
        dummy_args = {n: nd_zeros(shapes[n]) for n in self.arg_names}
        dummy_aux = {n: nd_zeros(s) for n, s in
                     zip(self.aux_names, aux_shapes)}
        runner = Executor(self.symbol, cpu(), dummy_args, {}, "null", dummy_aux)

        wd = self.wd
        optimizer, opt_hp = self.optimizer, dict(self.opt_hp)
        fixed = self.fixed_param_names
        clip = self.clip_gradient
        fused_opt = self.fused_optupdate
        zero_layout = self._zero_layout if self.zero else None
        mesh = self.mesh
        single_dev = int(_np.prod(list(self.mesh.shape.values()))) == 1
        dp_axis = self._dp_axis
        # Kernel-tier selection happens once at build (trace) time, never
        # per step: auto on TPU, forced/off/interpret via
        # MXNET_TPU_MESH_KERNEL_TIER (mesh_kernels.resolve_kernel_tier).
        from .mesh_kernels import resolve_kernel_tier
        kt_pallas, kt_interpret = resolve_kernel_tier()
        batch_size = list(batch_shapes.values())[0][0]
        rescale = self._rescale if self._rescale is not None else 1.0 / batch_size

        cdt = self.compute_dtype
        cast_names = frozenset(self.data_names)  # NEVER labels: class
        # indices >= 257 are unrepresentable in bf16's 8-bit significand
        supervise = self.supervise

        # batch rides in as TWO pytree args: data (dp-sharded, bf16-castable)
        # and labels (kept separate so the host-side metric fallback and
        # callbacks can keep distinct sharding/dtype treatment).
        # Supervised steps take one more runtime arg (the loss scale) and
        # return one more output (the all-finite verdict) — see _body.
        def step(params, opt_state, aux, data_part, label_part, rng, lr,
                 scale=None):
            batch = {**data_part, **label_part}
            if cdt is not None:
                batch = {n: (v.astype(cdt)
                             if n in cast_names
                             and jnp.issubdtype(v.dtype, jnp.floating) else v)
                         for n, v in batch.items()}

            def loss_fn(p):
                if cdt is not None:
                    p = {n: v.astype(cdt) for n, v in p.items()}
                outs, aux_upd = runner._run_graph({**p, **batch}, aux, rng, True)
                # BN running stats must stay fp32 even when activations
                # are bf16 (reference keeps moving_mean/var fp32 in fp16
                # training)
                if cdt is not None:
                    aux_upd = {n: v.astype(jnp.float32)
                               for n, v in aux_upd.items()}
                return outs, aux_upd
            # device-side names for the trace (metadata only): forward +
            # backward here — on a mesh the partitioner's gradient
            # all-reduce falls inside it — and `update` below
            with jax.named_scope("fwd_bwd"):
                outs, vjp, aux_upd = jax.vjp(loss_fn, params, has_aux=True)
                if supervise:
                    # loss-scaled backward: the cotangent seed IS the
                    # runtime scale (a power of two, so the cast and the
                    # unscale multiply below are exact in bf16/fp32 — scale
                    # 1.0 makes the math bitwise identical to the unscaled
                    # seed). Loss heads pick the seed up multiplicatively
                    # (ops/nn._loss_op); implicit mid-chain loss sites read
                    # the scope instead.
                    from ..ops.nn import loss_grad_scale_scope
                    s32 = jnp.asarray(scale, jnp.float32)
                    seeds = tuple(jnp.full(o.shape, s32.astype(o.dtype))
                                  for o in outs)
                    with loss_grad_scale_scope(s32):
                        grads = vjp(seeds)[0]
                else:
                    seeds = tuple(jnp.ones(o.shape, o.dtype) for o in outs)
                    grads = vjp(seeds)[0]
            if supervise:
                inv = jnp.float32(1.0) / s32
                grads = {n: g * inv.astype(g.dtype)
                         for n, g in grads.items()}
                # in-graph all-finite verdict: every output plus the
                # global gradient norm (an f32 norm overflowing to inf is
                # a numeric fault by definition). Device scalars only —
                # the host reads the verdict where async dispatch already
                # blocks, never adding a sync.
                good = jnp.bool_(True)
                for o in outs:
                    if jnp.issubdtype(o.dtype, jnp.floating):
                        good &= jnp.all(jnp.isfinite(o))
                gsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                          for g in grads.values())
                good &= jnp.isfinite(gsq)
            if cdt is not None and zero_layout is None:
                # fp32 master update (mp_sgd semantics); the ZERO path
                # casts inside its shard_map island instead
                # (apply_update_sharded cast_grads=) so the cast sits in
                # the update loop in both variants
                grads = {n: g.astype(jnp.float32)
                         for n, g in grads.items()}
            hp = dict(opt_hp, lr=lr)
            with jax.named_scope("update"):
                if zero_layout is not None:
                    # ZeRO cross-replica sharded update (arxiv 2004.13336):
                    # a shard_map island where each replica slices its 1/dp
                    # (dp, chunk) block of the all-reduced grads and updates
                    # its shard of params + slots (fp32 masters included:
                    # the mp_sgd-style bf16->fp32 grad cast runs on the
                    # shards, inside the island's update loop), then the
                    # fresh params all-gather. Bit-parity with both paths
                    # below.
                    from .optim_update import apply_update_sharded
                    new_params, new_state = apply_update_sharded(
                        optimizer, hp, params, opt_state, grads, zero_layout,
                        mesh, rescale=rescale, clip=clip, wd=wd,
                        fused=fused_opt,
                        cast_grads=jnp.float32 if cdt is not None else None,
                        use_pallas=kt_pallas, interpret=kt_interpret)
                elif fused_opt:
                    # one fused sweep per param block (prologue + update in
                    # the kernel) — bit-parity with the tree-map path below.
                    # pallas_call is not auto-partitionable, so multi-device
                    # meshes route through the fused_update_mesh shard_map
                    # island (transient dp-sharded chunks, params/slots
                    # all-gathered back): inside the manual region the kernel
                    # is a plain per-device op, so the kernel tier engages on
                    # every mesh instead of silently lax-falling-back.
                    if single_dev:
                        from ..kernels.opt_update import fused_update_step
                        new_params, new_state = fused_update_step(
                            optimizer, hp, params, opt_state, grads,
                            rescale=rescale, clip=clip, wd=wd,
                            use_pallas=kt_pallas, interpret=kt_interpret)
                    else:
                        from .mesh_kernels import fused_update_mesh
                        new_params, new_state = fused_update_mesh(
                            optimizer, hp, params, opt_state, grads, mesh,
                            dp_axis, rescale=rescale, clip=clip, wd=wd,
                            use_pallas=kt_pallas, interpret=kt_interpret)
                else:
                    from .optim_update import apply_update, grad_prologue
                    grads = grad_prologue(params, grads, rescale=rescale,
                                          clip=clip, wd=wd)
                    new_params, new_state = apply_update(
                        optimizer, hp, params, opt_state, grads)
            if fixed:
                new_params = {n: (params[n] if n in fixed else v)
                              for n, v in new_params.items()}
            if supervise:
                # donation-safe carry: a bad step keeps params/opt_state/
                # BN aux EXACTLY as they were — jnp.where builds fresh
                # output buffers, so the skipped state never aliases the
                # poisoned update math (and XLA may still alias the
                # donated inputs on the clean path)
                def _carry(new, old):
                    return jnp.where(good, new, old)
                new_params = {n: _carry(v, params[n])
                              for n, v in new_params.items()}
                new_state = jax.tree_util.tree_map(_carry, new_state,
                                                   opt_state)
                aux_upd = {n: _carry(v, aux[n])
                           for n, v in aux_upd.items()}
                return new_params, new_state, aux_upd, outs, good
            return new_params, new_state, aux_upd, outs

        st_sharding = self._state_shardings()
        in_shardings = (
            {n: self._repl for n in self.param_names},
            st_sharding,
            {n: self._repl for n in self.aux_names},
            {n: self._batch_shard for n in self.data_names},
            {n: self._batch_shard for n in self.label_names
             if n in self.arg_names},
            self._repl,
            None,
        )
        # pin the returned state to the same dp-sharded layout (weight-
        # update sharding): XLA then reduce-scatters grads into the state
        # shards and all-gathers the updated weights
        out_shardings = ({n: self._repl for n in self.param_names},
                         st_sharding, None, None)
        if supervise:
            in_shardings = in_shardings + (None,)   # loss scale (scalar)
            out_shardings = out_shardings + (None,)  # all-finite verdict
        # batch args (3, 4) are NOT donated: no step output matches the
        # batch shapes, so XLA could never alias them — donation would only
        # warn per compile and force callers that reuse device-resident
        # batches (bench _phase_step) into per-step defensive copies.
        # ZERO donation contract: the O(params) param buffers stay donated,
        # but the PARTITIONED optimizer slots are not — XLA:CPU's fp
        # contraction inside in-place (donated) loops is layout-dependent,
        # and donating the (dp, chunk) slots costs the sharded-vs-replicated
        # update its bitwise parity (1-ulp drift in the momentum term).
        # Rebuffering the slots each step costs O(params/dp) transient
        # memory — the exact class ZeRO just freed, dp-fold smaller than
        # what the param donation saves.
        donate_argnums = (0,) if self.zero else (0, 1)
        from ..analysis.runtime import lint_enabled
        if lint_enabled():
            self._lint_step(step, donate_argnums)
        # the ONE lower/compile/cache path (compile/builder.py): dispatch
        # goes through the builder — straight into the AOT executable
        # after warmup() (fit pre-pays the compile), the usual jit
        # trace/compile otherwise. No lint hook here: the fused step's
        # jaxpr sweep stays deferred to the first __call__ (real batch
        # dtypes are only known then — see _lint_step).
        from ..compile.builder import ProgramBuilder
        self._step = ProgramBuilder(step, site="train.fused_step",
                                    donate_argnums=donate_argnums,
                                    in_shardings=in_shardings,
                                    out_shardings=out_shardings)
        self._batch_shapes = {k: tuple(v) for k, v in batch_shapes.items()}

    def _lint_step(self, step, donate_argnums):
        """MXNET_TPU_LINT compile-time passes over the fused step
        (docs/faq/analysis.md): the PR-3 donation contract (params/
        opt_state only — never batch buffers), donation aliasability,
        f64 leaks, and dead subgraphs/params."""
        from ..analysis.graph_passes import check_donation
        from ..analysis.runtime import report_findings
        # under ZERO the state arg carries partitioned (dp, chunk) slot
        # blocks — its own donatable role (TPL203 accepts it in train
        # mode; this step donates params only, see _build_step)
        roles = ("params", "opt_state_shard" if self.zero else "opt_state",
                 "aux", "batch", "batch", "rng", "lr")
        if self.supervise:
            roles = roles + ("lr",)  # the loss scale: a runtime scalar
            # with the same (never-donated) contract as lr
        report_findings(check_donation(donate_argnums, roles, mode="train",
                                       where="tpu_step"))
        # the jaxpr sweep AND the donation-aliasing check wait for the
        # first __call__: batch dtypes are only known then (uint8 image
        # batches skip the bf16 cast an f32-guessed trace would take),
        # and the aliasing check needs the REAL program outputs — deriving
        # them from the input dicts would compare them to themselves and
        # never fire
        self._step_fn = step
        self._lint_donate_argnums = donate_argnums
        self._lint_sweep_pending = True

    # ------------------------------------------------------------------
    def warmup(self, batch_dtypes=None):
        """Ahead-of-time compile the fused step from ABSTRACT shapes, so
        the first batch pays dispatch only — the AOT warmup training
        lacked while serving had it (ISSUE 14). ``Module.fit`` calls this
        between optimizer init and the first batch (MXNET_TPU_TRAIN_AOT).

        ``batch_dtypes`` maps input/label name -> numpy dtype (default
        float32 — the NDArrayIter contract). A mismatch with the real
        batch is harmless: the builder's dispatch lookup misses and the
        step jit-compiles exactly as without warmup. With
        ``MXNET_TPU_COMPILE_CACHE`` set the compile itself is mostly a
        persistent-cache disk read on warm restarts. Returns self."""
        self._step.aot(*self.abstract_step_args(batch_dtypes))
        return self

    def abstract_step_args(self, batch_dtypes=None):
        """The abstract (ShapeDtypeStruct) argument tuple the step's
        program family keys under — what warmup compiles and what the
        TPL3xx program audit extracts the contract from, so both
        observe the SAME ProgramBuilder entry."""
        if self._step is None:
            raise MXNetError("call init() first")
        dts = {k: _np.dtype(v) for k, v in (batch_dtypes or {}).items()}
        f32 = _np.dtype(_np.float32)

        def sds(tree):
            return jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(tuple(x.shape), x.dtype),
                tree)

        def batch_sds(names):
            return {n: jax.ShapeDtypeStruct(self._batch_shapes[n],
                                            dts.get(n, f32))
                    for n in names
                    if n in self._batch_shapes and n in self.arg_names}

        from .. import random as _rnd
        key = _rnd.fixed_key()
        args = (sds(self.params), sds(self.opt_state), sds(self.aux),
                batch_sds(self.data_names), batch_sds(self.label_names),
                jax.ShapeDtypeStruct(tuple(key.shape), key.dtype),
                jax.ShapeDtypeStruct((), f32))
        if self.supervise:
            args = args + (jax.ShapeDtypeStruct((), f32),)  # loss scale
        return args

    def comm_plan(self):
        """Declared collective plan for the fused step (the TPL301/302
        contract, analysis/program_audit.py): which collective ops, on
        which mesh axis, this program is ALLOWED to contain, plus the
        analytic per-axis comm-byte ideal where the layout arithmetic
        provides one (the ZeRO accounting, parallel/zero.py). Anything
        the partitioner inserts beyond this plan is a stray collective —
        the PR 7 hazard (13 silent all-gathers in the ZeRO island) as a
        failing lint."""
        from ..analysis.program_audit import CommPlan
        dp = self._dp_axis
        n_devices = int(_np.prod(list(self.mesh.shape.values())))
        if n_devices == 1:
            return CommPlan(site=self._step.site if self._step else
                            "train.fused_step", allowed=(), max_programs=1)
        # the grad sum over dp: present in every multi-replica variant
        allowed = [("all-reduce", dp, None)]
        ideal = None
        if self.zero:
            # explicit ZeRO island: full-grad all-reduce in, fresh params
            # all-gather out; the partitioner may fold the sum into a
            # reduce-scatter (same axis, same bytes)
            allowed += [("all-gather", dp, None),
                        ("reduce-scatter", dp, None)]
            comm = self._zero_layout.comm_bytes()
            ideal = {dp: comm["grad_allreduce_bytes"]
                     + comm["gather_bytes"]}
        elif self.shard_update:
            # annotation WUS: XLA reduce-scatters grads into the state
            # shards and all-gathers the updated weights. Where a dim the
            # partitioner tiled with padding (ResNet-50's 1000-row
            # classifier: 4 x 256 in the backward dot) meets its even
            # state shard (4 x 250), XLA:TPU re-aligns the rows with a
            # neighbour collective-permute (seen on a v5e 2x2, PR 22).
            allowed += [("reduce-scatter", dp, None),
                        ("all-gather", dp, None),
                        ("collective-permute", dp, None)]
        if self.fused_optupdate and not self.zero:
            # fused_update_mesh island regathers params+slots over dp
            allowed += [("all-gather", dp, None)]
        return CommPlan(site=self._step.site if self._step else
                        "train.fused_step", allowed=allowed,
                        ideal_bytes_per_axis=ideal, max_programs=1)

    def __call__(self, batch_np, rng=None, lr=None, scale=None):
        """Run one step on a global batch (dict name->numpy or jax.Array).

        Device-resident inputs already on the right sharding (e.g.
        prefetch-staged batches) pass through zero-copy; anything else is
        resharded/staged device-side without a host hop."""
        if self._step is None:
            raise MXNetError("call init() first")
        data_part, label_part = {}, {}
        data_names = frozenset(self.data_names)
        for name, arr in batch_np.items():
            if isinstance(arr, jax.Array):  # already on device
                if arr.sharding != self._batch_shard:  # reshard, no
                    arr = jax.device_put(arr, self._batch_shard)  # host hop
            else:
                arr = jax.device_put(jnp.asarray(arr), self._batch_shard)
            (data_part if name in data_names else label_part)[name] = arr
        if rng is None:
            if self._needs_rng:
                rng = jax.device_put(
                    jax.random.PRNGKey(_np.random.randint(0, 2 ** 31)),
                    self._repl)
            else:
                # deterministic graph (no dropout/sample ops): one cached
                # replicated key — fresh-key construction + device_put cost
                # ~150us of host dispatch per step otherwise
                if self._fixed_rng is None:
                    from .. import random as _rnd
                    self._fixed_rng = jax.device_put(
                        _rnd.fixed_key(), self._repl)
                rng = self._fixed_rng
        else:
            rng = jax.device_put(rng, self._repl)
        if lr is None:
            lr = self.lr
        if self.supervise and scale is None:
            scale = 1.0
        if self._lint_sweep_pending:
            # deferred MXNET_TPU_LINT jaxpr sweep (see _lint_step): one
            # abstract trace of the REAL argument signature, first step only
            self._lint_sweep_pending = False
            from ..analysis.graph_passes import check_donation_aliasing
            from ..analysis.runtime import check_traced, report_findings
            step_args = (self.params, self.opt_state, self.aux, data_part,
                         label_part, rng, _np.float32(lr))
            if self.supervise:
                step_args = step_args + (_np.float32(scale),)
            # the builder's cached trace (ISSUE 20 satellite): the same
            # Traced the first-step compile lowers from — lint pays no
            # second trace of the step body
            _, jaxpr = check_traced(self._step_fn, step_args,
                                    "tpu_step.fused_step", want_jaxpr=True,
                                    jaxpr=self._step.jaxpr(*step_args))
            if jaxpr is not None:
                leaves = jax.tree_util.tree_leaves
                in_avals = [[(v.shape, v.dtype) for v in leaves(part)]
                            for part in step_args[:3]]
                out_avals = [(v.shape, v.dtype) for v in jaxpr.out_avals
                             if hasattr(v, "dtype")]
                report_findings(check_donation_aliasing(
                    in_avals, out_avals, self._lint_donate_argnums,
                    where="tpu_step"))
        if self.supervise:
            (self.params, self.opt_state, aux_upd, outs,
             self.last_flag) = self._step(
                self.params, self.opt_state, self.aux, data_part,
                label_part, rng, _np.float32(lr), _np.float32(scale))
        else:
            self.params, self.opt_state, aux_upd, outs = self._step(
                self.params, self.opt_state, self.aux, data_part,
                label_part, rng, _np.float32(lr))
        self.moms = self.opt_state.get("mom") or {}
        self.aux.update(aux_upd)
        return outs
