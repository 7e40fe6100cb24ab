"""Executor — binds a Symbol to devices + arrays and runs it.

Reference: include/mxnet/executor.h:53, src/executor/graph_executor.cc (2343 LoC:
NNVM passes, memory planning, engine pushes). TPU-native: the whole graph traces
into ONE jitted XLA program per (is_train, input-shapes) key — XLA subsumes
PlanMemory/DetectInplaceAddTo/bulking. Training uses a fused forward+backward
program (outputs + gradients + aux updates in a single XLA call), the same
fusion the reference approximates with bulked engine segments.
"""
from __future__ import annotations

import functools

import numpy as _np
import jax
import jax.numpy as jnp

from .base import MXNetError
from .context import Context, current_context
from .ndarray.ndarray import NDArray, zeros
from . import random as _rnd

__all__ = ["Executor"]


class Executor:
    def __init__(self, symbol, ctx, args, args_grad=None, grad_req="write",
                 aux_states=None, group2ctx=None):
        self._symbol = symbol
        self._ctx = ctx if isinstance(ctx, Context) else Context(ctx)
        self._group2ctx = group2ctx  # sharding hint (reference: PlaceDevice pass)
        self._group_shardings = None

        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()

        self.arg_dict = self._normalize(args, arg_names, "args")
        self.aux_dict = self._normalize(aux_states or {}, aux_names, "aux_states",
                                        allow_missing=True)
        for name in aux_names:
            if name not in self.aux_dict:
                raise MXNetError("missing aux state %r" % name)

        if isinstance(grad_req, str):
            self._grad_req = {n: grad_req for n in arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self._grad_req = dict(zip(arg_names, grad_req))
        else:
            self._grad_req = dict(grad_req)
            for n in arg_names:
                self._grad_req.setdefault(n, "null")

        if args_grad is None:
            args_grad = {}
        self.grad_dict = self._normalize(args_grad, arg_names, "args_grad",
                                         allow_missing=True)
        for n in arg_names:
            if self._grad_req.get(n, "null") != "null" and n not in self.grad_dict:
                self.grad_dict[n] = zeros(self.arg_dict[n].shape, ctx=self._ctx)

        self._arg_names = arg_names
        self._aux_names = aux_names
        self._grad_names = [n for n in arg_names
                            if self._grad_req.get(n, "null") != "null"]
        self._outputs = None  # lazily materialized (see outputs property)
        self._cached = {}  # ("fwd"/"fb"/"mon", mode) -> ProgramBuilder/jit
        self._monitor_cb = None
        self._monitor_active = False
        self._pending_monitor = []

        # node tables built once (trace order)
        self._topo = [n for n in symbol._topo() if not n.is_variable]
        self._var_nodes = symbol._variables()
        self._aux_var_ids = symbol._aux_set()
        # deterministic graphs skip the per-forward key split — at ~150us
        # of jax.random dispatch per call it dominated small-graph forward
        # overhead (the jitted fn still takes a key arg; reuse a fixed one)
        self._needs_rng = symbol._needs_rng()

        if group2ctx:
            self._group_shardings = self._build_group_shardings(group2ctx)

        from .analysis.runtime import lint_enabled
        if lint_enabled():
            self._lint_bind()

    def _lint_bind(self):
        """MXNET_TPU_LINT bind-time passes (docs/faq/analysis.md): params
        the graph never consumes (the reference raised at bind; _normalize
        accepts dict extras silently) and infer_shape vs
        infer_shape_partial drift — both surfaced before any compile."""
        from .analysis.graph_passes import (check_infer_shape_consistency,
                                            check_symbol_unused_args)
        from .analysis.runtime import report_findings
        try:
            findings = check_symbol_unused_args(
                self._symbol, list(self.arg_dict) + list(self.aux_dict),
                where="Executor.bind")
            findings += check_infer_shape_consistency(
                self._symbol,
                {n: a.shape for n, a in self.arg_dict.items()},
                where="Executor.bind")
        except Exception as e:
            # the observer never fails a bind that succeeds with lint off
            import logging
            logging.getLogger("mxnet_tpu.analysis").warning(
                "tpulint: bind-time passes crashed: %s", e)
            return
        report_findings(findings)

    # ------------------------------------------------------------------
    # group2ctx -> mesh sharding (TPU-native model parallelism)
    # ------------------------------------------------------------------
    def _build_group_shardings(self, group2ctx):
        """Map ctx groups onto a model-parallel mesh axis.

        The reference places each ctx group's ops on its own device
        (PlaceDevice, graph_executor.cc:406) so a model too big for one
        device spreads across several. The TPU-native form: one mesh axis
        'mp' over the union of group devices; every grouped parameter is
        sharded along its first mp-divisible axis, everything else is
        replicated. XLA GSPMD then partitions the (single) program and
        inserts the ICI collectives the reference's copy nodes imply —
        the same memory scaling without host-visible placement.
        """
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        devices, seen = [], set()
        for c in group2ctx.values():
            d = (c if isinstance(c, Context) else Context(c)).jax_device
            if d.id not in seen:
                seen.add(d.id)
                devices.append(d)
        if len(devices) < 2:
            return None
        mesh = Mesh(_np.asarray(devices), ("mp",))
        repl = NamedSharding(mesh, PartitionSpec())
        attrs = self._symbol.attr_dict()
        shardings = {}
        n = len(devices)
        for name in (self._symbol.list_arguments()
                     + self._symbol.list_auxiliary_states()):
            group = attrs.get(name, {}).get("ctx_group")
            spec = repl
            if group is not None and group in group2ctx:
                arr = self.arg_dict.get(name)
                if arr is None:
                    arr = self.aux_dict.get(name)
                if arr is not None:
                    for axis, dim in enumerate(arr.shape):
                        if dim % n == 0 and dim >= n:
                            parts = [None] * len(arr.shape)
                            parts[axis] = "mp"
                            spec = NamedSharding(mesh, PartitionSpec(*parts))
                            break
            shardings[name] = spec
        shardings["__default__"] = repl
        return shardings

    def _apply_group_shardings(self, arg_vals, aux_vals):
        sh = self._group_shardings
        default = sh["__default__"]
        return ({n: jax.device_put(v, sh.get(n, default))
                 for n, v in arg_vals.items()},
                {n: jax.device_put(v, sh.get(n, default))
                 for n, v in aux_vals.items()})

    # ------------------------------------------------------------------
    def _normalize(self, arrays, names, what, allow_missing=False):
        if isinstance(arrays, dict):
            out = dict(arrays)
        elif isinstance(arrays, (list, tuple)):
            if len(arrays) != len(names):
                raise MXNetError("%s length %d != expected %d (%s)"
                                 % (what, len(arrays), len(names), names))
            out = dict(zip(names, arrays))
        else:
            raise MXNetError("%s must be list or dict" % what)
        if not allow_missing:
            for n in names:
                if n not in out:
                    raise MXNetError("missing %s entry %r" % (what, n))
        return out

    # ------------------------------------------------------------------
    # pure graph interpreter (traced under jit)
    # ------------------------------------------------------------------
    def _run_graph(self, arg_vals, aux_vals, key, is_train,
                   collect_interior=False):
        # int8 strategy picks per-platform lowerings at TRACE time; scope
        # the choice to THIS executor's bound device (the process-default
        # backend diverges exactly when an executor is bound off it)
        from .ops.quantization import int8_platform_hint
        with int8_platform_hint(self._ctx.jax_device.platform):
            return self._run_graph_impl(arg_vals, aux_vals, key, is_train,
                                        collect_interior)

    def _run_graph_impl(self, arg_vals, aux_vals, key, is_train,
                        collect_interior=False):
        vals = {}
        for node in self._var_nodes:
            src = aux_vals if id(node) in self._aux_var_ids else arg_vals
            if node.name in src:
                vals[(id(node), 0)] = src[node.name]
        aux_updates = {}
        for node in self._topo:
            params = node.make_params()
            ins = []
            for (inp, oidx) in node.inputs:
                v = vals.get((id(inp), oidx))
                if v is None:
                    raise MXNetError("executor: missing input for node %s" % node.name)
                ins.append(v)
            rng = None
            if node.op.need_rng:
                key, rng = jax.random.split(key)
            outs = node.op.apply(params, ins, is_train=is_train, rng=rng)
            n_vis = node.op.n_outputs(params)
            for i in range(n_vis):
                vals[(id(node), i)] = outs[i]
            aux_names_node = node.op.list_aux(params)
            n_in = len(node.op.list_inputs(params))
            for j, aux_upd in enumerate(outs[n_vis:]):
                aux_node = node.inputs[n_in + j][0]
                aux_updates[aux_node.name] = aux_upd
        outputs = []
        for node, oidx in self._symbol._outputs:
            if node.is_variable:
                outputs.append(vals[(id(node), 0)])
            else:
                outputs.append(vals[(id(node), oidx)])
        if collect_interior:
            interior = []
            for node in self._topo:
                n_vis = node.op.n_outputs(node.make_params())
                for i in range(n_vis):
                    suffix = "_output" if n_vis == 1 else "_output%d" % i
                    interior.append((node.name + suffix,
                                     vals[(id(node), i)]))
            return tuple(outputs), aux_updates, interior
        return tuple(outputs), aux_updates

    # ------------------------------------------------------------------
    # compiled entry points — ProgramBuilder per program family (the ONE
    # lower/compile/cache seam, compile/builder.py): dispatch goes through
    # the builder, which runs a matching AOT executable when one exists
    # (warmup/program_cost compiled it) and falls back to jit otherwise
    # ------------------------------------------------------------------
    def _fwd_fn(self, is_train):
        key = ("fwd", is_train)
        if key not in self._cached:
            def f(arg_vals, aux_vals, rng):
                return self._run_graph(arg_vals, aux_vals, rng, is_train)

            def _sweep(args):
                # MXNET_TPU_LINT compile-time passes (docs/faq/analysis.md):
                # sweep the forward jaxpr for f64 leaks and dead subgraphs /
                # params unused by any output before paying the XLA compile.
                # The builder runs this once per distinct program — repeat
                # warmups neither re-trace nor re-count
                from .analysis.runtime import check_traced
                arg_sds, aux_sds, _ = args
                check_traced(
                    f, args,
                    "Executor.warmup(%s)" % self._symbol.list_outputs()[:1],
                    # pytree flattening order: sorted dict keys, then rng
                    input_names=(sorted(arg_sds) + sorted(aux_sds) + ["rng"]),
                    # the builder's cached trace — the compile this hook
                    # precedes lowers from the SAME Traced, and so do
                    # program_cost and the TPL3xx audit (ISSUE 20)
                    jaxpr=self._cached[key].jaxpr(*args))

            from .compile.builder import ProgramBuilder
            self._cached[key] = ProgramBuilder(f, site="executor.forward",
                                               lint_hook=_sweep)
        return self._cached[key]

    def _fb_fn(self, with_out_grads):
        key = ("fb", with_out_grads)
        if key not in self._cached:
            grad_names = tuple(self._grad_names)
            # MXNET_BACKWARD_DO_MIRROR: trade FLOPs for memory by
            # rematerializing forward activations in the backward pass
            # (reference: graph mirroring, src/executor/graph_executor.cc +
            # docs/faq/env_var.md). TPU-native form: jax.checkpoint.
            from .base import env_flag
            do_mirror = env_flag("MXNET_BACKWARD_DO_MIRROR")

            def f(grad_args, other_args, aux_vals, rng, out_grads=None):
                def inner(ga):
                    all_args = dict(other_args)
                    all_args.update(ga)
                    outs, aux_upd = self._run_graph(all_args, aux_vals, rng, True)
                    return outs, aux_upd
                if do_mirror:
                    # save matmul/conv outputs, rematerialize elementwise
                    # chains in the backward — the reference's mirroring
                    # recomputes exactly the activation-type ops. A bare
                    # whole-graph checkpoint would re-run the matmuls too
                    # (+1 full forward of FLOPs) without lowering the
                    # peak any further.
                    inner = jax.checkpoint(
                        inner,
                        policy=jax.checkpoint_policies.dots_saveable)
                outs, vjp, aux_upd = jax.vjp(inner, grad_args, has_aux=True)
                if out_grads is None:
                    seeds = tuple(jnp.ones(o.shape, o.dtype) for o in outs)
                else:
                    seeds = tuple(out_grads)
                grads = vjp(seeds)[0]
                return outs, aux_upd, grads

            from .compile.builder import ProgramBuilder
            # no lint hook: the fused fwd+bwd program is only AOT-built
            # via program_cost, which never swept (the graph passes run
            # on the forward program at warmup)
            self._cached[key] = ProgramBuilder(f, site="executor.train_step")
        return self._cached[key]

    # ------------------------------------------------------------------
    # AOT compilation (serving warmup path; reference analog: the bind-time
    # memory planning that let reference executors serve with zero
    # first-request overhead — here the cost being fronted is XLA compile)
    # ------------------------------------------------------------------
    def warmup(self, is_train=False):
        """Ahead-of-time compile the forward program for the BOUND shapes
        via jit.lower(...).compile(), so the first forward() pays dispatch
        only — no trace, no XLA compile. With MXNET_TPU_COMPILE_CACHE set
        (base.configure_compile_cache) the compiled program also persists
        across process restarts. Bucketed multi-shape warmup lives one
        level up in serving/ (InferenceEngine.warmup); this entry point
        covers the single bound shape. Returns self for chaining."""
        from .base import configure_compile_cache
        configure_compile_cache()
        if self._group_shardings is not None:
            return self  # sharded programs compile through the jit path
        if self._ctx.jax_device != jax.devices()[0]:
            # lowering from abstract shapes pins the DEFAULT device; an
            # executor bound elsewhere would hit a committed-device
            # mismatch on every forward — let jit specialize instead
            return self
        if is_train and self._grad_names:
            # train-mode forward on a gradient-bound executor dispatches
            # the fused fwd+bwd program (_fb_fn), which never consults
            # the AOT table — compiling _fwd_fn(True) here would be a
            # multi-second no-op
            return self
        arg_sds = {n: jax.ShapeDtypeStruct(a.shape, a._data.dtype)
                   for n, a in self.arg_dict.items()}
        aux_sds = {n: jax.ShapeDtypeStruct(a.shape, a._data.dtype)
                   for n, a in self.aux_dict.items()}
        rng = _rnd.fixed_key()
        rng_sds = jax.ShapeDtypeStruct(rng.shape, rng.dtype)
        # the builder caches per distinct program and runs the lint sweep
        # inside its miss branch — repeat warmups neither re-trace nor
        # re-count, and forward() dispatches the executable via lookup
        self._fwd_fn(bool(is_train)).aot(arg_sds, aux_sds, rng_sds)
        return self

    def has_compiled_forward(self, is_train=False):
        """Whether a forward program for this mode has already been built
        (jit wrapper exists => a forward ran and paid its compile). Part
        of the executor's public surface so callers — Module's serving
        router — need not poke the private jit-cache key format."""
        return ("fwd", bool(is_train)) in self._cached

    def _next_key(self):
        """Fresh PRNG key for stochastic graphs; the shared constant key
        for deterministic ones (jax.random.split costs ~150us of host
        dispatch per call — most of a small graph's forward time — and
        drawing from the global chain would perturb user-visible state)."""
        return _rnd.next_key() if self._needs_rng else _rnd.fixed_key()

    # ------------------------------------------------------------------
    # public API (reference: executor.py forward/backward/outputs)
    # ------------------------------------------------------------------
    def program_cost(self):
        """Compile-time accounting for the fused forward+backward program:
        {"flops", "peak_bytes", "temp_bytes"} from XLA's own cost/memory
        analysis (peak_bytes is the headline — the peak live set incl.
        activations) — chip-independent, no execution. Used by
        example/memcost to measure the MXNET_BACKWARD_DO_MIRROR remat
        trade exactly (the reference estimated it by watching
        nvidia-smi)."""
        arg_vals = {n: a._data for n, a in self.arg_dict.items()}
        aux_vals = {n: a._data for n, a in self.aux_dict.items()}
        # lowering consumes only shapes: never draw from the global RNG
        # chain for it (that would shift later dropout masks)
        rng = _rnd.fixed_key()
        if self._grad_names:
            grad_args = {n: arg_vals.pop(n) for n in self._grad_names}
            builder = self._fb_fn(False)
            args = (grad_args, arg_vals, aux_vals, rng)
        else:
            builder = self._fwd_fn(True)
            args = (arg_vals, aux_vals, rng)
        # one lowering, cached in the builder: the compile below reuses
        # it, a repeat program_cost() re-traces nothing, and the compiled
        # executable is the SAME object a later forward/backward with
        # these shapes dispatches (no second program for the analysis)
        lowered = builder.lowered(*args)
        ca = lowered.cost_analysis()
        ma = builder.aot(*args).memory_analysis()
        return {"flops": float(ca.get("flops", 0.0)),
                # peak live set (activations included) — temp_size alone
                # misses buffers XLA classifies as program outputs
                "peak_bytes": float(getattr(ma, "peak_memory_in_bytes", 0)),
                "temp_bytes": float(getattr(ma, "temp_size_in_bytes", 0))}

    def forward(self, is_train=False, **kwargs):
        dev = self._ctx.jax_device
        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError("unknown forward argument %r" % k)
            if isinstance(v, NDArray):
                v = v._data
            if isinstance(v, jax.Array):
                # already device-resident (e.g. a prefetch-staged batch):
                # adopt the buffer as-is — np.asarray() would round-trip
                # it device->host->device. A buffer committed to ANOTHER
                # single device (a default-context host NDArray handed to
                # an executor bound on the chip) is moved here, as the
                # reference's copy into the bound array does; jit refuses
                # arguments committed to two devices.
                devs = v.devices()
                if len(devs) == 1 and devs != {dev}:
                    v = jax.device_put(v, dev)
                self.arg_dict[k]._data = v
            else:
                self.arg_dict[k]._data = jax.device_put(_np.asarray(v), dev)

        arg_vals = {n: a._data for n, a in self.arg_dict.items()}
        aux_vals = {n: a._data for n, a in self.aux_dict.items()}
        if self._group_shardings is not None:
            arg_vals, aux_vals = self._apply_group_shardings(arg_vals, aux_vals)
        rng = self._next_key()

        from . import profiler as _prof
        _profiling = _prof.is_running()
        if _profiling:
            import time as _time
            _t0 = _time.perf_counter()
        if is_train and self._grad_names:
            grad_args = {n: arg_vals.pop(n) for n in self._grad_names}
            outs, aux_upd, grads = self._fb_fn(False)(grad_args, arg_vals,
                                                      aux_vals, rng)
            self._pending_grads = grads
        else:
            # warmed executors dispatch straight into the AOT-compiled
            # executable — the builder's lookup path; no trace, no
            # jit-cache walk on the serving path (group-sharded programs
            # never warm, so they always take the builder's jit branch)
            outs, aux_upd = self._fwd_fn(is_train)(arg_vals, aux_vals, rng)
            self._pending_grads = None
        if _profiling:
            jax.block_until_ready(outs)
            _prof.record_op_event(
                "graph_forward_backward" if (is_train and self._grad_names)
                else "graph_forward",
                _time.perf_counter() - _t0, category="executor")
        for name, val in aux_upd.items():
            self.aux_dict[name]._data = val
        # swap buffers into the EXISTING output NDArrays when possible:
        # reference executors write bind-allocated outputs in place, so
        # references held across forwards must see the new values
        if self._outputs is not None and len(self._outputs) == len(outs):
            for nd_obj, val in zip(self._outputs, outs):
                nd_obj._data = val
        else:
            self.outputs = [NDArray(o, ctx=self._ctx) for o in outs]
        if self._monitor_cb is not None and self._monitor_active:
            self._collect_monitor(is_train, rng)
        return self.outputs

    # ------------------------------------------------------------------
    # monitor hooks (reference: GraphExecutor monitor callback,
    # src/executor/graph_executor.cc:123 — per-op output stat hooks)
    # ------------------------------------------------------------------
    def set_monitor_callback(self, callback, monitor_all=False):
        self._monitor_cb = callback
        self._monitor_active = True
        self._pending_monitor = []

    def monitor_activate(self, active):
        """Gate the interior-capture side program (Monitor.tic/toc toggle it
        so off-interval batches pay nothing)."""
        self._monitor_active = bool(active)
        if not active:
            self._pending_monitor = []

    def _monitor_fn(self, is_train):
        key = ("mon", is_train)
        if key not in self._cached:
            def f(arg_vals, aux_vals, rng):
                _, _, interior = self._run_graph(arg_vals, aux_vals, rng,
                                                 is_train,
                                                 collect_interior=True)
                return [v for _, v in interior]
            self._cached[key] = jax.jit(f)
        return self._cached[key]

    def _collect_monitor(self, is_train, rng):
        arg_vals = {n: a._data for n, a in self.arg_dict.items()}
        aux_vals = {n: a._data for n, a in self.aux_dict.items()}
        # names come from an untraced pass; values from the jitted one
        names = []
        for node in self._topo:
            n_vis = node.op.n_outputs(node.make_params())
            for i in range(n_vis):
                suffix = "_output" if n_vis == 1 else "_output%d" % i
                names.append(node.name + suffix)
        vals = self._monitor_fn(is_train)(arg_vals, aux_vals, rng)
        self._pending_monitor.extend(zip(names, vals))

    def monitor_flush(self):
        cb = self._monitor_cb
        if cb is None:
            self._pending_monitor = []
            return
        for name, arr in self._pending_monitor:
            cb(name, arr)
        self._pending_monitor = []

    def backward(self, out_grads=None, is_train=True):
        if not self._grad_names:
            return
        if out_grads is not None:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            arg_vals = {n: a._data for n, a in self.arg_dict.items()}
            aux_vals = {n: a._data for n, a in self.aux_dict.items()}
            rng = self._next_key()
            og = tuple(g._data for g in out_grads)
            if self._group_shardings is not None:
                arg_vals, aux_vals = self._apply_group_shardings(arg_vals,
                                                                 aux_vals)
                repl = self._group_shardings["__default__"]
                rng = jax.device_put(rng, repl)
                og = tuple(jax.device_put(g, repl) for g in og)
            grad_args = {n: arg_vals.pop(n) for n in self._grad_names}
            _, _, grads = self._fb_fn(True)(grad_args, arg_vals, aux_vals,
                                            rng, og)
        else:
            if getattr(self, "_pending_grads", None) is None:
                raise MXNetError("backward() called before forward(is_train=True)")
            grads = self._pending_grads
        gather = None
        if self._group_shardings is not None:
            # EVERY grad from a mesh-sharded program is committed to the
            # mp mesh (replicated ones included), so all must move to the
            # bind context before the eager optimizer update mixes them
            # with single-device weights. For replicated grads this is a
            # local copy (the full array already lives on each device);
            # only genuinely sharded grads pay a cross-device gather.
            dev = self._ctx.jax_device
            gather = lambda a: jax.device_put(a, dev)
        for name in self._grad_names:
            g = grads[name]
            if gather is not None:
                g = gather(g)
            dst = self.grad_dict[name]
            if self._grad_req.get(name) == "add":
                dst._data = dst._data + g
            else:
                dst._data = g.astype(dst.dtype) if g.dtype != dst.dtype else g
        self._pending_grads = None

    # convenience accessors (reference: executor.py)
    @property
    def arg_arrays(self):
        return [self.arg_dict[n] for n in self._arg_names]

    @property
    def grad_arrays(self):
        return [self.grad_dict.get(n) for n in self._arg_names]

    @property
    def aux_arrays(self):
        return [self.aux_dict[n] for n in self._aux_names]

    @property
    def outputs(self):
        """Output NDArrays. Valid before the first forward (reference
        graph_executor allocates outputs at bind): zeros of the inferred
        shapes are materialized lazily on first access, so bind itself
        pays no inference cost."""
        if self._outputs is None:
            try:
                _, out_shapes, _ = self._symbol.infer_shape(
                    **{n: a.shape for n, a in self.arg_dict.items()})
                self._outputs = [zeros(tuple(s), ctx=self._ctx)
                                 for s in out_shapes]
            except MXNetError:
                self._outputs = []
        return self._outputs

    @outputs.setter
    def outputs(self, value):
        self._outputs = value

    @property
    def output_dict(self):
        """reference executor.py output_dict property."""
        return dict(zip(self._symbol.list_outputs(), self.outputs))

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for name, array in arg_params.items():
            if name in self.arg_dict:
                array.copyto(self.arg_dict[name])
            elif not allow_extra_params:
                raise MXNetError("Found name %r not in executor arguments" % name)
        if aux_params:
            for name, array in aux_params.items():
                if name in self.aux_dict:
                    array.copyto(self.aux_dict[name])
                elif not allow_extra_params:
                    raise MXNetError("Found name %r not in executor aux states" % name)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Re-bind with new shapes; jit recompiles per-shape automatically."""
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)
        new_args = {}
        for name, shape in zip(self._arg_names, arg_shapes):
            cur = self.arg_dict[name]
            if shape == cur.shape:
                new_args[name] = cur
            else:
                new_args[name] = zeros(shape, ctx=self._ctx, dtype=cur.dtype)
        new_aux = {}
        for name, shape in zip(self._aux_names, aux_shapes):
            cur = self.aux_dict[name]
            new_aux[name] = cur if shape == cur.shape else zeros(shape, ctx=self._ctx)
        grad_arrays = {n: zeros(a.shape, ctx=self._ctx)
                       for n, a in new_args.items()
                       if self._grad_req.get(n, "null") != "null"}
        return Executor(self._symbol, self._ctx, new_args, grad_arrays,
                        self._grad_req, new_aux, group2ctx=self._group2ctx)
