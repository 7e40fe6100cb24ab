"""Module — symbolic training API (reference: python/mxnet/module/module.py)."""
from __future__ import annotations

import logging

from ..base import MXNetError, atomic_write
from ..context import Context, cpu
from ..initializer import Uniform, InitDesc
from .. import optimizer as opt_mod
from ..model import (_create_kvstore, _initialize_kvstore,
                     _update_params_on_kvstore, _update_params,
                     load_checkpoint)
from ..io import DataDesc
from .base_module import BaseModule, _check_input_names
from .executor_group import DataParallelExecutorGroup

__all__ = ["Module"]


class Module(BaseModule):
    def __init__(self, symbol, data_names=("data",), label_names=("softmax_label",),
                 logger=logging, context=None, work_load_list=None,
                 fixed_param_names=None, state_names=None, group2ctxs=None,
                 compression_params=None):
        super().__init__(logger=logger)
        if context is None:
            context = cpu()
        if isinstance(context, Context):
            context = [context]
        self._context = context
        self._work_load_list = work_load_list or [1] * len(context)

        self._symbol = symbol
        data_names = list(data_names) if data_names is not None else []
        label_names = list(label_names) if label_names is not None else []

        arg_names = symbol.list_arguments()
        input_names = data_names + label_names + (state_names or [])
        self._param_names = [x for x in arg_names if x not in input_names]
        self._fixed_param_names = fixed_param_names or []
        self._aux_names = symbol.list_auxiliary_states()
        self._data_names = data_names
        self._label_names = label_names
        self._state_names = state_names or []
        self._output_names = symbol.list_outputs()

        _check_input_names(symbol, data_names, "data", True)
        _check_input_names(symbol, label_names, "label", False)
        _check_input_names(symbol, self._state_names, "state", True)
        _check_input_names(symbol, self._fixed_param_names, "fixed_param", True)

        self._arg_params = None
        self._aux_params = None
        self._params_dirty = False
        self._compression_params = compression_params
        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = None
        self._rsp_param_names = None  # stype cache, filled lazily after bind
        self._updater = None
        self._preload_opt_states = None
        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None
        self._group2ctxs = group2ctxs
        # serving-engine predict path (serving/engine.py): bucketed AOT
        # programs + padded dispatch replace per-shape jit recompiles
        self._serving_engine = None
        # fused tpu_sync train path (parallel/tpu_step.py): one XLA program
        # per iteration instead of per-param push/pull (model.py:59-88)
        self._fused_step = None
        self._fused_outputs = None
        self._fused_active = False
        self._fused_dirty = False   # fused params newer than exec_group's
        self._monitor = None
        # bounded async dispatch (docs/faq/perf.md): up to
        # MXNET_ASYNC_DISPATCH_DEPTH fused steps stay in flight; the host
        # blocks on step i-depth so it never runs unboundedly ahead of the
        # device queue (in-graph metrics removed the per-batch sync that
        # used to bound it implicitly)
        from collections import deque
        self._inflight = deque()
        self._dispatch_depth = 2
        self._fused_step_count = 0  # fault-site context (train.step)

    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = "%s-%04d.states" % (prefix, epoch)
        return mod

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        from ..model import save_checkpoint
        arg_params, aux_params = self.get_params()
        save_checkpoint(prefix, epoch, self.symbol, arg_params, aux_params)
        if save_optimizer_states:
            self.save_optimizer_states("%s-%04d.states" % (prefix, epoch))

    # ------------------------------------------------------------------
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        # executors pre-allocate outputs at bind, so this is valid
        # before the first forward too
        outputs = self._exec_group.get_outputs()
        return list(zip(self._output_names, [o.shape for o in outputs]))

    # ------------------------------------------------------------------
    def get_params(self):
        assert self.binded and self.params_initialized
        if self._params_dirty:
            self._sync_params_from_devices()
        return (self._arg_params, self._aux_params)

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False,
                    allow_extra=False):
        if self.params_initialized and not force_init:
            return
        assert self.binded, "call bind before initializing the parameters"

        if self._arg_params is None:
            self._arg_params = {name: arrs[0].copy() if arrs else None
                                for name, arrs in zip(self._param_names,
                                                      self._exec_group.param_arrays)}
        if self._aux_params is None:
            self._aux_params = {name: arrs[0].copy()
                                for name, arrs in zip(self._aux_names,
                                                      self._exec_group.aux_arrays)}

        attrs = self._symbol.attr_dict()

        def _impl(name, arr, cache):
            if cache is not None and name in cache:
                cache_arr = cache[name]
                if cache_arr is not arr:
                    if cache_arr.shape != arr.shape:
                        raise MXNetError("shape mismatch for %s: %s vs %s"
                                         % (name, cache_arr.shape, arr.shape))
                    cache_arr.copyto(arr)
            else:
                if not allow_missing:
                    raise RuntimeError("%s is not presented" % name)
                if initializer is not None:
                    initializer(InitDesc(name, attrs.get(name)), arr)

        for name in self._param_names:
            arr = self._arg_params[name]
            if arg_params is not None and name in arg_params:
                _impl(name, arr, arg_params)
            elif arg_params is not None and not allow_missing:
                raise RuntimeError("%s is not presented" % name)
            elif initializer is not None:
                initializer(InitDesc(name, attrs.get(name)), arr)
        for name in self._aux_names:
            arr = self._aux_params[name]
            if aux_params is not None and name in aux_params:
                _impl(name, arr, aux_params)
            elif initializer is not None:
                initializer(InitDesc(name, attrs.get(name)), arr)

        self.params_initialized = True
        self._params_dirty = False
        self._exec_group.set_params(self._arg_params, self._aux_params,
                                    allow_extra=allow_extra)
        if self._fused_step is not None:
            # externally-set values become the fused step's device copies
            # (optimizer state and compiled program are preserved)
            self._fused_step.reload_params(self._arg_params, self._aux_params)
            self._fused_dirty = False

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        # checkpoint-loading API: surface extra names here (reference does
        # it in executor copy_params_from); fit(arg_params=...) through
        # init_params stays permissive so truncated-symbol fine-tuning
        # keeps working
        if not allow_extra:
            extra = set(arg_params or ()) - set(self._param_names)
            extra |= set(aux_params or ()) - set(self._aux_names)
            if extra:
                raise MXNetError(
                    "parameters %s are not needed by the symbol "
                    "(pass allow_extra=True to ignore)" % sorted(extra))
        if not allow_missing:
            self.init_params(initializer=None, arg_params=arg_params,
                             aux_params=aux_params, allow_missing=allow_missing,
                             force_init=force_init, allow_extra=allow_extra)
            return
        if self.params_initialized and not force_init:
            logging.warning("Parameters already initialized and force_init=False. "
                            "set_params call ignored.")
            return
        self._exec_group.set_params(arg_params, aux_params, allow_extra=allow_extra)
        if self._fused_step is not None:
            merged_args = dict(self._arg_params or {})
            merged_args.update(arg_params or {})
            merged_aux = dict(self._aux_params or {})
            merged_aux.update(aux_params or {})
            self._fused_step.reload_params(merged_args, merged_aux)
            self._fused_dirty = False
        self._params_dirty = True
        self.params_initialized = True

    # ------------------------------------------------------------------
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        """reference: module.py:418."""
        if force_rebind:
            self._reset_bind()
        if self.binded:
            self.logger.warning("Already binded, ignoring bind()")
            return

        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self._grad_req = grad_req

        if not for_training:
            assert not inputs_need_grad

        self._data_shapes, self._label_shapes = _parse_data_desc(
            self.data_names, self.label_names, data_shapes, label_shapes)

        self._exec_group = DataParallelExecutorGroup(
            self._symbol, self._context, self._work_load_list, self._data_shapes,
            self._label_shapes, self._param_names, for_training, inputs_need_grad,
            shared_group=None, logger=self.logger,
            fixed_param_names=self._fixed_param_names, grad_req=grad_req,
            state_names=self._state_names, group2ctxs=self._group2ctxs)
        self.binded = True

        if self.params_initialized:
            # params were set before bind (e.g. Module.load) — push to executors
            self._exec_group.set_params(self._arg_params, self._aux_params)

        if shared_module is not None and shared_module.params_initialized:
            self.set_params(*shared_module.get_params())

    def _reset_bind(self):
        self.binded = False
        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None
        self._rsp_param_names = None
        self._serving_engine = None
        self._inflight.clear()

    def reshape(self, data_shapes, label_shapes=None):
        assert self.binded
        self._data_shapes, self._label_shapes = _parse_data_desc(
            self.data_names, self.label_names, data_shapes, label_shapes)
        self._exec_group.reshape(self._data_shapes, self._label_shapes)
        if self.params_initialized:
            self._exec_group.set_params(self._arg_params, self._aux_params)

    # ------------------------------------------------------------------
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        """reference: module.py:473."""
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring...")
            return
        if self._params_dirty:
            self._sync_params_from_devices()

        kvstore_type = (kvstore if isinstance(kvstore, str)
                        else getattr(kvstore, "type", "") or "")
        (kvstore, update_on_kvstore) = _create_kvstore(
            kvstore, len(self._context), self._arg_params)
        batch_size = self._exec_group.batch_size
        if kvstore and "dist" in kvstore.type and "_sync" in kvstore.type:
            batch_size *= kvstore.num_workers
        rescale_grad = 1.0 / batch_size

        idx2name = {}
        if update_on_kvstore:
            idx2name.update(enumerate(self._exec_group.param_names))
        else:
            for k in range(len(self._context)):
                idx2name.update({i * len(self._context) + k: n
                                 for i, n in enumerate(self._exec_group.param_names)})
        if isinstance(optimizer, str):
            optimizer_params = dict(optimizer_params)
            if "rescale_grad" not in optimizer_params:
                optimizer_params["rescale_grad"] = rescale_grad
            optimizer = opt_mod.create(optimizer, sym=self.symbol,
                                       param_idx2name=idx2name, **optimizer_params)
        else:
            assert isinstance(optimizer, opt_mod.Optimizer)
            if optimizer.rescale_grad != rescale_grad:
                self.logger.warning(
                    "Optimizer created manually outside Module but rescale_grad "
                    "is not normalized to 1.0/batch_size/num_workers (%s vs. %s). ",
                    optimizer.rescale_grad, rescale_grad)
            if not optimizer.idx2name:
                optimizer.idx2name = idx2name.copy()

        self._optimizer = optimizer
        self._kvstore = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._updater = None

        self._try_build_fused_step(kvstore_type)

        if kvstore:
            if self._compression_params:
                kvstore.set_gradient_compression(self._compression_params)
            if update_on_kvstore:
                kvstore.set_optimizer(self._optimizer)
            _initialize_kvstore(kvstore=kvstore,
                                param_arrays=self._exec_group.param_arrays,
                                arg_params=self._arg_params,
                                param_names=self._param_names,
                                update_on_kvstore=update_on_kvstore)
        if not update_on_kvstore:
            self._updater = opt_mod.get_updater(optimizer)

        self.optimizer_initialized = True

        if self._preload_opt_states is not None:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None

    # ------------------------------------------------------------------
    # fused tpu_sync path: ONE jitted XLA program per iteration doing
    # forward + backward + gradient psum over 'dp' + optimizer update with
    # donated buffers — replacing the reference's per-param
    # push/pull/update loop (reference model.py:126-136, SURVEY §3.1)
    # ------------------------------------------------------------------
    def _try_build_fused_step(self, kvstore_type):
        self._fused_step = None
        if not ("tpu" in kvstore_type
                or (kvstore_type == "device" and len(self._context) > 1)):
            return
        if not self.for_training or self._grad_req != "write":
            return
        if self.inputs_need_grad or self._state_names or self._monitor:
            return
        if self._compression_params:
            # an explicit compression request must actually compress: the
            # fused step's in-graph psum rides ICI where 2-bit compression
            # buys nothing, so honor the request on the kvstore push path
            # (which applies error-feedback quantization) instead of
            # silently ignoring it (docs/faq/distributed.md)
            self.logger.info(
                "kvstore=%s: gradient compression requested; using the "
                "kvstore aggregation path (drop compression_params to get "
                "the fused in-graph step)", kvstore_type)
            return
        import jax as _jax
        if _jax.process_count() > 1:
            # multi-process goes through the kvstore allreduce path (the
            # in-graph cross-host psum lives in parallel/collectives.py)
            return
        if self._label_shapes is None:
            return
        from .. import optimizer as _opt
        opt = self._optimizer
        if type(opt) is _opt.SGD:
            fused_name, hp = "sgd", {"momentum": opt.momentum}
        elif type(opt) is _opt.Adam:
            fused_name, hp = "adam", {"beta1": opt.beta1, "beta2": opt.beta2,
                                      "eps": opt.epsilon}
        else:
            self.logger.info("kvstore=%s: optimizer %s has no fused kernel; "
                             "using the per-param update path",
                             kvstore_type, type(opt).__name__)
            return
        # row_sparse params need the kvstore row_sparse path
        attrs = self._symbol.attr_dict()
        if any(attrs.get(n, {}).get("__storage_type__") == "row_sparse"
               for n in self._param_names):
            return
        batch_size = self._data_shapes[0].shape[0]
        if batch_size % len(self._context) != 0:
            self.logger.warning(
                "kvstore=%s: batch %d not divisible by %d devices; "
                "fused step disabled", kvstore_type, batch_size,
                len(self._context))
            return
        from ..parallel.mesh import data_parallel_mesh
        from ..parallel.tpu_step import DataParallelTrainStep
        try:
            devices = [c.jax_device for c in self._context]
        except MXNetError:
            return
        mesh = data_parallel_mesh(devices)
        batch_shapes = {d.name: d.shape for d in self._data_shapes}
        batch_shapes.update({l.name: l.shape for l in self._label_shapes})
        # Mixed precision: optimizer multi_precision=True (reference fp16 +
        # mp_sgd master weights) or MXNET_FUSED_COMPUTE_DTYPE selects the
        # in-program compute dtype; masters/opt state/BN aux stay fp32.
        import os as _os
        compute_dtype = _os.environ.get("MXNET_FUSED_COMPUTE_DTYPE") or \
            ("bfloat16" if getattr(opt, "multi_precision", False) else None)
        if compute_dtype is not None:
            import jax.numpy as _jnp
            try:
                _jnp.dtype(compute_dtype)
            except TypeError:
                self.logger.warning(
                    "MXNET_FUSED_COMPUTE_DTYPE=%r is not a dtype; "
                    "running the fused step in fp32", compute_dtype)
                compute_dtype = None
        supervisor = getattr(self, "_supervisor", None)
        step = DataParallelTrainStep(
            self._symbol, mesh, lr=opt.lr, wd=opt.wd,
            data_names=self._data_names, label_names=self._label_names,
            rescale_grad=opt.rescale_grad, optimizer=fused_name, opt_hp=hp,
            fixed_param_names=self._fixed_param_names,
            clip_gradient=opt.clip_gradient, compute_dtype=compute_dtype,
            supervise=supervisor is not None)
        step.init_from(self._arg_params, self._aux_params, batch_shapes)
        if supervisor is not None:
            # derive the default loss scale from the step's compute dtype
            supervisor.attach_step(step)
        self._fused_step = step
        self._fused_dirty = False
        from ..base import get_env
        self._dispatch_depth = max(0, get_env("MXNET_ASYNC_DISPATCH_DEPTH",
                                              2, int))
        self._inflight.clear()
        self.logger.info("kvstore=%s: fused train step active "
                         "(fwd+bwd+allreduce+%s in one XLA program over %d "
                         "device(s))", kvstore_type, fused_name, len(devices))
        # AOT warmup for TRAINING (ISSUE 14) — pre-pay the fused-step
        # compile from abstract shapes before the first batch, the same
        # front-loading serving warmup has always done; with
        # MXNET_TPU_COMPILE_CACHE set a warm restart turns this into a
        # persistent-cache disk read. Opt out with MXNET_TPU_TRAIN_AOT=0.
        if get_env("MXNET_TPU_TRAIN_AOT", 1, int):
            dtypes = {d.name: d.dtype
                      for d in list(self._data_shapes)
                      + list(self._label_shapes or [])}
            try:
                step.warmup(dtypes)
            except TypeError as e:
                # the declared dtypes are a guess; one the graph rejects
                # at trace time only forfeits the pre-pay — the first
                # step compiles from the real batch. Anything else (an
                # XLA/Mosaic compile error above all) is the program's
                # own failure and surfaces here.
                self.logger.warning(
                    "fused-step AOT warmup rejected the declared batch "
                    "dtypes (first batch will compile instead): %s", e)

    def _fused_lr(self):
        """Per-step learning rate honoring the optimizer's lr scheduler
        (num_update counts fused global steps)."""
        opt = self._optimizer
        opt.num_update += 1
        if opt.lr_scheduler is not None:
            return opt.lr_scheduler(opt.num_update)
        return opt.lr

    def _fused_forward(self, data_batch):
        import numpy as _np2
        from ..ndarray.ndarray import NDArray as _ND
        fused = self._fused_step

        def _raw(arr):
            # hand the step the device buffer itself: .asnumpy() would pull
            # an already-staged batch device->host only for the step to push
            # it straight back (3 link transfers per batch instead of 1).
            # jax arrays are immutable and NDArray mutation swaps buffers,
            # so the captured array can't change under the step.
            if isinstance(arr, _ND):
                return arr._data
            # tpulint: allow-host-sync host-numpy fallback; device arrays take the _data branch
            return _np2.asarray(arr)

        from .. import profiler as _prof
        from ..resilience import faults as _faults
        import time as _time
        # fault site on the host side of every fused dispatch (cached-flag
        # no-op when no spec is set — the zero-overhead contract); the
        # train_chaos gates SIGKILL here mid-epoch
        _faults.fault_point("train.step", step=self._fused_step_count)
        self._fused_step_count += 1
        sup = self._supervisor
        with _prof.span("mx.fit.step.dispatch",
                        step=self._fused_step_count - 1):
            batch = {}
            for desc, arr in zip(self._data_shapes, data_batch.data):
                batch[desc.name] = _raw(arr)
            for desc, arr in zip(self._label_shapes or [],
                                 data_batch.label or []):
                batch[desc.name] = _raw(arr)
            batch = {k: v for k, v in batch.items() if k in fused.arg_names}
            # device-prefetched batches (io_device.DevicePrefetchIter)
            # arrive already on the fused step's batch sharding and pass
            # through zero-copy; anything else is staged by the step itself
            _t0 = _time.perf_counter()
            if sup is not None and fused.supervise:
                # supervised step: the loss scale rides as a runtime arg and
                # the in-graph all-finite verdict rides the output tuple
                outs = fused(batch, lr=self._fused_lr(),
                             scale=sup.step_scale())
                flag = fused.last_flag
            else:
                outs = fused(batch, lr=self._fused_lr())
                flag = None
        # host enqueue time only: nothing here waits for the device, with
        # the profiler on or off, so a profiled fit overlaps as any other
        _dispatch_s = _time.perf_counter() - _t0
        _prof.record_pipeline_event(steps=1, dispatch_ms=_dispatch_s * 1e3)
        if _prof.is_running():
            _prof.record_op_event("tpu_sync_fused_step", _dispatch_s,
                                  category="xla_graph_exec")
        from ..ndarray.ndarray import _new_from_jax
        self._fused_outputs = [_new_from_jax(o) for o in outs]
        self._fused_active = True
        self._fused_dirty = True
        self._params_dirty = True
        # bounded async dispatch: retain outputs of the last `depth` steps
        # and block on step i-depth before dispatching further
        self._inflight.append((outs, flag))
        while len(self._inflight) > self._dispatch_depth:
            self._retire_oldest_inflight()

    def _retire_oldest_inflight(self):
        """Block on (and, supervised, judge) the oldest in-flight step —
        the ONE host point that reads the step verdict, so supervision
        adds zero sync points to the dispatch pipeline."""
        from .. import profiler as _prof
        import time as _time
        oldest, flag = self._inflight.popleft()
        _t1 = _time.perf_counter()
        sup = self._supervisor
        with _prof.span("mx.fit.step.retire"):
            if sup is not None and flag is not None:
                # bounded readback (stall deadline) + verdict observation:
                # NaN skip accounting, loss-scale backoff, NumericDivergence
                sup.await_ready(oldest, flag)
            else:
                import jax as _jax
                _jax.block_until_ready(oldest)
        _prof.record_pipeline_event(
            readback_stall_ms=(_time.perf_counter() - _t1) * 1e3)

    def _drain_inflight_flags(self):
        """Epoch-boundary drain (supervised fits only): every dispatched
        step's verdict must be observed before the checkpoint captures
        the supervisor state, or a resumed run would replay with a stale
        loss scale."""
        if self._supervisor is None:
            return
        while self._inflight:
            self._retire_oldest_inflight()
        self._supervisor.idle()

    def _sync_fused_to_execs(self):
        """Push fused-step params into exec_group (before eval/predict)."""
        if self._fused_step is None or not self._fused_dirty:
            return
        arg_np, aux_np = self._fused_step.export_params()
        for name, v in arg_np.items():
            self._arg_params[name][:] = v
        for name, v in aux_np.items():
            self._aux_params[name][:] = v
        self._exec_group.set_params(self._arg_params, self._aux_params)
        self._fused_dirty = False

    # ------------------------------------------------------------------
    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        if (self._fused_step is not None and self._monitor is None
                and (is_train is None or is_train)
                and getattr(data_batch, "label", None)):
            self._fused_forward(data_batch)
            return
        self._fused_active = False
        self._sync_fused_to_execs()
        curr_data_shapes = tuple(i.shape for i in self._data_shapes)
        if isinstance(data_batch, list):
            new_data_shapes = tuple(b.data[0].shape for b in data_batch)
        else:
            new_data_shapes = tuple(i.shape for i in data_batch.data)
        if curr_data_shapes != new_data_shapes:
            if hasattr(data_batch, "provide_data") and data_batch.provide_data:
                new_dshape = data_batch.provide_data
            else:
                new_dshape = [DataDesc(i.name, shape, i.dtype, i.layout)
                              for i, shape in zip(self._data_shapes, new_data_shapes)]
            if hasattr(data_batch, "provide_label") and data_batch.provide_label:
                new_lshape = data_batch.provide_label
            elif (hasattr(data_batch, "label") and data_batch.label
                  and self._label_shapes):
                new_lshape = [DataDesc(i.name, j.shape, i.dtype, i.layout)
                              for i, j in zip(self._label_shapes, data_batch.label)]
            else:
                new_lshape = None
            self.reshape(new_dshape, new_lshape)
        self._exec_group.forward(data_batch, is_train)

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        if self._fused_active:
            return  # gradient already consumed inside the fused program
        self._exec_group.backward(out_grads=out_grads)

    def update(self):
        """reference: module.py update — kvstore push/pull or local updater.

        Under the fused tpu_sync path the optimizer already ran inside the
        jitted step (forward), so this is a no-op."""
        assert self.binded and self.params_initialized and self.optimizer_initialized
        if self._fused_active:
            self._params_dirty = True
            return
        self._params_dirty = True
        grad_arrays = self._sparsify_grads(self._exec_group.grad_arrays)
        if self._update_on_kvstore:
            _update_params_on_kvstore(self._exec_group.param_arrays,
                                      grad_arrays,
                                      self._kvstore, self._exec_group.param_names)
        else:
            _update_params(self._exec_group.param_arrays,
                           grad_arrays,
                           updater=self._updater,
                           num_device=len(self._context),
                           kvstore=self._kvstore,
                           param_names=self._exec_group.param_names)

    def _sparsify_grads(self, grad_arrays):
        """Dense→row_sparse grad conversion for params declared stype='row_sparse'.

        Reference computes row_sparse grads natively in sparse kernels
        (src/operator/tensor/dot-inl.h csr.T @ dense → rsp); the TPU executor
        computes dense grads (XLA has no sparse), so the sparse-update / kvstore
        row_sparse path recovers the nonzero rows here, on device, before push."""
        if self._rsp_param_names is None:
            attrs = self._symbol.attr_dict()
            self._rsp_param_names = frozenset(
                n for n in self._exec_group.param_names
                if attrs.get(n, {}).get("__storage_type__") == "row_sparse")
        if not self._rsp_param_names:
            return grad_arrays
        from ..ndarray import sparse as _sp
        out = []
        for name, dev_grads in zip(self._exec_group.param_names, grad_arrays):
            if name in self._rsp_param_names:
                dev_grads = [g if isinstance(g, _sp.BaseSparseNDArray)
                             else _sp.row_sparse_from_dense(g) for g in dev_grads]
            out.append(dev_grads)
        return out

    # ------------------------------------------------------------------
    # serving-engine predict path: static-shape inference routes through
    # serving/engine.py — bucketed pre-compiled XLA programs with padded
    # dispatch, so an odd-sized final batch (or a caller-varied batch
    # size) reuses a warmed program instead of recompiling via reshape.
    # MXNET_SERVING_PREDICT=0 restores the plain executor sweep.
    # ------------------------------------------------------------------
    def _predict_serving_engine(self):
        """The module's InferenceEngine, built lazily and refreshed with
        the current params; None when this module can't serve (then
        predict falls back to the executor path)."""
        from ..base import env_flag
        if not env_flag("MXNET_SERVING_PREDICT", True):
            return None
        if not (self.binded and self.params_initialized):
            return None
        if (len(self._context) != 1 or self._state_names
                or self._monitor is not None or self.inputs_need_grad):
            return None
        for desc in self._data_shapes:
            layout = getattr(desc, "layout", None)
            if layout and "N" in layout and layout.find("N") != 0:
                return None  # engine pads/splits along axis 0 only
        if (self._serving_engine is None and self._exec_group.execs
                and self._exec_group.execs[0].has_compiled_forward()):
            # score/eval already paid this module's inference compile on
            # the executor path; building the engine now would compile the
            # same program a second time for nothing. Modules that predict
            # FIRST (the serving pattern) still get the engine — and keep
            # it for every later predict.
            return None
        try:
            # hand the engine the executors' own DEVICE param buffers:
            # same device -> device_put is a no-op alias, so neither the
            # build nor the per-predict refresh moves any bytes, and the
            # engine always serves the training-current weights (exec
            # arrays are the authoritative device copies on every update
            # path; the fused step syncs into them here)
            self._sync_fused_to_execs()
            exe0 = self._exec_group.execs[0]
            arg_params = {n: exe0.arg_dict[n] for n in self._param_names
                          if n in exe0.arg_dict}
            aux_params = dict(exe0.aux_dict)
            if self._serving_engine is None:
                from ..serving import InferenceEngine
                # named engine: Module predicts record per-model latency
                # histograms (profiler.latency_counters "serving.<name>")
                # alongside ModelServer-registered models
                self._serving_engine = InferenceEngine(
                    self._symbol, arg_params, aux_params,
                    ctx=self._context[0],
                    buckets=(self._data_shapes[0].shape[0],),
                    name=getattr(self._symbol, "name", None) or "module")
            else:
                self._serving_engine.update_params(arg_params, aux_params)
            return self._serving_engine
        except Exception as e:
            self.logger.debug("serving predict unavailable (%s); "
                              "falling back to executors", e)
            self._serving_engine = None
            return None

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False, sparse_row_id_fn=None):
        """reference: base_module.py predict, routed through the serving
        engine when shapes are static (single device, batch-major layout,
        no sparse pulls) — see _predict_serving_engine."""
        eng = (self._predict_serving_engine()
               if sparse_row_id_fn is None else None)
        if eng is None:
            return super().predict(
                eval_data, num_batch=num_batch, merge_batches=merge_batches,
                reset=reset, always_output_list=always_output_list,
                sparse_row_id_fn=sparse_row_id_fn)
        if reset:
            eval_data.reset()
        per_batch = []
        try:
            for i, batch in enumerate(eval_data):
                if i == num_batch:
                    break
                n_pad = getattr(batch, "pad", 0) or 0
                request = {}
                for desc, arr in zip(self._data_shapes, batch.data):
                    request[desc.name] = arr[:arr.shape[0] - n_pad] \
                        if n_pad else arr
                # feed labels when the batch carries them: graphs whose
                # inference output consumes the label (MakeLoss heads) must
                # see the same values the executor path would
                for desc, arr in zip(self._label_shapes or [],
                                     getattr(batch, "label", None) or []):
                    request[desc.name] = arr[:arr.shape[0] - n_pad] \
                        if n_pad else arr
                per_batch.append(eng.predict(request))
        except Exception as e:
            # a serve-incompatible graph only reveals itself at dispatch —
            # a bound input with no batch axis (MXNetError), or a bucket
            # program that fails to compile/run (raw XLA errors): fall
            # back to the executor sweep rather than regress predict()
            self._serving_engine = None
            if not reset:
                raise  # a half-consumed non-resettable sweep can't replay
            self.logger.debug("serving predict failed (%s); falling back "
                              "to executors", e)
            return super().predict(
                eval_data, num_batch=num_batch,
                merge_batches=merge_batches, reset=True,
                always_output_list=always_output_list)
        return self._merge_predict_outputs(per_batch, merge_batches,
                                           always_output_list)

    def get_outputs(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        if self._fused_active:
            return list(self._fused_outputs)
        return self._exec_group.get_outputs(merge_multi_context=merge_multi_context)

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.params_initialized and self.inputs_need_grad
        return self._exec_group.get_input_grads(merge_multi_context=merge_multi_context)

    def update_metric(self, eval_metric, labels):
        if self._fused_active:
            # in-graph metric path: per-batch increments stay device
            # scalars (realized only at metric.get()), so no asnumpy()
            # blocks the pipeline. Custom/unsupported metrics fall back
            # to the eager numpy update (MXNET_INGRAPH_METRICS=0 forces
            # the fallback everywhere).
            from ..base import env_flag
            if not (env_flag("MXNET_INGRAPH_METRICS", True)
                    and eval_metric.update_device(labels,
                                                  self._fused_outputs)):
                eval_metric.update(labels, self._fused_outputs)
            return
        self._exec_group.update_metric(eval_metric, labels)

    def _wrap_train_iter(self, train_data):
        """Wrap the user iterator in a DevicePrefetchIter (io_device.py)
        staging the NEXT batch onto the fused step's dp-sharded device
        layout while the current step executes. Fused path only —
        MXNET_DEVICE_PREFETCH=0 opts out, MXNET_DEVICE_PREFETCH_DEPTH
        resizes the staging buffer (default 2 = double buffering)."""
        from ..base import env_flag, get_env
        if self._fused_step is None or \
                not env_flag("MXNET_DEVICE_PREFETCH", True):
            return train_data
        from ..io_device import DevicePrefetchIter, default_stage_fn
        if isinstance(train_data, DevicePrefetchIter):
            return train_data
        if not (hasattr(train_data, "next") and hasattr(train_data, "reset")):
            return train_data
        return DevicePrefetchIter(
            train_data,
            stage_fn=default_stage_fn(
                sharding=self._fused_step._batch_shard),
            depth=max(1, get_env("MXNET_DEVICE_PREFETCH_DEPTH", 2, int)))

    def _sync_params_from_devices(self):
        if self._fused_step is not None and self._fused_dirty:
            arg_np, aux_np = self._fused_step.export_params()
            for name, v in arg_np.items():
                self._arg_params[name][:] = v
            for name, v in aux_np.items():
                self._aux_params[name][:] = v
            self._exec_group.set_params(self._arg_params, self._aux_params)
            self._fused_dirty = False
            self._params_dirty = False
            return
        self._exec_group.get_params(self._arg_params, self._aux_params)
        if self._kvstore and self._update_on_kvstore:
            # weights live on the kvstore; pull the authoritative copies
            for param_name, param_val in sorted(self._arg_params.items()):
                if param_val.stype == "row_sparse":
                    from ..ndarray.ndarray import arange as _nd_arange
                    self._kvstore.row_sparse_pull(
                        param_name, out=[param_val],
                        row_ids=_nd_arange(0, param_val.shape[0]))
        self._params_dirty = False

    def save_optimizer_states(self, fname):
        """Write FULL optimizer state: per-index slots (incl.
        multi-precision master weights), num_update / per-index counters
        and the lr scheduler — checkpoint/state.py's tagged payload, so
        a restored run's schedule continues bit-exactly. Legacy files
        (bare states pickle, fused {"fused","state"} blob) stay loadable
        below."""
        assert self.optimizer_initialized
        if self._update_on_kvstore and self._fused_step is None:
            self._kvstore.save_optimizer_states(fname)
            return
        from ..checkpoint import state as ckpt_state
        atomic_write(fname, ckpt_state.optimizer_payload_bytes(self))

    def load_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._update_on_kvstore and self._fused_step is None:
            self._kvstore.load_optimizer_states(fname)
            return
        from ..checkpoint import state as ckpt_state
        with open(fname, "rb") as f:
            ckpt_state.apply_optimizer_payload(self, f.read())

    def install_monitor(self, mon):
        assert self.binded
        self._monitor = mon  # interior capture needs executors; disables fused
        if self._fused_step is not None:
            self._sync_fused_to_execs()
            self._fused_step = None
        for exec_ in self._exec_group.execs:
            mon.install(exec_)

    def prepare(self, data_batch, sparse_row_id_fn=None):
        """Pull sharded rows before forward (reference: module.py prepare)."""
        assert self.binded
        if sparse_row_id_fn is not None and self._kvstore is not None:
            row_ids = sparse_row_id_fn(data_batch)
            for name, rid in row_ids.items():
                if name in self._param_names:
                    idx = self._param_names.index(name)
                    self._kvstore.row_sparse_pull(
                        name, out=self._exec_group.param_arrays[idx],
                        row_ids=rid)


def _parse_data_desc(data_names, label_names, data_shapes, label_shapes):
    data_shapes = [x if isinstance(x, DataDesc) else DataDesc(*x)
                   for x in data_shapes]
    _check_names_match(data_names, data_shapes, "data", True)
    if label_shapes is not None:
        label_shapes = [x if isinstance(x, DataDesc) else DataDesc(*x)
                        for x in label_shapes]
        _check_names_match(label_names, label_shapes, "label", False)
    else:
        _check_names_match(label_names, [], "label", False)
    return data_shapes, label_shapes


def _check_names_match(data_names, data_shapes, name, throw):
    actual = [x[0] for x in data_shapes]
    if sorted(data_names) != sorted(actual):
        msg = "Data provided by %s_shapes don't match names specified by %s_names " \
              "(%s vs. %s)" % (name, name, str(data_shapes), str(data_names))
        if throw:
            raise ValueError(msg)
        logging.warning(msg)
