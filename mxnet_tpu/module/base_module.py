"""BaseModule with fit/score/predict (reference: python/mxnet/module/base_module.py:395)."""
from __future__ import annotations

import logging
import time

import numpy as _np

from ..base import MXNetError
from .. import metric as metric_mod
from .. import profiler as _prof
from ..model import BatchEndParam
from ..initializer import Uniform
from ..ndarray.ndarray import NDArray

__all__ = ["BaseModule"]


def _check_input_names(symbol, names, typename, throw):
    args = symbol.list_arguments()
    for name in names:
        if name in args:
            continue
        candidates = [arg for arg in args if not arg.endswith("_weight")
                      and not arg.endswith("_bias") and not arg.endswith("_gamma")
                      and not arg.endswith("_beta")]
        msg = ("\033[91mYou created Module with Module(..., %s_names=%s) but input "
               "with name '%s' is not found in symbol.list_arguments(). Did you "
               "mean one of:\n\t%s\033[0m"
               % (typename, str(names), name, "\n\t".join(candidates)))
        if throw:
            raise ValueError(msg)
        logging.warning(msg)


def _as_list(obj):
    if obj is None:
        return []
    return obj if isinstance(obj, (list, tuple)) else [obj]


class BaseModule:
    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None
        self._total_exec_bytes = 0
        # active TrainingSupervisor (resilience/supervisor.py) while a
        # supervised fit runs; None otherwise (one attribute read per
        # step on the fused path — the zero-overhead contract)
        self._supervisor = None

    # ------------------------------------------------------------------
    # high-level API
    # ------------------------------------------------------------------
    def forward_backward(self, data_batch):
        """reference: base_module.py:191."""
        self.forward(data_batch, is_train=True)
        self.backward()

    def _wrap_train_iter(self, train_data):
        """Hook for subclasses to wrap the fit() training iterator (Module
        adds device-resident prefetch on the fused path); default no-op."""
        return train_data

    def _drain_inflight_flags(self):
        """Hook: supervised fused modules observe every outstanding step
        verdict at the epoch boundary (Module overrides); default no-op."""
        return

    def _eval_batches(self, eval_data, num_batch, reset, sparse_row_id_fn):
        """Shared inference-mode sweep for score/predict/iter_predict:
        reset (optionally), stop after `num_batch`, run the eval-mode
        forward, and hand back (index, batch) pairs."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        for i, batch in enumerate(eval_data):
            if i == num_batch:  # num_batch=None never stops early
                return
            self.prepare(batch, sparse_row_id_fn=sparse_row_id_fn)
            self.forward(batch, is_train=False)
            yield i, batch

    def _unpadded_outputs(self, batch, copy=False):
        """Current outputs with the batch's padding rows stripped (the
        last iterator batch may be padded up to batch_size)."""
        n_pad = batch.pad
        outs = [o[:o.shape[0] - n_pad] for o in self.get_outputs()]
        return [o.copy() for o in outs] if copy else outs

    def score(self, eval_data, eval_metric, num_batch=None, batch_end_callback=None,
              score_end_callback=None, reset=True, epoch=0, sparse_row_id_fn=None):
        """reference: base_module.py score — metric sweep over eval_data."""
        if not isinstance(eval_metric, metric_mod.EvalMetric):
            eval_metric = metric_mod.create(eval_metric)
        eval_metric.reset()

        seen = 0
        for nbatch, batch in self._eval_batches(eval_data, num_batch, reset,
                                                sparse_row_id_fn):
            self.update_metric(eval_metric, batch.label)
            # locals() is part of the BatchEndParam contract: monitor/debug
            # callbacks reach into the scoring scope, and reference-era
            # callbacks index locals by the reference's variable names —
            # alias them alongside ours unconditionally so score_end
            # callbacks see them even when no batch_end_callback is set.
            eval_batch = batch  # noqa: F841
            actual_num_batch = seen  # noqa: F841
            if batch_end_callback is not None:
                params = BatchEndParam(epoch=epoch, nbatch=nbatch,
                                       eval_metric=eval_metric,
                                       locals=locals())
                for callback in _as_list(batch_end_callback):
                    callback(params)
            seen += 1
        if score_end_callback:
            actual_num_batch = seen  # noqa: F841 (reference name, locals())
            params = BatchEndParam(epoch=epoch, nbatch=seen,
                                   eval_metric=eval_metric, locals=locals())
            for callback in _as_list(score_end_callback):
                callback(params)
        return eval_metric.get_name_value()

    def iter_predict(self, eval_data, num_batch=None, reset=True,
                     sparse_row_id_fn=None):
        """reference: base_module.py iter_predict — lazy per-batch outputs."""
        for nbatch, batch in self._eval_batches(eval_data, num_batch, reset,
                                                sparse_row_id_fn):
            yield (self._unpadded_outputs(batch), nbatch, batch)

    @staticmethod
    def _merge_predict_outputs(per_batch, merge_batches, always_output_list):
        """Concatenate per-batch output columns (shared by the executor
        predict path below and Module's serving-engine predict path)."""
        if not per_batch or not merge_batches:
            return per_batch
        if len({len(outs) for outs in per_batch}) != 1:
            raise ValueError("Cannot merge batches: output count varies "
                             "across mini-batches (bucketing?)")
        from ..ndarray.ndarray import concatenate
        merged = [concatenate(list(column)) for column in zip(*per_batch)]
        if len(merged) == 1 and not always_output_list:
            return merged[0]
        return merged

    def predict(self, eval_data, num_batch=None, merge_batches=True, reset=True,
                always_output_list=False, sparse_row_id_fn=None):
        """reference: base_module.py predict — collect (and by default
        concatenate) eval-mode outputs across batches."""
        per_batch = [self._unpadded_outputs(batch, copy=True)
                     for _, batch in self._eval_batches(
                         eval_data, num_batch, reset, sparse_row_id_fn)]
        return self._merge_predict_outputs(per_batch, merge_batches,
                                           always_output_list)

    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            optimizer="sgd", optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=Uniform(0.01), arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None, monitor=None,
            sparse_row_id_fn=None, checkpoint_manager=None, supervisor=None):
        """reference: base_module.py:395 — the epoch loop (:511-520).

        ``checkpoint_manager`` (checkpoint.CheckpointManager) makes fit
        preemption-safe: training auto-resumes from the newest committed
        epoch-boundary checkpoint in the manager's directory (params,
        optimizer slots, lr-schedule counters, RNG chain — bit-exact
        continuation), saves asynchronously every `manager.save_period`
        epochs, and, when the manager has a `preemption_signal`, flushes
        one final checkpoint on that signal.

        ``supervisor`` (resilience.TrainingSupervisor) wraps the whole
        fit in the training-failure loop: in-graph NaN/Inf step skipping
        with dynamic loss scaling, stall detection, bounded auto-restart
        with checkpoint resume, and exact data-position replay (the
        checkpoint manifests grow the iterator cursor + shuffle-RNG
        chain). None consults ``MXNET_TPU_TRAIN_SUPERVISE`` once; pass
        False to force supervision off."""
        assert num_epoch is not None, "please specify number of epochs"

        if supervisor is None:
            from ..resilience.supervisor import supervisor_from_env
            supervisor = supervisor_from_env(checkpoint_manager)
        if supervisor:
            return supervisor.run_fit(self, dict(
                train_data=train_data, eval_data=eval_data,
                eval_metric=eval_metric,
                epoch_end_callback=epoch_end_callback,
                batch_end_callback=batch_end_callback, kvstore=kvstore,
                optimizer=optimizer, optimizer_params=optimizer_params,
                eval_end_callback=eval_end_callback,
                eval_batch_end_callback=eval_batch_end_callback,
                initializer=initializer, arg_params=arg_params,
                aux_params=aux_params, allow_missing=allow_missing,
                force_rebind=force_rebind, force_init=force_init,
                begin_epoch=begin_epoch, num_epoch=num_epoch,
                validation_metric=validation_metric, monitor=monitor,
                sparse_row_id_fn=sparse_row_id_fn,
                checkpoint_manager=checkpoint_manager))

        self.bind(data_shapes=train_data.provide_data,
                  label_shapes=train_data.provide_label,
                  for_training=True, force_rebind=force_rebind)
        if monitor is not None:
            self.install_monitor(monitor)
        self.init_params(initializer=initializer, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)
        self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                            optimizer_params=optimizer_params)

        # overlapped pipeline: stage the next batch onto device while the
        # current step runs (Module wraps in io_device.DevicePrefetchIter
        # on the fused path; MXNET_DEVICE_PREFETCH=0 opts out)
        _user_train_data = train_data
        train_data = self._wrap_train_iter(train_data)

        if validation_metric is None:
            validation_metric = eval_metric
        if not isinstance(eval_metric, metric_mod.EvalMetric):
            eval_metric = metric_mod.create(eval_metric)

        preempt_hook_installed = False
        if checkpoint_manager is not None:
            # auto-resume AFTER bind/init_params/init_optimizer so the
            # restored params overwrite the fresh initialization and the
            # optimizer slots have a live updater to land in. The wrapped
            # train iterator rides along: a manifest carrying a
            # data_position (exact cursor + shuffle-RNG chain) replays
            # the exact batch schedule; the active supervisor's
            # loss-scale/streak state restores the same way.
            begin_epoch = checkpoint_manager.resume(
                self, begin_epoch, train_data=train_data,
                supervisor=self._supervisor)
            if checkpoint_manager.preemption_signal and \
                    not checkpoint_manager._prev_handlers:
                # scoped to THIS fit (uninstalled in the finally below):
                # repeated fits must not stack handlers, and a SIGTERM
                # after training ends has nothing left to flush
                checkpoint_manager.install_preemption_hook()
                preempt_hook_installed = True

        flush_targets = list(_as_list(epoch_end_callback or []))
        if checkpoint_manager is not None:
            flush_targets.append(checkpoint_manager)

        def _flush_async_callbacks(raising):
            """Await async epoch callbacks (do_checkpoint(background=True))
            and the checkpoint manager's writer queue, so in-flight
            daemon writers never die mid-write — even when fit is
            unwinding an exception (then wait() errors are logged, not
            raised, to avoid masking the original)."""
            for callback in flush_targets:
                if callable(getattr(callback, "wait", None)):
                    try:
                        callback.wait()
                    except Exception as e:
                        if not raising:
                            raise
                        self.logger.error("async checkpoint flush: %s", e)

        ################################################################################
        # training loop
        ################################################################################
        try:
            self._fit_epochs(
                train_data, eval_data, eval_metric, validation_metric,
                epoch_end_callback, batch_end_callback, eval_end_callback,
                eval_batch_end_callback, begin_epoch, num_epoch, monitor,
                sparse_row_id_fn, checkpoint_manager)
        except BaseException:
            _flush_async_callbacks(raising=True)
            raise
        finally:
            if checkpoint_manager is not None:
                checkpoint_manager.set_live_capture(None)
                if preempt_hook_installed:
                    checkpoint_manager.uninstall_preemption_hook()
            # tear down a prefetch wrapper THIS fit created: an exception
            # mid-epoch (stall/crash the supervisor will retry) must not
            # leave the old wrapper's stager thread racing a retry
            # attempt's fresh wrapper for the same base iterator
            if train_data is not _user_train_data and \
                    callable(getattr(train_data, "_shutdown", None)):
                train_data._shutdown()
        _flush_async_callbacks(raising=False)

    def _fit_epochs(self, train_data, eval_data, eval_metric,
                    validation_metric, epoch_end_callback,
                    batch_end_callback, eval_end_callback,
                    eval_batch_end_callback, begin_epoch, num_epoch,
                    monitor, sparse_row_id_fn, checkpoint_manager=None):
        for epoch in range(begin_epoch, num_epoch):
            if checkpoint_manager is not None:
                # what a SIGTERM mid-epoch flushes: current params under
                # this epoch's step, tagged mid_epoch (resume skips those
                # and re-runs the epoch from its boundary — the bit-exact
                # choice; serving hot-swap still sees the fresher weights)
                checkpoint_manager.set_live_capture(
                    lambda e=epoch: dict(step=e, module=self, epoch=e))
            tic = time.time()
            eval_metric.reset()
            source = iter(train_data)
            staged = getattr(train_data, "counters", None)

            def next_batch():
                # the step loop's wait for a batch; `hit` where the iterator
                # counts stalls (io_device.DevicePrefetchIter)
                with _prof.span("mx.fit.next_batch") as sp:
                    stalls = staged["stalls"] if staged else 0
                    out = next(source)
                    if staged:
                        sp.set_metadata(hit=staged["stalls"] == stalls)
                return out

            batch = next_batch()
            nbatch, last, epoch_values = 0, False, []
            while not last:
                if monitor is not None:
                    monitor.tic()
                self.forward_backward(batch)
                self.update()
                # pull + stage the NEXT batch while this step's device
                # work is still in flight (the reference's double-buffer;
                # here it overlaps host IO with the async dispatch)
                try:
                    upcoming = next_batch()
                    self.prepare(upcoming, sparse_row_id_fn=sparse_row_id_fn)
                except StopIteration:
                    upcoming, last = None, True
                with _prof.span("mx.fit.metric"):
                    self.update_metric(eval_metric, batch.label)
                if monitor is not None:
                    monitor.toc_print()
                if last:
                    # snapshot metrics BEFORE batch callbacks: Speedometer
                    # auto-resets the metric, and the epoch log below must
                    # report the full epoch's aggregate
                    epoch_values = eval_metric.get_name_value()
                if batch_end_callback is not None:
                    cb_params = BatchEndParam(epoch=epoch, nbatch=nbatch,
                                              eval_metric=eval_metric,
                                              locals=locals())
                    with _prof.span("mx.fit.callback"):
                        for callback in _as_list(batch_end_callback):
                            callback(cb_params)
                nbatch += 1
                batch = upcoming

            for name, val in epoch_values:
                self.logger.info("Epoch[%d] Train-%s=%f", epoch, name, val)
            self.logger.info("Epoch[%d] Time cost=%.3f", epoch,
                             time.time() - tic)

            # supervised fits: observe every dispatched step's verdict
            # before params are pulled/checkpointed (NumericDivergence
            # surfaces here at the latest; the checkpointed supervisor
            # state must reflect the whole epoch)
            self._drain_inflight_flags()
            # pull params to the host once per epoch: epoch callbacks see
            # materialized values, and multi-device aux states re-sync
            arg_snapshot, aux_snapshot = self.get_params()
            self.set_params(arg_snapshot, aux_snapshot)
            if epoch_end_callback is not None:
                for callback in _as_list(epoch_end_callback):
                    callback(epoch, self.symbol, arg_snapshot, aux_snapshot)

            if checkpoint_manager is not None and (
                    (epoch + 1) % checkpoint_manager.effective_save_period()
                    == 0 or epoch == num_epoch - 1):
                # crash-exact resume extras: the train iterator's exact
                # position (pending_reset=True — the original run resets
                # AFTER this save, and resume replays that reset against
                # the restored shuffle-RNG chain) and the supervisor's
                # loss-scale/streak state
                extra = {}
                if callable(getattr(train_data, "iter_checkpoint", None)):
                    try:
                        extra["data_position"] = {
                            "epoch": epoch, "pending_reset": True,
                            "iter": train_data.iter_checkpoint()}
                    except Exception as e:
                        self.logger.warning(
                            "train iterator position not captured (%s); "
                            "resume replays from the epoch boundary with "
                            "a fresh iterator", e)
                if self._supervisor is not None:
                    extra["supervisor_state"] = \
                        self._supervisor.state_dict()
                # async: buffers are pinned here, serialization and the
                # atomic commit happen on the manager's writer thread
                checkpoint_manager.save(
                    step=epoch, module=self, epoch=epoch,
                    arg_params=arg_snapshot, aux_params=aux_snapshot,
                    **extra)

            if eval_data is not None:
                for name, val in self.score(
                        eval_data, validation_metric,
                        score_end_callback=eval_end_callback,
                        batch_end_callback=eval_batch_end_callback,
                        epoch=epoch):
                    self.logger.info("Epoch[%d] Validation-%s=%f", epoch,
                                     name, val)

            train_data.reset()

    # ------------------------------------------------------------------
    # symbol/params accessors
    # ------------------------------------------------------------------
    @property
    def symbol(self):
        return self._symbol

    @property
    def data_names(self):
        raise NotImplementedError

    @property
    def output_names(self):
        raise NotImplementedError

    @property
    def data_shapes(self):
        raise NotImplementedError

    @property
    def label_shapes(self):
        raise NotImplementedError

    @property
    def output_shapes(self):
        raise NotImplementedError

    def get_params(self):
        raise NotImplementedError

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False,
                    allow_extra=False):
        raise NotImplementedError

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init, allow_extra=allow_extra)

    def save_params(self, fname):
        arg_params, aux_params = self.get_params()
        from ..model import save_params as _save
        _save(fname, arg_params, aux_params)

    def load_params(self, fname):
        from ..model import load_params as _load
        arg_params, aux_params = _load(fname)
        self.set_params(arg_params, aux_params)

    # ------------------------------------------------------------------
    # computation interface (implemented by subclasses)
    # ------------------------------------------------------------------
    def prepare(self, data_batch, sparse_row_id_fn=None):
        if sparse_row_id_fn is not None:
            row_ids = sparse_row_id_fn(data_batch)
            if row_ids and hasattr(self, "_kvstore") and self._kvstore is not None:
                for name, rid in row_ids.items():
                    if name in getattr(self, "_arg_params", {}):
                        pass  # rows pulled by Module.prepare override

    def forward(self, data_batch, is_train=None):
        raise NotImplementedError

    def backward(self, out_grads=None):
        raise NotImplementedError

    def update(self):
        raise NotImplementedError

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError

    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError

    def update_metric(self, eval_metric, labels):
        raise NotImplementedError

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        raise NotImplementedError

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        raise NotImplementedError

    def install_monitor(self, mon):
        raise NotImplementedError
