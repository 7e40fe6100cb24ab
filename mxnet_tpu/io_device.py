"""Device-resident batch prefetch — the input half of the overlapped
training pipeline.

The reference framework's dependency engine overlaps IO, H2D copy and
compute by scheduling them as independent engine ops (MXNet paper §engine;
iter_prefetcher.h). The TPU-native equivalent: a background thread pulls
host batches from the wrapped iterator and *stages* them onto the device
(`jax.device_put` against the fused step's dp-sharded batch layout —
sharding-aware, uint8 rides the link untouched) while the current fused
step is still executing.  `next()` then hands the training loop a batch
whose arrays are already device-resident, so the fused step dispatches
with zero host→device transfer on the critical path.

The buffer is bounded (`depth` staged batches, default 2 = classic double
buffering) so the stager can never run unboundedly ahead of compute.
`Module.fit` wraps the user iterator in this automatically when the fused
tpu_sync step is active; `MXNET_DEVICE_PREFETCH=0` opts out and
`MXNET_DEVICE_PREFETCH_DEPTH` resizes the buffer (docs/faq/perf.md).
"""
from __future__ import annotations

import queue
import threading
import time

import numpy as _np

from .base import MXNetError
from .io import DataIter, DataBatch

__all__ = ["DevicePrefetchIter", "default_stage_fn"]


def default_stage_fn(device=None, sharding=None):
    """Build a stage function placing each batch's data/label arrays on
    `sharding` (a jax.sharding.Sharding — e.g. the fused step's dp batch
    shard) or `device` (default: the first jax device).

    The staged batch is marked `_device_staged`: its arrays already sit on
    the fused step's batch sharding, so the step consumes them zero-copy
    (no re-transfer, no reshard) and they stay readable afterwards for
    metrics/callbacks."""
    import jax
    from .ndarray.ndarray import NDArray, _new_from_jax
    target = sharding if sharding is not None else \
        (device if device is not None else jax.devices()[0])

    def _put(arr):
        # tpulint: allow-host-sync host batch normalized before H2D staging; NDArrays pass their buffer
        raw = arr._data if isinstance(arr, NDArray) else _np.asarray(arr)
        return _new_from_jax(jax.device_put(raw, target))

    def stage(batch):
        staged = DataBatch(
            data=[_put(a) for a in (batch.data or [])],
            label=[_put(a) for a in (batch.label or [])],
            pad=getattr(batch, "pad", None),
            index=getattr(batch, "index", None),
            bucket_key=getattr(batch, "bucket_key", None),
            provide_data=getattr(batch, "provide_data", None),
            provide_label=getattr(batch, "provide_label", None))
        staged._device_staged = True
        return staged

    return stage


def _batch_nbytes(batch):
    """Bytes the stager moves for one batch (shapes only, no copy)."""
    return sum(int(getattr(getattr(a, "_data", a), "nbytes", 0))
               for a in list(batch.data or []) + list(batch.label or []))


class DevicePrefetchIter(DataIter):
    """Background-thread iterator wrapper staging the NEXT batch onto
    device while the current step runs.

    Differences from `PrefetchingIter`: batches come out device-resident
    (via `stage_fn`), the buffer depth is configurable, the worker starts
    lazily on the first `next()` (a reset wrapper leaves the base iterator
    untouched until data is actually demanded), and the end-of-stream /
    error sentinel is sticky — once the worker terminates, every later
    `next()` re-raises instead of deadlocking on an empty queue.

    Exposes `counters` (hits/stalls/stall_ms/staged) and mirrors them into
    `profiler.record_pipeline_event` for the bench's overlap report.
    """

    _STOP = object()
    _MAX_RESTARTS = 3  # watchdog re-supervision budget per epoch

    def __init__(self, base_iter, stage_fn=None, depth=2):
        super().__init__(getattr(base_iter, "batch_size", 0))
        self.base = base_iter
        self.depth = max(1, int(depth))
        self.stage_fn = stage_fn if stage_fn is not None else default_stage_fn()
        self._queue = queue.Queue(maxsize=self.depth)
        self._stop = threading.Event()
        self._thread = None
        self._terminal = None
        # the worker's real exception, kept OUTSIDE the queue transport:
        # if the terminal sentinel is ever lost (a put() raced shutdown),
        # the training loop's error still carries the root cause instead
        # of a generic death message
        self._worker_error = None
        # the batch pulled from the base iterator but not yet DELIVERED:
        # a worker death between pull and delivery must not drop it — the
        # watchdog-restarted worker re-stages it first (ISSUE 15)
        self._pending = None
        self._restarts = 0
        self._hb = None
        self.counters = {"hits": 0, "stalls": 0, "stall_ms": 0.0, "staged": 0}

    # ------------------------------------------------------------------
    @property
    def provide_data(self):
        return self.base.provide_data

    @property
    def provide_label(self):
        return self.base.provide_label

    @property
    def default_bucket_key(self):
        return self.base.default_bucket_key

    # ------------------------------------------------------------------
    def _worker(self):
        from . import profiler as _prof
        from .resilience import faults as _faults
        from .resilience.retry import RetryPolicy
        from .resilience.watchdog import watchdog as _watchdog
        # restart policy (ISSUE 15): a thread death that never delivered
        # its terminal sentinel is re-supervised through the factory —
        # the heartbeat closes ONLY on exits that DID transport their
        # outcome (clean stop, StopIteration, sticky error), so a silent
        # death IS detectable and restartable
        hb = self._hb
        if hb is None or hb.closed:
            hb = self._hb = _watchdog().register(
                "mx-device-prefetch", thread=self._thread,
                on_death="restart", restart=self._restart_worker)
        # transient H2D staging failures (device hiccup, OOM-race on a
        # shared host) retry under the one policy instead of killing the
        # whole epoch's pipeline on the first blip
        stage_retry = RetryPolicy(site="prefetch.stage")

        def _stage_once(b):
            _faults.fault_point("prefetch.stage",
                                staged=self.counters["staged"])
            return self.stage_fn(b)

        try:
            while not self._stop.is_set():
                hb.beat()
                if self._pending is None:
                    try:
                        with _prof.span("mx.prefetch.fetch"):
                            self._pending = self.base.next()
                    except StopIteration:
                        self._put(self._STOP)
                        hb.close()
                        return
                t0 = time.perf_counter()
                with _prof.span("mx.prefetch.stage",
                                bytes=_batch_nbytes(self._pending)):
                    staged = stage_retry.call(_stage_once, self._pending)
                _prof.record_pipeline_event(
                    prefetch_stage_ms=(time.perf_counter() - t0) * 1e3)
                self.counters["staged"] += 1
                hb.idle()  # a put() blocked on a full queue is downstream
                #            backpressure, not a prefetch stall
                with _prof.span("mx.prefetch.put"):
                    self._put(staged)
                self._pending = None  # delivered (or shutdown drained it)
            hb.close()  # clean stop
        except BaseException as e:  # transported to next(), then sticky
            self._worker_error = e
            self._put(e)
            hb.close()  # outcome delivered: a surfaced exit, not a death

    def _put(self, item):
        # bounded put that a concurrent reset() can always interrupt
        while not self._stop.is_set():
            try:
                self._queue.put(item, timeout=0.05)
                return
            except queue.Full:
                pass  # tpulint: allow-swallowed-exception bounded-put poll: Full just re-checks the stop flag

    def _start(self):
        self._thread = threading.Thread(target=self._worker,
                                        name="mx-device-prefetch", daemon=True)
        self._thread.start()

    def _restart_worker(self):
        """Watchdog restart factory (on_death="restart"): rebuild the
        stager after a silent death — the pending (pulled-but-never-
        delivered) batch is re-staged first, so no batch is dropped or
        reordered. Raises (=> restart_failed, surfaced) when the iterator
        is stopped/terminal or the budget is spent."""
        if self._stop.is_set() or self._terminal is not None:
            raise MXNetError("prefetch stager stopped/terminal — "
                             "not restartable")
        if self._restarts >= self._MAX_RESTARTS:
            raise MXNetError(
                "prefetch stager exceeded its restart budget (%d)"
                % self._MAX_RESTARTS)
        self._restarts += 1
        self._worker_error = None
        self._start()
        return self._thread

    def _maybe_restart(self):
        """next()'s dead-worker path: give the watchdog's restart policy
        one immediate chance (scan now instead of waiting out the scan
        interval). True when a restart was applied."""
        hb = self._hb
        if hb is None or getattr(hb, "closed", True) \
                or self._restarts >= self._MAX_RESTARTS:
            return False
        before = self._restarts
        from .resilience.watchdog import watchdog as _watchdog
        _watchdog().scan()
        return self._restarts > before or (
            self._thread is not None and self._thread.is_alive())

    def _shutdown(self):
        if self._hb is not None:
            # retire supervision BEFORE stopping the thread: a shutdown
            # must never read as a death (and never trigger a restart)
            self._hb.close()
            self._hb = None
        if self._thread is None:
            return
        self._stop.set()
        # drain until the worker exits — a put() blocked on a full queue
        # could otherwise land a stale batch after a one-shot drain
        while self._thread.is_alive():
            try:
                self._queue.get(timeout=0.05)
            except queue.Empty:
                pass  # tpulint: allow-swallowed-exception shutdown drain poll: Empty re-checks worker liveness
        self._thread.join(timeout=5)
        self._thread = None
        while True:
            try:
                self._queue.get_nowait()
            except queue.Empty:
                break  # tpulint: allow-swallowed-exception queue fully drained: Empty IS the exit condition
        self._stop.clear()

    # ------------------------------------------------------------------
    def reset(self):
        self._shutdown()
        self.base.reset()
        self._terminal = None
        self._worker_error = None
        self._pending = None
        self._restarts = 0
        # worker restarts lazily on the next next(): after the final epoch
        # the base iterator is left freshly reset, not advanced by an
        # eagerly-refilling stager

    # -- ResumableIter capability: forwarded to the base iterator -------
    def iter_checkpoint(self):
        """Exact data position (io.py ResumableIter) — valid at an epoch
        boundary, where the stager has delivered its terminal sentinel
        and the base iterator's cursor IS the consumed position. A
        mid-flight capture would be off by the staged read-ahead."""
        if not callable(getattr(self.base, "iter_checkpoint", None)):
            raise MXNetError("base iterator %s is not resumable"
                             % type(self.base).__name__)
        if self._thread is not None and self._thread.is_alive() \
                and self._terminal is None:
            raise MXNetError(
                "DevicePrefetchIter position is only capturable at an "
                "epoch boundary (the stager reads ahead of consumption)")
        return self.base.iter_checkpoint()

    def iter_restore(self, state):
        self._shutdown()
        self._terminal = None
        self._worker_error = None
        self._pending = None
        self._restarts = 0
        self.base.iter_restore(state)

    def next(self):
        from . import profiler as _prof
        if self._terminal is not None:
            raise self._terminal
        if self._thread is None:
            self._start()
        stall_ms = None
        try:
            item = self._queue.get_nowait()
        except queue.Empty:
            t0 = time.perf_counter()
            while True:
                try:
                    item = self._queue.get(timeout=0.1)
                    break
                except queue.Empty:
                    if self._thread is None or not self._thread.is_alive():
                        # the worker enqueues its terminal sentinel BEFORE
                        # exiting, so a dead thread + empty queue here can
                        # still race one in-flight put — drain once more
                        # before declaring the sentinel lost
                        try:
                            item = self._queue.get_nowait()
                            break
                        except queue.Empty:
                            if self._maybe_restart():
                                # the watchdog's restart policy revived
                                # the stager (pending batch re-staged
                                # first: nothing dropped) — keep waiting
                                continue
                            cause = self._worker_error
                            msg = "device prefetch worker died " \
                                  "without a sentinel"
                            if cause is not None:
                                msg += " (root cause: %s: %s)" \
                                    % (type(cause).__name__, cause)
                            self._terminal = MXNetError(msg)
                            self._terminal.__cause__ = cause
                            raise self._terminal
            stall_ms = (time.perf_counter() - t0) * 1e3
        if item is self._STOP:
            self._terminal = StopIteration()
            raise self._terminal
        if isinstance(item, BaseException):
            self._terminal = item
            raise item
        # hit/stall accounting covers REAL batches only (the terminal
        # sentinel above is pipeline bookkeeping, not overlap efficiency)
        if stall_ms is None:
            self.counters["hits"] += 1
            _prof.record_pipeline_event(prefetch_hit=1)
        else:
            self.counters["stalls"] += 1
            self.counters["stall_ms"] += stall_ms
            _prof.record_pipeline_event(prefetch_stall=1,
                                        prefetch_stall_ms=stall_ms)
        return item

    def iter_next(self):
        raise NotImplementedError

    def __del__(self):
        try:
            self._stop.set()
        except Exception:
            pass  # tpulint: allow-swallowed-exception interpreter-teardown destructor must never raise
