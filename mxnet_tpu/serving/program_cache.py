"""Bucketed AOT program cache — the compiled-executable store of the serving
subsystem.

Reference anchors: the dependency engine's op bulking (MXNet paper §4,
amortizing per-op dispatch) and TF-Serving's "one compiled graph, many
requests" layer (arXiv:1605.08695 §4.4). TPU-native form: requests are
rounded UP to a small set of batch buckets, each bucket's XLA program is
compiled ONCE ahead of time via ``jax.jit(f).lower(...).compile()``, and the
pure-inference program donates its input-batch buffers so XLA can reuse them
for outputs (no per-request allocation churn on device).

Why buckets: ``jax.jit`` recompiles per input shape, and a production traffic
mix of batch sizes 1..32 would otherwise pay a multi-second XLA compile for
every new size the first time it appears (the exact failure mode of the
headline bench's bare-jit path, executor.py). With buckets (1, 4, 8, 16, 32)
at most five programs ever exist, every request shape maps onto one, and
warmup can pre-pay all of them before traffic arrives.

Cold-start persistence: when ``MXNET_TPU_COMPILE_CACHE`` names a directory,
JAX's persistent compilation cache is pointed at it (base.py:
``configure_compile_cache``) so the bucket programs survive process restarts
— warmup after a redeploy becomes a disk read, not an XLA compile.
"""
from __future__ import annotations

import threading

import numpy as _np

from ..base import MXNetError

__all__ = ["BucketedProgramCache", "DEFAULT_BUCKETS", "bucket_for"]

DEFAULT_BUCKETS = (1, 4, 8, 16, 32)


def bucket_for(n, buckets):
    """Smallest configured bucket >= n, or n itself when it exceeds the
    largest bucket (an oversized request compiles its exact shape rather
    than failing — it is cached too, so a steady oversized flow pays one
    compile, same contract as a bucket)."""
    if n <= 0:
        raise MXNetError("batch size must be positive, got %d" % n)
    for b in buckets:
        if n <= b:
            return b
    return n


def _donate_supported():
    """Buffer donation is a no-op (with a per-compile warning) on the CPU
    backend; only enable it where XLA honors it."""
    import jax
    return jax.devices()[0].platform != "cpu"


class BucketedProgramCache:
    """Compile-once store of per-bucket XLA executables for one model.

    Parameters
    ----------
    fn : callable(batch_vals, param_vals, aux_vals, rng) -> tuple
        Pure inference function. ``batch_vals`` is a dict of batch-major
        input arrays (the donated argument), ``param_vals``/``aux_vals``
        are the weight dicts (NOT donated — they are reused every call),
        ``rng`` is a PRNG key (a fixed one for deterministic graphs).
    buckets : tuple of int
        Allowed batch sizes, ascending.
    donate : bool or "auto"
        Donate the batch argument's buffers on the inference path.
        "auto" enables it only on backends that honor donation (not CPU).
    device : jax.Device or None
        Device the programs compile for. Lowering from abstract shapes
        pins jit's default device, so a non-default target (e.g. tpu(1))
        must be named explicitly or every call would hit a committed-
        device mismatch. None keeps the default.
    site : str
        Compile-counter label (``profiler.compile_counters()``); the
        serving engine passes its latency key (``serving.<model>``) so a
        rollover/rejoin compile stampede is attributable per model.
    """

    def __init__(self, fn, buckets=DEFAULT_BUCKETS, donate="auto",
                 device=None, site="serving"):
        if not buckets:
            raise MXNetError("program cache needs at least one bucket")
        self._buckets = tuple(sorted(int(b) for b in buckets))
        if self._buckets[0] <= 0:
            raise MXNetError("buckets must be positive, got %s"
                             % (self._buckets,))
        if donate == "auto":
            donate = _donate_supported()
        self._donate = bool(donate)
        self._fn = fn  # unjitted original: the MXNET_TPU_LINT trace target
        from ..analysis.runtime import lint_enabled
        # snapshot at construction: run() is the serving dispatch hot path
        # and must not pay a per-request os.environ read for the guard
        self._lint = lint_enabled()
        self._lint_escapes_seen = set()  # TPL204 reported once per size
        self._lint_donation_checked = False  # TPL203 once per cache
        import jax
        # donate_argnums=0: only the per-request batch dict is donated;
        # the params/aux dicts are long-lived and survive every call
        self._donate_argnums = (0,) if self._donate else ()
        # the ONE lower/compile/cache path (compile/builder.py): the
        # builder owns key -> lowered -> executable with compile-outside-
        # lock concurrency, the persistent compile cache, the compile
        # counters, and runs _lint_compile_hook once per distinct program
        from ..compile.builder import ProgramBuilder
        self._builder = ProgramBuilder(fn, site=site,
                                       donate_argnums=self._donate_argnums,
                                       lint_hook=self._lint_compile_hook)
        self._sharding = None
        if device is not None and device != jax.devices()[0]:
            # abstract lowering otherwise pins jit's default device; a
            # sharding-annotated ShapeDtypeStruct pins the real target
            from jax.sharding import SingleDeviceSharding
            self._sharding = SingleDeviceSharding(device)
        self._lock = threading.Lock()
        self.compiles = 0            # programs built (AOT or on demand)
        self.hits = 0                # executions served by a cached program
        self.misses = 0              # executions that had to compile first
        # per-bucket measured compile-warm step time: EWMA mean + sample
        # count + a decaying-max TAIL. The engine feeds this from real
        # timed executions; the SLA batcher reads the mean for early
        # dispatch and the tail for the shed-feasibility test — on a
        # contended host the mean says what a step usually costs while
        # the tail says what the request at the deadline edge must
        # survive (GC pause, GIL handoff, scheduler hiccup). Compile-
        # bearing samples are the caller's job to exclude.
        self._step_time = {}         # bucket -> [ewma_s, n_samples, tail_s]
        # MXNET_TPU_COMPILE_CACHE wiring (configure_compile_cache) now
        # happens once inside the ProgramBuilder construction above

    # ------------------------------------------------------------------
    @property
    def buckets(self):
        return self._buckets

    @property
    def donate(self):
        return self._donate

    def bucket_for(self, n):
        return bucket_for(n, self._buckets)

    # ------------------------------------------------------------------
    # measured step time (the SLA batcher's shed/early-dispatch signal)
    # ------------------------------------------------------------------
    def observe_step_time(self, bucket, seconds):
        """Fold one measured compile-warm execution time for `bucket`:
        EWMA mean (alpha 0.3 — tracks host drift within a few samples
        while damping single-run noise) and decaying max tail (a spike
        registers immediately and fades at 0.85/sample once conditions
        improve)."""
        seconds = float(seconds)
        if seconds <= 0:
            return
        with self._lock:
            rec = self._step_time.get(bucket)
            if rec is None:
                self._step_time[bucket] = [seconds, 1, seconds]
            else:
                rec[0] += 0.3 * (seconds - rec[0])
                rec[1] += 1
                rec[2] = max(seconds, rec[2] * 0.85)

    def step_time(self, bucket):
        """EWMA mean compile-warm step time for `bucket` in seconds, or
        None while unmeasured."""
        with self._lock:
            rec = self._step_time.get(bucket)
            return rec[0] if rec is not None else None

    def step_time_tail(self, bucket):
        """Decaying-max step time for `bucket` (seconds), or None while
        unmeasured — what the shed-feasibility test budgets for."""
        with self._lock:
            rec = self._step_time.get(bucket)
            return rec[2] if rec is not None else None

    def step_samples(self, bucket):
        """How many timed executions have been folded for `bucket`."""
        with self._lock:
            rec = self._step_time.get(bucket)
            return rec[1] if rec is not None else 0

    # ------------------------------------------------------------------
    def _abstract(self, shape, dtype):
        import jax
        if self._sharding is not None:
            return jax.ShapeDtypeStruct(shape, dtype,
                                        sharding=self._sharding)
        return jax.ShapeDtypeStruct(shape, dtype)

    def _sds(self, tree):
        return {k: self._abstract(tuple(_np.shape(v)), v.dtype)
                for k, v in tree.items()}

    def _lint_compile_hook(self, args):
        """MXNET_TPU_LINT compile-time passes (docs/faq/analysis.md),
        invoked by the builder ONCE per distinct program, before the
        XLA compile: the serving donation contract (only the per-request
        batch may be donated — a donated weight buffer is freed under the
        next request), then a jaxpr sweep for f64 leaks and dead
        subgraphs."""
        from ..analysis.graph_passes import check_donation
        from ..analysis.runtime import check_traced, report_findings
        batch_sds = args[0]
        if not self._lint_donation_checked:
            # the donate spec is cache-wide — one report, not one per
            # bucket compile
            self._lint_donation_checked = True
            report_findings(check_donation(
                self._donate_argnums, ("batch", "params", "aux", "rng"),
                mode="serving", where="program_cache.compile"))
        check_traced(self._fn, args,
                     "serving program (batch=%s)"
                     % sorted((k, tuple(v.shape))
                              for k, v in batch_sds.items()),
                     # the builder's cached trace — the compile about to
                     # happen lowers from the SAME Traced (ISSUE 20)
                     jaxpr=self._builder.jaxpr(*args))

    def _get(self, batch_sds, param_sds, aux_sds, rng_sd, count=True):
        # two threads racing the same bucket produce ONE compile (the
        # counter is the test contract) and compiles never stall dispatch
        # of already-cached bucket programs — both owned by the builder's
        # claim-under-lock/compile-outside-it pipeline now
        prog, built = self._builder.aot_info(
            batch_sds, param_sds, aux_sds, rng_sd,
            mode="ondemand" if count else "aot")
        with self._lock:
            if built:
                self.compiles += 1
                if count:
                    self.misses += 1
            elif count:
                self.hits += 1
        return prog

    # ------------------------------------------------------------------
    def warmup(self, batch_template, params, aux, rng, buckets=None):
        """AOT-compile the program for each bucket.

        ``batch_template`` maps input name -> ShapeDtypeStruct-like with the
        CONFIGURED batch size in axis 0; each bucket's shapes are derived by
        swapping that axis. Returns the number of programs compiled (cached
        buckets — e.g. restored via the persistent cache — still count as
        compiles here the first time this process sees them)."""
        param_sds = self._sds(params)
        aux_sds = self._sds(aux)
        rng_sd = self._abstract(tuple(_np.shape(rng)), rng.dtype)
        n_before = self.compiles
        for b in (buckets or self._buckets):
            batch_sds = {
                k: self._abstract((int(b),) + tuple(v.shape[1:]), v.dtype)
                for k, v in batch_template.items()}
            self._get(batch_sds, param_sds, aux_sds, rng_sd, count=False)
        return self.compiles - n_before

    def run(self, batch_vals, param_vals, aux_vals, rng):
        """Execute the cached program for these shapes (compiling on miss).

        ``batch_vals`` must already be padded to a bucket (the batcher's
        job); its buffers are donated when donation is enabled — the caller
        must not reuse them after this call."""
        if self._lint and batch_vals:
            # recompilation-hazard pass: a batch size above the top bucket
            # compiles its own exact-shape program per distinct size — so
            # the hazard is per distinct size, reported once, not per
            # request (a steady oversized client must not spam the log
            # and skew the TPL204 counter on every dispatch)
            n = int(_np.shape(next(iter(batch_vals.values())))[0] or 0)
            if n not in self._lint_escapes_seen:
                self._lint_escapes_seen.add(n)
                from ..analysis.graph_passes import check_bucket_escape
                from ..analysis.runtime import report_findings
                findings = check_bucket_escape(n, self._buckets,
                                               "program_cache.run")
                if findings:
                    report_findings(findings)
        batch_sds = self._sds(batch_vals)
        param_sds = self._sds(param_vals)
        aux_sds = self._sds(aux_vals)
        rng_sd = self._abstract(tuple(_np.shape(rng)), rng.dtype)
        prog = self._get(batch_sds, param_sds, aux_sds, rng_sd)
        return prog(batch_vals, param_vals, aux_vals, rng)

    def comm_plan(self):
        """Declared comm contract for the TPL3xx program audit: serving
        programs are single-program-per-bucket and collective-free (any
        mesh comm belongs to the model fn, not the cache) — the family
        cardinality IS the bucket count, which is exactly what TPL303
        pins (a per-request-shape recompile shows up as programs >
        len(buckets))."""
        from ..analysis.program_audit import CommPlan
        return CommPlan(site=self._builder.site, allowed=(),
                        max_programs=len(self._buckets))

    def stats(self):
        with self._lock:
            step_ms = {str(b): round(rec[0] * 1e3, 3)
                       for b, rec in sorted(self._step_time.items())}
            tail_ms = {str(b): round(rec[2] * 1e3, 3)
                       for b, rec in sorted(self._step_time.items())}
        return {"compiles": self.compiles, "hits": self.hits,
                "misses": self.misses,
                "programs": self._builder.program_count(),
                "donate": self._donate, "step_time_ms": step_ms,
                "step_tail_ms": tail_ms}
