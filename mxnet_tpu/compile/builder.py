"""ProgramBuilder — THE graph-to-executable seam (ROADMAP item 5, ISSUE 14).

The survey's executor layer has exactly one graph->executable path
(``nnvm::ApplyPass(g, "PlanMemory")`` feeding a single bind); our
reproduction had grown four — Executor bind/warmup AOT, the serving
bucket cache, and the fused/sharded train-step builds — each with its own
cache, donation rules, and lint hook. This module is the one path they
all route through now:

    shape/dtype/sharding/donation key -> jit.lower() -> .compile()
                                      -> cached executable

with three cross-cutting concerns attached exactly once:

* the PERSISTENT compile cache (``MXNET_TPU_COMPILE_CACHE``,
  base.configure_compile_cache): executables survive process restarts, so
  a fleet worker's warmup after scale-up is mostly disk reads — the
  offline-compilation leverage of arxiv 1810.09868;
* tpulint compile-time sweeps (TPL201-205): the builder guarantees a
  site's ``lint_hook`` runs ONCE per distinct program, never on a cache
  hit (each site keeps its own rule content — donation roles, input
  names — because the contracts genuinely differ per site);
* always-on compile counters (``profiler.record_compile`` /
  ``compile_counters()``): per-site compile wall-clock, AOT-vs-on-demand
  split, in-process cache hits, and persistent-cache-backed compiles.

Concurrency contract (inherited from the serving cache, now owned here):
a thread claims a key's compile under the lock but COMPILES OUTSIDE it —
racers for the same program wait on the pending entry; threads wanting
other cached programs sail past. A failed compile unparks the key so the
next request retries.

Zero-overhead contract: env is read at construction only
(``configure_compile_cache`` is process-idempotent, the lint flag is
snapshotted); ``__call__``/``aot`` never touch ``os.environ``.
"""
from __future__ import annotations

import threading
import time

from ..base import configure_compile_cache

__all__ = ["ProgramBuilder"]


class _Pending:
    """Placeholder parked in the program map while its owner compiles —
    threads wanting the SAME program wait on `ready`; threads wanting
    other (cached) programs are never blocked."""

    __slots__ = ("ready", "program", "error")

    def __init__(self):
        self.ready = threading.Event()
        self.program = None
        self.error = None


class _Ambiguous:
    """Sentinel for a shape signature claimed by two different programs
    (same shapes/dtypes, different explicit shardings): dispatch-time
    lookup refuses to guess and falls back to the jit path."""

    __slots__ = ()


_AMBIGUOUS = _Ambiguous()

# serializes corrupt-cache-entry recovery: the bypass toggles the
# PROCESS-GLOBAL jax_enable_compilation_cache flag, and builder compiles
# deliberately run outside the per-builder lock — without this, recovery
# A's re-enable lands before recovery B's bypass compile and B re-reads
# the same corrupt entry (the crash this path exists to prevent)
_CACHE_BYPASS_LOCK = threading.Lock()


class ProgramBuilder:
    """One program family's lower/compile/cache pipeline.

    Parameters
    ----------
    fn : callable
        The pure program body. Jitted once at construction with the
        donation/sharding options below; ``aot``/``lowered`` trace it
        from abstract (or concrete) arguments.
    site : str
        Observability label — the key compile counters aggregate under
        (``executor.forward``, ``serving.<model>``, ``train.fused_step``).
    donate_argnums : tuple of int
        Buffer-donation spec, applied to both the jit wrapper and every
        AOT executable (they lower through the same wrapper, so the
        donation contract cannot drift between paths).
    in_shardings, out_shardings : optional
        Passed through to ``jax.jit`` when given — the train steps pin
        their dp/state layouts here.
    lint_hook : callable(args) or None
        Site-specific compile-time lint (donation contract + jaxpr
        sweep). With ``MXNET_TPU_LINT=1`` (snapshotted at construction)
        the builder invokes it exactly once per distinct program key,
        before the lowering; cache hits never re-run it. A crashing hook
        logs and never fails the build it observes.
    """

    def __init__(self, fn, site="program", donate_argnums=(),
                 in_shardings=None, out_shardings=None, lint_hook=None):
        import jax
        configure_compile_cache()   # MXNET_TPU_COMPILE_CACHE, idempotent
        self._fn = fn
        self.site = str(site)
        self._donate_argnums = tuple(donate_argnums or ())
        kw = {}
        if self._donate_argnums:
            kw["donate_argnums"] = self._donate_argnums
        if in_shardings is not None:
            kw["in_shardings"] = in_shardings
        if out_shardings is not None:
            kw["out_shardings"] = out_shardings
        self._jit = jax.jit(fn, **kw)
        from ..analysis.runtime import lint_enabled
        # snapshot at construction: aot()/__call__ are dispatch hot paths
        # and must never pay an os.environ read for the guard
        self._lint = lint_enabled()
        self._lint_hook = lint_hook
        self._lint_swept = set()     # program keys already swept
        self._lock = threading.Lock()
        self._programs = {}          # full key -> executable | _Pending
        self._traced = {}            # full key -> jax Traced
        self._lowered = {}           # full key -> jax Lowered
        self._by_shape = {}          # shape key -> executable | _AMBIGUOUS
        self.compiles = 0            # programs built by THIS builder
        self.traces = 0              # distinct traces performed
        self.lowerings = 0           # distinct lowerings performed

    # ------------------------------------------------------------------
    # keys
    # ------------------------------------------------------------------
    @staticmethod
    def _shape_sig(args):
        """shape_key for an argument pytree — what dispatch-time lookup
        uses: shape/dtype/weak_type only (concrete arrays always carry
        an implicit sharding; including it would unmatch every
        warmup-compiled program). Dispatch-hot: dtype OBJECTS key
        directly (np.dtype hashes fast; stringifying one per leaf per
        call measurably taxes every Executor.forward), and a leaf with
        no dtype (a bare python scalar) keys by its type, which can
        never equal an abstract leaf's dtype — such calls simply fall
        back to jit. Weak-typed scalars lower to a DIFFERENT program
        than their strong twins; sharing a key would dispatch an
        executable whose input avals reject the other kind."""
        from jax.tree_util import tree_flatten
        leaves, treedef = tree_flatten(args)
        return treedef, tuple(
            (tuple(getattr(leaf, "shape", ())),
             getattr(leaf, "dtype", None) or type(leaf),
             bool(getattr(leaf, "weak_type", False)))
            for leaf in leaves)

    @staticmethod
    def _sigs(args):
        """(full_key, shape_key) for an argument pytree.

        The full key — what programs cache under — adds each
        ShapeDtypeStruct leaf's EXPLICIT sharding (the serving cache pins
        non-default devices that way), so distinct sharding configs can
        never share an executable."""
        import jax
        leaves, treedef = jax.tree_util.tree_flatten(args)
        full, shape = [], []
        for leaf in leaves:
            dt = getattr(leaf, "dtype", None)
            sig = (tuple(getattr(leaf, "shape", ())),
                   dt if dt is not None else type(leaf),
                   bool(getattr(leaf, "weak_type", False)))
            shape.append(sig)
            if isinstance(leaf, jax.ShapeDtypeStruct) \
                    and getattr(leaf, "sharding", None) is not None:
                sig = sig + (str(leaf.sharding),)
            full.append(sig)
        return (treedef, tuple(full)), (treedef, tuple(shape))

    def key(self, *args):
        """The cache key these arguments build under (donation and any
        jit-level shardings are per-builder config, constant across it)."""
        return self._sigs(args)[0]

    # ------------------------------------------------------------------
    # tracing / lowering (cached; the analysis entry points)
    # ------------------------------------------------------------------
    def traced(self, *args):
        """The cached ``jax.stages.Traced`` for these arguments, tracing
        at most once per distinct program. Every analysis consumer —
        the jaxpr lint sweep (TPL2xx), ``lowered()``/``program_cost``,
        and the TPL3xx program audit — derives from this ONE trace;
        before ISSUE 20 the same program could be traced three times
        (make_jaxpr for lint, jit.lower for cost, a twin for audit).

        Only analysis entry points retain the Traced; plain dispatch
        compiles that never asked for analysis let theirs go (see the
        retention rule on :meth:`lowered`)."""
        key, _ = self._sigs(args)
        with self._lock:
            tr = self._traced.get(key)
        if tr is not None:
            return tr
        tr = self._jit.trace(*args)
        with self._lock:
            if key in self._traced:
                return self._traced[key]
            self._traced[key] = tr
            self.traces += 1
        return tr

    def jaxpr(self, *args):
        """Closed jaxpr of the program these arguments select — the
        TPL2xx sweep input, shared with the trace the compile uses
        (``Traced.jaxpr`` is the same body ``make_jaxpr`` would build,
        minus the second trace)."""
        return self.traced(*args).jaxpr

    def lowered(self, *args):
        """The cached ``jax.stages.Lowered`` for these arguments, tracing
        and lowering at most once per distinct program —
        ``cost_analysis()`` callers (Executor.program_cost) and the
        program audit reuse the same trace+lowering the compile does
        instead of re-tracing a throwaway twin.

        Only THIS entry point retains the Lowered (an analysis consumer
        asked for it); compiles that lower internally let theirs go out
        of scope once the executable exists — a serving process holding
        one HLO module per bucket per replica per version for its whole
        lifetime would be a memory regression over the old build sites."""
        key, _ = self._sigs(args)
        with self._lock:
            low = self._lowered.get(key)
        if low is not None:
            return low
        low = self.traced(*args).lower()
        with self._lock:
            if key in self._lowered:
                return self._lowered[key]
            self._lowered[key] = low
            self.lowerings += 1
        return low

    # ------------------------------------------------------------------
    # compile (cached; compile-outside-lock)
    # ------------------------------------------------------------------
    def aot(self, *args, mode="aot"):
        """The compiled executable for these arguments (abstract
        ShapeDtypeStructs or concrete arrays), compiling on first use.
        ``mode`` labels the compile counter: "aot" for warmup paths,
        "ondemand" when a dispatch had to pay it."""
        return self.aot_info(*args, mode=mode)[0]

    def aot_info(self, *args, mode="aot"):
        """Like :meth:`aot` but returns ``(executable, built)`` — `built`
        is True only for the call that actually compiled (the serving
        cache derives its one-compile-per-bucket counters from it)."""
        key, shape_key = self._sigs(args)
        with self._lock:
            entry = self._programs.get(key)
            if entry is None:
                # claim the compile under the lock (racers for the same
                # program must produce ONE compile) but compile OUTSIDE
                # it: a multi-second XLA compile must not stall dispatch
                # of already-cached programs
                entry = _Pending()
                self._programs[key] = entry
                owner = True
            else:
                owner = False
        from .. import profiler as _prof
        if not owner:
            if isinstance(entry, _Pending):
                entry.ready.wait()
                if entry.error is not None:
                    raise entry.error
                entry = entry.program
            _prof.record_compile_hit(self.site)
            return entry, False
        try:
            prog = self._compile(key, args, mode)
        except BaseException as e:
            entry.error = e
            with self._lock:   # next request retries the compile
                self._programs.pop(key, None)
            entry.ready.set()
            raise
        entry.program = prog
        with self._lock:
            self._programs[key] = prog
            self.compiles += 1
            prev = self._by_shape.get(shape_key)
            if prev is None:
                self._by_shape[shape_key] = prog
            elif prev is not prog:
                self._by_shape[shape_key] = _AMBIGUOUS
        entry.ready.set()
        return prog, True

    def _compile(self, key, args, mode):
        from .. import profiler as _prof
        if self._lint and self._lint_hook is not None \
                and key not in self._lint_swept:
            # once per distinct program — a warmup/run re-request of a
            # cached program neither re-traces nor re-counts
            self._lint_swept.add(key)
            try:
                self._lint_hook(args)
            except Exception as e:
                # the analyzer observes; a hook crash (jaxpr structure
                # drift, site bug) must log, never abort the build
                import logging
                logging.getLogger("mxnet_tpu.analysis").warning(
                    "tpulint: compile-time hook for %s crashed: %s",
                    self.site, e)
        with _prof.span("mx.compile", site=self.site,
                        aot=(mode == "aot")) as sp:
            with self._lock:
                lowered = self._lowered.get(key)
                traced = self._traced.get(key)
            if lowered is None:
                # lower WITHOUT retaining: the executable is what this path
                # is for, and nothing re-reads an un-requested Lowered (see
                # lowered() for the analysis-consumer retention rule). A
                # trace an analysis consumer already paid for IS reused —
                # lint + audit + compile share one trace per program.
                lowered = traced.lower() if traced is not None \
                    else self._jit.lower(*args)
                with self._lock:
                    self.lowerings += 1
            # persistent-hit attribution diffs the THREAD-local event count:
            # jax fires the cache-hit event synchronously on the compiling
            # thread, so a concurrent compile on another thread (the whole
            # point of compile-outside-lock) can never cross-contaminate it
            phits0 = _prof.thread_persistent_cache_hits()
            t0 = time.perf_counter()
            try:
                from ..resilience import faults as _faults
                _faults.fault_point("compile.cache_read", builder=self.site)
                prog = lowered.compile()
            except Exception as e:
                prog = self._compile_after_cache_corruption(lowered, e)
            ms = (time.perf_counter() - t0) * 1e3
            hit = _prof.thread_persistent_cache_hits() > phits0
            sp.set_metadata(persistent_hit=hit)
        _prof.record_compile(self.site, ms, aot=(mode == "aot"),
                             persistent_hit=hit)
        return prog

    def _compile_after_cache_corruption(self, lowered, err):
        """A compile that failed WITH a persistent compile cache
        configured may be a truncated/corrupt cache entry (half-written by
        a killed process, bit-rotted on shared disk) — that must degrade
        to a cache miss, never crash warmup. Recompile once with the cache
        bypassed. Where the retry fails too, the cache was not the cause:
        the FIRST error — the XLA/Mosaic compile error itself — is what
        surfaces, and nothing is counted as corruption. No cache
        configured: the original error surfaces untouched."""
        from ..base import compile_cache_dir
        if compile_cache_dir() is None:
            raise err
        import jax
        with _CACHE_BYPASS_LOCK:
            jax.config.update("jax_enable_compilation_cache", False)
            try:
                prog = lowered.compile()  # tpulint: allow-lock-device-call recovery must serialize: the bypass toggles the process-global compilation-cache flag
            except Exception:
                raise err from None
            finally:
                jax.config.update("jax_enable_compilation_cache", True)
        from .. import profiler as _prof
        _prof.record_compile_corrupt(self.site)
        import logging
        logging.getLogger(__name__).warning(
            "persistent compile cache read failed for %s (%s: %s); "
            "degraded to a cache miss and recompiled", self.site,
            type(err).__name__, err)
        return prog

    # ------------------------------------------------------------------
    # dispatch
    # ------------------------------------------------------------------
    def lookup(self, *args):
        """The already-compiled executable matching these concrete
        arguments' shapes/dtypes, or None (unbuilt, or ambiguous across
        shardings). Cheap: one pytree flatten when any program exists,
        nothing at all before the first compile."""
        if not self._by_shape:
            return None
        prog = self._by_shape.get(self._shape_sig(args))
        return None if prog is _AMBIGUOUS else prog

    def __call__(self, *args):
        """Execute: straight into the AOT executable when one matches
        (warmed paths pay dispatch only — no trace, no jit-cache walk).
        A miss builds the program through the SAME aot pipeline — so
        every compile in the tree, warmup or first-dispatch, lands in
        one cache and one counter family — then dispatches it."""
        prog = self.lookup(*args)
        if prog is None:
            # on-demand: the first dispatch of this shape pays the
            # lower+compile (counted as such); later calls look it up
            prog = self.aot_info(*args, mode="ondemand")[0]
        return prog(*args)

    # ------------------------------------------------------------------
    # audit hook (TPL3xx, ISSUE 20) — beside the lint sweep, same seam
    # ------------------------------------------------------------------
    def contract(self, *args, **kw):
        """Extract this program's audited contract (collectives, comm
        bytes per mesh axis, compiled-cost/memory numbers, realized
        donation, family cardinality) via analysis.program_audit. Reuses
        the builder's own cached trace/lowering — never a throwaway
        twin. Keyword args pass through to ``extract_contract``
        (``mesh=``, ``plan=``)."""
        from ..analysis.program_audit import extract_contract
        return extract_contract(self, args, **kw)

    def program_keys(self):
        """Full cache keys of the programs this builder compiled — the
        TPL303 family-cardinality input (keys differing only in
        weak_type/layout are distinct programs by construction; the
        audit flags sites where that split actually happened)."""
        with self._lock:
            return [k for k, v in self._programs.items()
                    if not isinstance(v, _Pending)]

    # ------------------------------------------------------------------
    def program_count(self):
        """Number of executables this builder holds (pending compiles
        excluded)."""
        with self._lock:
            return sum(1 for v in self._programs.values()
                       if not isinstance(v, _Pending))

    def stats(self):
        """Small observability dict: programs/compiles/traces/lowerings."""
        with self._lock:
            programs = sum(1 for v in self._programs.values()
                           if not isinstance(v, _Pending))
            return {"site": self.site, "programs": programs,
                    "compiles": self.compiles,
                    "traces": self.traces,
                    "lowerings": self.lowerings,
                    "donate_argnums": self._donate_argnums}
