"""Profiler (reference: src/profiler/profiler.h:256, python/mxnet/profiler.py).

TPU-native: wraps the JAX/XLA profiler (XPlane/perfetto traces) behind the
mx.profiler API. `dump()` finalizes the trace directory; chrome://tracing-style
output comes from the JAX trace viewer artifacts.
"""
from __future__ import annotations

import json
import os
import time
import threading

import jax

__all__ = ["set_config", "set_state", "dump", "dumps", "pause", "resume",
           "span",
           "record_pipeline_event", "pipeline_counters",
           "record_analysis_check", "record_analysis_finding",
           "analysis_counters", "record_kernel_roofline", "kernel_counters",
           "record_zero_sharding", "zero_counters",
           "record_latency", "latency_counters",
           "latency_histogram", "percentile_from_counts",
           "record_retry", "retry_counters",
           "record_watchdog_event", "watchdog_counters",
           "record_fault_injection", "fault_counters",
           "record_fleet_event", "fleet_counters",
           "record_supervisor_event", "supervisor_counters",
           "record_decode_event", "decode_counters",
           "record_compile", "record_compile_hit", "record_compile_corrupt",
           "compile_counters", "thread_persistent_cache_hits",
           "record_lowering", "lowering_counters",
           "CompilePhases", "record_import", "compile_phase_counters"]

_state = {"running": False, "filename": "profile.json", "events": [],
          "jax_trace_dir": None, "lock": threading.Lock()}


def set_config(**kwargs):
    """profile_symbolic/profile_imperative/... accepted for API parity."""
    if "filename" in kwargs:
        _state["filename"] = kwargs["filename"]
    _state.update({k: v for k, v in kwargs.items() if k != "filename"})


def set_state(state="stop", profile_process="worker"):
    if state == "run":
        if not _state["running"]:
            trace_dir = os.path.splitext(_state["filename"])[0] + "_jax_trace"
            try:
                # host spans (`span`) and the device planes, without the
                # Python tracer: recording every Python call doubled the
                # idle share of the decode loop it was asked to measure
                # (PERF.md section 5, PR 26)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                _state["jax_trace_dir"] = trace_dir
            except Exception:
                _state["jax_trace_dir"] = None
            _state["running"] = True
            _state["start_time"] = time.time()
    elif state == "stop":
        if _state["running"]:
            if _state["jax_trace_dir"]:
                try:
                    jax.profiler.stop_trace()
                except Exception:
                    pass
            _state["running"] = False


def pause(profile_process="worker"):
    set_state("stop")


def resume(profile_process="worker"):
    set_state("run")


def span(name, **attrs):
    """A host span on the JAX profiler's own clock: a context manager
    (``jax.profiler.TraceAnnotation``) that lands in the same
    ``.xplane.pb`` as the device planes, whoever started the trace —
    ``set_state("run")`` here or any ``jax.profiler.start_trace``. With no
    trace session listening it records nothing: that is the off state.
    Attributes go in as keyword arguments (``span("mx.x", n=3)``), or
    later through the returned object's ``set_metadata(**kw)`` for what
    is known only at the end; never format them into the name. The
    ``mx.*`` spans of the program are listed in docs/faq/perf.md."""
    return jax.profiler.TraceAnnotation(name, **attrs)


def is_running():
    return _state["running"]


# ----------------------------------------------------------------------
# training-pipeline overlap counters (module fused path + io_device
# prefetcher). Unlike trace events these are always on — plain counter
# adds — so the bench io_train phase can report overlap efficiency
# without paying for a full profiler session.
# ----------------------------------------------------------------------
_PIPELINE_ZERO = {"steps": 0, "prefetch_hit": 0, "prefetch_stall": 0,
                  "prefetch_stall_ms": 0.0, "prefetch_stage_ms": 0.0,
                  "dispatch_ms": 0.0, "readback_stall_ms": 0.0}
_pipeline = dict(_PIPELINE_ZERO)


def record_pipeline_event(**deltas):
    """Accumulate step-time breakdown counters: `prefetch_hit`/
    `prefetch_stall`[`_ms`] (was the next batch already staged?),
    `prefetch_stage_ms` (worker H2D staging), `dispatch_ms` (host time to
    enqueue the fused step) and `readback_stall_ms` (blocking on step
    i-depth under bounded async dispatch)."""
    with _state["lock"]:
        for k, v in deltas.items():
            _pipeline[k] = _pipeline.get(k, 0) + v


def pipeline_counters(reset=False):
    """Snapshot (optionally reset) the pipeline overlap counters."""
    with _state["lock"]:
        out = dict(_pipeline)
        if reset:
            _pipeline.clear()
            _pipeline.update(_PIPELINE_ZERO)
    return out


# ----------------------------------------------------------------------
# static-analysis counters (MXNET_TPU_LINT=1 compile-time graph passes,
# mxnet_tpu/analysis/runtime.py). Always-on plain adds, like the pipeline
# counters: the bench/CI can assert "N programs checked, 0 findings"
# without a profiler session.
# ----------------------------------------------------------------------
_ANALYSIS_ZERO = {"programs_checked": 0, "findings": 0, "errors": 0,
                  "warnings": 0}
_analysis = dict(_ANALYSIS_ZERO)


def record_analysis_check(n=1):
    """Count one program (jaxpr) swept by the compile-time passes."""
    with _state["lock"]:
        _analysis["programs_checked"] += n


def record_analysis_finding(rule_id, severity):
    """Count one finding, total + per-severity + per-rule."""
    with _state["lock"]:
        _analysis["findings"] += 1
        if severity == "error":
            _analysis["errors"] += 1
        elif severity == "warning":
            _analysis["warnings"] += 1
        key = "rule:%s" % rule_id
        _analysis[key] = _analysis.get(key, 0) + 1


def analysis_counters(reset=False):
    """Snapshot (optionally reset) the static-analysis counters."""
    with _state["lock"]:
        out = dict(_analysis)
        if reset:
            _analysis.clear()
            _analysis.update(_ANALYSIS_ZERO)
    return out


# ----------------------------------------------------------------------
# per-kernel roofline counters (ISSUE 6): each hand-written kernel's win
# is a GATED NUMBER — measured vs ideal, recorded by whoever measured
# (bench phases, tools/flash_tune, tests) and snapshotted like the
# pipeline counters. Always-on plain dict writes, no profiler session.
# ----------------------------------------------------------------------
_kernels = {}


def record_kernel_roofline(kernel, measured, ideal, unit=""):
    """Record one kernel's measured-vs-ideal pair (e.g. achieved TFLOP/s
    vs roofline TFLOP/s, or HLO bytes vs must-move bytes). The ratio is
    derived, not stored, so a re-record with a better measurement is
    self-consistent."""
    with _state["lock"]:
        _kernels[kernel] = {
            "measured": float(measured), "ideal": float(ideal),
            "unit": unit,
            "measured_vs_ideal": (round(float(measured) / float(ideal), 4)
                                  if ideal else None)}


def kernel_counters(reset=False):
    """Snapshot (optionally reset) the per-kernel roofline records."""
    with _state["lock"]:
        out = {k: dict(v) for k, v in _kernels.items()}
        if reset:
            _kernels.clear()
    return out


# ----------------------------------------------------------------------
# ZeRO weight-update-sharding counters (ISSUE 7): the memory/traffic
# contract of MXNET_TPU_ZERO as plain numbers — per-replica optimizer-slot
# bytes vs the replicated baseline, and the per-step scatter/gather
# volumes — recorded by the fused step at build and banked by the
# MULTICHIP bench. Always-on plain dict writes, like the kernel counters.
# ----------------------------------------------------------------------
_zero = {}


def record_zero_sharding(**kv):
    """Record the sharded-update layout accounting (dp, per-replica vs
    replicated optimizer-state bytes, scatter/gather volumes). One record
    per built step; a rebuild overwrites with its own layout."""
    with _state["lock"]:
        _zero.clear()
        _zero.update({k: (float(v) if isinstance(v, float) else int(v))
                      for k, v in kv.items()})
        _zero["enabled"] = 1


def zero_counters(reset=False):
    """Snapshot (optionally reset) the ZeRO update-sharding record.
    Empty dict when no sharded step was built."""
    with _state["lock"]:
        out = dict(_zero)
        if reset:
            _zero.clear()
    return out


# ----------------------------------------------------------------------
# serving latency histograms (ISSUE 8): always-on fixed log-spaced
# buckets, same style as the pipeline/kernel/zero counter families —
# plain adds under the state lock, no profiler session, snapshotted by
# the bench SLA phase, ModelServer.stats(), and the CI serving smoke.
# Keys are free-form; the serving tier records three per model —
# `serving.<model>.queue` (submit -> dispatch), `serving.<model>.device`
# (dispatch -> outputs ready) and `serving.<model>.total` — so tail
# latency decomposes into queue wait vs device time per model.
# ----------------------------------------------------------------------
# Buckets: 10 per decade from 1 µs (1e3 ns) to ~17 min (1e12 ns), fixed
# at import so every snapshot is mergeable. Percentiles come from the
# histogram (upper bucket edge: a conservative <= 26% overestimate at 10
# buckets/decade); mean/max are exact (sum/max tracked per key).
_LAT_MIN_EXP = 3
_LAT_MAX_EXP = 12
_LAT_PER_DECADE = 10
_LAT_EDGES_NS = tuple(
    10.0 ** (_LAT_MIN_EXP + i / float(_LAT_PER_DECADE))
    for i in range((_LAT_MAX_EXP - _LAT_MIN_EXP) * _LAT_PER_DECADE + 1))
_latency = {}


def _lat_bucket_index(ns):
    import math
    if ns <= _LAT_EDGES_NS[0]:
        return 0
    if ns >= _LAT_EDGES_NS[-1]:
        return len(_LAT_EDGES_NS) - 1
    return min(int(math.ceil((math.log10(ns) - _LAT_MIN_EXP)
                             * _LAT_PER_DECADE)),
               len(_LAT_EDGES_NS) - 1)


def record_latency(key, ns):
    """Record one latency observation (nanoseconds) under `key` into the
    fixed log-spaced histogram. Always on; one dict update + one list
    increment under the state lock."""
    ns = float(ns)
    if ns < 0:
        return
    idx = _lat_bucket_index(ns)
    with _state["lock"]:
        h = _latency.get(key)
        if h is None:
            h = _latency[key] = {
                "counts": [0] * len(_LAT_EDGES_NS),
                "count": 0, "sum_ns": 0.0, "max_ns": 0.0}
        h["counts"][idx] += 1
        h["count"] += 1
        h["sum_ns"] += ns
        h["max_ns"] = max(h["max_ns"], ns)


def _lat_percentile_ns(h, q):
    """q in [0,1] -> upper edge (ns) of the bucket where the cumulative
    count crosses q — a conservative (never-underestimating) percentile."""
    target = q * h["count"]
    cum = 0
    for i, c in enumerate(h["counts"]):
        cum += c
        if cum >= target and c:
            return _LAT_EDGES_NS[i]
    return h["max_ns"]


def latency_histogram(key):
    """Raw CUMULATIVE bucket counts for `key` (a copy; aligned with the
    fixed log-spaced edges), or None when nothing recorded. For callers
    that need WINDOWED percentiles — e.g. `ModelServer.health()`'s
    autoscaling signal — who diff two of their own snapshots and feed
    :func:`percentile_from_counts`."""
    with _state["lock"]:
        h = _latency.get(key)
        return list(h["counts"]) if h else None


def percentile_from_counts(counts, q):
    """Conservative (upper-bucket-edge) percentile in MILLISECONDS from
    a bucket-count list (typically a delta of two
    :func:`latency_histogram` snapshots). None when the window holds no
    samples."""
    total = sum(counts)
    if total <= 0:
        return None
    target = q * total
    cum = 0
    for i, c in enumerate(counts):
        cum += c
        if cum >= target and c:
            return _LAT_EDGES_NS[i] / 1e6
    return _LAT_EDGES_NS[-1] / 1e6


def latency_counters(reset=False, prefix=None):
    """Snapshot (optionally reset) the latency histograms as
    key -> {count, p50_ms, p95_ms, p99_ms, mean_ms, max_ms}. `prefix`
    filters keys (e.g. `serving.resnet`) without resetting others; reset
    with a prefix clears only the matching keys."""
    out = {}
    with _state["lock"]:
        for key, h in _latency.items():
            if prefix is not None and not key.startswith(prefix):
                continue
            if not h["count"]:
                continue
            out[key] = {
                "count": h["count"],
                "p50_ms": round(_lat_percentile_ns(h, 0.50) / 1e6, 3),
                "p95_ms": round(_lat_percentile_ns(h, 0.95) / 1e6, 3),
                "p99_ms": round(_lat_percentile_ns(h, 0.99) / 1e6, 3),
                "mean_ms": round(h["sum_ns"] / h["count"] / 1e6, 3),
                "max_ms": round(h["max_ns"] / 1e6, 3)}
        if reset:
            if prefix is None:
                _latency.clear()
            else:
                for key in [k for k in _latency if k.startswith(prefix)]:
                    del _latency[key]
    return out


# ----------------------------------------------------------------------
# resilience counters (ISSUE 9): the retry/backoff policy, the thread
# watchdog, and the fault-injection registry each record here — always-on
# plain adds like the pipeline family, so chaos tests and operators can
# assert "N retries, M recoveries, zero giveups" (or "the stall WAS
# detected") without a profiler session or a debugger.
# ----------------------------------------------------------------------
_RETRY_ZERO = {"retries": 0, "recoveries": 0, "giveups": 0}
_retry = dict(_RETRY_ZERO)
_WATCHDOG_ZERO = {"stalls": 0, "deaths": 0, "restarts": 0,
                  "stall_recoveries": 0}
_watchdog = dict(_WATCHDOG_ZERO)
_faults = {"injected": 0}


def record_retry(site, outcome):
    """Count one retry-policy event for `site` (e.g. "checkpoint.write").
    `outcome`: "retry" (a failed attempt that will be retried),
    "recovery" (success after >= 1 retry), "giveup" (attempts/budget
    exhausted — the error surfaced)."""
    total_key = {"retry": "retries", "recovery": "recoveries",
                 "giveup": "giveups"}.get(outcome)
    with _state["lock"]:
        if total_key is not None:
            _retry[total_key] += 1
        key = "%s.%s" % (site, outcome)
        _retry[key] = _retry.get(key, 0) + 1


def retry_counters(reset=False):
    """Snapshot (optionally reset) the retry counters: totals plus
    per-site `<site>.retry` / `<site>.recovery` / `<site>.giveup` keys."""
    with _state["lock"]:
        out = dict(_retry)
        if reset:
            _retry.clear()
            _retry.update(_RETRY_ZERO)
    return out


def record_watchdog_event(name, event):
    """Count one watchdog observation for thread `name`. `event`: "stall",
    "stall_recovered", "death", "restart", "restart_failed"."""
    total_key = {"stall": "stalls", "death": "deaths",
                 "restart": "restarts",
                 "stall_recovered": "stall_recoveries"}.get(event)
    with _state["lock"]:
        if total_key is not None:
            _watchdog[total_key] += 1
        key = "%s.%s" % (name, event)
        _watchdog[key] = _watchdog.get(key, 0) + 1


def watchdog_counters(reset=False):
    """Snapshot (optionally reset) the watchdog stall/death counters."""
    with _state["lock"]:
        out = dict(_watchdog)
        if reset:
            _watchdog.clear()
            _watchdog.update(_WATCHDOG_ZERO)
    return out


def record_fault_injection(site):
    """Count one fired injected fault (resilience.faults)."""
    with _state["lock"]:
        _faults["injected"] += 1
        _faults[site] = _faults.get(site, 0) + 1


# ----------------------------------------------------------------------
# serving-fleet counters (serving/pool.py + autoscaler.py, ISSUE 12):
# worker membership transitions and autoscaler actions, always-on adds
# like the watchdog family — the chaos/bench gates assert "the death WAS
# detected" and "capacity WAS restored" off these.
# ----------------------------------------------------------------------
_FLEET_ZERO = {"joins": 0, "rejoins": 0, "suspects": 0, "deads": 0,
               "recoveries": 0, "scale_ups": 0, "scale_downs": 0}
_fleet = dict(_FLEET_ZERO)


def record_fleet_event(event):
    """Count one fleet membership/autoscaler event: "join", "rejoin",
    "suspect", "dead", "recovery", "scale_up", "scale_down"."""
    total_key = {"join": "joins", "rejoin": "rejoins",
                 "suspect": "suspects", "dead": "deads",
                 "recovery": "recoveries", "scale_up": "scale_ups",
                 "scale_down": "scale_downs"}.get(event)
    with _state["lock"]:
        if total_key is not None:
            _fleet[total_key] += 1
        else:
            _fleet[event] = _fleet.get(event, 0) + 1


def fleet_counters(reset=False):
    """Snapshot (optionally reset) the serving-fleet counters."""
    with _state["lock"]:
        out = dict(_fleet)
        if reset:
            _fleet.clear()
            _fleet.update(_FLEET_ZERO)
    return out


# ----------------------------------------------------------------------
# training-supervisor counters (resilience/supervisor.py, ISSUE 15):
# numeric-fault containment and restart/resume accounting — always-on
# plain adds like the retry family, so the train_chaos gates can assert
# "the NaN WAS skipped" / "the run WAS restarted" without a profiler
# session. Keys: steps (verdicts observed), bad_steps (skipped),
# divergences, restarts, stalls, scale_backoffs, scale_regrows, resumes.
# ----------------------------------------------------------------------
_SUPERVISOR_ZERO = {"steps": 0, "bad_steps": 0, "divergences": 0,
                    "restarts": 0, "stalls": 0, "scale_backoffs": 0,
                    "scale_regrows": 0, "resumes": 0}
_supervisor = dict(_SUPERVISOR_ZERO)


def record_supervisor_event(**deltas):
    """Accumulate training-supervisor counters (free-form int deltas)."""
    with _state["lock"]:
        for k, v in deltas.items():
            _supervisor[k] = _supervisor.get(k, 0) + v


def supervisor_counters(reset=False):
    """Snapshot (optionally reset) the training-supervisor counters."""
    with _state["lock"]:
        out = dict(_supervisor)
        if reset:
            _supervisor.clear()
            _supervisor.update(_SUPERVISOR_ZERO)
    return out


# ----------------------------------------------------------------------
# stateful-decode counters (serving/decode.py, ISSUE 18): continuous-
# batching decode engine accounting — always-on plain adds like the
# supervisor family, so tests and the decode_smoke gate can assert
# "tokens were produced", "the batch stayed full", "OOM was shed typed"
# without a profiler session. Keys: submitted, served, shed, failed,
# tokens (generated tokens emitted), prefills, steps (decode iterations),
# slot_steps (steps x active rows — occupancy numerator), slot_capacity
# (steps x batch slots — occupancy denominator), cache_oom (allocation
# failures shed typed), stream_frames (token frames crossing the wire),
# stream_resumes (mid-stream resume-by-id re-attaches).
# ----------------------------------------------------------------------
_DECODE_ZERO = {"submitted": 0, "served": 0, "shed": 0, "failed": 0,
                "tokens": 0, "prefills": 0, "steps": 0, "slot_steps": 0,
                "slot_capacity": 0, "cache_oom": 0, "stream_frames": 0,
                "stream_resumes": 0}
_decode = dict(_DECODE_ZERO)


def record_decode_event(**deltas):
    """Accumulate stateful-decode counters (free-form int deltas)."""
    with _state["lock"]:
        for k, v in deltas.items():
            _decode[k] = _decode.get(k, 0) + v


def decode_counters(reset=False):
    """Snapshot (optionally reset) the stateful-decode counters."""
    with _state["lock"]:
        out = dict(_decode)
        if reset:
            _decode.clear()
            _decode.update(_DECODE_ZERO)
    return out


def fault_counters(reset=False):
    """Snapshot (optionally reset) injected-fault counts per site."""
    with _state["lock"]:
        out = dict(_faults)
        if reset:
            _faults.clear()
            _faults["injected"] = 0
    return out


# ----------------------------------------------------------------------
# program-build counters (ISSUE 14): every lower/compile in the tree now
# runs through compile.builder.ProgramBuilder, which records here —
# always-on plain adds like the pipeline family. Per site (executor,
# serving.<model>, train.fused_step, ...): compiles, wall-clock compile
# ms, AOT vs on-demand split, in-process cache hits, and how many
# compiles were served by the PERSISTENT cross-process cache
# (MXNET_TPU_COMPILE_CACHE) — the fleet cold-start/scale-up signal a
# rollover compile stampede shows up in (ModelServer.health()'s
# compiles_in_window reads this family).
# ----------------------------------------------------------------------
_COMPILE_ZERO = {"compiles": 0, "compile_ms": 0.0, "aot": 0,
                 "ondemand": 0, "cache_hits": 0, "persistent_hits": 0,
                 "cache_corrupt": 0}
_compile_total = dict(_COMPILE_ZERO)
_compile_sites = {}
_pcache = {"hits": 0}
_pcache_tls = threading.local()


def _pcache_listener(event, **kwargs):
    # jax.monitoring fires this name once per compile served from the
    # persistent compilation cache. It fires SYNCHRONOUSLY on the thread
    # running the compile, so the thread-local count lets a builder
    # attribute a hit to ITS compile even while another thread's compile
    # (compile-outside-lock) is in flight.
    if event == "/jax/compilation_cache/cache_hits":
        _pcache_tls.hits = getattr(_pcache_tls, "hits", 0) + 1
        with _state["lock"]:
            _pcache["hits"] += 1


def thread_persistent_cache_hits():
    """Persistent-cache hits observed on THIS thread — what builders
    diff around a compile to attribute the hit, so concurrent compiles
    on other threads can never cross-contaminate the attribution."""
    return getattr(_pcache_tls, "hits", 0)


def record_compile(site, compile_ms, aot=True, persistent_hit=False):
    """Record one program compile at `site`: wall-clock ms, whether it
    was ahead-of-time (warmup) or on-demand (first dispatch paid it),
    and whether the XLA executable came from the persistent cache."""
    with _state["lock"]:
        for d in (_compile_total,
                  _compile_sites.setdefault(site, dict(_COMPILE_ZERO))):
            d["compiles"] += 1
            d["compile_ms"] += float(compile_ms)
            d["aot" if aot else "ondemand"] += 1
            if persistent_hit:
                d["persistent_hits"] += 1


def record_compile_hit(site):
    """Record one execution served by an already-built cached program."""
    with _state["lock"]:
        for d in (_compile_total,
                  _compile_sites.setdefault(site, dict(_COMPILE_ZERO))):
            d["cache_hits"] += 1


def record_compile_corrupt(site):
    """Record one persistent-compile-cache entry that failed to load
    (truncated/corrupt bytes) and was degraded to a cache miss — the
    builder recompiled instead of crashing warmup (ISSUE 15)."""
    with _state["lock"]:
        for d in (_compile_total,
                  _compile_sites.setdefault(site, dict(_COMPILE_ZERO))):
            d["cache_corrupt"] += 1


def compile_counters(reset=False):
    """Snapshot (optionally reset) the program-build counters:
    ``{"total": {...}, "sites": {site: {...}}, "persistent_cache_hits":
    N, "persistent_cache_dir": path-or-None}``. compile_ms values are
    cumulative wall-clock milliseconds."""
    from .base import compile_cache_dir
    with _state["lock"]:
        out = {"total": dict(_compile_total),
               "sites": {k: dict(v) for k, v in _compile_sites.items()},
               "persistent_cache_hits": _pcache["hits"],
               "persistent_cache_dir": compile_cache_dir()}
        if reset:
            _compile_total.clear()
            _compile_total.update(_COMPILE_ZERO)
            _compile_sites.clear()
            _pcache["hits"] = 0
    return out


# ----------------------------------------------------------------------
# lowering counters: how often an op took a lowering chosen from its
# shapes, counted as the op's function runs: once a trace in a compiled
# program (the fused step, an executor), once a call in eager mode.
# ``conv_space_to_depth``: a strided convolution over few input channels
# run as a stride-1 one over a space-to-depth input (ops/nn.py).
# ``latent_heads_major``: an attention layer whose latent kernel takes its
# queries and gives its result heads-major (models/motif.py, a layer a
# decode step's trace).
# ----------------------------------------------------------------------
_LOWERING_ZERO = {"conv_space_to_depth": 0, "latent_heads_major": 0}
_lowering = dict(_LOWERING_ZERO)


def record_lowering(form):
    """Count one trace of an op through the lowering ``form``."""
    with _state["lock"]:
        _lowering[form] = _lowering.get(form, 0) + 1


def lowering_counters(reset=False):
    """Snapshot (optionally reset) the lowering counters."""
    with _state["lock"]:
        out = dict(_lowering)
        if reset:
            _lowering.clear()
            _lowering.update(_LOWERING_ZERO)
    return out


# ----------------------------------------------------------------------
# compile-phase counters: where a process's set-up goes. JAX reports the
# phases of every compile through jax.monitoring, with wall-clock start
# and end (time.time()) and the function's name: the Python-level trace,
# the lowering to MLIR (every Pallas kernel's Mosaic lowering inside it)
# and the backend's compile-or-load (a persistent-cache hit is a load).
# They fire on compiles only, never on a call to a built program, and
# for every compile in the process, inside ProgramBuilder or not. Traces
# nest (an outer function's trace holds its inner jits' own), so a phase
# keeps the UNION of its intervals, as wall time: a list of disjoint
# stretches. Beside it, per function, its summed seconds in bursts, which
# names the programs that cost most. Storage grows with the functions
# compiled, never with the calls made.
# ----------------------------------------------------------------------
_PHASE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend"}


def _merge_span(spans, start, end):
    """Fold [start, end] into ``spans``, a sorted list of disjoint
    ``[start, end]`` stretches. Intervals arrive as they end, so the
    stretches they touch are the last few."""
    i = len(spans)
    while i and spans[i - 1][1] >= start:
        i -= 1
    j = i
    while j < len(spans) and spans[j][0] <= end:
        start, end = min(start, spans[j][0]), max(end, spans[j][1])
        j += 1
    spans[i:j] = [[start, end]]


class CompilePhases:
    """Wall time of a process's compiles by phase (``trace``, ``lower``,
    ``backend``) and the package's import. ``listener`` is the
    ``jax.monitoring`` time-span listener; the module keeps one instance,
    registered at import. The listener only appends to a list (atomic
    under the interpreter lock, so it takes no lock of its own):
    intervals are folded into the stretches and into per-function bursts
    when a snapshot is taken, or once ``FOLD_AT`` of them wait."""

    PHASES = ("trace", "lower", "backend")
    FOLD_AT = 4096
    # a function's intervals less than this apart are one burst, which a
    # snapshot's cut takes or leaves whole
    BURST_GAP_S = 1.0

    def __init__(self):
        self._lock = threading.Lock()              # one fold at a time
        self._pending = pending = []               # (event, name, start, end)
        self._spans = {p: [] for p in self.PHASES}
        self._funs = {p: {} for p in self.PHASES}  # name -> [[end, s, n]]
        self._import = None

        append, fold_at, events = pending.append, self.FOLD_AT, _PHASE_EVENTS

        def listener(event, start_time, end_time, fun_name=None, **kwargs):
            if event in events:
                append((event, fun_name, start_time, end_time))
                if len(pending) >= fold_at:
                    self._fold()

        self.listener = listener

    def _fold(self):
        with self._lock:
            # intervals appended while this runs stay for the next fold
            n = len(self._pending)
            for event, name, start, end in self._pending[:n]:
                phase = _PHASE_EVENTS[event]
                _merge_span(self._spans[phase], start, end)
                bursts = self._funs[phase].setdefault(name, [])
                if bursts and start <= bursts[-1][0] + self.BURST_GAP_S:
                    burst = bursts[-1]
                    burst[0] = max(burst[0], end)
                    burst[1] += end - start
                    burst[2] += 1
                else:
                    bursts.append([end, end - start, 1])
            del self._pending[:n]

    def stamp_import(self, start, end):
        self._import = (start, end)

    def snapshot(self, before=None, top=5):
        """``{"import_s", "trace_s", "lower_s", "backend_s", "events",
        "top"}``. Each phase reads the seconds of the union of its
        intervals that end at or before ``before`` (a ``time.time()``;
        ``None``: all). Overlapping intervals are kept merged, so a
        stretch that runs past ``before`` is left out whole. ``events``
        counts a phase's intervals and ``top`` lists its ``top``
        functions by their summed seconds, ``[name, seconds]``, both cut
        at ``before`` by bursts. ``import_s`` is ``None`` until the
        import is stamped."""
        cut = float("inf") if before is None else before
        self._fold()
        with self._lock:
            imp = self._import
            out = {"import_s": None if imp is None else imp[1] - imp[0]}
            for p in self.PHASES:
                out[p + "_s"] = sum((e - s for s, e in self._spans[p]
                                     if e <= cut), 0.0)
            funs = {p: [(str(name), sum(b[1] for b in bursts if b[0] <= cut),
                         sum(b[2] for b in bursts if b[0] <= cut))
                        for name, bursts in self._funs[p].items()]
                    for p in self.PHASES}
        out["events"] = {p: sum(n for _, _, n in funs[p])
                         for p in self.PHASES}
        out["top"] = {p: [[name, s] for name, s, _ in sorted(
                          (f for f in funs[p] if f[2]),
                          key=lambda f: -f[1])[:top]]
                      for p in self.PHASES}
        return out


_phases = CompilePhases()


def record_import(start, end):
    """Stamp the package's own import (``mxnet_tpu/__init__.py``), wall
    clock: what ``compile_phase_counters()["import_s"]`` reads."""
    _phases.stamp_import(start, end)


def compile_phase_counters(before=None, top=5):
    """Where set-up went, by phase, as wall-clock seconds: the package's
    import (``import_s``), and the union over every compile of the
    process of its Python-level trace (``trace_s``), its lowering to MLIR
    (``lower_s``) and the backend's compile or persistent-cache load
    (``backend_s``), counting intervals that end by ``before`` (a
    ``time.time()``; the benchmark passes the opening of its window).
    ``events`` and ``top`` name how many compiles and which functions
    cost most in each phase (:meth:`CompilePhases.snapshot`)."""
    return _phases.snapshot(before, top)


# Both compile listeners are registered here, once, as the module is
# imported: nothing is recorded on a dispatch path, and a compile that
# runs before any ProgramBuilder exists is counted too.
jax.monitoring.register_event_listener(_pcache_listener)
jax.monitoring.register_event_time_span_listener(_phases.listener)


def record_op_event(name, dur_s, category="operator"):
    """Record one operator execution (called by the imperative runtime and
    executor when the profiler is running)."""
    with _state["lock"]:
        _state["events"].append({
            "name": name, "cat": category, "ph": "X",
            "ts": time.time() * 1e6, "dur": dur_s * 1e6,
            "pid": 0, "tid": threading.get_ident() % 1000,
        })


def aggregate_stats():
    """Per-op aggregate table (reference: src/profiler/aggregate_stats.cc
    DumpTable — Name / Total Count / total, avg, min, max ms)."""
    with _state["lock"]:
        events = list(_state["events"])
    stats = {}
    for e in events:
        s = stats.setdefault(e["name"], {"count": 0, "total": 0.0,
                                         "min": float("inf"), "max": 0.0,
                                         "cat": e.get("cat", "operator")})
        d_ms = e["dur"] / 1e3
        s["count"] += 1
        s["total"] += d_ms
        s["min"] = min(s["min"], d_ms)
        s["max"] = max(s["max"], d_ms)
    lines = ["Profile Statistics.",
             "\tNote the difference in units of the overall profiler.",
             "%-32s %-12s %-14s %-14s %-14s %-14s" %
             ("Name", "Total Count", "Time (ms)", "Min Time (ms)",
              "Max Time (ms)", "Avg Time (ms)")]
    lines.append("%-32s %-12s %-14s %-14s %-14s %-14s" %
                 ("----", "-----------", "---------", "-------------",
                  "-------------", "-------------"))
    for name in sorted(stats, key=lambda n: -stats[n]["total"]):
        s = stats[name]
        lines.append("%-32s %-12d %-14.4f %-14.4f %-14.4f %-14.4f" %
                     (name[:32], s["count"], s["total"], s["min"], s["max"],
                      s["total"] / s["count"]))
    return "\n".join(lines)


def dumps(reset=False, format="table"):
    """format='table': per-op aggregate stats (reference profiler.dumps);
    format='chrome': chrome://tracing JSON of the recorded events."""
    if format == "table":
        out = aggregate_stats()
        if reset:
            with _state["lock"]:
                _state["events"] = []
        return out
    with _state["lock"]:
        out = json.dumps({"traceEvents": list(_state["events"])})
        if reset:
            _state["events"] = []
    return out


def dump(finished=True, profile_process="worker"):
    """Write chrome://tracing JSON of host events (device trace in *_jax_trace)."""
    with open(_state["filename"], "w") as f:
        f.write(dumps(format="chrome"))
