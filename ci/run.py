#!/usr/bin/env python
"""CI harness (reference analog: ci/build.py docker matrix +
tests/jenkins/run_test_pip_installed.sh — SURVEY.md §2.9).

The reference CI builds libmxnet.so across a docker matrix and fans unit
tests over language bindings. The TPU-native equivalent is a staged local
pipeline: build the native runtime, run the Python suite on a virtual
8-device CPU mesh (how multi-chip sharding is validated without hardware,
SURVEY.md §4), run the C++ unit tests, then the driver-facing gates
(multichip dryrun; bench smoke on CPU).

Usage:
    python ci/run.py                 # full pipeline
    python ci/run.py build unit      # just those stages
    python ci/run.py --list
"""
import argparse
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

sys.path.insert(0, ROOT)
from ci.envutil import cpu_mesh_env as _env_cpu_mesh  # noqa: E402


def stage_build(_):
    """Build the native IO/storage runtime (src/Makefile -> libmxtpu_io.so)."""
    return subprocess.call(["make", "-C", os.path.join(ROOT, "src")])


def stage_lint(_):
    """tpulint static analysis over mxnet_tpu/ and tools/ (gating:
    any unsuppressed error-severity finding fails the stage —
    docs/faq/analysis.md)."""
    return subprocess.call(
        [sys.executable, "-m", "mxnet_tpu.analysis.lint",
         "mxnet_tpu", "tools"], cwd=ROOT)


def stage_program_audit_smoke(_):
    """Non-slow compiled-program gate (ISSUE 20): the TPL3xx audit —
    live program contracts (collectives/axes/bytes, compiled-cost,
    donation, family cardinality) extracted on the 8-device reference
    mesh must diff green against the committed ci/program_manifests/; a
    seeded manifest mutation must FAIL with the right TPL3xx rule; the
    deliberately mis-pinned ZeRO grad spec (the PR 7 hazard) must fail
    TPL301 naming the collective and the axis — then tpulint over the
    analysis modules."""
    rc = subprocess.call(
        [sys.executable, os.path.join(ROOT, "tools",
                                      "program_audit_smoke.py")],
        env=_env_cpu_mesh(8), cwd=ROOT)
    if rc != 0:
        return rc
    return subprocess.call(
        [sys.executable, "-m", "mxnet_tpu.analysis.lint",
         os.path.join("mxnet_tpu", "analysis")], cwd=ROOT)


def stage_unit(args):
    """Python unit suite on the virtual 8-device CPU mesh."""
    cmd = [sys.executable, "-m", "pytest",
           os.path.join(ROOT, "tests", "python", "unittest"), "-q"]
    if args.fast:
        cmd += ["-x"]
    return subprocess.call(cmd, env=_env_cpu_mesh(), cwd=ROOT)


def stage_train(args):
    """Convergence/fp16 training tests (reference tests/python/train)."""
    return subprocess.call(
        [sys.executable, "-m", "pytest",
         os.path.join(ROOT, "tests", "python", "train"), "-q"],
        env=_env_cpu_mesh(), cwd=ROOT)


def stage_cpp(_):
    """C++ unit tests (tests/cpp via the pytest driver that compiles them)."""
    return subprocess.call(
        [sys.executable, "-m", "pytest",
         os.path.join(ROOT, "tests", "python", "unittest",
                      "test_cpp_units.py"), "-q"],
        env=_env_cpu_mesh(), cwd=ROOT)


def stage_zero_smoke(_):
    """Non-slow multichip-dryrun smoke: compile + run the dp-sharded
    (MXNET_TPU_ZERO) train step on a forced 8-device host mesh
    (XLA_FLAGS=--xla_force_host_platform_device_count=8 via cpu_mesh_env)
    and gate on bit-parity with the replicated update — so dp-sharded
    programs compile in CI, not only in the bench harness."""
    return subprocess.call(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_zero(8)"], cwd=ROOT)


def stage_multichip(_):
    """Driver gate: full parallelism dryrun on an 8-device CPU mesh.
    The ZeRO phase is skipped here — zero_smoke already ran the identical
    sweep this CI pass (the driver's direct dryrun_multichip keeps it)."""
    env = dict(os.environ)
    env["_GRAFT_SKIP_ZERO_PHASE"] = "1"
    return subprocess.call(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(8)"],
        env=env, cwd=ROOT)


def stage_serving_smoke(_):
    """Non-slow serving-tier gate (ISSUE 8): two models on one
    ModelServer — solo-engine isolation, zero-compile rollover, and a
    forced-overload deadline trace whose served + shed accounting must
    sum to submitted — then tpulint (TPL101-TPL105) over the serving
    modules."""
    rc = subprocess.call(
        [sys.executable, os.path.join(ROOT, "tools", "serving_smoke.py")],
        env=_env_cpu_mesh(1), cwd=ROOT)
    if rc != 0:
        return rc
    return subprocess.call(
        [sys.executable, "-m", "mxnet_tpu.analysis.lint",
         os.path.join("mxnet_tpu", "serving")], cwd=ROOT)


def stage_frontdoor_smoke(_):
    """Non-slow cross-process serving gate (ISSUE 11): two client OS
    processes get bit-identical predictions over the TCP front door,
    deadline shed travels typed across the wire, and a graceful drain
    resolves every in-flight request (submitted == served + shed +
    failed, zero pending) — then tpulint over the serving modules."""
    rc = subprocess.call(
        [sys.executable, os.path.join(ROOT, "tools",
                                      "frontdoor_smoke.py")],
        env=_env_cpu_mesh(1), cwd=ROOT)
    if rc != 0:
        return rc
    return subprocess.call(
        [sys.executable, "-m", "mxnet_tpu.analysis.lint",
         os.path.join("mxnet_tpu", "serving")], cwd=ROOT)


def stage_decode_smoke(_):
    """Non-slow stateful-decode gate (ISSUE 18): two client OS processes
    stream autoregressive decodes bit-identical to solo decode, a
    connection killed mid-stream resumes by sequence id with zero token
    loss/duplication, cache pressure sheds typed across the wire
    (never-fit up front, mid-generation with partial output intact), the
    program family stays at len(buckets) + 1 and the paged allocator
    drains to zero live blocks. The transformer section (ISSUE 19)
    needs the 8-device host mesh: the flash kernel tier must ENGAGE
    (interpret off-TPU, asserted — never a silent lax fallback) and the
    tp-sharded-KV engine must match lax solo token-for-token — then
    tpulint over the serving modules."""
    rc = subprocess.call(
        [sys.executable, os.path.join(ROOT, "tools", "decode_smoke.py")],
        env=_env_cpu_mesh(8), cwd=ROOT)
    if rc != 0:
        return rc
    return subprocess.call(
        [sys.executable, "-m", "mxnet_tpu.analysis.lint",
         os.path.join("mxnet_tpu", "serving")], cwd=ROOT)


def stage_wire_fuzz_smoke(_):
    """Non-slow untrusted-wire gate (ISSUE 13): a fuzz corpus captured
    from REAL frontdoor+fleet traffic feeds >= 10k seeded mutations
    through the safe decoder — only typed FrameError, allocation
    bounded by the caps; a previous-protocol subprocess (old hello, old
    pickle codec) is served bit-identically by the safe-default gateway
    (rolling upgrade); a fuzz-spraying peer is evicted with exact
    accounting for everyone else — then tpulint (incl. TPL107
    wire-unpickle) over the serving modules."""
    rc = subprocess.call(
        [sys.executable, os.path.join(ROOT, "tools",
                                      "wire_fuzz_smoke.py")],
        env=_env_cpu_mesh(1), cwd=ROOT)
    if rc != 0:
        return rc
    return subprocess.call(
        [sys.executable, "-m", "mxnet_tpu.analysis.lint",
         os.path.join("mxnet_tpu", "serving")], cwd=ROOT)


def stage_fleet_smoke(_):
    """Non-slow cross-host serving gate (ISSUE 12): a REAL worker OS
    process joins the fleet (warmup + half-open probe) and serves
    bit-identical predictions; SIGKILLing it mid-trace loses nothing
    (submitted == served + shed + failed, requests reroute, the fleet
    marks the host SUSPECT/DEAD); a tampered frame is rejected by the
    HMAC auth BEFORE unpickling; the zero-overhead contract holds with
    fleet env unset — then tpulint over the serving modules."""
    rc = subprocess.call(
        [sys.executable, os.path.join(ROOT, "tools", "fleet_smoke.py")],
        env=_env_cpu_mesh(1), cwd=ROOT)
    if rc != 0:
        return rc
    return subprocess.call(
        [sys.executable, "-m", "mxnet_tpu.analysis.lint",
         os.path.join("mxnet_tpu", "serving")], cwd=ROOT)


def stage_chaos_smoke(_):
    """Non-slow resilience gate (ISSUE 9): replica-kill-under-load
    (served + shed == submitted, breaker opens, traffic reroutes) and
    checkpoint-write-fault (transient retried to commit; persistent
    surfaces with the previous committed checkpoint intact) scenarios,
    plus the zero-overhead fault-hook contract — then tpulint (incl.
    TPL106 swallowed-exception) over the resilience modules."""
    rc = subprocess.call(
        [sys.executable, os.path.join(ROOT, "tools", "chaos_smoke.py")],
        env=_env_cpu_mesh(1), cwd=ROOT)
    if rc != 0:
        return rc
    return subprocess.call(
        [sys.executable, "-m", "mxnet_tpu.analysis.lint",
         os.path.join("mxnet_tpu", "resilience"),
         os.path.join("mxnet_tpu", "checkpoint"),
         os.path.join("mxnet_tpu", "io_device.py")], cwd=ROOT)


def stage_train_chaos_smoke(_):
    """Non-slow training-failure gate (ISSUE 15): a supervised fit
    subprocess is SIGKILLed mid-epoch and auto-resumes BIT-identical to
    its uninterrupted twin (fused fp32, bf16-master, dp>1 dryrun, and the
    elastic ZeRO dp=2->4 resume); an injected NaN gradient is skipped
    in-graph with the typed NumericDivergence after K consecutive bad
    steps; and the zero-overhead contract holds (get_env poisoned across
    warmed dispatches, every train.* fault hook a cached-flag no-op) —
    then tpulint (incl. TPL109 unsupervised-thread) over the training
    path."""
    rc = subprocess.call(
        [sys.executable, os.path.join(ROOT, "tools",
                                      "train_chaos_smoke.py")],
        env=_env_cpu_mesh(1), cwd=ROOT)
    if rc != 0:
        return rc
    return subprocess.call(
        [sys.executable, "-m", "mxnet_tpu.analysis.lint",
         os.path.join("mxnet_tpu", "resilience"),
         os.path.join("mxnet_tpu", "checkpoint"),
         os.path.join("mxnet_tpu", "module"),
         os.path.join("mxnet_tpu", "parallel"),
         os.path.join("mxnet_tpu", "io.py"),
         os.path.join("mxnet_tpu", "io_device.py")], cwd=ROOT)


def stage_compile_cache_smoke(_):
    """Non-slow unified-builder gate (ISSUE 14): subprocess A compiles a
    serving engine's bucket programs cold into MXNET_TPU_COMPILE_CACHE,
    subprocess B warm-starts them — B must report persistent-cache-backed
    compiles, a <= 0.6x warmup ratio, and bit-identical predictions —
    then tpulint (incl. TPL108 raw-compile) over the migrated modules."""
    return subprocess.call(
        [sys.executable, os.path.join(ROOT, "tools",
                                      "compile_cache_smoke.py")],
        env=_env_cpu_mesh(1), cwd=ROOT)


def stage_bench_smoke(_):
    """bench.py's phase code must emit its JSON line. On the CPU because
    this stage SAYS so: cpu_mesh_env exports JAX_PLATFORMS=cpu, without
    which bench.py refuses any platform but the chip."""
    env = _env_cpu_mesh(1)
    return subprocess.call(
        [sys.executable, os.path.join(ROOT, "bench.py"), "--run"],
        env=env, cwd=ROOT)


STAGES = [
    ("build", stage_build),
    ("lint", stage_lint),
    ("program_audit_smoke", stage_program_audit_smoke),
    ("unit", stage_unit),
    ("train", stage_train),
    ("cpp", stage_cpp),
    ("zero_smoke", stage_zero_smoke),
    ("multichip", stage_multichip),
    ("serving_smoke", stage_serving_smoke),
    ("frontdoor_smoke", stage_frontdoor_smoke),
    ("decode_smoke", stage_decode_smoke),
    ("wire_fuzz_smoke", stage_wire_fuzz_smoke),
    ("fleet_smoke", stage_fleet_smoke),
    ("chaos_smoke", stage_chaos_smoke),
    ("train_chaos_smoke", stage_train_chaos_smoke),
    ("compile_cache_smoke", stage_compile_cache_smoke),
    ("bench_smoke", stage_bench_smoke),
]


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("stages", nargs="*",
                    help="subset of stages (default: all)")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--fast", action="store_true",
                    help="stop unit stage at first failure")
    args = ap.parse_args()
    if args.list:
        for name, fn in STAGES:
            print("%-12s %s" % (name, fn.__doc__.splitlines()[0]))
        return 0
    chosen = [s for s in STAGES if not args.stages or s[0] in args.stages]
    unknown = set(args.stages) - {n for n, _ in STAGES}
    if unknown:
        ap.error("unknown stages: %s" % ", ".join(sorted(unknown)))
    failed = []
    for name, fn in chosen:
        print("[ci] ==> %s" % name, flush=True)
        t0 = time.time()
        rc = fn(args)
        print("[ci] <== %s: %s (%.1fs)"
              % (name, "OK" if rc == 0 else "FAIL rc=%d" % rc,
                 time.time() - t0), flush=True)
        if rc != 0:
            failed.append(name)
            if args.fast:
                break
    if failed:
        print("[ci] FAILED: %s" % ", ".join(failed))
        return 1
    print("[ci] all stages green")
    return 0


if __name__ == "__main__":
    sys.exit(main())
