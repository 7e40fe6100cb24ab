#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/cells/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Finds the cell in BENCHMARK.json, its configuration, traffic mix, driver,
reference and per-layer readers by name (``harness/spec.py``), runs set-up and
the window in this one process, checks the timed path's output against the
plain reference once the window has closed, and prints one JSON object as the
last line of standard output. Earlier lines go to standard error.
"""
import time
T_PROCESS_START = time.time()

import argparse          # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from harness import spec as spec_mod     # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--spec-root", default=None,
                    help="tests: a directory with its own BENCHMARK.json")
    ap.add_argument("--rehearse", action="store_true",
                    help="tests: any platform, kernels interpreted; the line "
                         "printed says so and is no chip result")
    return ap.parse_args(argv)


def device_info(jax):
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _peak(stats):
    # the TPU runtime books live buffers (``peak_bytes_in_use``) and the
    # temporaries of the programs it runs (``peak_bytes_reserved``) apart;
    # the memory held on the chip is both (PERF.md, section 4)
    return int(stats.get("peak_bytes_in_use", 0)) \
        + int(stats.get("peak_bytes_reserved", 0))


def memory_peak(jax):
    """(peak bytes on the fullest chip, that chip's whole memory_stats)."""
    stats = [d.memory_stats() or {} for d in jax.devices()]
    full = max(stats, key=_peak)
    return _peak(full), full


class Run:
    """What a per-layer reader is handed."""

    def __init__(self, facts, peaks, chips, events, busy_s, window_s):
        self.facts, self.peaks, self.chips = facts, peaks, chips
        self.events = events
        self.device_busy_s, self.trace_window_s = busy_s, window_s


def prepare(args):
    """Everything before a driver exists: the cell, the cache, the device
    (refused unless it is the chip the cell asks for) and the context."""
    spec = spec_mod.Spec(args.spec_root)
    cell = spec.cell(args.workload)
    seconds = spec.benchmark["run_seconds"] if args.seconds is None \
        else args.seconds
    out_dir = os.path.join(spec_mod.REPO_ROOT, ".bench_out",
                           "%s-%d" % (cell["name"], os.getpid()))
    os.makedirs(out_dir, exist_ok=True)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(spec_mod.REPO_ROOT, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, spec_mod.REPO_ROOT)
    import jax
    import mxnet_tpu  # noqa: F401 — no package, no output: fail first
    from harness import peaks as peaks_mod
    from harness.context import Ctx

    dev = device_info(jax)
    if dev["platform"] != "tpu" and not args.rehearse:
        sys.exit("benchmark: platform is %r, not 'tpu'" % dev["platform"])
    if dev["count"] < cell["chips"] and not args.rehearse:
        sys.exit("benchmark: the cell asks for %d chip(s), JAX reports %d"
                 % (cell["chips"], dev["count"]))
    peaks = None
    if dev["platform"] == "tpu":
        peaks = peaks_mod.peaks_for(dev["kind"])     # unknown kind: an error
    ctx = Ctx(spec, cell, args.seed, seconds, args.trace, args.rehearse, dev,
              peaks, out_dir, T_PROCESS_START)
    return spec, cell, ctx, jax


def main(argv=None):
    args = parse(argv)
    spec, cell, ctx, jax = prepare(args)
    from harness import trace
    from harness.context import Compared
    dev, peaks, seconds, out_dir = (ctx.device, ctx.peaks, ctx.seconds,
                                    ctx.out_dir)
    ctx.log("start", seed=args.seed, seconds=seconds, trace=args.trace,
            jax=jax.__version__,
            compile_cache=jax.config.jax_compilation_cache_dir)
    driver = spec.module("drivers", ctx.config["driver"]).Driver(ctx)
    facts = driver.run()
    ctx.tracer.finish()
    setup_s = facts["window_open_wall"] - T_PROCESS_START
    mem, mem_stats = memory_peak(jax)
    ctx.log("memory", memory_peak_bytes=mem, setup_s=setup_s,
            memory_stats=mem_stats)

    events = busy_s = trace_window_s = None
    if args.trace:
        events = trace.read_xplane(trace.find_xplane(ctx.tracer.out_dir))
        busy_s = trace.busy_seconds(events)
        span = trace.window_ns(events)      # the trace's own clock
        trace_window_s = (span[1] - span[0]) / 1e9 if span else None
        shutil.rmtree(ctx.tracer.out_dir, ignore_errors=True)
    driver.release()
    t_check = time.time()
    compared = [Compared("compiles_in_window", facts["compiles_in_window"],
                         0)] + driver.check()
    ctx.log("checked", check_s=time.time() - t_check)
    correct = all(c.ok for c in compared)

    run = Run(facts, peaks, cell["chips"], events, busy_s, trace_window_s)
    metrics = {}
    if args.trace:
        for m in spec.metrics_for(cell["name"], "per_layer"):
            lm = spec.layer_metric(m["name"])
            value = spec.module("readers", lm["reader"]).read(
                run, lm.get("args", {}))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in spec.metrics_for(cell["name"], "end_to_end"):
            value = setup_s if m["name"] == "setup_s" \
                else facts["end_to_end"].get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"], "memory_peak_bytes": mem}
    result = {"correct": bool(correct), "attempted": int(facts["attempted"]),
              "failed": int(facts["failed"]), "metrics": metrics,
              "device": device}
    if args.trace:
        device["busy_s"] = busy_s
        device["window_s"] = trace_window_s
        result["breakdown"] = {
            "device_ops": [[n[:160], s]
                           for n, s in trace.top_device_ops(events)],
            "idle_gaps": [[n, s] for n, s in trace.idle_gaps(events)]}
        with open(os.path.join(out_dir, "trace_summary.json"), "w") as f:
            json.dump(trace.summary(events), f, indent=1)
    if args.rehearse:
        result["rehearsal"] = True
    result["compared"] = {c.name: c.as_json() for c in compared}
    for c in compared:
        print("compared %s value=%r limit=%r %s"
              % (c.name, c.value, c.limit, "ok" if c.ok else "FAILED"),
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
