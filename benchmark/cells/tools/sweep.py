#!/usr/bin/env python3
"""The one sweep that finds an open-loop cell's knee: the cell's own driver at
a few fixed rates in ONE process on the chip, a window each.

    python3 benchmark/cells/tools/sweep.py --workload <cell> --rates 1,2,3 \
        --seconds 30 [--seed 1]

The knee is the highest rate at which the waiting queue does not grow through
the window (``waiting_at_close`` stays near zero and the drain is short). The
cell then runs at 0.8 of it, written into its traffic file by hand.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import run as run_mod       # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    type=lambda t: [float(x) for x in t.split(",")])
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--spec-root", default=None)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default="chiprun_out/sweep.jsonl")
    a = ap.parse_args()
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    for i, rate in enumerate(a.rates):
        args = argparse.Namespace(workload=a.workload, seed=a.seed + i,
                                  seconds=a.seconds, trace=0,
                                  spec_root=a.spec_root, rehearse=a.rehearse)
        spec, cell, ctx, jax = run_mod.prepare(args)
        ctx.traffic["rate_rps"] = rate
        driver = spec.module("drivers", ctx.config["driver"]).Driver(ctx)
        facts = driver.run()
        driver.release()
        row = {"rate_rps": rate, "seed": a.seed + i, "seconds": a.seconds,
               "requests": facts["attempted"], "unfinished": facts["failed"],
               "waiting_at_close": facts["waiting_at_close"],
               "drained_s": facts["drained_s"],
               "tokens_per_s": facts["tokens"] / a.seconds,
               "ttft_p50_ms": facts["ttft_p50_ms"],
               "generator_late_p95_ms": facts["generator_late_p95_ms"],
               **facts["end_to_end"]}
        print(json.dumps(row), flush=True)
        with open(a.out, "a") as f:
            f.write(json.dumps(dict(row, workload=a.workload)) + "\n")
        del driver


if __name__ == "__main__":
    main()
