#!/usr/bin/env python3
"""Readings the limits are set from, many seeds in ONE process on the chip.

    python3 benchmark/cells/tools/proof.py --workload <cell> \
        --seeds 11,12,... --control-seeds 11,12,13 [--seconds 3]

For every seed the cell's own driver runs (set-up, first steps, a short
window) and the check reads each compared number against the plain reference
(the lower reading). For the control seeds the reference, computed in the
nearest precision below the one the configuration states, is put in the
program's place (the upper reading); a training cell also reads the planted
fault "half of the batch left out", with ``--witness bfloat16`` the reference
rounding as the stated precision does (what that precision alone costs) and,
with ``--leaves``, writes every side's per-leaf norms beside the table. One JSON line per reading on
standard output; the whole table again under ``chiprun_out/``.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import run as run_mod       # noqa: E402


def ints(text):
    return [int(t) for t in text.split(",") if t]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=ints, default=[])
    ap.add_argument("--control-seeds", type=ints, default=[])
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--spec-root", default=None)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--witness", default=None,
                    help="training: one more precision (a name the "
                         "reference's Precision takes) in the program's place")
    ap.add_argument("--leaves", action="store_true",
                    help="training: write each side's per-leaf norms too")
    ap.add_argument("--out", default="chiprun_out/proof.jsonl")
    a = ap.parse_args()
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)

    def emit(**row):
        print(json.dumps(row), flush=True)
        with open(a.out, "a") as f:
            f.write(json.dumps(dict(row, workload=a.workload)) + "\n")

    for seed in sorted(set(a.seeds) | set(a.control_seeds)):
        args = argparse.Namespace(workload=a.workload, seed=seed,
                                  seconds=a.seconds, trace=0,
                                  spec_root=a.spec_root, rehearse=a.rehearse)
        spec, cell, ctx, jax = run_mod.prepare(args)
        driver = spec.module("drivers", ctx.config["driver"]).Driver(ctx)
        t0 = time.time()
        facts = driver.run()
        driver.release()

        def read(side="program", **kw):
            row = {c.name: c.value for c in driver.check(**kw)}
            row.update(driver.reported)
            if a.leaves and getattr(driver, "leaf_rows", None):
                with open("%s.leaves.%d.%s.json" % (a.out, seed, side),
                          "w") as f:
                    json.dump(driver.leaf_rows, f)
            return row

        emit(seed=seed, side="program", run_s=time.time() - t0,
             end_to_end=facts["end_to_end"],
             compiles_in_window=facts["compiles_in_window"], **read())
        if seed in a.control_seeds:
            control = ctx.config["control"]
            if ctx.config["driver"] == "fit_train":
                quant = ctx.reference.Precision(control)
                emit(seed=seed, side="control:" + control,
                     **read("control", quant=quant))
                if a.witness:
                    emit(seed=seed, side="witness:" + a.witness, **read(
                        "witness", quant=ctx.reference.Precision(a.witness)))
                emit(seed=seed, side="fault:half_batch",
                     **read("half_batch", rows=driver.batch // 2))
            else:
                emit(seed=seed, side="control:" + control,
                     **read(control_in_place=True))
        del driver


if __name__ == "__main__":
    main()
