"""Reader: the seconds of set-up spent in one phase, ``args["phase"]``:
``import`` (the package's own import, stamped by ``mxnet_tpu/__init__.py``),
``trace``, ``lower`` or ``backend`` (the union over every compile of the
process of its Python-level trace, its lowering to MLIR and the backend's
compile or persistent-cache load). Read from
``mxnet_tpu.profiler.compile_phase_counters`` up to the opening of the window,
so the check's own compiles, which come after it, are left out. A program
without that function reads ``None``. The first read of a run prints the
snapshot, with the functions that cost most in each phase, as one
``setup_phases`` line on standard error."""
import json
import sys


def read(run, args):
    snap = getattr(run, "setup_phases", None)
    if snap is None:
        from mxnet_tpu import profiler
        counters = getattr(profiler, "compile_phase_counters", None)
        if counters is None:
            return None
        snap = run.setup_phases = counters(
            before=run.facts.get("window_open_wall"))
        print(json.dumps({"event": "setup_phases", **snap}), file=sys.stderr,
              flush=True)
    return snap.get(args["phase"] + "_s")
