"""Reader: a program's share of the memory roofline: the bytes ONE execution
must move (the fact ``args["bytes_fact"]``, counted by the driver from the
program's counters and ``harness/flops_moe_mla.py``) over the device time of
one execution (the median of the XLA modules matching ``args["pattern"]``)
times the HBM peak of ``device_kind``. ``None``, never 0, when the trace, the
fact or the peak is missing."""
import statistics

from harness import trace


def read(run, args):
    nbytes = run.facts.get(args["bytes_fact"])
    if run.events is None or not nbytes or run.peaks is None:
        return None
    times = trace.module_times(run.events, args["pattern"])
    if not times:
        return None
    least_s = nbytes / (run.peaks["hbm_bytes_per_s"] * run.chips)
    return 100.0 * least_s / statistics.median(times)
