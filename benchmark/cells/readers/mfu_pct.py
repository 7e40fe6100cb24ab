"""Reader: the whole step's share of the chip's peak: model operations of the
run's window (counted from shapes by ``harness.flops``, recomputation never
counted) over the window's seconds, against the peak of ``device_kind``."""


def read(run, args):
    ops, secs = run.facts.get("model_flops"), run.facts.get("window_s")
    if not ops or not secs or run.peaks is None:
        return None
    return 100.0 * ops / secs / (run.peaks["flops_per_s"] * run.chips)
