"""Reader: a kernel's share of the memory roofline inside one program: the
bytes the kernel must move in ONE execution of the program (the fact
``args["bytes_fact"]``) over the device time of the operations whose name
matches ``args["op_pattern"]`` inside one execution of the XLA module matching
``args["module_pattern"]`` (the median over the traced executions: the trace's
edges cut the first and the last short), times the HBM peak of
``device_kind``. ``None``, never 0, when the trace, the fact, the peak, the
program or the operation is missing (a program that has no such kernel)."""
import re
import statistics

from harness import trace


def op_seconds_per_execution(events, module_pattern, op_pattern):
    """For each execution of the matching modules on the first device plane,
    the summed device seconds of the matching operations that start inside
    it; executions without any are left out."""
    planes = trace.device_planes(events)
    if not planes:
        return []
    mod_rx, op_rx = re.compile(module_pattern), re.compile(op_pattern)
    ops = sorted((e.start_ns, e.dur_ns)
                 for e in trace.on_line(events, planes[0], trace.OP_LINE)
                 if op_rx.search(e.name))
    out, i = [], 0
    for m in sorted((e.start_ns, e.dur_ns) for e in trace.on_line(
            events, planes[0], trace.MODULE_LINE) if mod_rx.search(e.name)):
        start, end = m[0], m[0] + m[1]
        while i < len(ops) and ops[i][0] < start:
            i += 1
        total = 0.0
        while i < len(ops) and ops[i][0] < end:
            total += ops[i][1]
            i += 1
        if total:
            out.append(total / 1e9)
    return out


def read(run, args):
    nbytes = run.facts.get(args["bytes_fact"])
    if run.events is None or not nbytes or run.peaks is None:
        return None
    times = op_seconds_per_execution(run.events, args["module_pattern"],
                                     args["op_pattern"])
    if not times:
        return None
    least_s = nbytes / (run.peaks["hbm_bytes_per_s"] * run.chips)
    return 100.0 * least_s / statistics.median(times)
