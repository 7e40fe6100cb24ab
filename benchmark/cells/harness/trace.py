"""From a profiler trace to numbers: the reduction every PR shares.

A trace is read into plain tuples so that the arithmetic below can be checked
on a hand-made event list (tests) as well as on a chip's ``.xplane.pb``:

    Event(plane, line, name, start_ns, dur_ns)

On a TPU the device planes are named ``/device:TPU:<n>``; their line
``XLA Modules`` holds one event per executed program and ``XLA Ops`` one per
device operation. Host threads are lines of the ``/host:CPU`` plane, where the
benchmark's own ``TraceAnnotation`` spans (``bench.*``) appear on the same
clock.
"""
import glob
import os
import re
from collections import namedtuple

Event = namedtuple("Event", "plane line name start_ns dur_ns")

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return paths[-1]


def read_xplane(path):
    """Every event of the trace as an ``Event``."""
    from jax.profiler import ProfileData
    events = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                events.append(Event(plane.name, line.name, ev.name,
                                    float(ev.start_ns),
                                    float(ev.duration_ns)))
    return events


def device_planes(events):
    return sorted({e.plane for e in events if DEVICE_PLANE.match(e.plane)})


def on_line(events, plane, line):
    return [e for e in events if e.plane == plane and e.line == line]


def union_ns(intervals):
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def window_ns(events):
    """The traced window: first start to last end over the device planes'
    operations and the benchmark's own spans."""
    planes = set(device_planes(events))
    marks = [e for e in events
             if (e.plane in planes and e.line in (OP_LINE, MODULE_LINE))
             or e.name.startswith(SPAN_PREFIX)]
    if not marks:
        return None
    return (min(e.start_ns for e in marks),
            max(e.start_ns + e.dur_ns for e in marks))


def busy_seconds(events):
    """Seconds in which an operation ran on the device: the union of the
    device-operation intervals, averaged over the device planes."""
    planes = device_planes(events)
    if not planes:
        return None
    per_plane = []
    for p in planes:
        ops = on_line(events, p, OP_LINE) or on_line(events, p, MODULE_LINE)
        per_plane.append(union_ns(
            [(e.start_ns, e.start_ns + e.dur_ns) for e in ops]) / 1e9)
    return sum(per_plane) / len(per_plane)


def module_times(events, pattern):
    """Seconds of each execution of the programs whose name matches
    ``pattern``, on the first device plane, in order. The first and the last
    may be cut by the edge of the trace: take a median, never a mean."""
    planes = device_planes(events)
    if not planes:
        return []
    rx = re.compile(pattern)
    hits = sorted((e.start_ns, e.dur_ns)
                  for e in on_line(events, planes[0], MODULE_LINE)
                  if rx.search(e.name))
    return [d / 1e9 for _, d in hits]


def top_device_ops(events, k=10):
    planes = device_planes(events)
    if not planes:
        return []
    total = {}
    for e in on_line(events, planes[0], OP_LINE):
        total[e.name] = total.get(e.name, 0.0) + e.dur_ns / 1e9
    return sorted(total.items(), key=lambda kv: -kv[1])[:k]


def host_spans(events):
    return [e for e in events if e.name.startswith(SPAN_PREFIX)
            and not DEVICE_PLANE.match(e.plane)]


def idle_gaps(events, k=10):
    """The 400 longest gaps between device operations, each named for the
    benchmark span that covers most of it on the host (``host:other`` where
    none does), summed by that name."""
    planes = device_planes(events)
    if not planes:
        return []
    ops = on_line(events, planes[0], OP_LINE) or \
        on_line(events, planes[0], MODULE_LINE)
    ivs = sorted((e.start_ns, e.start_ns + e.dur_ns) for e in ops)
    gaps, end = [], None
    for s, e in ivs:
        if end is not None and s > end:
            gaps.append((end, s))
        end = e if end is None else max(end, e)
    spans = host_spans(events)
    gaps.sort(key=lambda g: g[0] - g[1])
    by_name = {}
    if len(gaps) > 400:                 # the many short ones, unnamed
        by_name["gaps_not_among_400_longest"] = sum(
            ge - gs for gs, ge in gaps[400:]) / 1e9
    for gs, ge in gaps[:400]:
        best, best_cover = "host:other", 0.0
        for sp in spans:
            cover = min(ge, sp.start_ns + sp.dur_ns) - max(gs, sp.start_ns)
            if cover > best_cover:
                best, best_cover = sp.name, cover
        by_name[best] = by_name.get(best, 0.0) + (ge - gs) / 1e9
    return sorted(by_name.items(), key=lambda kv: -kv[1])[:k]


def summary(events, k=12):
    """What a reader needs to see before writing a pattern: planes, their
    lines with event counts, and on each line the names that took most time:
    [name, seconds, count, median start-to-start period in seconds] (the
    period is what shows two lines, or two planes, keeping different
    clocks). Each line also gives its first start and last end (seconds
    after the trace's first event) and the median period and length of all
    its events."""
    out, starts, whole = {}, {}, {}
    t0 = min(e.start_ns for e in events) if events else 0.0
    for e in events:
        line = out.setdefault(e.plane, {}).setdefault(
            e.line, {"events": 0, "names": {}})
        line["events"] += 1
        whole.setdefault((e.plane, e.line), []).append(
            (e.start_ns, e.dur_ns, e.name))
        line["names"][e.name] = line["names"].get(e.name, 0.0) + e.dur_ns
        starts.setdefault((e.plane, e.line, e.name), []).append(e.start_ns)
    for pname, plane in out.items():
        for lname, line in plane.items():
            evs = sorted(whole[pname, lname])
            gaps = sorted(b[0] - a[0] for a, b in zip(evs, evs[1:]))
            durs = sorted(e[1] for e in evs)
            line["first_start_s"] = (evs[0][0] - t0) / 1e9
            line["last_end_s"] = (max(e[0] + e[1] for e in evs) - t0) / 1e9
            if DEVICE_PLANE.match(pname) and lname != OP_LINE:
                line["head"] = [[n[:40], (s - t0) / 1e9, d / 1e9]
                                for s, d, n in evs[:12]]
            line["median_period_s"] = \
                gaps[len(gaps) // 2] / 1e9 if gaps else None
            line["median_length_s"] = durs[len(durs) // 2] / 1e9
            top = sorted(line["names"].items(), key=lambda kv: -kv[1])
            top = top[:k] + [kv for kv in top[k:]
                             if kv[0].startswith(SPAN_PREFIX)]
            line["names"] = []
            for n, d in top:
                at = sorted(starts[pname, lname, n])
                gaps = sorted(b - a for a, b in zip(at, at[1:]))
                period = gaps[len(gaps) // 2] / 1e9 if gaps else None
                line["names"].append([n, d / 1e9, len(at), period])
    return out
