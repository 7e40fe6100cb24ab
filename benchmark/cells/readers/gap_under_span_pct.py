"""Reader: the share of the traced stretch in which the device sat idle while
the decode engine's thread was inside given ``mx.decode.*`` spans.

Device gaps are found as ``harness.trace.idle_gaps`` finds them: between the
intervals of ``XLA Ops`` (else ``XLA Modules``) on the first device plane. Each
gap is PARTITIONED along the engine thread's timeline, and every piece goes to
the innermost span that covers it: a gap that starts under
``mx.decode.step.emit`` and ends under ``mx.decode.admit`` is split at the
boundary, not handed whole to the longer part. Pieces whose innermost span is
among ``args["spans"]`` are summed and divided by ``run.trace_window_s``.
``"spans": null`` sums what lies under none of ``args["leaves"]``: under a
parent only (``mx.decode.iteration``, ``mx.decode.step``), under
``mx.decode.wait``, or under no span at all. A list and ``null`` over the same
leaves add up to the gaps' whole share: the readers of one cell split
``device_idle_pct`` between them.

The engine's thread is told by its spans' names, because the harness's events
keep no thread id and every Python thread's line is named alike: all spans
that start with ``mx.decode.`` except ``mx.decode.submit`` (the caller's thread).

``args["clock_check"]``, a pattern of program names on ``XLA Modules``: also
prints to standard error how far any whole execution of that program sticks
out of the interval from the start of an ``mx.decode.step.dispatch`` to the end
of the ``mx.decode.step.readback`` that follows it. Host spans and device
events share one clock only if that is about nothing.

A program without the spans (the parent of the PR that brought them) gives
``None`` for a list and, for ``null``, ``None`` as well: nothing to read.
"""
import bisect
import json
import re
import sys

from harness import trace

FAMILY = "mx.decode."
OTHER_THREADS = ("mx.decode.submit",)


def device_gaps(events):
    """(start, end) of every gap between device operations, in order."""
    planes = trace.device_planes(events)
    if not planes:
        return []
    ops = trace.on_line(events, planes[0], trace.OP_LINE) or \
        trace.on_line(events, planes[0], trace.MODULE_LINE)
    gaps, end = [], None
    for s, e in sorted((e.start_ns, e.start_ns + e.dur_ns) for e in ops):
        if end is not None and s > end:
            gaps.append((end, s))
        end = e if end is None else max(end, e)
    return gaps


def thread_spans(events):
    return [e for e in events
            if e.name.startswith(FAMILY) and e.name not in OTHER_THREADS
            and not trace.DEVICE_PLANE.match(e.plane)]


def innermost_segments(spans):
    """The thread's timeline as sorted, disjoint (start, end, name) pieces,
    each named for the innermost span covering it. Spans of one thread nest;
    a child that sticks out of its parent by clock jitter is cut to it."""
    marks = sorted((e.start_ns, -(e.start_ns + e.dur_ns), e.name)
                   for e in spans)
    segs, stack = [], []            # stack of [end, name], outermost first

    def emit(start, end, name):
        if end > start:
            segs.append((start, end, name))

    cursor = None
    for start, neg_end, name in marks:
        end = -neg_end
        while stack and stack[-1][0] <= start:      # closed before this one
            top_end, top_name = stack.pop()
            emit(cursor, top_end, top_name)
            cursor = top_end
        if stack:
            emit(cursor, start, stack[-1][1])
            end = min(end, stack[-1][0])
        cursor = start
        stack.append([end, name])
    while stack:
        top_end, top_name = stack.pop()
        emit(cursor, top_end, top_name)
        cursor = top_end
    return segs


def gap_seconds_by_span(events, spans=None):
    """{innermost span name, or None where no span covers: seconds of device
    gap under it}. ``spans``: the engine thread's (default: found by name)."""
    segs = innermost_segments(thread_spans(events) if spans is None
                              else spans)
    starts = [s[0] for s in segs]
    out = {}
    for gs, ge in device_gaps(events):
        covered = 0.0
        i = max(bisect.bisect_right(starts, gs) - 1, 0)
        while i < len(segs) and segs[i][0] < ge:
            piece = min(ge, segs[i][1]) - max(gs, segs[i][0])
            if piece > 0:
                out[segs[i][2]] = out.get(segs[i][2], 0.0) + piece / 1e9
                covered += piece
            i += 1
        out[None] = out.get(None, 0.0) + (ge - gs - covered) / 1e9
    return out


def clock_excess_ms(events, pattern):
    """(worst excess in ms, executions looked at): how far a whole execution
    of the program sticks out of [dispatch start, next readback end]."""
    planes = trace.device_planes(events)
    rx = re.compile(pattern)
    mods = sorted((e.start_ns, e.start_ns + e.dur_ns)
                  for e in trace.on_line(events, planes[0], trace.MODULE_LINE)
                  if rx.search(e.name)) if planes else []
    host = [e for e in events if not trace.DEVICE_PLANE.match(e.plane)]
    disp = sorted(e.start_ns for e in host
                  if e.name == "mx.decode.step.dispatch")
    back = sorted((e.start_ns, e.start_ns + e.dur_ns) for e in host
                  if e.name == "mx.decode.step.readback")
    ivs = []
    for d in disp:
        j = bisect.bisect_left(back, (d, d))
        if j < len(back):
            ivs.append((d, back[j][1]))
    if not ivs:
        return None, 0
    worst, n = 0.0, 0
    for ms, me in mods[1:-1]:            # the trace's edges cut first and last
        worst = max(worst, min(max(0.0, s - ms) + max(0.0, me - e)
                               for s, e in ivs))
        n += 1
    return worst / 1e6, n


def read(run, args):
    if run.events is None or not run.trace_window_s:
        return None
    spans = thread_spans(run.events)
    if not spans:
        return None
    if args.get("clock_check"):
        worst, n = clock_excess_ms(run.events, args["clock_check"])
        print(json.dumps({"event": "clock_check",
                          "program": args["clock_check"], "executions": n,
                          "worst_excess_ms": worst}),
              file=sys.stderr, flush=True)
    by_span = gap_seconds_by_span(run.events, spans)
    if args.get("spans") is None:
        secs = sum(v for k, v in by_span.items()
                   if k not in args.get("leaves", ()))
    else:
        secs = sum(by_span.get(name, 0.0) for name in args["spans"])
    return 100.0 * secs / run.trace_window_s
