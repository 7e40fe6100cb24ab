"""Open-loop and backlog load from ONE thread, timed by the benchmark.

Latency is taken from the time a request was DUE, not from when the generator
got round to sending it, so a stall is charged to every request it delayed;
how late the generator ran is reported beside it.
"""
import time


def send_all(requests, submit, t0, span, on_tick=None, stop_at=None):
    """Send each request at ``t0 + due_s`` (monotonic clock). ``submit(req)``
    must not block. Returns when all are sent or ``stop_at`` passes."""
    for req in requests:
        while True:
            now = time.monotonic()
            if on_tick is not None:
                on_tick(now)
            wait = t0 + req.due_s - now
            if wait <= 0:
                break
            time.sleep(min(wait, 0.02))
        if stop_at is not None and time.monotonic() >= stop_at:
            return
        with span("bench.submit"):
            req.sent_s = time.monotonic() - t0
            submit(req)


def wait_until(t_end, on_tick=None, done=None, step=0.02):
    """Sleep to ``t_end``, ticking; stop early when ``done()`` says so."""
    while True:
        now = time.monotonic()
        if on_tick is not None:
            on_tick(now)
        if now >= t_end or (done is not None and done()):
            return
        time.sleep(min(step, max(0.0, t_end - now)))


def lateness_ms(requests):
    return [(r.sent_s - r.due_s) * 1e3 for r in requests
            if r.sent_s is not None]


def ttft_ms(requests):
    """First token minus due time; a request with no token counts as inf."""
    return [((r.token_s[0] - r.due_s) * 1e3 if r.token_s else float("inf"))
            for r in requests]


def inter_token_ms(requests):
    gaps = []
    for r in requests:
        ts = r.token_s
        gaps.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:]))
    return gaps
