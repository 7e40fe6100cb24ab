"""What a driver and a reader are handed: the cell's data, the device, the
clock's marks, spans and the tracer."""
import contextlib
import json
import os
import sys
import threading
import time

import numpy as np


def key_from_seed(seed, stream=0):
    """A raw uint32[2] PRNG key from any whole-number seed (the driver's are
    larger than 32 signed bits hold)."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)
    return np.asarray(words, np.uint32)


class Compared:
    """One number compared with its limit; ``ok`` when value <= limit."""

    def __init__(self, name, value, limit):
        self.name, self.value, self.limit = name, float(value), float(limit)

    @property
    def ok(self):
        return np.isfinite(self.value) and self.value <= self.limit

    def as_json(self):
        return {"value": self.value, "limit": self.limit}


class Tracer:
    """Traces a short stretch of the window: from ``delay_s`` after the
    window opens for ``length_s``. Drivers call ``tick`` from wherever they
    already are; nothing is traced unless the run asked for it."""

    def __init__(self, enabled, out_dir, delay_s=1.0, length_s=5.0):
        self.enabled = enabled
        self.out_dir = out_dir
        self.delay_s, self.length_s = delay_s, length_s
        self.t_open = None
        self.started = self.stopped = None
        self.stall_s = 0.0      # spent inside start_trace on the caller
        self._writer = None

    def open_window(self, now):
        self.t_open = now

    def tick(self, now=None):
        if not self.enabled or self.t_open is None or self.stopped:
            return
        now = time.monotonic() if now is None else now
        import jax
        if self.started is None:
            if now >= self.t_open + self.delay_s:
                jax.profiler.start_trace(self.out_dir)
                self.started = time.monotonic()
                self.stall_s += self.started - now
        elif now >= self.started + self.length_s:
            self.stop()

    def stop(self):
        """Ends the traced stretch. Writing the trace out takes seconds, so
        it runs on a thread of its own: the caller is the load generator or
        the training loop, and must not stall. ``finish`` waits for it."""
        if self.enabled and self.started is not None and not self.stopped:
            import jax
            self.stopped = time.monotonic()
            self._writer = threading.Thread(target=jax.profiler.stop_trace,
                                            name="bench-trace-writer")
            self._writer.start()

    def finish(self):
        if self._writer is not None:
            self._writer.join()
            self._writer = None


class Ctx:
    def __init__(self, spec, cell, seed, seconds, trace, rehearse, device,
                 peaks, out_dir, t_process_start):
        self.spec = spec
        self.cell = cell
        self.config = spec.config(cell["config"])
        self.traffic = spec.traffic(cell["traffic"])
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.rehearse = rehearse
        self.device = device
        self.peaks = peaks
        self.out_dir = out_dir
        self.t_process_start = t_process_start
        tr = self.traffic.get("trace", {})
        self.tracer = Tracer(bool(trace), os.path.join(out_dir, "trace"),
                             tr.get("delay_s", 1.0), tr.get("length_s", 5.0))
        self.reference = spec.module("references", self.config["reference"])

    def span(self, name):
        """A host span on the profiler's own clock (free when not tracing)."""
        if not self.tracer.enabled:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def log(self, event, **fields):
        """An earlier line of the run, on standard error; every one names the
        device it ran on."""
        line = {"event": event, "cell": self.cell["name"],
                "platform": self.device["platform"],
                "device_kind": self.device["kind"],
                "device_count": self.device["count"]}
        line.update(fields)
        print(json.dumps(line), file=sys.stderr, flush=True)

    def limit(self, name):
        return self.traffic["limits"][name]
