"""What one run records for its readers: host spans, samples, snapshots."""
from __future__ import annotations

import contextlib
import time


class Record:
    """Filled by the builder's wrappers and the driver, read by the
    per-layer readers.  All times are ``time.perf_counter()`` seconds."""

    def __init__(self, trace: bool):
        self.trace = bool(trace)
        self.spans = {}          # name -> [(t0, t1)]
        self.samples = {}        # name -> [(t, value)]
        self.snapshots = {}      # "start" / "end" -> dict
        self.values = {}         # what the driver measured at its client
        self.window = None       # (t0, t1) of the measured window
        self.trace_summary = None
        self.context = {}        # cfg, traffic, chips, peaks, ...

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span on the benchmark's own clock and, while the
        profiler runs, in its trace under the same name."""
        import jax
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.spans.setdefault(name, []).append(
                    (t0, time.perf_counter()))

    def sample(self, name: str, value: float):
        self.samples.setdefault(name, []).append(
            (time.perf_counter(), float(value)))

    def in_window(self, pairs):
        """Entries of a span or sample list that START inside the
        window."""
        if self.window is None:
            return list(pairs)
        t0, t1 = self.window
        return [p for p in pairs if t0 <= p[0] < t1]
