"""paddle.profiler — tracing facade over jax.profiler.

Reference parity: python/paddle/profiler/ (``Profiler`` with a
wait/warmup/active ``make_scheduler`` state machine, ``RecordEvent``
host ranges, chrome-trace export + summary tables) over the C++
RecordEvent/CUPTI tracers (SURVEY.md §5 tracing row).

TPU-native design: device+host tracing is jax.profiler's XPlane
capture (viewable in TensorBoard's profile plugin / Perfetto — the
trace-viewer replacement for chrome://tracing); ``RecordEvent`` maps
onto ``jax.profiler.TraceAnnotation`` so user ranges appear inside the
same timeline; the scheduler state machine and per-step timing summary
are host-side (identical semantics to the reference's).
"""
from __future__ import annotations

import json
import os
import time
from enum import Enum
from typing import Callable, Iterable, Optional

from ..observability import tracing as _tracing

__all__ = ["Profiler", "ProfilerState", "ProfilerTarget", "RecordEvent",
           "make_scheduler", "export_chrome_tracing", "load_profiler_result"]

# Profilers between start() and stop().  A RecordEvent that runs while
# one is open also leaves (name, t0, t1) in that session's own list,
# for the chrome-trace stub of a ``timer_only`` session (which has no
# XPlane); with none open it reads no clock and keeps nothing.
_SESSIONS: list = []


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3  # last RECORD step of a cycle


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1      # accepted for API parity; device tracing is the TPU
    CUSTOM_DEVICE = 2


def make_scheduler(*, closed: int, ready: int, record: int,
                   repeat: int = 0, skip_first: int = 0) -> Callable:
    """paddle.profiler.make_scheduler parity: per-step state callable."""
    cycle = closed + ready + record

    def schedule(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= cycle * repeat:
            return ProfilerState.CLOSED
        pos = s % cycle
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return schedule


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None
                          ) -> Callable:
    """on_trace_ready factory (API parity).  The capture is XPlane/
    TensorBoard format under ``dir_name`` — open with TensorBoard's
    profile plugin; a chrome-trace JSON stub with the step table is
    also written for quick inspection."""

    def handler(prof: "Profiler"):
        prof._export_dir = dir_name
        os.makedirs(dir_name, exist_ok=True)
        events = [{"name": f"step {i}", "ph": "X", "pid": 0, "tid": 0,
                   "ts": int(t0 * 1e6), "dur": int((t1 - t0) * 1e6)}
                  for i, (t0, t1) in enumerate(prof._step_times)]
        # RecordEvent host ranges of this session land on their own
        # track next to the steps
        begin = prof._session_begin or 0.0
        events.extend(
            {"name": name, "ph": "X", "pid": 0, "tid": 1,
             "ts": int(t0 * 1e6), "dur": int((t1 - t0) * 1e6)}
            for name, t0, t1 in prof._host_events)
        # the observability tracer's spans (request spans, scheduler
        # queue waits, engine chunk/window spans) land on their own
        # track — the profiler session and the serving tracer share
        # one timeline, which is what makes the Paddle-shaped
        # profiler API a real end-to-end export
        tracer = _tracing.get_tracer()
        if tracer is not None:
            events.extend(e for e in tracer.chrome_events(tid=2)
                          if e["ts"] >= int(begin * 1e6))
        with open(os.path.join(dir_name, "steps.chrome_trace.json"),
                  "w") as f:
            json.dump({"traceEvents": events}, f)

    # the Profiler reads this to keep the XPlane capture and the step
    # table in ONE directory when the user only passes on_trace_ready
    handler._export_dir = dir_name
    return handler


class RecordEvent:
    """Host range annotation visible in the device trace
    (reference: paddle.profiler.RecordEvent over C++ RecordEvent): the
    Paddle-shaped name for ``observability.tracing.phase``, which is
    what it opens.  When the observability tracer is enabled, the range
    ALSO records as a span there — nesting under whatever span is
    active on this thread (e.g. the scheduler's admit span), so
    profiler-annotated engine work lands inside the request's trace."""

    def __init__(self, name: str, event_type=None):
        self.name = name
        self._ann = None
        self._t0 = None
        self._span = _tracing.NULL_SPAN

    def begin(self):
        self._ann = _tracing.phase(self.name)
        self._ann.__enter__()
        self._span = _tracing.span(self.name)
        self._t0 = time.perf_counter() if _SESSIONS else None

    def end(self):
        self._span.end()
        self._span = _tracing.NULL_SPAN
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self._t0 is not None:
            rng = (self.name, self._t0, time.perf_counter())
            for prof in _SESSIONS:
                prof._host_events.append(rng)
            self._t0 = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()


class Profiler:
    """paddle.profiler.Profiler parity over jax.profiler traces.

    Usage (identical shape to the reference):
        p = Profiler(scheduler=make_scheduler(closed=1, ready=1, record=2),
                     on_trace_ready=export_chrome_tracing("./prof"))
        p.start()
        for batch in loader:
            train_step(batch)
            p.step()
        p.stop()
    """

    def __init__(self, *, targets: Optional[Iterable] = None,
                 scheduler=None, on_trace_ready: Optional[Callable] = None,
                 timer_only: bool = False,
                 trace_dir: Optional[str] = None):
        if scheduler is None:
            self._schedule = lambda step: ProfilerState.RECORD
        elif isinstance(scheduler, (tuple, list)):  # paddle (start, end)
            lo, hi = scheduler
            self._schedule = make_scheduler(closed=lo, ready=0,
                                            record=hi - lo, repeat=1)
        else:
            self._schedule = scheduler
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        if trace_dir is None:
            # keep the XPlane capture next to the handler's export so
            # `on_trace_ready=export_chrome_tracing(dir)` puts the whole
            # profile in ONE directory (as the docstring usage promises)
            trace_dir = getattr(on_trace_ready, "_export_dir",
                                "./profiler_log")
        self._trace_dir = trace_dir
        self._export_dir = trace_dir
        self.current_state = ProfilerState.CLOSED
        self._step_num = 0
        self._tracing = False
        self._step_times = []
        self._step_begin = None
        self._session_begin = None
        self._host_events = []

    # -- lifecycle -----------------------------------------------------------
    def start(self):
        self._step_num = 0
        self._apply_state(self._schedule(0))
        self._step_begin = time.perf_counter()
        self._session_begin = self._step_begin
        if self not in _SESSIONS:
            _SESSIONS.append(self)
        return self

    def stop(self):
        # close out the in-flight step interval: work done between the
        # last step() (or start()) and stop() is a step too — without
        # this a start()...stop() session with no step() calls records
        # nothing and summary() claims "no steps recorded"
        if self._step_begin is not None:
            now = time.perf_counter()
            if now > self._step_begin:
                self._step_times.append((self._step_begin, now))
            self._step_begin = None
        if self in _SESSIONS:
            _SESSIONS.remove(self)
        self._stop_trace()
        if self._on_trace_ready is not None:
            self._on_trace_ready(self)
        return self

    def step(self, num_samples: Optional[int] = None):
        now = time.perf_counter()
        if self._step_begin is not None:
            self._step_times.append((self._step_begin, now))
        self._step_begin = now
        self._step_num += 1
        self._apply_state(self._schedule(self._step_num))

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    # -- internals -----------------------------------------------------------
    def _apply_state(self, state: ProfilerState):
        recording = state in (ProfilerState.RECORD,
                              ProfilerState.RECORD_AND_RETURN)
        if recording and not self._tracing and not self._timer_only:
            self._start_trace()
        elif not recording and self._tracing:
            self._stop_trace()
        self.current_state = state

    def _start_trace(self):
        import jax
        os.makedirs(self._trace_dir, exist_ok=True)
        jax.profiler.start_trace(self._trace_dir)
        self._tracing = True

    def _stop_trace(self):
        if self._tracing:
            import jax
            jax.profiler.stop_trace()
            self._tracing = False

    # -- summaries -----------------------------------------------------------
    def summary(self, sorted_by=None, op_detail: bool = True,
                thread_sep: bool = False, time_unit: str = "ms"):
        """Step-timing table (host view; kernel detail lives in the
        exported XPlane trace)."""
        if not self._step_times:
            return "no steps recorded"
        durs = [(t1 - t0) * 1e3 for t0, t1 in self._step_times]
        import numpy as np
        lines = ["step time (ms): "
                 f"avg={np.mean(durs):.3f} min={np.min(durs):.3f} "
                 f"max={np.max(durs):.3f} steps={len(durs)}"]
        return "\n".join(lines)


def load_profiler_result(path: str):
    with open(path) as f:
        return json.load(f)
