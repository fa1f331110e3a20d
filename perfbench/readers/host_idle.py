"""Why the chip idled: the idle gaps of the device trace, each put down
to the INNERMOST host phase that covers it.

The serving loop's thread writes its phases into the profiler's trace
on the device's own clock (``paddle_tpu.observability.tracing.phase``):
``sched.step`` > ``engine.step`` > ``engine.step.pack`` ..., with
``serve.loop.cmds`` / ``serve.loop.wait`` between iterations.  This
reader takes the window from the ``pb.trace.window`` span and the gaps
from ``trace_reduce.reduce_device`` (the same as ``device.idle_share``
reads), and walks the spans of the ONE thread line that holds
``sched.step`` events.  Every nanosecond of idle lands in exactly one
bucket: the innermost span over it, or ``(none)`` where that thread was
in no span (another thread's spans count for nothing).

JAX writes its own events on that line too (``PjitFunction(...)``,
``np.asarray(jax.Array)``, ``shard_args``, nested inside the phases);
only names that start with one of the spec's ``phases`` prefixes count
as phases.  The log line also carries the finer split, by the innermost
event of ANY name, for whoever sizes the next change.

Spec: ``phases`` (name prefixes of the program's phases), ``spans``
(bucket names to sum: a parent's name means its SELF time, ``(none)``
the uncovered rest), ``per`` (divide by the number of these spans that
START in the window), ``scale`` (seconds -> unit).  ``None`` without a
device plane or without a ``per`` span, as on a program that has no
such phases.  The whole table goes on a log line of its own, once a run.
"""
from __future__ import annotations

import functools
import json
import os

from perfbench import trace_reduce

NONE = "(none)"
LOOP_SPAN = "sched.step"        # marks the loop thread's line


def innermost_segments(spans):
    """(name, start, dur) spans of ONE thread, nested or disjoint ->
    [(t0, t1, name)] that do not overlap: each stretch under the
    innermost span that covers it.  A child that outlives its parent
    is cut at the parent's end."""
    segs, stack, at = [], [], None        # stack of (name, end)

    def close_until(t):
        nonlocal at
        while stack and stack[-1][1] <= t:
            name, end = stack.pop()
            if end > at:
                segs.append((at, end, name))
                at = end

    for name, start, dur in sorted(spans, key=lambda e: (e[1], -e[2])):
        close_until(start)
        end = start + dur
        if stack:
            if start > at:
                segs.append((at, start, stack[-1][0]))
            end = min(end, stack[-1][1])
        at = start
        stack.append((name, end))
    close_until(float("inf"))
    return segs


def attribute(gaps, spans):
    """Idle time by bucket.  ``gaps``: [(a, b)] in time order, not
    overlapping; ``spans``: one thread's (name, start, dur).  The values
    sum to the gaps' total length."""
    segs = innermost_segments(spans)
    out, k = {}, 0
    for a, b in gaps:
        covered = 0
        while k < len(segs) and segs[k][1] <= a:
            k += 1
        j = k
        while j < len(segs) and segs[j][0] < b:
            lo, hi = max(a, segs[j][0]), min(b, segs[j][1])
            if hi > lo:
                out[segs[j][2]] = out.get(segs[j][2], 0) + (hi - lo)
                covered += hi - lo
            j += 1
        if (b - a) - covered > 0:
            out[NONE] = out.get(NONE, 0) + ((b - a) - covered)
    return out


def table(devices, lines, window, phases=("",)):
    """``devices``: {chip: [(text, start, dur)]}; ``lines``: the host
    threads, each a list of (name, start, dur); ``window``: (t0, t1);
    ``phases``: the name prefixes that make an event a phase.
    -> (idle by phase averaged over the chips, phases that start in the
    window counted by name, idle by the innermost event of any name,
    the thread's own time in the window by innermost phase), in the
    input's unit; None without a device event."""
    chips = [ev for ev in devices.values() if ev]
    if not chips or window is None:
        return None
    loop = max(lines, default=[],
               key=lambda ln: sum(1 for e in ln if e[0] == LOOP_SPAN))
    if not any(e[0] == LOOP_SPAN for e in loop):
        loop = []
    own = [e for e in loop if e[0].startswith(tuple(phases))]
    buckets, detail = {}, {}
    for ev in chips:
        gaps = trace_reduce.reduce_device(ev, window)["gaps"]
        for into, spans in ((buckets, own), (detail, loop)):
            for name, t in attribute(gaps, spans).items():
                into[name] = into.get(name, 0) + t / len(chips)
    counts = {}
    for name, start, _ in own:
        if window[0] <= start < window[1]:
            counts[name] = counts.get(name, 0) + 1
    host = attribute([window], own)       # the whole window as one gap
    return buckets, counts, detail, host


def load(path: str, window_span: str):
    """The profiler's file -> (devices, host thread lines, window)."""
    from jax.profiler import ProfileData
    devices, lines, window = {}, [], None
    for plane in ProfileData.from_file(path).planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    devices[int(m.group(1))] = [
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.duration_ns)
                       for e in line.events]
                lines.append(evs)
                w = [(s, s + d) for n, s, d in evs if n == window_span]
                if w:
                    window = (min(a for a, _ in w), max(b for _, b in w))
    return devices, lines, window


@functools.lru_cache(maxsize=4)
def table_of(path: str, phases: tuple):
    """Parsed and reduced once a process, however many metrics ask;
    prints the table when it is first made."""
    from perfbench import run
    t = table(*load(path, run.WINDOW_SPAN), phases)
    if t is None:
        return None
    buckets, counts, detail, host = t
    idle = sum(buckets.values())

    def rows(d, n=None):
        return [[k, v * 1e-9, 100.0 * v / idle if idle else 0.0]
                for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
    print("[perfbench host_idle] " + json.dumps(
        {"idle_s": idle * 1e-9,
         "phase_seconds_share_of_idle": rows(buckets),
         "phases_started_in_window": counts,
         "innermost_event_of_any_name": rows(detail, 16),
         "thread_seconds_by_phase": {k: v * 1e-9 for k, v in host.items()}}),
        flush=True)
    return t


def read(rec, spec):
    if rec.trace_summary is None:         # no device plane: off the chip
        return None
    from perfbench import run
    (cell,) = spec["workloads"]
    path = trace_reduce.find_xplane(os.path.join(run.TRACE_DIR, cell))
    t = table_of(path, tuple(spec["phases"])) if path else None
    if t is None:
        return None
    buckets, counts = t[:2]
    n = counts.get(spec["per"], 0)
    if not n:
        return None
    idle_ns = sum(buckets.get(name, 0) for name in spec["spans"])
    return idle_ns * 1e-9 / n * spec.get("scale", 1.0)
