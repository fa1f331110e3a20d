"""From a profiler trace (xplane) to busy/idle, time by operation name
and idle gaps by host span.

The reduction works on plain tuples so that it can be checked on a
synthetic trace; ``load_xplane`` turns the profiler's file into them
through ``jax.profiler.ProfileData`` (nothing but JAX).

What a v5e trace looks like (read by hand from a real one, PR 22's
leftover): one plane per chip, ``/device:TPU:<n>``, whose line ``XLA
Ops`` holds every device operation as ``%<instr>.<k> = <hlo text>`` with
start and duration in nanoseconds; container operations (``while``,
``conditional``, ``call``) span their children on the same line.  A
Pallas kernel is a ``custom-call`` whose text names
``custom_call_target="tpu_custom_call"`` and whose instruction name is
the ``pallas_call``'s ``name=`` (``jvp_`` in front under autodiff).  Host
threads are lines of ``/host:CPU``; a ``jax.profiler.TraceAnnotation``
shows there under its own name, on the same clock.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "pb."
PALLAS_MARK = 'custom_call_target="tpu_custom_call"'
BETWEEN = "between_spans"


def op_name(text: str) -> str:
    """``%jvp_flash_fwd_.2 = ...`` -> ``jvp_flash_fwd_``; the HLO
    instruction's name without its numeric suffix."""
    m = re.match(r"%?([^\s=]+)", text)
    name = m.group(1) if m else text
    return re.sub(r"\.\d+$", "", name)


def kernel_name(text: str) -> str:
    """A Pallas kernel's stable name: the instruction name without the
    prefixes autodiff puts in front and the underscore it leaves behind."""
    return re.sub(r"^(jvp_|transpose_|vmap_)+|_+$", "", op_name(text))


def is_pallas(text: str) -> bool:
    return PALLAS_MARK in text


def merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def self_times(events):
    """(name, start, dur) events of ONE line, possibly nested -> list of
    (name, self_seconds_in_input_units): an event's duration minus its
    direct children's."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []          # stack of [end, index into out]
    for name, start, dur in evs:
        end = start + dur
        while stack and start >= stack[-1][0]:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= dur
        out.append([name, dur])
        stack.append((end, len(out) - 1))
    return [(n, max(d, 0.0)) for n, d in out]


def reduce_device(events, window):
    """One chip.  ``events``: (text, start, dur); ``window``: (t0, t1) in
    the same unit.  Returns busy time, per-name self time, the share of
    busy time inside named Pallas kernels, and the idle gaps."""
    t0, t1 = window
    clipped = []
    for text, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            clipped.append((text, a, b - a))
    busy_iv = merged((s, s + d) for _, s, d in clipped)
    busy = sum(e - s for s, e in busy_iv)
    by_name, pallas = {}, 0.0
    for text, d in self_times(clipped):
        if is_pallas(text):
            name = kernel_name(text)
            pallas += d
        else:
            name = op_name(text)
        by_name[name] = by_name.get(name, 0.0) + d
    gaps, at = [], t0
    for s, e in busy_iv:
        if s > at:
            gaps.append((at, s))
        at = e
    if t1 > at:
        gaps.append((at, t1))
    return {"busy": busy, "window": t1 - t0, "by_name": by_name,
            "pallas": pallas, "gaps": gaps}


def gaps_by_span(gaps, spans):
    """Idle time summed by the benchmark span the host was in.
    ``spans``: (name, start, dur) of host TraceAnnotations named
    ``pb.*``; spans of one thread do not overlap, so a gap's time goes
    to each span for the part they share and the rest to
    ``between_spans``."""
    out = {}
    spans = sorted((s, s + d, n) for n, s, d in spans)
    for a, b in gaps:
        covered = 0.0
        for s, e, n in spans:
            if s >= b:
                break
            lo, hi = max(a, s), min(b, e)
            if hi > lo:
                out[n] = out.get(n, 0.0) + (hi - lo)
                covered += hi - lo
        rest = (b - a) - covered
        if rest > 0:
            out[BETWEEN] = out.get(BETWEEN, 0.0) + rest
    return out


def top(d: dict, n=10, scale=1.0):
    return [[k, v * scale] for k, v in
            sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def summarize(per_device_events, host_spans, window=None, unit=1e-9):
    """All chips.  ``per_device_events``: {chip: [(text, start, dur)]}.
    ``window`` defaults to the extent of the device events.  Times come
    back in seconds (``unit`` converts the input's)."""
    chips = {k: v for k, v in per_device_events.items() if v}
    if not chips:
        return None
    if window is None:
        window = (min(s for ev in chips.values() for _, s, _ in ev),
                  max(s + d for ev in chips.values() for _, s, d in ev))
    n = len(chips)
    busy = pallas = 0.0
    by_name, gaps = {}, {}
    for ev in chips.values():
        r = reduce_device(ev, window)
        busy += r["busy"] / n
        pallas += r["pallas"] / n
        for k, v in r["by_name"].items():
            by_name[k] = by_name.get(k, 0.0) + v / n
        for k, v in gaps_by_span(r["gaps"], host_spans).items():
            gaps[k] = gaps.get(k, 0.0) + v / n
    win = window[1] - window[0]
    return {
        "chips": n,
        "busy_s": busy * unit,
        "window_s": win * unit,
        "idle_share": 100.0 * (1.0 - busy / win) if win > 0 else None,
        "named_share": 100.0 * pallas / busy if busy > 0 else None,
        "device_ops": top(by_name, 10, unit),
        "idle_gaps": top(gaps, 10, unit),
    }


def find_xplane(trace_dir: str):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def load_xplane(path: str):
    """-> ({chip: [(text, start_ns, dur_ns)]}, [(span, start_ns, dur_ns)])"""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(m.group(1))] = [
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns, e.duration_ns))
    return devices, spans


def summarize_dir(trace_dir: str, span_window=None):
    """Reduce the newest trace under ``trace_dir``.  With
    ``span_window`` (a span name) the window is the extent of that host
    span, so start-up and tear-down of the profiler stay outside."""
    path = find_xplane(trace_dir)
    if path is None:
        return None
    devices, spans = load_xplane(path)
    window = None
    if span_window:
        w = [(s, s + d) for n, s, d in spans if n == span_window]
        if w:
            window = (min(a for a, _ in w), max(b for _, b in w))
    inner = [s for s in spans if s[0] != span_window]
    return summarize(devices, inner, window)
