"""A figure of the reduced profiler trace (perfbench/trace_reduce.py).
Spec ``field``: ``idle_share`` or ``named_share``, both in percent."""


def read(rec, spec):
    if rec.trace_summary is None:
        return None
    return rec.trace_summary.get(spec["field"])
