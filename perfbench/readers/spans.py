"""The median duration of a benchmark span inside the window, on the
host's clock.  Spec: ``span`` (name), ``scale`` (seconds -> unit)."""
from perfbench import stats


def read(rec, spec):
    durs = [b - a for a, b in rec.in_window(rec.spans.get(spec["span"], []))]
    if not durs:
        return None
    return stats.median(durs) * spec.get("scale", 1.0)
