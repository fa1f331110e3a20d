"""Counts and ratios the program already keeps, read from its snapshots
at the window's start and end, or from samples taken at each engine
step.  Spec ``kind``:

- ``hist_mean_delta``: a histogram's (sum_end - sum_start) / (count_end -
  count_start) at ``path`` of the scheduler's snapshot;
- ``counters_per_span``: the summed growth of the counters at ``paths``
  over the number of ``span`` spans in the window;
- ``sample_mean``: the mean of the samples named ``sample``.
"""


def _at(snap, path):
    for key in path:
        snap = snap[key]
    return snap


def read(rec, spec):
    kind = spec["kind"]
    if kind == "sample_mean":
        vals = [v for _, v in rec.in_window(rec.samples.get(spec["sample"],
                                                            []))]
        return sum(vals) / len(vals) if vals else None
    a, b = rec.snapshots.get("start"), rec.snapshots.get("end")
    if a is None or b is None:
        return None
    if kind == "hist_mean_delta":
        ha, hb = _at(a, spec["path"]), _at(b, spec["path"])
        n = hb["count"] - ha["count"]
        if n <= 0:
            return None
        return (hb["sum"] - ha["sum"]) / n * spec.get("scale", 1.0)
    if kind == "counters_per_span":
        grown = sum(_at(b, p) - _at(a, p) for p in spec["paths"])
        n = len(rec.in_window(rec.spans.get(spec["span"], [])))
        return grown / n if n else None
    raise ValueError(f"unknown snapshot kind {kind!r}")
