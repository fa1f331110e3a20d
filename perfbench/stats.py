"""Percentiles, spreads and open-loop arithmetic.  Pure Python."""
from __future__ import annotations

import math
import statistics


def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation between
    closest ranks (numpy's default), of a non-empty sequence."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values):
    return percentile(values, 50.0)


def iqr_share(values):
    """The spread the benchmark's bounds are set from: the distance
    between the first and third quartile as ``statistics.quantiles(v,
    n=4)`` gives them, as a share of the median."""
    q1, q2, q3 = statistics.quantiles([float(v) for v in values], n=4)
    return (q3 - q1) / q2 if q2 else float("inf")


def due_times(gaps, start=0.0):
    """Open loop: request i is due at start + the sum of the first i+1
    gaps, whatever the system does."""
    out, t = [], float(start)
    for g in gaps:
        t += float(g)
        out.append(t)
    return out


def mean_gap(token_times):
    """Mean gap between a request's output tokens from the times they
    reached the client, (last - first) / (n - 1); None under 2 tokens.
    Tokens that arrive together (one decode window) share a time."""
    if len(token_times) < 2:
        return None
    return (token_times[-1] - token_times[0]) / (len(token_times) - 1)
