"""Spreads of the end-to-end metrics from run_set.py's summaries, by the
contract's rule: per cell and metric, each set's distance between the
first and third quartile (``statistics.quantiles(v, n=4)``) as a share
of its median, the wider of the two sets, and five times the widest
over the cells as the bound.

    python3 -m perfbench.chip_calls.spread chiprun_out/sets_sat.txt.summary.json ...

A summary's plain runs (``--trace 0``) are taken in order; the first
half is set 1 and the second half set 2 (same seeds in both).
"""
import json
import statistics
import sys

from perfbench.stats import iqr_share


def main(paths):
    cells = {}
    for p in paths:
        for r in json.load(open(p)):
            a = r["args"].split()
            if r["line"] is None or a[a.index("--trace") + 1] != "0" \
                    or "--traffic-override" in a:
                continue
            cells.setdefault(a[a.index("--workload") + 1], []).append(r)
    widest = {}
    for cell, runs in cells.items():
        half = len(runs) // 2
        sets = [runs[:half], runs[half:]] if half >= 3 else [runs]
        print(f"{cell}: {len(runs)} plain runs, correct "
              f"{[r['line']['correct'] for r in runs]}")
        for m in runs[0]["line"]["metrics"]:
            vals = [[r["line"]["metrics"][m]["value"] for r in s
                     if m in r["line"]["metrics"]] for s in sets]
            sp = [iqr_share(v) for v in vals if len(v) >= 3]
            med = [statistics.median(v) for v in vals]
            print(f"  {m}: medians {[round(x, 4) for x in med]} spreads "
                  f"{[round(100 * x, 3) for x in sp]} %  values "
                  f"{[[round(x, 3) for x in v] for v in vals]}")
            if sp:
                widest[m] = max(widest.get(m, 0.0), max(sp))
    for m, w in widest.items():
        print(f"widest {m}: {100 * w:.3f} %  -> bound "
              f"{max(5 * w, 0.01):.4f}")


if __name__ == "__main__":
    main(sys.argv[1:])
