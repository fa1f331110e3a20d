"""Find a serving cell's knee again, in ONE chip call
(``perfbench/README.md``, "Finding a cell's rates again"):

    chiprun --chips 1 --timeout 3400 -- python3 perfbench/chip_calls/sweep_knee.py --cell CELL [--coarse 6 8 10 12 14 16] [--seed0 N]

Each run is one ``perfbench.run`` process of the cell under
``--traffic-override '{"arrival": {"rate_rps": R}, "drain_s": 0}'``,
``--seconds 40``, untraced, every run on a seed of its own; this parent
never touches JAX.  The coarse rates go up in order (give rates that
bracket the knee); between the last rate sustained and the first not,
the 0.5 grid is bisected.  A rate gets its second seed only while its
first is sustained (one seed that fails decides it).  Then the knee K's
shares are tried once each: 1.5 K and 2.0 K (rounded to 0.5, ``drain_s``
0) and 0.8 K (rounded to 0.1, the mix's own ``drain_s``).

A rate is SUSTAINED when, on both seeds (the numbers are the driver's
info line; ``sustained()`` below):
  1. the window's output tokens a second >= 0.97 of those offered, R x
     ``output_tokens_mean``;
  2. ``unanswered_in_window`` <= 1 % of ``requests_due_in_window``;
  3. ``unanswered_at_end_of_third`` does not grow from the first third to
     the last (by more than 2 requests: the count is of one instant);
  4. ``generator_lateness_p95_ms`` < 20.

Rows go to ``chiprun_out/sweep_<cell>_<seed0>.json`` after every run (a
call cut at its limit keeps what it got), full logs to
``chiprun_out/logs/``.
"""
import argparse
import json
import os
import subprocess
import sys
import time

COARSE = [6.0, 8.0, 10.0, 12.0, 14.0, 16.0]
OUT = "chiprun_out"
GROWTH_SLACK = 2


def sustained(rate, info, seconds):
    """The four conditions on one run's info; returns (ok, the reasons
    it is not)."""
    why = []
    if info["window_output_tokens"] / seconds < \
            0.97 * rate * info["output_tokens_mean"]:
        why.append("tokens/s under offered")
    if info["unanswered_in_window"] > 0.01 * info["requests_due_in_window"]:
        why.append("unanswered")
    thirds = [t["unanswered_at_end_of_third"] for t in info["by_third"]]
    if thirds[-1] > thirds[0] + GROWTH_SLACK:
        why.append("thirds grow")
    if info["generator_lateness_p95_ms"] >= 20:
        why.append("generator late")
    return not why, why


class Sweep:
    def __init__(self, cell, seed0, seconds=40.0):
        self.cell, self.seed0, self.seconds = cell, seed0, seconds
        self.tag = f"sweep_{cell}_{seed0}"
        self.rows = []
        os.makedirs(f"{OUT}/logs", exist_ok=True)

    def run(self, rate, note, drain_s=0):
        """One run at ``rate``; ``drain_s`` None leaves the mix's own."""
        over = {"arrival": {"rate_rps": rate}}
        if drain_s is not None:
            over["drain_s"] = drain_s
        seed = self.seed0 + len(self.rows)
        args = ["--workload", self.cell, "--seed", str(seed), "--seconds",
                str(self.seconds), "--trace", "0", "--traffic-override",
                json.dumps(over)]
        t0 = time.time()
        p = subprocess.run([sys.executable, "-m", "perfbench.run"] + args,
                           capture_output=True, text=True)
        tag = f"{self.tag}.{len(self.rows):02d}"
        with open(f"{OUT}/logs/{tag}.out", "w") as f:
            f.write(p.stdout)
        with open(f"{OUT}/logs/{tag}.err", "w") as f:
            f.write(p.stderr)
        lines = p.stdout.strip().splitlines()
        row = {"rate": rate, "seed": seed, "rc": p.returncode, "note": note,
               "wall_s": round(time.time() - t0, 1)}
        try:
            line = json.loads(lines[-1])
            info = json.loads(next(ln for ln in reversed(lines)
                                   if "] info " in ln).split("] info ", 1)[1])
            d, e2e = info["driver"], info["end_to_end_of_this_run"]
            ok, why = sustained(rate, d, self.seconds)
            row.update(
                correct=line["correct"], failed=line["failed"],
                sustained=ok, not_because=why,
                tokens_per_s=e2e["serve_tokens_per_s"],
                offered_tokens_per_s=rate * d["output_tokens_mean"],
                finished_per_s=d["requests_finished_per_s_whole_run"],
                due=d["requests_due_in_window"],
                unanswered=d["unanswered_in_window"],
                thirds_unanswered=[t["unanswered_at_end_of_third"]
                                   for t in d["by_third"]],
                thirds_ttft_median_s=[t["ttft_median_s"]
                                      for t in d["by_third"]],
                lateness_p95_ms=d["generator_lateness_p95_ms"],
                tpot_p95_ms=e2e.get("tpot_p95_ms"),
                ttft_p95_ms=e2e.get("ttft_p95_ms"),
                setup_s=line["metrics"]["setup_s"]["value"],
                check=info["check"], compiles=info["compiles_in_window"])
        except (ValueError, StopIteration, KeyError, IndexError) as e:
            row.update(sustained=False, not_because=[f"no result: {e!r}"],
                       stderr=p.stderr[-1500:])
        self.rows.append(row)
        with open(f"{OUT}/{self.tag}.json", "w") as f:
            json.dump(self.rows, f, indent=1)
        print("ROW " + json.dumps(row), flush=True)
        return row["sustained"]

    def rate_sustained(self, rate, note):
        """Two seeds; the second only while the first is sustained."""
        return self.run(rate, note) and self.run(rate, note)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--coarse", type=float, nargs="+", default=COARSE)
    ap.add_argument("--seed0", type=int, required=True)
    a = ap.parse_args(argv)
    sw = Sweep(a.cell, a.seed0)
    lo, hi = None, None                   # last sustained, first not
    for rate in a.coarse:
        if hi is not None:
            sw.run(rate, "coarse, above the first rate not sustained")
        elif sw.rate_sustained(rate, "coarse"):
            lo = rate
        else:
            hi = rate
    if lo is None or hi is None:
        print(f"KNEE not bracketed by --coarse: last sustained {lo}, "
              f"first not {hi}")
        return 1
    a, b = int(round(lo * 2)), int(round(hi * 2))      # the 0.5 grid
    while b - a > 1:
        mid = (a + b) // 2
        if sw.rate_sustained(mid / 2, "fine"):
            a = mid
        else:
            b = mid
    knee = a / 2
    print(f"KNEE {knee} req/s (first not sustained {b / 2})", flush=True)
    sw.run(round(knee * 1.5 * 2) / 2, "1.5 K")
    sw.run(round(knee * 2.0 * 2) / 2, "2.0 K")
    sw.run(round(knee * 0.8, 1), "0.8 K, the mix's drain", drain_s=None)
    return 0


if __name__ == "__main__":
    sys.exit(main())
