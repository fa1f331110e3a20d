"""Run benchmark runs from SEVERAL checkouts one after another in one
chip call, for a parent-against-change comparison on the same chip:

    chiprun --chips 1 --timeout 3000 -- python3 perfbench/chip_calls/ab_set.py perfbench/chip_calls/pr25_pairs.txt

Each line of the list is a directory (relative to where this is
started: ``_checkout/parent``, ``_checkout/change``, unpacked there with
``git archive``) followed by the arguments of one ``perfbench.run``,
which runs with that directory as its working directory.  Like
``run_set.py`` this parent never touches JAX; logs go to
``chiprun_out/logs/`` and one summary to ``chiprun_out/<list>.summary.json``.
"""
import json
import os
import shlex
import subprocess
import sys
import time


def main(path, out="chiprun_out"):
    out = os.path.abspath(out)
    os.makedirs(f"{out}/logs", exist_ok=True)
    with open(path) as f:
        runs = [ln.split("#")[0].strip() for ln in f]
    runs = [r.split(None, 1) for r in runs if r]
    summary = []
    for k, (where, args) in enumerate(runs):
        t0 = time.time()
        p = subprocess.run([sys.executable, "-m", "perfbench.run"]
                           + shlex.split(args), capture_output=True,
                           text=True, cwd=where)
        wall = time.time() - t0
        tag = f"{os.path.basename(path)}.{k:02d}"
        with open(f"{out}/logs/{tag}.out", "w") as f:
            f.write(p.stdout)
        with open(f"{out}/logs/{tag}.err", "w") as f:
            f.write(p.stderr)
        lines = p.stdout.strip().splitlines()
        last = lines[-1] if lines else ""
        print(f"RUN {k} [{where}: {args}] rc={p.returncode} "
              f"wall={wall:.1f}s")
        print("  " + last[:1500])
        for ln in lines:
            if "host_idle]" in ln:
                print("  " + ln[:2500])
        if p.returncode != 0:
            print("  STDERR " + p.stderr[-1500:].replace("\n", "\n  "))
        try:
            line = json.loads(last)
        except ValueError:
            line = None
        summary.append({"where": where, "args": args, "rc": p.returncode,
                        "wall_s": wall, "line": line})
        # after every run: a call cut at its time limit keeps what it got
        with open(f"{out}/{os.path.basename(path)}.summary.json",
                  "w") as f:
            json.dump(summary, f, indent=1)


if __name__ == "__main__":
    main(*sys.argv[1:3])
