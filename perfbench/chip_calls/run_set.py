"""Run a list of benchmark runs one after another, each in a process of
its own, from ONE chip call:

    chiprun --chips 1 --timeout 3000 -- python3 perfbench/chip_calls/run_set.py perfbench/chip_calls/call1.txt

Each line of the list is the arguments of one ``perfbench.run``
(``#`` starts a comment).  This parent never touches JAX, so every child
gets the chip.  Full output goes to ``chiprun_out/logs/`` (or under the
directory given as a second argument); the end of
this script's own output (which is all the chip tool shows) carries each
run's result line and a cut of its info line.
"""
import json
import os
import shlex
import subprocess
import sys
import time


def main(path, out="chiprun_out"):
    os.makedirs(f"{out}/logs", exist_ok=True)
    with open(path) as f:
        runs = [ln.split("#")[0].strip() for ln in f]
    runs = [r for r in runs if r]
    summary = []
    for k, args in enumerate(runs):
        t0 = time.time()
        p = subprocess.run([sys.executable, "-m", "perfbench.run"]
                           + shlex.split(args), capture_output=True,
                           text=True)
        wall = time.time() - t0
        tag = f"{os.path.basename(path)}.{k:02d}"
        with open(f"{out}/logs/{tag}.out", "w") as f:
            f.write(p.stdout)
        with open(f"{out}/logs/{tag}.err", "w") as f:
            f.write(p.stderr)
        lines = p.stdout.strip().splitlines()
        last = lines[-1] if lines else ""
        info = next((ln for ln in reversed(lines) if "] info " in ln), "")
        print(f"RUN {k} [{args}] rc={p.returncode} wall={wall:.1f}s")
        print("  " + last[:1800])
        print("  " + info[:1500])
        if p.returncode != 0:
            print("  STDERR " + p.stderr[-1500:].replace("\n", "\n  "))
        try:
            summary.append({"args": args, "rc": p.returncode,
                            "wall_s": wall, "line": json.loads(last)})
        except ValueError:
            summary.append({"args": args, "rc": p.returncode,
                            "wall_s": wall, "line": None})
    with open(f"{out}/{os.path.basename(path)}.summary.json",
              "w") as f:
        json.dump(summary, f, indent=1)


if __name__ == "__main__":
    main(*sys.argv[1:3])
