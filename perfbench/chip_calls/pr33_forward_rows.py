"""PR 33: benchmark runs that also say which programs their steps
launched — ``metrics_snapshot()["forward_rows"]`` of the run's engine at
the run's end (rows the mixed step and the window programs ran, and the
rows live in them: warm-up, probes, ramp and window together), with its
steps and window compiles — which the benchmark's own line does not
carry.  On a checkout from before PR 33 the snapshot has no such key and
the report says ``null``.

    chiprun --chips 1 --timeout 1800 -- python3 perfbench/chip_calls/pr33_forward_rows.py perfbench/chip_calls/pr33_traced_a.txt
    python3 perfbench/chip_calls/pr33_forward_rows.py --run --workload moe_serve_steady --seed 2147489318 --seconds 40 --trace 1

The first form is ``ab_set.py``'s (a list of ``<checkout> <arguments of
one perfbench.run>``, one process a line with that checkout as its
working directory, logs under ``chiprun_out/logs/``, one summary beside
them); the second is one run in the working directory's checkout.
``perfbench.run`` leaves through ``os._exit``: the report keeps every
engine built alive and asks each for its snapshot from a hook on that.
"""
import json
import os
import runpy
import shlex
import subprocess
import sys
import time

KEYS = ("forward_rows", "steps", "window_compiles", "mixed_compiles",
        "generated_tokens", "step_prefill_tokens")


def one_run(argv):
    sys.path.insert(0, os.getcwd())          # the checkout run from
    from paddle_tpu.inference.engine import LLMEngine
    engines = []
    real_init, real_exit = LLMEngine.__init__, os._exit

    def remembering(self, *a, **kw):
        real_init(self, *a, **kw)
        engines.append(self)

    def report_and_exit(code):
        for eng in engines:
            try:
                snap = eng.metrics_snapshot()
                print("[pr33 forward_rows] " + json.dumps(
                    {k: snap.get(k) for k in KEYS}), flush=True)
            except Exception as ex:  # noqa: BLE001 - say it, then leave
                print(f"[pr33 forward_rows] failed: {ex!r}", flush=True)
        real_exit(code)
    LLMEngine.__init__ = remembering
    os._exit = report_and_exit
    sys.argv = ["perfbench.run"] + argv
    runpy.run_module("perfbench.run", run_name="__main__")


def run_list(path, out="chiprun_out"):
    out = os.path.abspath(out)
    os.makedirs(f"{out}/logs", exist_ok=True)
    with open(path) as f:
        runs = [ln.split("#")[0].strip() for ln in f]
    runs = [r.split(None, 1) for r in runs if r]
    summary = []
    for k, (where, args) in enumerate(runs):
        t0 = time.time()
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--run"]
            + shlex.split(args), capture_output=True, text=True, cwd=where)
        tag = f"{os.path.basename(path)}.{k:02d}"
        with open(f"{out}/logs/{tag}.out", "w") as f:
            f.write(p.stdout)
        with open(f"{out}/logs/{tag}.err", "w") as f:
            f.write(p.stderr)
        lines = p.stdout.strip().splitlines()
        rows = [ln for ln in lines if ln.startswith("[pr33 forward_rows]")]
        last = next((ln for ln in reversed(lines)
                     if ln.startswith('{"correct"')), "")
        print(f"RUN {k} [{where}: {args}] rc={p.returncode} "
              f"wall={time.time() - t0:.1f}s")
        for ln in [last[:2500]] + rows + [
                ln[:2500] for ln in lines if "host_idle]" in ln]:
            print("  " + ln)
        if p.returncode != 0:
            print("  STDERR " + p.stderr[-1500:].replace("\n", "\n  "))
        try:
            line = json.loads(last)
        except ValueError:
            line = None
        summary.append({"where": where, "args": args, "rc": p.returncode,
                        "line": line, "forward_rows": rows})
        with open(f"{out}/{os.path.basename(path)}.summary.json",
                  "w") as f:
            json.dump(summary, f, indent=1)


if __name__ == "__main__":
    if sys.argv[1] == "--run":
        one_run(sys.argv[2:])
    else:
        run_list(*sys.argv[1:3])
