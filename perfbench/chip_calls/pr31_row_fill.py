"""PR 31: one benchmark run that also says how full the expert layer's
sorted buffer ran — ``metrics_snapshot()["moe"]["row_fill"]`` of the
run's engine at the run's end (warm-up, probes, ramp and window
together) — which the benchmark's own line does not carry.

    chiprun --chips 1 --timeout 900 -- python3 perfbench/chip_calls/pr31_row_fill.py --workload moe_serve_steady --seed 2147488137 --seconds 40 --trace 0

``perfbench.run`` tears its system down and leaves through
``os._exit``, and takes snapshots of its own only in a traced run: the
report keeps every engine built alive and asks each for its snapshot
(host counters) from a hook on the exit.
"""
import json
import os
import runpy
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))            # the checkout's root

KEYS = ("row_fill", "buffer_rows", "absent_slots", "dropped_tokens",
        "experts_held", "top_k")


def main():
    from paddle_tpu.inference.engine import LLMEngine
    engines = []
    real_init, real_exit = LLMEngine.__init__, os._exit

    def remembering(self, *a, **kw):
        real_init(self, *a, **kw)
        engines.append(self)

    def report_and_exit(code):
        for eng in engines:
            try:
                moe = eng.metrics_snapshot().get("moe") or {}
                print("[pr31 row_fill] " + json.dumps(
                    {k: moe.get(k) for k in KEYS}
                    | {"kept_slots": sum(moe.get("expert_tokens", []))}),
                    flush=True)
            except Exception as ex:  # noqa: BLE001 - say it, then leave
                print(f"[pr31 row_fill] failed: {ex!r}", flush=True)
        real_exit(code)
    LLMEngine.__init__ = remembering
    os._exit = report_and_exit
    sys.argv = ["perfbench.run"] + sys.argv[1:]
    runpy.run_module("perfbench.run", run_name="__main__")


if __name__ == "__main__":
    main()
