"""How ``longctx_overload.json``'s ``order_seed`` was chosen (PR 28): a
scheduler simulation on this machine's CPU, no chip.

    python3 -m perfbench.chip_calls.pr28_order_scan 0 3000

At 2.0 x overload a window serves half of the generator's cycle, and
which half is the seed's phase.  The step programs are bound by their
512 prompt rows, so output tokens a second follow (output tokens) /
(prompt tokens) of the requests served.  For each ``order_seed`` this
lays the cycle out as ``open_loop.make_requests`` does, serves it from
every phase through a model of the engine's step (FIFO prefill of
``budget`` rows a step, every decoding slot one row, ``a + b * rows``
seconds a step: 30 ms + 0.024 ms reproduces the cell's 2.15 req/s and 29
decode rows) and prints the layouts whose tokens/s vary least over the
phases, under several (a, b).  It gives no device number: PERF.md has
what the chip then read.  A changed rate, window or budget changes the
cycle, so the choice has to be made again.  About a second a layout and
step model in one process: run ranges side by side.
"""
import sys

import numpy as np

from perfbench import manifest, stats
from perfbench.drivers import open_loop

STEP_MODELS = ((0.030, 0.000024), (0.016, 0.00005), (0.036, 0.000012),
               (0.027, 0.0000216), (0.033, 0.0000264))


def cycle_of(traffic, order_seed, seconds):
    n = int(round(traffic["arrival"]["rate_rps"] * seconds))
    order = np.random.default_rng(order_seed)
    return [open_loop.spread_out(v, order) for v in (
        open_loop.gaps(traffic["arrival"], n),
        open_loop.lengths(traffic["prompt_len"], n),
        open_loop.lengths(traffic["output_len"], n))]


def requests_from(cycle, phase, total):
    gaps, plen, olen = cycle
    at = [(phase + k) % len(gaps) for k in range(total)]
    due = stats.due_times([gaps[j] for j in at])
    return [(due[i], plen[j], olen[j]) for i, j in enumerate(at)]


def tokens_per_s(reqs, ramp, seconds, a, b, slots=64, budget=512):
    t, nxt, waiting, pre, dec, tokens = 0.0, 0, [], [], [], 0
    while t < ramp + seconds:
        while nxt < len(reqs) and reqs[nxt][0] <= t:
            waiting.append(reqs[nxt])
            nxt += 1
        while waiting and len(pre) + len(dec) < slots:
            _, p, o = waiting.pop(0)
            pre.append([p, o])
        if not pre and not dec:
            t = reqs[nxt][0] if nxt < len(reqs) else ramp + seconds
            continue
        left, rows, first = budget, len(dec), []
        while pre and left > 0:
            take = min(left, pre[0][0])
            pre[0][0] -= take
            left -= take
            rows += take
            if pre[0][0] == 0:
                first.append(pre.pop(0)[1] - 1)   # its first token
        t += a + b * rows
        if ramp <= t < ramp + seconds:
            tokens += len(dec) + len(first)
        dec = [d - 1 for d in dec if d > 1] + [d for d in first if d > 0]
    return tokens / seconds


def main(lo, hi, seconds=40.0):
    traffic = manifest.traffic("longctx_overload")
    ramp = traffic["ramp_s"]
    total = int(round(traffic["arrival"]["rate_rps"] * (ramp + seconds)))
    rows = []
    for order_seed in range(lo, hi):
        cycle = cycle_of(traffic, order_seed, seconds)
        worst = 0.0
        for a, b in STEP_MODELS:
            v = [tokens_per_s(requests_from(cycle, ph, total), ramp,
                              seconds, a, b) for ph in range(len(cycle[0]))]
            worst = max(worst, float(np.std(v) / np.mean(v)))
        rows.append((worst, order_seed))
    for worst, order_seed in sorted(rows)[:10]:
        print(f"order_seed {order_seed}: standard deviation over the "
              f"phases at most {100 * worst:.2f} % (simulated on a CPU)")


if __name__ == "__main__":
    main(int(sys.argv[1]), int(sys.argv[2]))
