"""How much of ``ssm_moe_serve_reason``'s spread is the layout of the
cycle (PR 32): a scheduler simulation on this machine's CPU, no chip.

    python3 -m perfbench.chip_calls.pr32_order_scan phases          # order_seed 0 at the measured seeds' phases
    python3 -m perfbench.chip_calls.pr32_order_scan scan 0 200      # layouts by their spread over all phases

The cycle is laid out as ``open_loop.make_requests`` does and served
from a phase through a model of the engine's loop: FIFO admission into
128 slots, a MIXED step (one forward: up to 512 prompt rows + one token
a decoding slot) whenever a prompt waits, else a WINDOW of ``n``
forwards with ``n`` the largest power of two under both 8 and the
fewest tokens any decoding slot has left (``_step_mixed``), ``busy``
seconds a forward plus ``host`` seconds a step.  It gives no device
number: PERF.md has what the chip read.
"""
import statistics
import sys

import numpy as np

from perfbench import manifest
from perfbench.chip_calls.pr28_order_scan import cycle_of, requests_from

MEASURED = {2147489201: 2162.7, 2147489202: 2181.975, 2147489203: 2159.325,
            2147489204: 2156.45, 2147489205: 2177.325, 2147489206: 2143.375,
            2147489207: 2185.125, 2147489208: 2140.25, 2147489209: 2159.575,
            2147489210: 2178.475, 2147489211: 2145.925, 2147489212: 2079.7,
            2147489041: 2175.675, 2147489042: 2156.275, 2147489043: 2175.45,
            2147489044: 2168.975, 2147489045: 2172.225, 2147489046: 2191.6}


def tokens_per_s(reqs, ramp, seconds, busy=0.053, mixed=0.066, host=0.019,
                 slots=128, budget=512, k=8):
    t, nxt, waiting, pre, dec, tokens = 0.0, 0, [], [], [], 0
    end = ramp + seconds
    while t < end:
        while nxt < len(reqs) and reqs[nxt][0] <= t:
            waiting.append(reqs[nxt])
            nxt += 1
        while waiting and len(pre) + len(dec) < slots:
            _, p, o = waiting.pop(0)
            pre.append([p, o])
        if not pre and not dec:
            t = reqs[nxt][0] if nxt < len(reqs) else end
            continue
        if pre:                                   # a mixed step
            left, first = budget, []
            while pre and left > 0:
                take = min(left, pre[0][0])
                pre[0][0] -= take
                left -= take
                if pre[0][0] == 0:
                    first.append(pre.pop(0)[1] - 1)
            t += mixed + host
            if ramp <= t < end:
                tokens += len(dec) + len(first)
            dec = [d - 1 for d in dec if d > 1] + [d for d in first if d > 0]
            continue
        n = min([k] + dec)
        while n & (n - 1):
            n &= n - 1
        for _ in range(n):
            t += busy
            if ramp <= t < end:
                tokens += len(dec)
        t += host
        dec = [d - n for d in dec if d > n]
    return tokens / seconds


def spread(v):
    q = statistics.quantiles(v, n=4)
    return (q[2] - q[0]) / statistics.median(v)


def main(what="phases", lo=0, hi=100, seconds=40.0):
    traffic = manifest.traffic("reason_overload")
    ramp = traffic["ramp_s"]
    rate = traffic["arrival"]["rate_rps"]
    n_cycle = int(round(rate * seconds))
    total = int(round(rate * (ramp + seconds)))
    if what == "phases":
        cycle = cycle_of(traffic, traffic["order_seed"], seconds)
        sim, got = [], []
        for seed, value in MEASURED.items():
            ph = int(np.random.default_rng(seed).integers(n_cycle))
            sim.append(tokens_per_s(requests_from(cycle, ph, total), ramp,
                                    seconds))
            got.append(value)
            print(f"seed {seed} phase {ph:3d}: simulated {sim[-1]:7.1f} "
                  f"measured {value:7.1f}")
        print("correlation", float(np.corrcoef(sim, got)[0, 1]),
              "simulated spread over these phases", spread(sim))
        return
    rows = []
    for order_seed in range(int(lo), int(hi)):
        cycle = cycle_of(traffic, order_seed, seconds)
        v = [tokens_per_s(requests_from(cycle, ph, total), ramp, seconds)
             for ph in range(n_cycle)]
        rows.append((float(np.std(v) / np.mean(v)), spread(v),
                     (max(v) - min(v)) / np.mean(v), order_seed))
    for sd, sp, rng, order_seed in sorted(rows)[:8] + sorted(rows)[-2:]:
        print(f"order_seed {order_seed}: over all {n_cycle} phases standard "
              f"deviation {100 * sd:.2f} %, quartile spread {100 * sp:.2f} %, "
              f"range {100 * rng:.2f} % (simulated on a CPU)")


if __name__ == "__main__":
    main(*sys.argv[1:4])
