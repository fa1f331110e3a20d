#!/bin/bash
# PR 31: row_fill of the three serving cells (one run each through
# pr31_row_fill.py, the change only), logs under chiprun_out/logs/.  (Three
# earlier calls, seeds ..131-33 — the sat run traced —, ..134-36 and ..137-39,
# printed no row_fill: the benchmark tears its engine down before it exits and
# snapshots it only in a traced run; their nine runs stand as runs.)
#   chiprun --chips 1 --timeout 1500 -- bash perfbench/chip_calls/pr31_row_fill.sh
mkdir -p chiprun_out/logs
k=0
for args in "--workload moe_serve_steady --seed 2147488151 --seconds 40 --trace 0" \
            "--workload moe_serve_sat --seed 2147488152 --seconds 40 --trace 0" \
            "--workload hybrid_serve_longctx --seed 2147488153 --seconds 40 --trace 0"; do
  python3 perfbench/chip_calls/pr31_row_fill.py $args \
    > chiprun_out/logs/pr31_row_fill.$k.out 2> chiprun_out/logs/pr31_row_fill.$k.err
  echo "RUN $k [$args] rc=$?"
  grep -h "row_fill\]" chiprun_out/logs/pr31_row_fill.$k.out
  grep -h "host_idle\]" chiprun_out/logs/pr31_row_fill.$k.out | cut -c1-600
  tail -n 1 chiprun_out/logs/pr31_row_fill.$k.out | cut -c1-2500
  k=$((k+1))
done
