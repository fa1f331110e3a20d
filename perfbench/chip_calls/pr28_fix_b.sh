#!/bin/bash
# PR 28, fix session, chip call B (one chip), everything from the committed
# files in _checkout/change: the controls through the judges (each has to read
# NOT ok beside a sound check()), then pr28_fix_b.txt's runs.
#   chiprun --chips 1 --timeout 3400 -- bash perfbench/chip_calls/pr28_fix_b.sh
mkdir -p chiprun_out/logs
out=$PWD/chiprun_out
(cd _checkout/change && timeout -k 10 600 python3 perfbench/chip_calls/pr28_controls.py controls 2147484100 \
   > "$out/logs/pr28_fix_b.controls.out" 2> "$out/logs/pr28_fix_b.controls.err"; echo "== controls rc=$?")
tail -n 1 "$out/logs/pr28_fix_b.controls.out" | cut -c1-5000
python3 perfbench/chip_calls/ab_set.py perfbench/chip_calls/pr28_fix_b.txt
