#!/bin/bash
# PR 33, chip call 4: plain alternating pairs parent | change, then the traced pair(s) with the engine's forward_rows
#   chiprun --chips 1 --timeout 3500 -- bash perfbench/chip_calls/pr33_call4.sh
python3 perfbench/chip_calls/ab_set.py perfbench/chip_calls/pr33_pairs_c.txt
python3 perfbench/chip_calls/pr33_forward_rows.py perfbench/chip_calls/pr33_traced_c.txt
