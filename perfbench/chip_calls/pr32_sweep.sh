#!/bin/bash
# PR 32's third chip call: the rate sweep, then the seven controls through the cell's judges
#   chiprun --chips 1 --timeout 2400 -- bash perfbench/chip_calls/pr32_sweep.sh
python3 perfbench/chip_calls/run_set.py perfbench/chip_calls/pr32_sweep.txt
mkdir -p chiprun_out
python3 perfbench/chip_calls/pr32_controls.py 2147489031 > chiprun_out/pr32_controls.json 2> chiprun_out/pr32_controls.err
echo "controls rc=$?"
tail -c 6000 chiprun_out/pr32_controls.json
