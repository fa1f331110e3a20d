#!/bin/bash
# PR 31's last chip call: chip_smoke.py from the committed files of the final
# tree alone (`git archive $(git write-tree)` unpacked into _checkout/final),
# then two more pairs of dense_train_8k against the parent.
#   chiprun --chips 1 --timeout 2400 -- bash perfbench/chip_calls/pr31_final.sh
mkdir -p chiprun_out/logs
(cd _checkout/final && python3 chip_smoke.py) > chiprun_out/logs/pr31_smoke.out 2> chiprun_out/logs/pr31_smoke.err
echo "chip_smoke rc=$?"
tail -n 4 chiprun_out/logs/pr31_smoke.out | cut -c1-1500
python3 perfbench/chip_calls/ab_set.py perfbench/chip_calls/pr31_train.txt
