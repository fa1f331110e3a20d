#!/bin/bash
# PR 28, fix session, chip call A (one chip): the controls through the judges,
# a sweep with prefill_token_budget tried at 2048 (the file says 512), the DeltaNet path alone by descriptor
# count (what hung at 576), and the served logits' distance from the reference.
#   chiprun --chips 1 --timeout 2400 -- bash perfbench/chip_calls/pr28_fix_a.sh
mkdir -p chiprun_out/logs
run() {  # name, seconds, command...
  local name=$1 limit=$2; shift 2
  local t0=$(date +%s)
  timeout -k 10 "$limit" "$@" > "chiprun_out/logs/pr28_fix_a.$name.out" 2> "chiprun_out/logs/pr28_fix_a.$name.err"
  local rc=$?
  echo "== $name rc=$rc wall=$(( $(date +%s) - t0 ))s"
  tail -n 3 "chiprun_out/logs/pr28_fix_a.$name.out" | cut -c1-3000
  [ $rc -ne 0 ] && tail -n 25 "chiprun_out/logs/pr28_fix_a.$name.err" | cut -c1-400
  return 0
}
run controls 600 python3 perfbench/chip_calls/pr28_controls.py controls 2147484001
run sweep 1200 python3 perfbench/chip_calls/run_set.py perfbench/chip_calls/pr28_sweep2.txt
for n in 83 144 288 576; do
  run gdn$n 100 python3 perfbench/chip_calls/pr28_kernels.py gdn $n
done
run logits 600 python3 perfbench/chip_calls/pr28_controls.py logits 2147484002
