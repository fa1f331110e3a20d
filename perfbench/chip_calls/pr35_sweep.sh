#!/bin/bash
# PR 35: the knee of deepseek_moe_16b_4l under the chat lengths, found again on this tree (PERF.md section 2 has the table).
# Chip call 1 ran the coarse rates 6-16 req/s and judged condition 1 by requests finished a second, as ISSUE 35 wrote it:
# "K" = 4.5 req/s, an artefact of the requests in flight at the close.  The tool no longer has that reading; its rows
# stand in the table for 6, 14 and 16 req/s.
# Chip call 2, THIS file (condition 1 by the window's tokens a second against those offered; two seeds at 8, 10 and 12,
# the 0.5 grid bisected, then 1.5 K, 2.0 K and 0.8 K once each).  The cell's mix then held 8 req/s, not yet 0.8 K:
#   chiprun --chips 1 --timeout 2400 -- bash perfbench/chip_calls/pr35_sweep.sh
python3 perfbench/chip_calls/sweep_knee.py --cell moe_chat_knee80 --coarse 8 10 12 --seed0 2147489415
echo "sweep rc=$?"
