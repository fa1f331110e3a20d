"""Model FLOP/s utilisation: the operations the model needs per token
(perfbench/flops.py: forward + backward, recomputation not counted)
times the tokens per second the window measured, over chips times the
peak of the device in perfbench/peaks.json."""
from perfbench import flops


def read(rec, spec):
    c = rec.context
    if c.get("peaks") is None or "tokens_per_s" not in c:
        return None
    per_token = flops.train_flops_per_token(c["cfg"], c["traffic"]["seq_len"])
    return flops.mfu_percent(per_token, c["tokens_per_s"], c["chips"],
                             c["peaks"]["bf16_flops_per_s"])
