"""On-chip jax.profiler trace of the headline 770m train step
(standalone; needs a TPU).  Captures 3 steps, aggregates
device-side op durations by kernel/fusion class, prints ONE json line
— the op-level evidence behind BASELINE.md's MFU analysis.

Caveat: `while.N` regions (the CE chunk loop) appear alongside their
interior fusions, so the class totals can exceed the wall step time —
read `top_ops` with the loop rows in mind (BASELINE.md's table does).

Usage: python tests/profile_headline.py [--steps 3]
"""
import gzip
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

import jax

import paddle_tpu as paddle
from paddle_tpu.jit.train import CompiledTrainStep
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

import bench as B

OUT = "/tmp/headline_trace"


def build_step():
    dev, kind, peak, hbm, on_tpu = B._device()
    assert on_tpu, "needs the TPU"
    # the bench's llama-770m recipe shape, explicit
    cfg = LlamaConfig(
        vocab_size=128256, hidden_size=1536, intermediate_size=6144,
        num_hidden_layers=16, num_attention_heads=12,
        num_key_value_heads=4, max_position_embeddings=8192,
        recompute=True, recompute_granularity="core_attn")
    model = LlamaForCausalLM(cfg)
    model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-4, parameters=model.parameters(),
        grad_clip=paddle.ClipGradByGlobalNorm(1.0))
    step = CompiledTrainStep(
        model, lambda m, b: m(b["input_ids"], labels=b["labels"]), opt)
    data = B._train_batch(cfg.vocab_size, 2, 8192)
    return step, data


def capture(step, data, n=3):
    for _ in range(2):                      # compile + warm
        loss = step(data)
    # device_get of the loss scalar is the barrier: it waits for the
    # whole donated-state chain of both steps
    float(np.asarray(jax.device_get(loss)))
    os.makedirs(OUT, exist_ok=True)
    with jax.profiler.trace(OUT):
        for _ in range(n):
            loss = step(data)
        float(np.asarray(jax.device_get(loss)))
    # newest trace dir
    base = os.path.join(OUT, "plugins", "profile")
    run = sorted(os.listdir(base))[-1]
    for f in os.listdir(os.path.join(base, run)):
        if f.endswith(".trace.json.gz"):
            return os.path.join(base, run, f)
    raise RuntimeError("no trace.json.gz produced")


def classify(name: str, args) -> str:
    long = str(args.get("long_name", "")) + " " + str(
        args.get("hlo_op", "")) + " " + name
    if "tpu_custom_call" in long or "custom-call" in long:
        for k in ("_fwd_kernel", "_bwd_dq", "_bwd_dkv", "gmm", "dmask"):
            if k in long:
                return f"flash:{k}"
        return "custom_call"
    for pat, cls in (
            (r"fused_linear_cross_entropy|log_softmax|logits", "ce"),
            (r"adamw|apply_updates|global_norm|clip", "optimizer"),
            (r"rope|rotary", "rope"),
            (r"rms_norm|rsqrt", "norm"),
            (r"copy", "copy"),
            (r"all-reduce|all-gather|reduce-scatter|collective",
             "collective"),
            (r"convert", "convert"),
            (r"transpose", "transpose"),
            (r"dot|conv", "matmul"),
            (r"fusion", "fusion_other"),
    ):
        if re.search(pat, long):
            return cls
    return "other"


def aggregate(path, n_steps):
    with gzip.open(path) as f:
        data = json.load(f)
    evs = data["traceEvents"]
    # find TPU device pid
    tpu_pids = {e["pid"] for e in evs
                if e.get("ph") == "M" and e.get("name") == "process_name"
                and "TPU" in str(e.get("args", {}).get("name", ""))}
    # ONLY the "XLA Ops" thread: the Steps / XLA Modules threads carry
    # container spans that would double-count every op beneath them
    op_tids = {(e["pid"], e["tid"]) for e in evs
               if e.get("ph") == "M" and e.get("name") == "thread_name"
               and e.get("pid") in tpu_pids
               and e.get("args", {}).get("name") == "XLA Ops"}
    # module-root regions sneak onto the ops thread as bare numbers
    # ("2", "5", ...) spanning a whole step — drop them
    totals = {}
    names = {}
    total_us = 0.0
    for e in evs:
        if e.get("ph") != "X" \
                or (e.get("pid"), e.get("tid")) not in op_tids:
            continue
        dur = float(e.get("dur", 0.0))
        nm = e.get("name", "?")
        if nm.startswith("jit_") or nm.startswith("Pjit") \
                or nm.isdigit():
            continue
        cls = classify(nm, e.get("args", {}))
        totals[cls] = totals.get(cls, 0.0) + dur
        key = (cls, nm[:60])
        names[key] = names.get(key, 0.0) + dur
        total_us += dur
    per_step = {k: round(v / n_steps / 1e3, 3)
                for k, v in sorted(totals.items(), key=lambda x: -x[1])}
    top = [{"class": k[0], "name": k[1],
            "ms_per_step": round(v / n_steps / 1e3, 3)}
           for k, v in sorted(names.items(), key=lambda x: -x[1])[:20]]
    return {"device_ms_per_step_by_class": per_step,
            "device_total_ms_per_step": round(total_us / n_steps / 1e3,
                                              2),
            "top_ops": top}


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=3)
    n = ap.parse_args().steps
    step, data = build_step()
    path = capture(step, data, n)
    res = aggregate(path, n)
    res["trace"] = path
    print(json.dumps(res))
