#!/usr/bin/env python3
"""chip_smoke.py — the standing proof that the main paths run on the chip.

    python chip_smoke.py              # one v5e chip: train, serve, moe
    python chip_smoke.py --chips 4    # ONLY the multi-chip paths and what
                                      # each is compared with (four chips,
                                      # one process)
    python chip_smoke.py --rehearse [--chips 4]
                                      # sandbox rehearsal: tiny sizes on
                                      # whatever platform JAX finds

One process, the only one to touch JAX.  Every phase goes through the
entry points a user calls (``CompiledTrainStep``, ``ShardedTrainStep``,
``start_http_frontend(Scheduler(LLMEngine(model)))``) at the full width
of a model the repo supports, with seeded random weights and depth cut
where one chip forces it.  Each phase prints compile seconds, step or
request times (host clock, ending in ``device_get``), peak device bytes
and the Pallas kernels found in each compiled program, and FAILS if a
kernel it names is missing, a value is not finite, a request does not
finish, a warm program recompiles, or tokens disagree with the path they
are compared with.  Nothing is caught and carried past: the first
failure ends the run with ``"ok": false`` and a non-zero exit code.

The last line of standard output is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``
with the device as JAX reports it.  Without ``--rehearse`` the script
refuses any platform but ``tpu`` (exit 2, no result line).

Token comparisons (kernel vs jnp reference, grouped vs dense dispatch,
tp=4 vs tp=1) use one stated rule for bf16 ties.  The compared-with path
is served first.  Where the path under test differs from it, the
reference's token is teacher-forced (a follow-up request whose prompt is
the shared context plus that token) and the comparison goes on, so EVERY
generated position is compared under a context both paths share.  A
difference is accepted only if an independent teacher-forced forward of
the same weights over that context puts BOTH candidate tokens within
``TIE_ULPS`` bf16 ulps (of the top logit's binade) of its top logit, and
at least ``MIN_EQUAL`` of all positions must be equal outright.  (With
seeded random weights the top two of 100k bf16 logits sit within an ulp
or two of each other every ~20 tokens, so ties are expected there; with
the fitted weights of the serve phase there are none.)
"""
from __future__ import annotations

import argparse
import collections
import faulthandler
import gc
import json
import math
import os
import re
import sys
import threading
import time
import traceback
import urllib.request

TIE_ULPS = 2
MIN_EQUAL = 0.8
WATCHDOG_S = 1150          # the driver allows 1200 s, compiles included
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "fused_update",
           "add_norm", "matmul_rope", "ragged_paged_append_attend",
           "paged_decode", "gmm")


def log(*a):
    print(*a, flush=True)


# -- sizes ---------------------------------------------------------------------

def sizes(rehearse: bool) -> dict:
    if rehearse:
        return dict(
            llama=dict(vocab_size=512, hidden_size=128,
                       intermediate_size=256, num_attention_heads=8,
                       num_key_value_heads=4),
            layers=2, seq=256, batch=2, steps=6, lr=3e-3,
            page=16, max_len=256, max_seqs=8, n_req=8, new=12,
            prompt_lens=[40, 9, 70, 5, 100, 20, 33, 12],
            moe=dict(vocab_size=256, hidden_size=64,
                     num_attention_heads=4, num_key_value_heads=4,
                     moe_intermediate_size=32,
                     shared_expert_intermediate_size=64, num_experts=8,
                     num_experts_per_tok=2, max_position_embeddings=256),
            moe_layers=2, moe_max_seqs=4, moe_new=8,
            moe_prompt_lens=[20, 5, 37, 11, 9, 30, 3, 14],
            moe_dense_budget=8,
            mc_layers=2, mc_seq=64, mc_steps=3, mc_new=8)
    return dict(
        # the shape of the only on-chip training record (BASELINE.md)
        llama=dict(vocab_size=128256, hidden_size=1536,
                   intermediate_size=6144, num_attention_heads=12,
                   num_key_value_heads=4),
        layers=16, seq=8192, batch=2, steps=24, lr=1e-4,
        page=128, max_len=2048, max_seqs=8, n_req=8, new=64,
        prompt_lens=[300, 70, 513, 40, 1000, 150, 260, 90],
        moe=None,                      # deepseek_moe_16b_config() widths
        moe_layers=4, moe_max_seqs=4, moe_new=16,
        moe_prompt_lens=[200, 40, 300, 90, 60, 150, 30, 120],
        moe_dense_budget=4,
        mc_layers=4, mc_seq=4096, mc_steps=4, mc_new=16)


# -- what a compiled program holds --------------------------------------------

def kernel_counts(hlo_text: str) -> dict:
    """Pallas kernels in a compiled program: ``tpu_custom_call``
    instructions, by the stable ``name=`` each ``pallas_call`` carries
    (it lands in the instruction's ``op_name``)."""
    out = collections.Counter()
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.search(r'op_name="([^"]*)"', line)
        op = m.group(1) if m else ""
        hit = [k for k in KERNELS if k in op]
        out[max(hit, key=len) if hit else "unnamed"] += 1
    return dict(out)


def make_audit_watch():
    """A ``CompileWatch`` that also keeps, for every compile it sees,
    what the compiled program holds: an AOT compile of the very same
    call (same HLO — a persistent-cache hit right after the dispatch
    compiled it) gives the text and XLA's own memory analysis."""
    from paddle_tpu.observability import introspection as insp

    class AuditWatch(insp.CompileWatch):
        def __init__(self):
            super().__init__(on_recompile="warn", enable_metrics=False)
            self.audits = []

        def record_compile(self, program, **kw):
            jitfn = kw.get("jitfn")
            if jitfn is not None:
                t0 = time.perf_counter()
                compiled = jitfn.lower(*kw.get("args", ()),
                                       **(kw.get("kwargs") or {})).compile()
                ma = compiled.memory_analysis()
                rec = {"program": program,
                       "first_call_s": round(kw.get("seconds", 0.0), 2),
                       "aot_recompile_s": round(
                           time.perf_counter() - t0, 2),
                       "kernels": kernel_counts(compiled.as_text()),
                       "arg_gb": round(
                           ma.argument_size_in_bytes / 1e9, 3),
                       "temp_gb": round(ma.temp_size_in_bytes / 1e9, 3)}
                self.audits.append(rec)
                log("  compile", json.dumps(rec))
            return super().record_compile(program, **kw)

        def kernels_of(self, program, since=0):
            tot = collections.Counter()
            for a in self.audits[since:]:
                if a["program"] == program:
                    tot.update(a["kernels"])
            return dict(tot)

        def compiles(self):
            return {k: v["compiles"]
                    for k, v in self.snapshot(False)["programs"].items()}

    insp._WATCH = AuditWatch()
    return insp._WATCH


def need_kernels(watch, program, names, on_tpu, since=0):
    got = watch.kernels_of(program, since)
    if not on_tpu:
        log(f"  kernel check skipped off the TPU ({program}: {got})")
        return
    missing = [n for n in names if not any(n in k for k in got)]
    if missing:
        raise AssertionError(
            f"{program}: kernels {missing} absent from the compiled "
            f"program (found {got})")
    log(f"  kernels present in {program}: {got}")


def mem_line(tag):
    import jax
    rows = []
    for d in jax.devices():
        st = d.memory_stats() or {}
        rows.append({"id": d.id,
                     "in_use_gb": round(st.get("bytes_in_use", 0) / 1e9, 3),
                     "peak_gb": round(
                         st.get("peak_bytes_in_use", 0) / 1e9, 3)})
    log(f"  memory[{tag}]", json.dumps(rows))
    return rows


def free_device(tag):
    import jax
    gc.collect()
    jax.clear_caches()
    gc.collect()
    return mem_line(tag)


# -- models --------------------------------------------------------------------

def build_llama(sz, layers, seq, seed):
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    paddle.seed(seed)
    cfg = LlamaConfig(num_hidden_layers=layers,
                      max_position_embeddings=seq, recompute=True,
                      recompute_granularity="core_attn", **sz["llama"])
    model = LlamaForCausalLM(cfg)
    return paddle.amp.decorate(model, level="O2", dtype="bfloat16"), cfg


def build_moe(sz, seed):
    import paddle_tpu as paddle
    from paddle_tpu.models.qwen2_moe import (Qwen2MoeConfig,
                                             Qwen2MoeForCausalLM,
                                             deepseek_moe_16b_config)
    paddle.seed(seed)
    cfg = deepseek_moe_16b_config() if sz["moe"] is None \
        else Qwen2MoeConfig(**sz["moe"])
    cfg.num_hidden_layers = sz["moe_layers"]
    model = Qwen2MoeForCausalLM(cfg)
    return paddle.amp.decorate(model, level="O2", dtype="bfloat16"), cfg


def train_batch(vocab, batch, seq, seed):
    import numpy as np
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, vocab, size=(batch, seq), dtype=np.int32)
    labels = np.concatenate(
        [ids[:, 1:], np.full((batch, 1), -100, np.int32)], axis=1)
    return {"input_ids": ids, "labels": labels}


def loss_fn(m, b):
    return m(b["input_ids"], labels=b["labels"])      # fused linear+CE


def run_steps(step, batch, n):
    """n optimizer steps on one batch; host-clock seconds per step, each
    ending in ``device_get`` of the loss."""
    import jax
    import numpy as np
    losses, times = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        loss = float(np.asarray(jax.device_get(step(batch))))
        times.append(time.perf_counter() - t0)
        losses.append(loss)
        if not np.isfinite(loss):
            raise AssertionError(f"non-finite loss {loss} at step "
                                 f"{len(losses)}")
    return losses, times


# -- the bf16 tie rule ---------------------------------------------------------

class TieJudge:
    """Teacher-forced logits from the model's own forward (one jitted
    program, context padded to a fixed length) — built lazily, only
    when two streams differ."""

    def __init__(self, model, pad_to):
        self.model, self.pad_to, self._fn = model, pad_to, None

    def logits_after(self, context):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from paddle_tpu.jit.train import traced_forward
        if self._fn is None:
            model = self.model

            def fwd(params, ids, last):
                out = traced_forward(
                    model, lambda m, b: m(b["input_ids"]), params,
                    {"input_ids": ids}, jax.random.key(0))
                return out[0, last].astype(jnp.float32)
            self._fn = jax.jit(fwd)
            self._params = model.raw_state_dict()
        ids = np.zeros((1, self.pad_to), np.int32)
        ids[0, :len(context)] = context
        return np.asarray(jax.device_get(self._fn(
            self._params, ids, np.int32(len(context) - 1))))


def bf16_ulp(x):
    """Spacing of bf16 values in the binade of ``x`` (8 significant
    bits)."""
    return 2.0 ** (math.floor(math.log2(max(abs(x), 2.0 ** -100))) - 7)


def split_at_difference(todo, diffs):
    """One round of the comparison.  ``todo``: (request, context, got,
    want) with ``got`` generated from ``context``.  Counts the equal
    positions, records each stream's FIRST difference in ``diffs`` and
    returns the follow-ups (request, context', want_rest): the shared
    context extended by the reference's token, and what the reference
    went on to say."""
    equal, follow = 0, []
    for i, ctx, a, b in todo:
        if len(a) != len(b):
            raise AssertionError(f"request {i} produced {len(a)} tokens, "
                                 f"the reference {len(b)}")
        j = next((j for j in range(len(a)) if a[j] != b[j]), None)
        if j is None:
            equal += len(a)
            continue
        equal += j
        diffs.append({"request": i, "context": ctx + b[:j],
                      "got": a[j], "want": b[j]})
        if j + 1 < len(b):
            follow.append((i, ctx + b[:j + 1], b[j + 1:]))
    return equal, follow


def judge_ties(what, cmp, judge):
    """The stated tie rule (module docstring) over a finished
    comparison ``cmp`` = {"equal", "total", "diffs"}.  Every difference
    is printed before any of them fails the run."""
    bad = []
    for d in cmp["diffs"]:
        logits = judge.logits_after(d["context"])
        top = float(logits.max())
        la, lb = float(logits[d["got"]]), float(logits[d["want"]])
        gap = (top - min(la, lb)) / bf16_ulp(top)
        log(f"  {what}: request {d['request']} position "
            f"{len(d['context'])}: {d['got']} (logit {la:.4f}) vs "
            f"{d['want']} ({lb:.4f}), top {top:.4f}: {gap:.2f} bf16 ulps")
        if gap > TIE_ULPS:
            bad.append(d["request"])
    n = len(cmp["diffs"])
    log(f"  {what}: all {cmp['total']} positions compared under a shared "
        f"context: {cmp['equal']} equal, {n} differ")
    if cmp["equal"] + n != cmp["total"]:
        raise AssertionError(f"{what}: {cmp['total'] - cmp['equal'] - n} "
                             f"positions were never compared")
    if bad:
        raise AssertionError(f"{what}: differences in requests {bad} are "
                             f"not bf16 ties (> {TIE_ULPS} ulps)")
    if cmp["equal"] < MIN_EQUAL * cmp["total"]:
        raise AssertionError(f"{what}: fewer than {MIN_EQUAL:.0%} of the "
                             f"positions are equal")


# -- serving through the HTTP front end ---------------------------------------

def http_stream(url, rid, prompt, max_tokens, on_tokens=None,
                timeout=300.0):
    body = json.dumps({"id": rid, "prompt": [int(t) for t in prompt],
                       "max_tokens": max_tokens, "stream": True}).encode()
    req = urllib.request.Request(
        url + "/v1/completions", data=body,
        headers={"Content-Type": "application/json"})
    toks, t0, ttft, final = [], time.perf_counter(), None, None
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        for line in resp:
            line = line.strip()
            if not line:
                continue
            ev = json.loads(line)
            if ev.get("tokens"):
                if ttft is None:
                    ttft = time.perf_counter() - t0
                toks.extend(ev["tokens"])
                if on_tokens is not None:
                    on_tokens(len(toks))
            if ev.get("done"):
                final = ev
    return {"tokens": toks, "ttft_s": ttft,
            "total_s": time.perf_counter() - t0, "final": final}


def http_batch(fe, tag, prompts, news, waves=1):
    """Stream ``prompts[i]`` for ``news[i]`` tokens each, concurrently,
    in ``waves`` waves: a wave starts once a request of the one before
    has streamed a few tokens, so prefill chunks ride steps that also
    decode.  Fails on a request that does not finish."""
    results = [None] * len(prompts)
    gates = [threading.Event() for _ in prompts]

    def client(i):
        try:
            results[i] = http_stream(
                fe.url, f"{tag}-{i}", prompts[i], news[i],
                on_tokens=lambda n: n >= 4 and gates[i].set())
        finally:
            gates[i].set()          # never leave a later wave waiting
    per = max(-(-len(prompts) // waves), 1)
    threads = []
    for first in range(0, len(prompts), per):
        if first:
            gates[first - per].wait(timeout=300)
        for i in range(first, min(first + per, len(prompts))):
            th = threading.Thread(target=client, args=(i,))
            th.start()
            threads.append(th)
    for th in threads:
        while th.is_alive():
            th.join(timeout=1.0)
            if not fe._loop_thread.is_alive():
                raise AssertionError(
                    f"[{tag}] the scheduling loop thread died (its "
                    f"traceback is on stderr)")
    for i, r in enumerate(results):
        ok = r is not None and r["final"] is not None and \
            r["final"].get("state") == "finished" and \
            len(r["tokens"]) == news[i]
        if not ok:
            raise AssertionError(f"[{tag}] request {i} did not finish: "
                                 f"{r}")
    return results


def serve(tag, model, prompts, new, watch, on_tpu, *, warm_prompt,
          must_hold=(), reference=None, **engine_kw):
    """Serve ``prompts`` through start_http_frontend(Scheduler(
    LLMEngine(model))): warm every program, then send the requests in
    three waves.  With ``reference`` (the token lists of the path this
    one is compared with) every difference is followed up on the same
    engine (``split_at_difference``) until all positions are compared.
    Returns (token lists, the comparison or None).  Fails on an
    unfinished request, a compile after warm-up, a dirty shutdown, or a
    missing kernel."""
    from paddle_tpu.inference.engine import LLMEngine
    from paddle_tpu.serving.scheduler import Scheduler
    from paddle_tpu.serving.server import start_http_frontend

    since = len(watch.audits)
    t0 = time.perf_counter()
    engine = LLMEngine(model, **engine_kw)
    sched = Scheduler(engine, chunked_prefill=True)
    fe = start_http_frontend(sched, request_timeout=900.0)
    log(f"  [{tag}] engine up in {time.perf_counter() - t0:.1f}s at "
        f"{fe.url}")
    cmp = None
    try:
        # warm-up: a prompt longer than one page, and a budget that
        # walks every window bucket (8, 4, 2) down to the single step
        k = int(engine_kw.get("steps_per_sync", 1))
        t0 = time.perf_counter()
        w = http_stream(fe.url, f"{tag}-warm", warm_prompt, 2 * k,
                        timeout=900.0)
        log(f"  [{tag}] warm-up request: {len(w['tokens'])} tokens in "
            f"{time.perf_counter() - t0:.1f}s (compiles included)")
        warm = watch.compiles()

        t0 = time.perf_counter()
        results = http_batch(fe, tag, prompts, [new] * len(prompts),
                             waves=3)
        wall = time.perf_counter() - t0
        tokens = [r["tokens"] for r in results]
        log(f"  [{tag}] {len(prompts)} requests, "
            f"{sum(map(len, tokens))} tokens in {wall:.2f}s; ttft_s "
            f"{[round(r['ttft_s'], 3) for r in results]}, total_s "
            f"{[round(r['total_s'], 3) for r in results]}")
        if reference is not None:
            cmp = {"equal": 0, "total": sum(map(len, reference)),
                   "diffs": []}
            todo = [(i, list(p), a, list(b)) for i, (p, a, b)
                    in enumerate(zip(prompts, tokens, reference))]
            rounds = 0
            while todo:
                equal, follow = split_at_difference(todo, cmp["diffs"])
                cmp["equal"] += equal
                if not follow:
                    break
                rounds += 1
                outs = http_batch(fe, f"{tag}-follow{rounds}",
                                  [c for _, c, _ in follow],
                                  [len(b) for _, _, b in follow])
                todo = [(i, c, o["tokens"], b)
                        for (i, c, b), o in zip(follow, outs)]
            log(f"  [{tag}] {len(cmp['diffs'])} differences from the "
                f"reference followed up in {rounds} rounds of "
                f"teacher-forced requests")
        after = watch.compiles()
        if after != warm:
            raise AssertionError(f"[{tag}] compiles after warm-up: "
                                 f"{warm} -> {after}")
        log(f"  [{tag}] zero recompiles after warm-up: {after}")
        counts = {k: v for k, v in sorted(sched.metrics_snapshot().items())
                  if isinstance(v, (int, float))}
        log(f"  [{tag}] scheduler: {json.dumps(counts)[:600]}")
    except BaseException:
        fe.kill()           # a draining shutdown could wait on a dead step
        raise
    fe.shutdown(drain=True)
    if fe._loop_thread.is_alive() or fe._http_thread.is_alive() \
            or sched.busy():
        raise AssertionError(f"[{tag}] front end or scheduler did not "
                             f"shut down cleanly")
    for program, names in must_hold:
        need_kernels(watch, program, names, on_tpu, since)
    mem_line(tag)
    return tokens, cmp, engine


# -- phases (one chip) ---------------------------------------------------------

def phase_train(sz, seed, watch, on_tpu):
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.jit.train import CompiledTrainStep
    from paddle_tpu.ops.pallas import fused_train
    log("== phase train: CompiledTrainStep, LlamaForCausalLM "
        f"{sz['llama']} x {sz['layers']} layers, seq {sz['seq']}, "
        f"batch {sz['batch']}, AdamW + global-norm clip")
    model, cfg = build_llama(sz, sz["layers"], sz["seq"], seed)
    opt = paddle.optimizer.AdamW(
        learning_rate=sz["lr"], parameters=model.parameters(),
        grad_clip=paddle.ClipGradByGlobalNorm(1.0))
    step = CompiledTrainStep(model, loss_fn, opt, seed=seed)
    log(f"  fused_train.kernels_active() = {fused_train.kernels_active()}")
    batch = train_batch(cfg.vocab_size, sz["batch"], sz["seq"], seed)
    losses, times = run_steps(step, batch, sz["steps"])
    init = float(np.log(cfg.vocab_size))
    log(f"  losses {[round(x, 4) for x in losses]}")
    log(f"  first call {times[0]:.1f}s (compile included); steady step "
        f"median {float(np.median(times[2:])):.4f}s over "
        f"{len(times) - 2} steps; tokens/step "
        f"{sz['batch'] * sz['seq']}")
    if abs(losses[0] - init) > 0.5:
        raise AssertionError(f"first loss {losses[0]} is not near "
                             f"ln(vocab) = {init:.2f}")
    if not losses[-1] < losses[0] - 0.5:
        raise AssertionError(f"loss did not fall: {losses[0]} -> "
                             f"{losses[-1]}")
    if step.step_compiles() != 1:
        raise AssertionError(f"{step.step_compiles()} step programs")
    need_kernels(watch, "train.compiled_step",
                 ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                  "fused_update", "add_norm", "matmul_rope"), on_tpu)
    mem_line("train")
    step.sync_to_model()           # the fitted weights go on to serve
    step.state = None
    del step, opt
    free_device("after train")
    return model, cfg, batch["input_ids"], losses[-1]


def phase_serve(sz, model, cfg, memorised, final_loss, watch, on_tpu):
    import jax.numpy as jnp
    from paddle_tpu.runtime import device as rdev
    log("== phase serve: start_http_frontend(Scheduler(LLMEngine)) on the "
        f"fitted weights, bf16 pools, page {sz['page']}, max_len "
        f"{sz['max_len']}, steps_per_sync 8")
    rows = memorised.shape[0]
    prompts = [memorised[i % rows, :n].tolist()
               for i, n in enumerate(sz["prompt_lens"][:sz["n_req"]])]
    kw = dict(max_seqs=sz["max_seqs"], max_len=sz["max_len"],
              page_size=sz["page"], dtype=jnp.bfloat16, steps_per_sync=8)
    warm_prompt = memorised[0, :sz["page"] + 7].tolist()

    # first the path the kernels are compared with — the engine's own
    # jnp reference attention: the platform switch every kernel site
    # reads says "no TPU" while these programs trace, and nothing else
    # changes
    since = len(watch.audits)
    real = rdev.is_compiled_with_tpu
    rdev.is_compiled_with_tpu = lambda: False
    try:
        want, _, engine = serve("reference", model, prompts, sz["new"],
                                watch, on_tpu, warm_prompt=warm_prompt,
                                **kw)
    finally:
        rdev.is_compiled_with_tpu = real
    held = [a for a in watch.audits[since:] if a["kernels"]]
    if held:
        raise AssertionError(f"the reference engine's programs hold "
                             f"Pallas kernels: {held}")
    del engine
    free_device("after reference engine")

    got, cmp, engine = serve(
        "kernel", model, prompts, sz["new"], watch, on_tpu,
        warm_prompt=warm_prompt, reference=want,
        must_hold=(("engine.mixed_step", ("ragged_paged_append_attend",)),
                   ("engine.mixed_window",
                    ("ragged_paged_append_attend",))), **kw)
    del engine
    free_device("after kernel engine")

    judge = TieJudge(model, sz["max_len"])
    judge_ties("kernel vs jnp reference", cmp, judge)
    # train -> serve, end to end: the served continuation of a memorised
    # prefix is the memorised batch wherever the fit got that far
    hit = tot = 0
    for i, n in enumerate(sz["prompt_lens"][:sz["n_req"]]):
        exp = memorised[i % rows, n:n + sz["new"]].tolist()
        hit += sum(int(a == b) for a, b in zip(got[i], exp))
        tot += len(exp)
    log(f"  served continuation equals the memorised batch at {hit} of "
        f"{tot} positions (final train loss {final_loss:.4f})")
    if final_loss < 0.1 and hit < 0.9 * tot:
        raise AssertionError("the served model does not reproduce the "
                             "batch it was fitted on")
    del judge


def phase_moe(sz, seed, watch, on_tpu):
    import jax.numpy as jnp
    import numpy as np
    log("== phase moe: Qwen2MoeForCausalLM at deepseek_moe_16b widths, "
        f"{sz['moe_layers']} layers, grouped vs dense dispatch")
    model, cfg = build_moe(sz, seed)
    rng = np.random.default_rng(seed + 1)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
               for n in sz["moe_prompt_lens"]]
    max_len = min(sz["max_len"], cfg.max_position_embeddings)
    kw = dict(max_seqs=sz["moe_max_seqs"], max_len=max_len,
              page_size=sz["page"], dtype=jnp.bfloat16, steps_per_sync=8)
    warm_prompt = prompts[0][:sz["page"] // 2] * 3
    # the dense oracle first.  It gathers one expert matrix per routed
    # row, so its flat batch is kept short; tokens do not depend on the
    # batching
    since = len(watch.audits)
    want, _, engine = serve(
        "dense", model, prompts, sz["moe_new"], watch, on_tpu,
        warm_prompt=warm_prompt, moe_dispatch="dense",
        prefill_token_budget=sz["moe_dense_budget"], **kw)
    held = [a for a in watch.audits[since:] if "gmm" in a["kernels"]]
    if held:
        raise AssertionError(f"the dense engine holds grouped-matmul "
                             f"kernels: {held}")
    del engine
    free_device("after dense engine")
    got, cmp, engine = serve(
        "grouped", model, prompts, sz["moe_new"], watch, on_tpu,
        warm_prompt=warm_prompt, moe_dispatch="grouped", reference=want,
        must_hold=(("engine.mixed_step",
                    ("ragged_paged_append_attend", "gmm")),
                   ("engine.mixed_window",
                    ("ragged_paged_append_attend", "gmm"))), **kw)
    del engine
    free_device("after grouped engine")
    judge_ties("grouped vs dense", cmp, TieJudge(model, max_len))
    del model
    free_device("after moe")


# -- the multi-chip paths (--chips 4) -----------------------------------------

def shard_report(tag, arr):
    """Per-device shards of one array; fails if it is not spread."""
    rows = [{"device": s.device.id, "shape": list(s.data.shape)}
            for s in arr.addressable_shards]
    log(f"  shards[{tag}] global {list(arr.shape)}: {json.dumps(rows)}")
    devs = {r["device"] for r in rows}
    whole = all(r["shape"] == list(arr.shape) for r in rows)
    if len(devs) < 4 or whole:
        raise AssertionError(f"{tag} is not spread over four devices")


def spread_check(tag):
    rows = mem_line(tag)
    import jax
    if jax.devices()[0].platform != "tpu":
        return                     # the CPU backend reports no stats
    idle = [r["id"] for r in rows[1:4] if r["in_use_gb"] <= 0.0]
    if idle:
        raise AssertionError(f"{tag}: devices {idle} hold nothing — "
                             f"everything sits on the first")


def phase_multichip(sz, seed, watch, on_tpu):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.topology import serving_mesh
    from paddle_tpu.distributed.trainer import ShardedTrainStep
    from paddle_tpu.jit.train import CompiledTrainStep
    from paddle_tpu.ops.pallas import fused_train

    if len(jax.devices()) < 4:
        raise AssertionError(f"--chips 4 needs four devices, JAX has "
                             f"{len(jax.devices())}")
    L, seq, n = sz["mc_layers"], sz["mc_seq"], sz["mc_steps"]
    log(f"== phase sharded-train: ShardedTrainStep stage 3 on sharding=2 "
        f"x mp=2 vs CompiledTrainStep on one chip; {sz['llama']} x {L} "
        f"layers, seq {seq}, batch 2")

    def make(step_cls, **kw):
        model, cfg = build_llama(sz, L, seq, seed)
        opt = paddle.optimizer.AdamW(
            learning_rate=sz["lr"], parameters=model.parameters(),
            grad_clip=paddle.ClipGradByGlobalNorm(1.0))
        return step_cls(model, loss_fn, opt, seed=seed, **kw), cfg

    step, cfg = make(CompiledTrainStep)
    batch = train_batch(cfg.vocab_size, 2, seq, seed)
    one, t_one = run_steps(step, batch, n)
    log(f"  one chip: losses {[round(x, 4) for x in one]}, first call "
        f"{t_one[0]:.1f}s, then {[round(t, 3) for t in t_one[1:]]}")
    step.state = None
    del step
    free_device("after one-chip train")

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 2,
                               "pp_degree": 1, "sharding_degree": 2,
                               "sep_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    step, cfg = make(ShardedTrainStep, stage=3)
    log(f"  under the mesh fused_train.kernels_active() = "
        f"{fused_train.kernels_active()} (by design: the sharded step "
        f"takes the reference update math)")
    four, t_four = run_steps(step, batch, n)
    log(f"  four chips: losses {[round(x, 4) for x in four]}, first "
        f"call {t_four[0]:.1f}s, then {[round(t, 3) for t in t_four[1:]]}")
    big = max(jax.tree_util.tree_leaves(step.state["params"]),
              key=lambda a: a.size)
    shard_report("largest parameter (stage 3)", big)
    spread_check("sharded train")
    tol = 0.01
    for i, (a, b) in enumerate(zip(one, four)):
        if abs(a - b) > tol * abs(a):
            raise AssertionError(f"step {i}: loss {b} on four chips vs "
                                 f"{a} on one (tolerance {tol:.0%})")
    log(f"  losses agree within {tol:.0%} at every step")
    step.state = None
    del step
    fleet.reset()
    free_device("after sharded train")

    log("== phase tp-serve: LLMEngine(mesh=serving_mesh(4), "
        "tp_axis='tp') vs tp=1, same requests")
    model, cfg = build_llama(sz, L, sz["max_len"], seed)
    rng = np.random.default_rng(seed + 2)
    prompts = [rng.integers(0, cfg.vocab_size, size=k).tolist()
               for k in sz["prompt_lens"]]
    kw = dict(max_seqs=sz["max_seqs"], max_len=sz["max_len"],
              page_size=sz["page"], dtype=jnp.bfloat16, steps_per_sync=8)
    held = (("engine.mixed_step", ("ragged_paged_append_attend",)),)
    warm_prompt = prompts[0][:sz["page"] // 2] * 3
    want, _, engine = serve("tp1", model, prompts, sz["mc_new"], watch,
                            on_tpu, warm_prompt=warm_prompt,
                            must_hold=held, **kw)
    del engine
    free_device("after tp=1 engine")
    got, cmp, engine = serve("tp4", model, prompts, sz["mc_new"], watch,
                             on_tpu, warm_prompt=warm_prompt,
                             must_hold=held, reference=want,
                             mesh=serving_mesh(4), tp_axis="tp", **kw)
    shard_report("q_proj stack", engine._stack[1])
    shard_report("KV pool", engine.cache.k_pages)
    spread_check("tp=4 engine")
    del engine
    free_device("after tp=4 engine")
    judge_ties("tp=4 vs tp=1", cmp, TieJudge(model, sz["max_len"]))


# -- main ----------------------------------------------------------------------

def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever platform JAX finds")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.rehearse and args.chips == 4 and \
            os.environ.get("JAX_PLATFORMS") == "cpu":
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()

    import jax
    from paddle_tpu.core.build import load_native
    from paddle_tpu.runtime.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.rehearse:
        print(f"chip_smoke: JAX found platform {dev.platform!r}, not a "
              f"TPU — refusing to run (use --rehearse for a sandbox "
              f"rehearsal)", file=sys.stderr)
        return 2
    log(f"chip_smoke: {json.dumps(device)}, jax {jax.__version__}, "
        f"compile cache at {cache_dir} "
        f"({len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0}"
        f" entries), native library built: {load_native() is not None}")
    sz = sizes(args.rehearse)
    watch = make_audit_watch()
    # a hang (a kernel that never signals, a wedged thread) must end in
    # stacks on stderr and a non-zero exit inside the caller's limit,
    # not in silence
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    t_all = time.perf_counter()
    try:
        if args.chips == 4:
            phase_multichip(sz, args.seed, watch, on_tpu)
        else:
            t0 = time.perf_counter()
            fitted = phase_train(sz, args.seed, watch, on_tpu)
            log(f"  phase train: {time.perf_counter() - t0:.1f}s")
            t0 = time.perf_counter()
            phase_serve(sz, *fitted, watch, on_tpu)
            log(f"  phase serve: {time.perf_counter() - t0:.1f}s")
            del fitted
            free_device("after serve")
            t0 = time.perf_counter()
            phase_moe(sz, args.seed, watch, on_tpu)
            log(f"  phase moe: {time.perf_counter() - t0:.1f}s")
    except Exception:       # the boundary: report, then fail the run
        traceback.print_exc()
        sys.stderr.flush()
        log(f"chip_smoke: FAILED after {time.perf_counter() - t_all:.1f}s")
        print(json.dumps({"ok": False, "device": device}), flush=True)
        return 1
    log(f"chip_smoke: all phases passed in "
        f"{time.perf_counter() - t_all:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
