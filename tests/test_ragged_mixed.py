"""Ragged unified step: one kernel, one compiled program for the
whole mixed prefill+decode batch (ISSUE 12).

Contracts under test:
* tokens equal to references that are not serving programs — the dense
  jitted ``model.generate()`` and the benchmark's float32
  ``perfbench.reference`` — on every path: plain greedy, int8 KV,
  full/partial prefix-cache hits, mid-stream preempt→resume (swap-in
  AND recompute), mid-prefill suspend/resume, migration export/import
  — for synchronous ``add_request`` and deferred ``begin_request``
  admission alike;
* ``mixed_compiles()`` stays flat across ARBITRARY batch mixes (the
  per-sequence descriptors are traced scalars: one XLA program);
* the host-side slot→row compaction: retired slots leave the mixed
  batch immediately (``mixed_batch_decode_slots`` gauge tracks LIVE
  rows, not allocated slots);
* scheduler ``chunked_prefill`` admission: tokens identical to the
  default scheduler, first-token bookkeeping moves to delivery, a
  mid-prefill request migrates policy-only, and a runtime
  ``prefill_token_budget`` of 0 cannot livelock the engine;
* (the Pallas kernel's own parity with the jnp reference is an
  interpret-mode test beside the other paged kernels,
  ``test_inference.py::TestPagedAttentionKernel``);
* a tier-1 budget guard keeps this module's fast footprint flat.

Everything runs JAX_PLATFORMS=cpu on the tiny llama config.
"""
import dataclasses
import re
from pathlib import Path

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import engine as E
from paddle_tpu.inference.engine import LLMEngine
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.models.qwen2_moe import (Qwen2MoeForCausalLM,
                                         qwen2_moe_tiny_config)
from paddle_tpu.serving import Scheduler

P = 8
PROMPTS = [[5, 9, 2, 14],                         # sub-page
           list(range(1, 20)),                    # 2.5 pages
           [7] * 33,                              # page-crossing
           [3, 1, 4, 1, 5, 9, 2, 6],              # exactly one page
           list(range(40, 51))]                   # 1.5 pages


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = LlamaForCausalLM(llama_tiny_config())
    m.eval()
    return m


def _drain(eng):
    while eng.has_work():
        eng.step()


def _mk(model, **kw):
    kw.setdefault("max_seqs", 8)
    kw.setdefault("max_len", 64)
    kw.setdefault("page_size", P)
    kw.setdefault("n_pages", 64)
    return LLMEngine(model, **kw)


def _serve(model, prompts, max_new=6, admit="add", **kw):
    eng = _mk(model, **kw)
    for i, p in enumerate(prompts):
        if admit == "begin":
            eng.begin_request(f"r{i}", p, max_new_tokens=max_new)
        else:
            eng.add_request(f"r{i}", p, max_new_tokens=max_new)
    _drain(eng)
    return [eng.result(f"r{i}") for i in range(len(prompts))], eng


def _generate(model, prompts, max_new=6):
    """The dense jitted ``generate()``: no pages, no batching."""
    return [np.asarray(model.generate(
        paddle.to_tensor(np.asarray(p, np.int32)[None]),
        max_new_tokens=max_new)[0].numpy())[0].tolist() for p in prompts]


def _judge(model, arch, prompts, served):
    """Served tokens against the benchmark's float32 reference
    (``perfbench.reference``: no kernels, no cache, no batching), read
    from the model's own weights and teacher-forced on what was
    served: each token the reference's argmax or a bf16 tie of it."""
    from perfbench import reference
    cfg = dataclasses.asdict(model.config)
    params = reference.canonical(arch, model.raw_state_dict(),
                                 cfg["num_hidden_layers"])
    for p, toks in zip(prompts, served):
        ref = reference.logits(params, cfg, list(p) + toks[:-1])
        verdict = reference.judge_served(ref, len(p), toks)
        assert verdict["ok"] and verdict["positions"] == len(toks), verdict


# -- the step loop against references that are not serving programs ------------
def test_served_tokens_match_generate_fp(model):
    """Acceptance: the ONE mixed-batch program serves the dense
    ``generate()``'s tokens, for both synchronous and deferred
    (chunk-riding) admission."""
    want = _generate(model, PROMPTS)
    got, _ = _serve(model, PROMPTS)
    assert got == want
    deferred, _ = _serve(model, PROMPTS, admit="begin")
    assert deferred == want


def test_int8_kv_served_tokens_pass_the_float32_judge(model):
    """int8 KV pages + scale rows ride the same program.  No dense
    reference quantizes its cache, so the stream is held to the float32
    reference teacher-forced on it (a rounding may move a token between
    tied logits, never off them), and the two admissions — pages
    quantized by the chunk program or inside mixed steps — agree."""
    got, _ = _serve(model, PROMPTS, kv_dtype="int8")
    _judge(model, "llama", PROMPTS, got)
    deferred, _ = _serve(model, PROMPTS, admit="begin",
                         kv_dtype="int8")
    assert deferred == got


@pytest.mark.parametrize("arch", ["llama", "qwen2_moe"])
def test_begin_request_tokens_pass_the_float32_judge(model, arch):
    """What the benchmark's ``correct`` compares on the chip, held here
    at tiny sizes: tokens served through ``begin_request`` + ``step()``
    — chunks packed beside decode rows, then 4-step windows — against
    ``perfbench.reference`` (the MoE as that reference knows it: no
    projection bias, no shared-expert gate)."""
    if arch == "qwen2_moe":
        paddle.seed(0)
        model = Qwen2MoeForCausalLM(dataclasses.replace(
            qwen2_moe_tiny_config(), attention_bias=False,
            use_shared_expert_gate=False))
        model.eval()
    got, eng = _serve(model, PROMPTS, max_new=9, admit="begin",
                      steps_per_sync=4)
    assert eng.metrics_snapshot()["window_compiles"] >= 1
    _judge(model, arch, PROMPTS, got)


def test_multi_step_windows_match(model):
    """steps_per_sync > 1: a pure-decode window runs several decode
    steps per host sync on the device — the token stream must equal
    the per-token engine's."""
    want, _ = _serve(model, PROMPTS[:3], max_new=9)
    got, _ = _serve(model, PROMPTS[:3], max_new=9, steps_per_sync=4)
    assert got == want


def test_mixed_compiles_one_across_mixes(model):
    """Acceptance: descriptors are traced scalars, so ONE compiled
    program serves every batch mix — warm with one shape, then throw
    arbitrary prefill/decode mixes at it and assert zero new
    compiles (delta form: the jit cache is process-global)."""
    eng = _mk(model)
    eng.begin_request("w", [1, 2, 3], max_new_tokens=2)
    _drain(eng)
    base = LLMEngine.mixed_compiles()
    assert base >= 1
    rng = np.random.default_rng(0)
    eng2 = _mk(model)
    for i in range(6):                       # staggered admissions:
        plen = int(rng.integers(1, 40))      # every step sees a new
        eng2.begin_request(f"m{i}",          # decode/prefill mix
                           rng.integers(1, 200, plen).tolist(),
                           max_new_tokens=int(rng.integers(1, 8)))
        eng2.step()
    _drain(eng2)
    assert LLMEngine.mixed_compiles() == base, \
        "a batch-mix change recompiled the unified program"
    assert eng2.metrics_snapshot()["mixed_compiles"] == base


def test_prefix_cache_parity(model):
    """Full-hit and partial-hit prefix-cache prefills serve the dense
    ``generate()``'s tokens (which shares nothing) with the hit
    accounting the page arithmetic gives."""
    sys_p = list(range(1, 17))               # 2 full shared pages
    prompts = [sys_p + [30 + i] for i in range(3)] + [sys_p]
    want = _generate(model, prompts)
    # r0 misses; r1, r2 hit both shared pages; r3 IS the shared prefix,
    # and the page holding a prompt's last token always recomputes
    hits = 2 * P + 2 * P + P
    got, eu = _serve(model, prompts)
    assert got == want
    assert eu.prefix_stats["hit_tokens"] == hits
    # deferred admission consults the prefix cache at begin_request
    # time: stage r0 to completion (registering the shared pages),
    # then let the rest ride the mixed step — full (r3) and partial
    # (r1, r2) hits, the same accounting
    ed = _mk(model)
    ed.begin_request("r0", prompts[0], max_new_tokens=6)
    _drain(ed)
    for i in (1, 2, 3):
        ed.begin_request(f"r{i}", prompts[i], max_new_tokens=6)
    _drain(ed)
    assert [ed.result(f"r{i}") for i in range(4)] == want
    assert ed.prefix_stats["hit_tokens"] == hits


# -- preemption / migration -----------------------------------------------------
def _interrupted(model, swap_pages, expect_path):
    prompt, n = PROMPTS[1], 8
    want, _ = _serve(model, [prompt], max_new=n)
    eng = _mk(model, swap_pool_pages=swap_pages)
    eng.add_request("r", prompt, max_new_tokens=n)
    for _ in range(3):
        eng.step()
    eng.suspend("r")
    path = eng.resume("r")
    assert path == expect_path
    _drain(eng)
    assert eng.result("r") == want[0]


def test_preempt_resume_swap_parity(model):
    """Mid-decode suspend→resume through the host swap pool: the
    restored slot re-enters the mixed batch bit-identically."""
    _interrupted(model, swap_pages=32, expect_path="swap_in")


def test_preempt_resume_recompute_parity(model):
    """Swap pool disabled: resume replays prefill + decoded tokens
    through the recompute path — same tokens."""
    _interrupted(model, swap_pages=0, expect_path="recompute")


def test_mid_prefill_suspend_resume(model):
    """A deferred request suspended BEFORE its first token holds no
    computed state worth swapping: suspend releases its pages
    (returns False — nothing swapped), resume restarts prefill via
    recompute, and the final tokens match an uninterrupted run."""
    prompt = PROMPTS[2]
    want, _ = _serve(model, [prompt], max_new=5)
    eng = _mk(model)
    eng.begin_request("r", prompt, max_new_tokens=5)
    eng.step()                               # first chunk only
    assert not eng.requests["r"].out
    assert eng.suspend("r") is False
    assert eng.resume("r") == "recompute"
    _drain(eng)
    assert eng.result("r") == want[0]


def test_migration_parity(model):
    """Export mid-decode from one engine, import into a
    second: the continuation produces the uninterrupted stream."""
    prompt, n = PROMPTS[1], 8
    want, _ = _serve(model, [prompt], max_new=n)
    src = _mk(model)
    src.add_request("r", prompt, max_new_tokens=n)
    for _ in range(3):
        src.step()
    src.suspend("r")
    pkg = src.export_request("r")
    dst = _mk(model)
    dst.import_request(pkg)
    dst.resume("r")
    _drain(dst)
    assert dst.result("r") == want[0]


# -- host-side compaction + occupancy gauges -----------------------------------
def test_compaction_and_interleave_gauges(model):
    """Retired slots leave the mixed batch immediately: after the
    short request finishes, the next step's batch holds exactly the
    LIVE rows (no padded/masked remnant), and the interleave gauges
    report the decode/prefill split of the last step."""
    eng = _mk(model)
    eng.add_request("short", [1, 2, 3], max_new_tokens=1)
    eng.add_request("long", [4, 5, 6], max_new_tokens=6)
    _drain(eng)
    snap = eng.metrics_snapshot()
    assert snap["mixed_batch_decode_slots"] == 1    # last step: long only
    eng.begin_request("tail", list(range(1, 18)), max_new_tokens=2)
    eng.step()                               # pure-prefill step
    snap = eng.metrics_snapshot()
    assert snap["mixed_batch_decode_slots"] == 0
    assert snap["mixed_batch_prefill_tokens"] > 0
    _drain(eng)
    assert len(eng.result("tail")) == 2


def test_runtime_budget_zero_no_livelock(model):
    """Lowering the RUNTIME prefill budget to 0 with only prefill
    pending must not livelock: the engine guarantees one page of
    forward progress when no decode work exists."""
    eng = _mk(model)
    eng.begin_request("r", list(range(1, 20)), max_new_tokens=2)
    eng.prefill_token_budget = 0
    for _ in range(40):
        if not eng.has_work():
            break
        eng.step()
    assert not eng.has_work()
    assert len(eng.result("r")) == 2


# -- scheduler chunk-level admission -------------------------------------------
def test_sched_chunked_prefill_parity(model):
    """chunked_prefill=True: prompts ride the mixed step instead of
    admission-time prefill — token streams stay identical to the
    default scheduler, and TTFT bookkeeping moves to delivery
    (first_token lands AFTER admitted, from a step)."""
    def run(**kw):
        s = Scheduler(_mk(model, max_seqs=4), max_queue=8, **kw)
        for i, p in enumerate(PROMPTS):
            s.submit(f"r{i}", p, max_new_tokens=6)
        s.run_until_idle(max_steps=400)
        return [s.result(f"r{i}") for i in range(len(PROMPTS))], s

    want, _ = run()
    got, sc = run(chunked_prefill=True, decode_tpot_slo=10.0)
    assert got == want
    tl = sc.request_timeline("r2")
    names = [e["event"] for e in tl["timeline"]]
    assert names.index("first_token") > names.index("admitted")
    assert tl["ttft"] is not None
    # generous SLO: additive recovery keeps the budget at its ceiling
    assert sc.engine.prefill_token_budget == sc.engine._pf_budget_static


def test_sched_slo_halves_budget(model):
    """An impossible decode SLO drives the AIMD controller to the
    floor (budget 1) without corrupting the token stream."""
    want, _ = _serve(model, PROMPTS[:2], max_new=4, max_seqs=4)
    s = Scheduler(_mk(model, max_seqs=4), max_queue=8,
                  chunked_prefill=True, decode_tpot_slo=1e-9)
    for i, p in enumerate(PROMPTS[:2]):
        s.submit(f"r{i}", p, max_new_tokens=4)
    s.run_until_idle(max_steps=400)
    assert [s.result(f"r{i}") for i in range(2)] == want
    assert s.engine.prefill_token_budget == 1


def test_sched_mid_prefill_migrates_policy_only(model):
    """A chunked-admission request migrated before its first token
    travels as a policy-only package (nothing computed is worth
    shipping; ``import_request`` refuses an empty stream) and
    completes bit-identically on the destination."""
    prompt = [9] * 30
    want, _ = _serve(model, [prompt], max_new=4)
    src = Scheduler(_mk(model, max_seqs=4), max_queue=8,
                    chunked_prefill=True)
    src.submit("big", prompt, max_new_tokens=4)
    src.step()                               # admit + first chunk
    assert not src.engine.requests["big"].out
    pkg = src.migrate_out("big")
    assert pkg["admitted"] is False and pkg["tokens"] == []
    assert pkg["swap"] is None
    assert "big" not in src.engine.requests  # engine side dropped
    dst = Scheduler(_mk(model, max_seqs=4), max_queue=8,
                    chunked_prefill=True)
    dst.migrate_in(pkg)
    dst.run_until_idle(max_steps=200)
    assert dst.result("big") == want[0]


# -- tier-1 budget guard -------------------------------------------------------
def test_tier1_budget_guard():
    """Adding ragged-mixed tests must not blow the 870 s tier-1
    wall-clock budget on the 1-core CI box."""
    here = Path(__file__).resolve()
    src = here.read_text()
    n_fast = 0
    for m in re.finditer(r"((?:@[\w.]+(?:\(.*?\))?\s*\n)*)"
                         r"def test_\w+\(", src, re.S):
        if "pytest.mark.slow" not in m.group(1) \
                and "skipif" not in m.group(1):
            n_fast += 1
    assert n_fast <= 16, (
        f"{n_fast} fast ragged-mixed tests — move the heavy ones "
        f"behind @pytest.mark.slow to protect the tier-1 budget")
