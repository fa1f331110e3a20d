"""On-device decode windows (ISSUE 16): the ``steps_per_sync`` window
as ONE compiled while_loop program — attend → sample → KV-append
chained in-graph, host synced only at window boundaries.

Contracts under test:
* a window's tokens equal the PER-TOKEN stream's (``steps_per_sync=1``:
  the step program, itself held to the dense ``model.generate()``) on
  every path — plain greedy, int8 KV, prefix-cache hits,
  preempt→resume (swap-in AND recompute), migration; sampled windows
  walk the ``inference.sampling`` key sequence (their per-token
  oracle under the window's own key is in ``test_step_crossings.py``);
* window-edge semantics: EOS on a window's last step, budget
  exhaustion at the window edge, ALL rows retiring early (the
  while_loop exits before n_steps — observable via
  ``last_window_steps``), ``steps_per_sync=1`` degenerating to the
  plain step program (zero window compiles), suspend/abort landing
  between windows;
* ``window_compiles()`` bounded by the declared power-of-two buckets
  with ZERO recompile anomalies under an enabled CompileWatch (the
  conftest guard re-asserts this for every test in this module);
* TPOT regression (the window-boundary over-count): only tokens
  actually DELIVERED advance the histogram;
* a tier-1 budget guard keeps this module's fast footprint flat.

Everything runs JAX_PLATFORMS=cpu on the tiny llama config.
"""
import re
from pathlib import Path

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.engine import LLMEngine
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config

P = 8
PROMPTS = [[5, 9, 2, 14],                         # sub-page
           list(range(1, 20)),                    # 2.5 pages
           [7] * 33,                              # page-crossing
           [3, 1, 4, 1, 5, 9, 2, 6]]              # exactly one page


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


def _serve(model, prompts, max_new=6, admit="add", eos=None, **kw):
    eng = _mk(model, **kw)
    for i, p in enumerate(prompts):
        if admit == "begin":
            eng.begin_request(f"r{i}", p, max_new_tokens=max_new,
                              eos_token_id=eos)
        else:
            eng.add_request(f"r{i}", p, max_new_tokens=max_new,
                            eos_token_id=eos)
    _drain(eng)
    return [eng.result(f"r{i}") for i in range(len(prompts))], eng


# -- the window program against the per-token stream ----------------------------
def _generate(model, prompts, max_new):
    return [np.asarray(model.generate(
        paddle.to_tensor(np.asarray(p, np.int32)[None]),
        max_new_tokens=max_new)[0].numpy())[0].tolist() for p in prompts]


@pytest.mark.parametrize("slots,prompts", [
    (8, PROMPTS), (4, PROMPTS), (8, PROMPTS[1:2])],
    ids=["half_full", "every_slot_live", "one_live_row"])
def test_window_matches_the_per_token_stream(model, slots, prompts):
    """Acceptance: the one-dispatch window produces the tokens of
    per-token (steps_per_sync=1) stepping — which are the dense
    ``generate()``'s — for synchronous and deferred admission alike.
    The window program runs ONE row a slot (the per-token step program
    slots + prefill budget rows): with every slot live it has no
    padding row at all, with one live row all the others are."""
    base, _ = _serve(model, prompts, max_new=9, max_seqs=slots)
    assert base == _generate(model, prompts, 9)
    scan, eng = _serve(model, prompts, max_new=9, steps_per_sync=4,
                       max_seqs=slots)
    assert scan == base
    ran = eng.metrics_snapshot()["forward_rows"]["window"]
    assert ran["capacity"] == 8 * slots         # 4 + 4 forwards, no more
    assert ran["live"] == 8 * len(prompts)
    deferred, _ = _serve(model, prompts, max_new=9, admit="begin",
                         steps_per_sync=4, max_seqs=slots)
    assert deferred == base


def test_two_window_buckets_match_the_per_token_stream(model):
    """``steps_per_sync=8`` with ragged budgets walks every bucket —
    8, 4, 2 and the plain step program — in one drain; each stream is
    the per-token engine's."""
    def run(sps):
        eng = _mk(model, steps_per_sync=sps)
        for i, (p, n) in enumerate(zip(PROMPTS, (30, 13, 7, 4))):
            eng.add_request(f"r{i}", p, max_new_tokens=n)
        sizes = set()
        while eng.has_work():
            eng.step()
            sizes.add(eng.last_window_steps)
        return [eng.result(f"r{i}") for i in range(4)], sizes

    want, sizes = run(1)
    assert sizes == {1}
    got, sizes = run(8)
    assert got == want
    assert sizes == {8, 4, 2, 1}


def test_window_int8_kv_parity(model):
    """int8 KV pools ride the window (quantize-append inside the
    while_loop, scale rows in the carry): the per-token int8 stream."""
    want, _ = _serve(model, PROMPTS, max_new=9, kv_dtype="int8")
    got, _ = _serve(model, PROMPTS, max_new=9, kv_dtype="int8",
                    steps_per_sync=4)
    assert got == want
    deferred, _ = _serve(model, PROMPTS, max_new=9, kv_dtype="int8",
                         admit="begin", steps_per_sync=4)
    assert deferred == want


def test_sampling_key_sequence_contract(model):
    """Stochastic decoding: the window derives its step keys in-graph
    through the ``split_step`` chain; ``window_keys`` pins the contract
    against a manual ``jax.random.split`` chain.  On the engine: a
    sampled window's stream is its seed's alone, and with one
    candidate left (``top_k=1``) every draw is the greedy token —
    the per-token stream's — so the sampling branch of the window
    body feeds back what it drew."""
    import jax

    from paddle_tpu.inference.sampling import split_step, window_keys

    key = jax.random.PRNGKey(3)
    subs, fin = window_keys(key, 4)
    k = key
    for want_sub in subs:
        k, sub = jax.random.split(k)
        assert np.array_equal(np.asarray(sub), np.asarray(want_sub))
    assert np.array_equal(np.asarray(fin), np.asarray(k))
    nk, sub = split_step(key)
    assert np.array_equal(np.asarray(sub), np.asarray(subs[0]))
    assert np.array_equal(np.asarray(nk),
                          np.asarray(jax.random.split(key)[0]))

    kw = dict(decode_strategy="sampling", top_k=5, temperature=0.8,
              max_new=9, steps_per_sync=4)
    a, _ = _serve(model, PROMPTS[:3], seed=11, **kw)
    b, _ = _serve(model, PROMPTS[:3], seed=11, **kw)
    c, _ = _serve(model, PROMPTS[:3], seed=12, **kw)
    assert a == b != c
    greedy, _ = _serve(model, PROMPTS[:3], max_new=9)
    one, _ = _serve(model, PROMPTS[:3], **dict(kw, top_k=1), seed=11)
    assert one == greedy


def test_prefix_cache_parity_window(model):
    """Prefix-hit admissions (shared pages mapped host-side) decode
    through windows as they do token by token, with the same hit
    accounting."""
    sys_p = list(range(1, 17))               # 2 full shared pages
    prompts = [sys_p + [30 + i] for i in range(3)] + [sys_p]
    want, eh = _serve(model, prompts, max_new=8)
    got, es = _serve(model, prompts, max_new=8, steps_per_sync=4)
    assert got == want
    assert es.prefix_stats["hit_tokens"] == \
        eh.prefix_stats["hit_tokens"] > 0


# -- preemption / migration between windows ------------------------------------
def _interrupted(model, swap_pages, expect_path):
    prompt, n = PROMPTS[1], 8
    want, _ = _serve(model, [prompt], max_new=n)
    eng = _mk(model, swap_pool_pages=swap_pages, steps_per_sync=4)
    eng.add_request("r", prompt, max_new_tokens=n)
    eng.step()                               # one multi-token window
    assert eng.suspend("r") is (expect_path == "swap_in")
    assert eng.resume("r") == expect_path
    _drain(eng)
    assert eng.result("r") == want[0]


def test_preempt_resume_swap_parity(model):
    """Suspend at a window boundary, restore through the host swap
    pool: the continuation's windows stay bit-identical."""
    _interrupted(model, swap_pages=32, expect_path="swap_in")


def test_preempt_resume_recompute_parity(model):
    """Swap pool disabled: resume replays prefill + generated tokens
    (the replay's own windows are the fixed-length program) and the
    windowed continuation matches the uninterrupted stream."""
    _interrupted(model, swap_pages=0, expect_path="recompute")


def test_migration_parity(model):
    """Export after a window on one engine, import into a second
    windowed engine: continuation == uninterrupted stream."""
    prompt, n = PROMPTS[1], 8
    want, _ = _serve(model, [prompt], max_new=n)
    src = _mk(model, steps_per_sync=4)
    src.add_request("r", prompt, max_new_tokens=n)
    src.step()
    src.suspend("r")
    pkg = src.export_request("r")
    dst = _mk(model, steps_per_sync=4)
    dst.import_request(pkg)
    dst.resume("r")
    _drain(dst)
    assert dst.result("r") == want[0]


# -- window-edge semantics -----------------------------------------------------
def test_eos_mid_and_last_step_of_window(model):
    """EOS landing anywhere in a window — the last step included —
    retires the request with the same tokens as per-token stepping
    (the in-graph done predicate mirrors the host merge exactly)."""
    ref, _ = _serve(model, [PROMPTS[1]], max_new=9)
    # generated index g = decode step g of the first 4-step window
    # (index 0 is the prefill token): g=4 is that window's LAST step
    for g in (2, 4):
        eos = ref[0][g]
        want, _ = _serve(model, [PROMPTS[1]], max_new=9, eos=eos)
        got, _ = _serve(model, [PROMPTS[1]], max_new=9, eos=eos,
                        steps_per_sync=4)
        assert got == want == [ref[0][:g + 1]]


def test_budget_exhaustion_at_window_edge(model):
    """Ragged remaining budgets: the window is capped by the SMALLEST
    remaining budget (then pow2-floored), so exhaustion only ever
    lands on a window's final step — mixed max_new values must retire
    each request at exactly its budget, with the tokens ``generate()``
    gives for that budget."""
    eng = _mk(model, steps_per_sync=8)
    eng.add_request("a", PROMPTS[0], max_new_tokens=9)
    eng.add_request("b", PROMPTS[1], max_new_tokens=3)
    _drain(eng)
    assert [eng.result("a")] == _generate(model, PROMPTS[:1], 9)
    assert [eng.result("b")] == _generate(model, PROMPTS[1:2], 3)


def test_all_rows_early_exit(model):
    """When every live row retires mid-window the while_loop stops
    paying for the remaining steps: ``last_window_steps`` comes back
    SHORT of the bucketed n_steps, tokens still the per-token ones."""
    ref, _ = _serve(model, [PROMPTS[1]], max_new=9)
    eos = ref[0][2]                          # retires at decode step 2
    want, _ = _serve(model, [PROMPTS[1]], max_new=9, eos=eos)
    eng = _mk(model, steps_per_sync=8)
    eng.add_request("r0", PROMPTS[1], max_new_tokens=9,
                    eos_token_id=eos)
    _drain(eng)
    assert [eng.result("r0")] == want
    # the first (only) decode window was bucketed to 8 steps but the
    # row hit EOS at step 2 — the device loop exited there
    assert eng.last_window_steps < 8
    assert eng.metrics_snapshot()["last_window_steps"] == \
        eng.last_window_steps


def test_steps_per_sync_one_degenerates(model):
    """steps_per_sync=1 must use today's single-step program — the
    window jits never trace, so ``window_compiles()`` stays flat."""
    base = LLMEngine.window_compiles()
    got, eng = _serve(model, PROMPTS[:2], max_new=6)   # default sps=1
    assert LLMEngine.window_compiles() == base
    assert eng.metrics_snapshot()["window_compiles"] == base
    assert got == _generate(model, PROMPTS[:2], 6)


def test_suspend_abort_between_windows(model):
    """Scheduler-shaped interventions land at window boundaries:
    suspend→resume mid-run keeps the stream bit-identical; abort
    between windows retires with the tokens delivered so far and the
    survivor finishes untouched."""
    want, _ = _serve(model, PROMPTS[:2], max_new=9)
    eng = _mk(model, steps_per_sync=4)
    for i, p in enumerate(PROMPTS[:2]):
        eng.add_request(f"r{i}", p, max_new_tokens=9)
    eng.step()
    eng.suspend("r0")
    eng.step()                               # r1 decodes alone
    eng.resume("r0")
    _drain(eng)
    assert [eng.result("r0"), eng.result("r1")] == want

    eng2 = _mk(model, steps_per_sync=4)
    for i, p in enumerate(PROMPTS[:2]):
        eng2.add_request(f"a{i}", p, max_new_tokens=9)
    eng2.step()
    n_before = len(eng2.requests["a0"].out)
    eng2.abort("a0")
    _drain(eng2)
    assert eng2.requests["a0"].cancelled
    assert len(eng2.result("a0")) == n_before
    assert eng2.result("a1") == want[1]


# -- compile bounds + recompile sentinel ---------------------------------------
def test_window_compiles_bounded_zero_recompiles(model):
    """Acceptance: ``mixed_compiles()`` stays bounded by the DECLARED
    power-of-two window buckets — under a CompileWatch armed to RAISE
    on anomalies, a full drain (buckets 4 and 2 for max_new=9 windows)
    plus a second same-geometry engine adds at most the allowance and
    zero recompile events."""
    from paddle_tpu.observability import introspection as I

    w = I.enable_compile_watch(on_recompile="raise")
    base = LLMEngine.window_compiles()
    _serve(model, PROMPTS[:3], max_new=9, steps_per_sync=4)
    _serve(model, PROMPTS[:3], max_new=9, steps_per_sync=4)
    delta = LLMEngine.window_compiles() - base
    assert delta <= 2, \
        f"{delta} window programs for declared buckets {{4, 2}}"
    snap = w.snapshot()
    prog = snap["programs"].get("engine.mixed_window", {})
    assert prog.get("recompiles", 0) == 0
    assert not snap["recompiles"]


def test_tpot_counts_delivered_tokens_only(model):
    """Regression (window-boundary TPOT over-count): a request that
    retires mid-window must advance the TPOT histogram by the tokens
    actually delivered, not by nsteps — whichever way it was admitted,
    and token by token too."""
    ref, _ = _serve(model, [PROMPTS[1]], max_new=9)
    eos = ref[0][2]
    for admit in ("add", "begin"):
        for sps in (8, 1):
            got, eng = _serve(model, [PROMPTS[1]], max_new=9, eos=eos,
                              admit=admit, steps_per_sync=sps)
            delivered = len(got[0]) - 1            # prefill tok = TTFT
            count = eng.metrics_snapshot()["tpot_seconds"]["count"]
            assert count == delivered == 2, (
                f"admit={admit} steps_per_sync={sps}: tpot count "
                f"{count} != delivered {delivered} (over-counted the "
                f"window)")


# -- tier-1 budget guard -------------------------------------------------------
def test_tier1_budget_guard():
    """Adding decode-window tests must not blow the 870 s tier-1
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
        f"{n_fast} fast decode-window tests — move the heavy ones "
        f"behind @pytest.mark.slow to protect the tier-1 budget")
