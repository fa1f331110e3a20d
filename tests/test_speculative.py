"""Speculative decoding (ISSUE 20): draft-model propose, one-dispatch
ragged verify, bit-exact accept.

Contracts under test, all on the forced 8-device CPU platform:

* GREEDY BIT-IDENTITY — ``LLMEngine(draft_model=...)`` delivers
  token-for-token the plain engine's greedy stream on every serving
  path: fp, int8 KV, prefix-cache hits, deferred (``begin_request``)
  admission with its plain-window prefill interludes, EOS retiring a
  request mid-window, plain decode in 4-step windows, a tp=2 mesh, and
  preempt→resume over BOTH restore paths;
* SAMPLED ACCEPTANCE — ``rejection_accept`` preserves the target's
  post-filter distribution for an arbitrary draft proposal (the
  speculative-sampling identity), and a sampled spec capsule replays
  BIT-EXACTLY on a fresh draft engine while a changed draft geometry
  is reported via the ``spec`` fingerprint field;
* ROLLBACK — ``PagedKVCache.rollback`` un-appends exactly ``n``
  tokens, keeps the pages attached (release-safe), mirrors
  ``advance``'s under-advance contract for int8 scale rows, and
  refuses nonsense (negative n, free slot, n > len);
* COMPILE STABILITY — runtime ``k_run`` and batch mix churn adds ZERO
  recompile anomalies: the draft / verify programs trace once inside
  their declared CompileWatch allowances (the conftest guard
  re-asserts zero recompiles for every test in this module);
* DELIVERED-ONLY ACCOUNTING — TPOT (and through it the scheduler's
  AIMD SLO input) advances by tokens actually DELIVERED, never by
  proposed-but-rejected draft tokens, for either admission;
* OBSERVABILITY — acceptance counters/rate in ``metrics_snapshot()``,
  the ``/statusz`` headline, and the ``/fleetz`` federation.

Everything runs JAX_PLATFORMS=cpu on the tiny llama config.
"""
import json
import re
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from conftest import requires_mesh

import paddle_tpu as paddle
from paddle_tpu.common.errors import EnforceError
from paddle_tpu.distributed.topology import serving_mesh
from paddle_tpu.inference.engine import LLMEngine
from paddle_tpu.inference.paged_cache import PagedKVCache
from paddle_tpu.inference import speculative as S
from paddle_tpu.inference import sampling as K
from paddle_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                     llama_tiny_config)
from paddle_tpu.observability import capsule as C
from paddle_tpu.observability import introspection as I

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


@pytest.fixture(scope="module")
def draft():
    # different weights on the same tiny geometry: proposals disagree
    # with the target often, so acceptance boundaries + corrections
    # (the interesting paths) are exercised constantly
    paddle.seed(1)
    d = LlamaForCausalLM(llama_tiny_config())
    d.eval()
    return d


def _mk(model, draft_model=None, k=3, **kw):
    kw.setdefault("max_seqs", 8)
    kw.setdefault("max_len", 64)
    kw.setdefault("page_size", P)
    kw.setdefault("n_pages", 64)
    if draft_model is not None:
        kw["draft_model"] = draft_model
        kw["spec_k"] = k
    return LLMEngine(model, **kw)


def _drain(eng):
    while eng.has_work():
        eng.step()


def _serve(eng, prompts, max_new=9, admit="add", eos=None):
    for i, p in enumerate(prompts):
        if admit == "begin":
            eng.begin_request(f"r{i}", p, max_new_tokens=max_new,
                              eos_token_id=eos)
        else:
            eng.add_request(f"r{i}", p, max_new_tokens=max_new,
                            eos_token_id=eos)
    _drain(eng)
    return [eng.result(f"r{i}") for i in range(len(prompts))]


# -- greedy bit-identity over the serving grid ---------------------------------
@pytest.mark.parametrize("case", ["fp", "int8", "prefix", "begin",
                                  "sps4", "eos"])
def test_greedy_bit_identical(model, draft, case):
    """Acceptance (the tentpole invariant): the speculative greedy
    stream is BIT-IDENTICAL to plain decode — matched rows deliver the
    draft token (== the verify argmax), mismatches deliver the
    target's correction, full acceptance the bonus row; rejected
    suffixes roll back and are never attended."""
    kw, admit, eos, prompts = {}, "add", None, PROMPTS
    if case == "int8":
        kw = {"kv_dtype": "int8"}
    elif case == "prefix":
        prompts = [PROMPTS[2], PROMPTS[2], PROMPTS[1]]  # shared pages
    elif case == "begin":
        admit = "begin"          # prefill interludes between windows
    elif case == "sps4":
        kw = {"steps_per_sync": 4}   # plain decode runs 4-step windows
    if case == "eos":
        ref = _serve(_mk(model), PROMPTS, max_new=9)
        eos = ref[0][3]          # retires r0 mid-window
    want = _serve(_mk(model, **kw), prompts, admit=admit, eos=eos)
    got = _serve(_mk(model, draft, **kw), prompts, admit=admit,
                 eos=eos)
    assert got == want, f"speculative greedy diverged on {case!r}"


def test_self_draft_full_acceptance(model):
    """Degenerate self-draft (draft == target): greedy acceptance is
    total — every window delivers k+1 tokens — and the acceptance
    plane reports exactly that."""
    eng = _mk(model, model, k=3)
    got = _serve(eng, PROMPTS, max_new=9)
    assert got == _serve(_mk(model), PROMPTS, max_new=9)
    s = eng.metrics_snapshot()["spec"]
    assert s["enabled"] and s["mode"] == "greedy" and s["k"] == 3
    assert s["acceptance_rate"] == 1.0
    assert s["proposed"] == s["accepted"]
    # 8 post-prefill tokens per request at k+1 per window = 2 windows
    assert s["windows"] == 2
    assert s["delivered"] == sum(len(t) - 1 for t in got)


@requires_mesh(2)
def test_greedy_bit_identical_tp2(model, draft):
    """The tp-sharded target verifies bit-identically: tokens on a
    tp=2 spec engine equal the tp=1 plain engine's (the draft stays
    replicated by design)."""
    want = _serve(_mk(model, max_seqs=4), PROMPTS[:3], max_new=8)
    eng = _mk(model, draft, max_seqs=4, mesh=serving_mesh(2))
    assert _serve(eng, PROMPTS[:3], max_new=8) == want


def test_preempt_resume_bit_identical(model, draft):
    """Suspend releases the draft slot (never swapped: cheaper to
    re-prefill); resume lazily re-attaches at the next window — tokens
    stay bit-identical on BOTH target restore paths."""
    want = _serve(_mk(model), PROMPTS[:2], max_new=9)
    for pool, path in [(64, "swap_in"), (0, "recompute")]:
        eng = _mk(model, draft, swap_pool_pages=pool)
        for i in range(2):
            eng.add_request(f"r{i}", PROMPTS[i], max_new_tokens=9)
        eng.step()
        eng.suspend("r0")
        assert eng.requests["r0"].draft_slot is None
        eng.step()
        assert eng.resume("r0") == path
        _drain(eng)
        got = [eng.result(f"r{i}") for i in range(2)]
        assert got == want, f"spec diverged across {path} resume"


# -- sampled acceptance --------------------------------------------------------
def test_rejection_accept_preserves_target_distribution():
    """The speculative-sampling identity: accept ``d ~ q`` w.p.
    ``min(1, p(d)/q(d))``, resample rejects from ``normalize(max(p -
    q, 0))`` — the delivered token's marginal is exactly ``p``,
    however bad the proposal."""
    import jax

    rng = np.random.default_rng(0)
    p = np.array([0.55, 0.25, 0.15, 0.05])
    q = np.array([0.10, 0.20, 0.30, 0.40])    # deliberately adversarial
    n = 800
    counts = np.zeros(4)
    for t in range(n):
        root = jax.random.PRNGKey(1000 + t)
        a_root, r_root = jax.random.split(root)
        d = rng.choice(4, p=q)
        toks, _ = S.rejection_accept(
            np.array([d]), q[None], np.stack([p, p]), a_root, r_root,
            row=0)
        counts[toks[0]] += 1
    emp = counts / n
    assert np.abs(emp - p).max() < 0.07, (emp, p)
    # k=0 degenerate bonus: no draft tokens, the delivered token is a
    # straight draw from p's row
    toks, a = S.rejection_accept(np.zeros(0, np.int64),
                                 np.zeros((0, 4)), p[None],
                                 jax.random.PRNGKey(1),
                                 jax.random.PRNGKey(2), row=0)
    assert a == 0 and len(toks) == 1 and 0 <= toks[0] < 4


def test_sampled_capsule_replay_and_fingerprint(model, draft):
    """Sampled speculative serving end to end: the capsule records
    ``spec_window`` records (accepted lengths included), replays
    BIT-EXACTLY on a FRESH draft engine through the same
    ``_spec_window`` entry, and a changed draft geometry is reported
    via the token-affecting ``spec`` fingerprint field."""
    kw = dict(decode_strategy="sampling", temperature=0.9, seed=7)
    store = C.enable_capsule_capture()
    try:
        eng = _mk(model, draft, **kw)
        out = _serve(eng, PROMPTS[:3], max_new=9)
        assert all(len(t) == 9 for t in out)
        caps = [store.get(f"r{i}") for i in range(3)]
        assert any(w["path"] == "spec_window" and "accepted" in w
                   for c in caps for w in c["windows"])
        fresh = _mk(model, draft, **{**kw, "seed": 99})
        for cap in caps:
            rep = C.replay_capsule(cap, fresh)
            assert rep["first_divergence"] is None, rep
            assert rep["fingerprint_mismatch"] == []
            assert rep["steps_compared"] == 9
        # changed draft GEOMETRY: reported, never silently
        # bit-exact-claimed (the fingerprint hashes the config — a
        # same-config weight swap shows up as token divergence instead)
        cfg = llama_tiny_config()
        paddle.seed(2)
        other = LlamaForCausalLM(
            LlamaConfig(**{**vars(cfg), "intermediate_size": 96}))
        other.eval()
        rep = C.replay_capsule(caps[0],
                               _mk(model, other, **{**kw, "seed": 99}))
        assert "spec" in rep["fingerprint_mismatch"]
        # draftless engine declines the spec windows with a note
        rep = C.replay_capsule(caps[0], _mk(model, **{**kw, "seed": 99}))
        assert "spec_windows_require_draft_engine" in rep["notes"]
    finally:
        C.disable_capsule_capture()


# -- rollback ------------------------------------------------------------------
def test_rollback_accounting():
    """``rollback`` is a host-side length decrement and NOTHING else:
    pages stay attached (release-safe), int8 scale rows ride the same
    watermark, and the guards refuse nonsense."""
    for kv_dtype in (None, "int8"):
        cache = PagedKVCache(n_pages=16, page_size=8, n_kv_heads=2,
                             head_dim=4, max_seqs=2, max_len=64,
                             num_layers=1, kv_dtype=kv_dtype)
        free0 = cache.free_pages()
        slot = cache.allocate(20)
        cache.set_len(slot, 20)
        held = free0 - cache.free_pages()
        cache.rollback(slot, 5)
        assert int(cache.seq_lens[slot]) == 15
        # un-append keeps every page attached: re-extending to the
        # original length grabs NOTHING new
        assert cache.free_pages() == free0 - held
        cache.extend(slot, 5)
        assert cache.free_pages() == free0 - held
        cache.rollback(slot, 0)            # no-op allowed
        assert int(cache.seq_lens[slot]) == 15
        with pytest.raises(EnforceError):
            cache.rollback(slot, -1)
        with pytest.raises(EnforceError):
            cache.rollback(slot, 16)       # > len
        cache.release(slot)
        assert cache.free_pages() == free0
        with pytest.raises(EnforceError):
            cache.rollback(slot, 1)        # free slot


def test_spec_rollback_frees_everything_on_retire(model, draft):
    """After a full speculative drain both pools are clean: every
    target AND draft page returns to its free list (advance + rollback
    balanced on every acceptance outcome)."""
    eng = _mk(model, draft)
    free_t = eng.cache.free_pages()
    free_d = eng._spec_cache.free_pages()
    _serve(eng, PROMPTS, max_new=9)
    assert eng.cache.free_pages() == free_t
    assert eng._spec_cache.free_pages() == free_d
    assert eng._spec_cache.metrics_snapshot()["oom_events"] == 0


# -- compile stability ---------------------------------------------------------
def test_compile_stability_churning_k(model, draft):
    """Zero recompile anomalies under a CompileWatch armed to RAISE:
    runtime ``k_run`` churn (budgets 9/5/3/2, batch sizes 3/2/1) stays
    inside the declared one-trace-per-program surface, and a second
    same-geometry engine adds ZERO new spec compiles."""
    w = I.enable_compile_watch(on_recompile="raise")
    for max_new, n in [(9, 3), (5, 2), (3, 1), (2, 2)]:
        _serve(_mk(model, draft), PROMPTS[:n], max_new=max_new)
    snap = w.snapshot()
    # warm-process note: earlier tests in this module may have traced
    # the spec programs already, so absolute counts can be ZERO here —
    # the contract is the ceiling (declared allowance) and no growth
    draft_c = snap["programs"]["engine.spec_draft"]["compiles"]
    verify_c = snap["programs"]["engine.spec_verify"]["compiles"]
    assert draft_c <= snap["programs"]["engine.spec_draft"]["allowed"]
    assert verify_c <= snap["programs"]["engine.spec_verify"]["allowed"]
    _serve(_mk(model, draft), PROMPTS[:3], max_new=9)
    snap2 = w.snapshot()
    assert snap2["programs"]["engine.spec_draft"]["compiles"] == \
        draft_c
    assert snap2["programs"]["engine.spec_verify"]["compiles"] == \
        verify_c
    assert not snap2["recompiles"]


# -- delivered-only accounting -------------------------------------------------
def test_tpot_counts_delivered_tokens_only(model, draft):
    """Regression (satellite of the window-boundary TPOT fix): the
    TPOT histogram — the scheduler AIMD's SLO input — advances by
    DELIVERED tokens only, never by proposed draft tokens, whether the
    prompt was prefilled at admission or in mixed-step interludes
    between the speculative windows."""
    for admit in ("add", "begin"):
        for sps in (1, 4):
            eng = _mk(model, draft, steps_per_sync=sps)
            _serve(eng, [PROMPTS[0]], admit=admit)
            delivered = len(eng.result("r0")) - 1  # prefill tok = TTFT
            count = eng.metrics_snapshot()["tpot_seconds"]["count"]
            assert count == delivered, (
                f"admit={admit} steps_per_sync={sps}: tpot count "
                f"{count} != delivered {delivered} (counted rejected "
                f"proposals?)")
            s = eng.metrics_snapshot()["spec"]
            assert s["delivered"] == delivered
            assert s["proposed"] >= s["accepted"] >= 0


# -- observability surface -----------------------------------------------------
def test_statusz_and_fleetz_spec_blocks(model, draft):
    """The acceptance plane surfaces everywhere an operator looks:
    ``metrics_snapshot()['spec']``, the ``/statusz`` target headline,
    and the ``/fleetz`` cross-replica federation (counters summed,
    rate recomputed from the merged counters)."""
    from paddle_tpu.serving import ReplicaRouter, Scheduler
    from paddle_tpu.serving.server import start_http_frontend

    scheds = []
    for _ in range(2):
        eng = _mk(model, draft, max_seqs=2)
        scheds.append(Scheduler(eng, max_queue=8))
    for j, sc in enumerate(scheds):
        sc.submit(f"s{j}", PROMPTS[j], max_new_tokens=6)
        sc.run_until_idle()
    router = ReplicaRouter(scheds)
    fl = router.fleet_snapshot()["fleet"]["spec"]
    per = [sc.engine.metrics_snapshot()["spec"] for sc in scheds]
    assert fl["proposed"] == sum(s["proposed"] for s in per)
    assert fl["accepted"] == sum(s["accepted"] for s in per)
    assert fl["delivered"] == sum(s["delivered"] for s in per) == 10
    assert fl["acceptance_rate"] == pytest.approx(
        fl["accepted"] / fl["proposed"])
    fe = start_http_frontend(scheds[0])
    try:
        st = json.loads(urllib.request.urlopen(
            fe.url + "/statusz").read())
        assert st["target"]["spec"]["mode"] == "greedy"
        assert st["target"]["spec"]["proposed"] == per[0]["proposed"]
    finally:
        fe.shutdown()


# -- draft validation ----------------------------------------------------------
def test_draft_validation(model):
    """Engine init refuses drafts it cannot verify against: vocab
    mismatch, rope table shorter than the serving limit, spec_k < 1,
    MoE drafts."""
    cfg = llama_tiny_config()
    bad_vocab = LlamaConfig(**{**vars(cfg), "vocab_size": 128})
    paddle.seed(3)
    d = LlamaForCausalLM(bad_vocab)
    d.eval()
    with pytest.raises(EnforceError, match="vocab"):
        _mk(model, d)
    bad_pos = LlamaConfig(**{**vars(cfg),
                             "max_position_embeddings": 16})
    paddle.seed(3)
    d = LlamaForCausalLM(bad_pos)
    d.eval()
    with pytest.raises(EnforceError, match="max_position"):
        _mk(model, d)
    with pytest.raises(EnforceError, match="spec_k"):
        _mk(model, model, k=0)
    from paddle_tpu.models.qwen2_moe import (Qwen2MoeForCausalLM,
                                             qwen2_moe_tiny_config)
    paddle.seed(3)
    moe = Qwen2MoeForCausalLM(qwen2_moe_tiny_config())
    moe.eval()
    with pytest.raises(EnforceError, match="dense"):
        LLMEngine(moe, max_seqs=4, max_len=64, page_size=P,
                  n_pages=64, draft_model=moe, spec_k=2)


# -- tier-1 budget guard -------------------------------------------------------
def test_tier1_budget_guard():
    """Adding speculative tests must not blow the 870 s tier-1
    wall-clock budget on the 1-core CI box."""
    here = Path(__file__).resolve()
    src = here.read_text()
    n_fast = 0
    for m in re.finditer(r"((?:@[\w.]+(?:\(.*?\))?\s*\n)*)"
                         r"def test_\w+\(", src, re.S):
        if "pytest.mark.slow" not in m.group(1) \
                and "skipif" not in m.group(1):
            n_fast += 1
    assert n_fast <= 14, (
        f"{n_fast} fast speculative tests — move the heavy ones "
        f"behind @pytest.mark.slow to protect the tier-1 budget")
