"""Request capsules — deterministic capture, bit-exact replay, and
the divergence audit plane (ISSUE 17).

Contracts under test:
* disabled is FREE: ``get_capsule_store()`` returns the shared
  ``NULL_CAPSULE_STORE`` singleton (identity-asserted) and tokens +
  compile counts are bit-identical with capture off vs armed;
* a captured request replays bit-exactly (``first_divergence is
  None``) across the steps_per_sync x admission grid, on int8 KV, after
  preempt -> resume on BOTH restore paths (swap-in and recompute),
  and after a cross-replica KV migration (the capsule rides the
  migration package);
* a tampered capsule reports the exact divergence step with expected
  vs got tokens and a logprob delta;
* triggered capture: slow TTFT, deadline miss at delivery, an engine
  error mid-step, and an AnomalySentinel trip each persist the
  capsule and cross-link it from the scheduler's request rows;
* the serving surface: ``GET /capsulez`` / ``GET /v1/capsule?rid=`` /
  ``POST /v1/replay``, the /statusz capsule block, and SSE framing of
  ``/v1/completions`` sharing one event encoding with chunked NDJSON;
* ``divergence_audit`` replays sampled capsules on another engine and
  ``ReplicaRouter.fleet_snapshot()`` federates the store counters.

Everything runs JAX_PLATFORMS=cpu on the tiny llama config.
"""
import json
import http.client
import re
import urllib.error
import urllib.request
from pathlib import Path

import pytest

import paddle_tpu as paddle
from paddle_tpu.common.errors import EnforceError
from paddle_tpu.inference import engine as E
from paddle_tpu.inference.engine import LLMEngine
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.observability import capsule as C
from paddle_tpu.observability import health as H
from paddle_tpu.serving import (ReplicaRouter, Scheduler,
                                start_http_frontend)


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = LlamaForCausalLM(llama_tiny_config())
    m.eval()
    return m


def _mk(model, **kw):
    cfg = dict(max_seqs=4, max_len=64, page_size=8, steps_per_sync=4)
    cfg.update(kw)
    return LLMEngine(model, **cfg)


def _run(eng, rid, prompt, n):
    eng.add_request(rid, prompt, max_new_tokens=n)
    while eng.has_work():
        eng.step()
    return eng.result(rid)


# -- disabled is free ----------------------------------------------------------
def test_null_store_identity_and_disabled_bit_identical(model):
    """Capture off: one module-global read hands back the shared NULL
    singleton; arming capture changes neither the token stream nor
    the compile counters."""
    assert C.get_capsule_store() is C.NULL_CAPSULE_STORE
    assert C.get_capsule_store().enabled is False
    assert C.get_capsule_store().capsulez() == {"enabled": False}

    want = _run(_mk(model), "off", [5, 9, 2, 14], 12)
    pre_c = E._paged_prefill_chunk._cache_size()
    dec_c = E._paged_decode_step._cache_size()
    C.enable_capsule_capture()
    try:
        got = _run(_mk(model), "on", [5, 9, 2, 14], 12)
        assert got == want, "capture armed must not perturb tokens"
        assert E._paged_prefill_chunk._cache_size() == pre_c, \
            "capture armed recompiled prefill"
        assert E._paged_decode_step._cache_size() == dec_c, \
            "capture armed recompiled decode"
        snap = C.get_capsule_store().snapshot()
        assert snap["enabled"] and snap["captured_total"] == 1
    finally:
        C.disable_capsule_capture()
    assert C.get_capsule_store() is C.NULL_CAPSULE_STORE


# -- replay: engine grid -------------------------------------------------------
@pytest.mark.parametrize("sps,admit", [(1, "add"), (4, "add"),
                                       (8, "add"), (4, "begin")])
def test_replay_bit_exact_across_grid(model, sps, admit):
    """A capsule replays with first_divergence None whichever programs
    recorded it: the step program alone (``steps_per_sync=1``), two
    window buckets (4, 8), a first token drawn inside a mixed step
    (``begin_request``: no key anchor at admission).  Replay runs the
    prefill and decode programs of recompute resume, not the step
    loop's, so it is an independent reading of the same stream."""
    C.enable_capsule_capture()
    eng = _mk(model, steps_per_sync=sps)
    if admit == "begin":
        eng.begin_request("g", [5, 9, 2, 14], max_new_tokens=10)
        while eng.has_work():
            eng.step()
        want = eng.result("g")
    else:
        want = _run(eng, "g", [5, 9, 2, 14], 10)
    cap = C.get_capsule_store().get("g")
    assert cap["tokens"] == want
    assert {w["n_steps"] for w in cap["windows"]} == {1, sps}
    rep = C.replay_capsule(cap, eng)
    assert rep["first_divergence"] is None, rep
    assert rep["steps_compared"] == len(want)


def test_replay_bit_exact_int8_kv(model):
    C.enable_capsule_capture()
    eng = _mk(model, kv_dtype="int8")
    want = _run(eng, "q", [3, 3, 7, 11, 2], 10)
    rep = C.replay_capsule(C.get_capsule_store().get("q"), eng)
    assert rep["first_divergence"] is None, rep
    assert rep["steps_compared"] == len(want)


# -- replay: preemption --------------------------------------------------------
def test_replay_bit_exact_after_preempt_resume_swap_in(model):
    C.enable_capsule_capture()
    eng = _mk(model)
    eng.add_request("s", [5, 9, 2, 14], max_new_tokens=12)
    eng.step()
    eng.step()
    assert eng.suspend("s") is True
    assert eng.resume("s") == "swap_in"
    while eng.has_work():
        eng.step()
    cap = C.get_capsule_store().get("s")
    assert ["suspend:swap", "resume:swap_in"] == \
        [e for e, _ in cap["events"]]
    rep = C.replay_capsule(cap, eng)
    assert rep["first_divergence"] is None, rep
    assert rep["steps_compared"] == len(eng.result("s"))


def test_replay_bit_exact_after_preempt_resume_recompute(model):
    C.enable_capsule_capture()
    eng = _mk(model, swap_pool_pages=0)       # no pool: recompute path
    eng.add_request("r", [5, 9, 2, 14], max_new_tokens=12)
    eng.step()
    eng.step()
    assert eng.suspend("r") is False
    assert eng.resume("r") == "recompute"
    while eng.has_work():
        eng.step()
    cap = C.get_capsule_store().get("r")
    assert ["suspend:drop", "resume:recompute"] == \
        [e for e, _ in cap["events"]]
    rep = C.replay_capsule(cap, eng)
    assert rep["first_divergence"] is None, rep


# -- replay: migration ---------------------------------------------------------
def test_capsule_rides_migration_and_replays(model):
    """Drain a mid-decode request A -> B: the capsule travels INSIDE
    the migration package (source store loses it, destination adopts
    it), the destination finishes recording, and the merged capsule
    replays bit-exactly on a THIRD engine."""
    C.enable_capsule_capture()
    src = Scheduler(_mk(model), max_queue=8)
    src.submit("m", [5, 9, 2, 14], max_new_tokens=12)
    src.step()
    src.step()
    pkg = src.migrate_out("m")
    assert pkg["capsule"] is not None and pkg["capsule"]["rid"] == "m"
    assert C.get_capsule_store().get("m") is None, \
        "source store must release the exported capsule"
    dst = Scheduler(_mk(model), max_queue=8)
    dst.migrate_in(pkg)
    dst.run_until_idle(max_steps=200)
    cap = C.get_capsule_store().get("m")
    assert cap["complete"] and cap["tokens"] == dst.result("m")
    names = [e for e, _ in cap["events"]]
    assert "exported" in names and "adopted" in names
    third = _mk(model)
    rep = C.replay_capsule(cap, third)
    assert rep["first_divergence"] is None, rep
    assert rep["steps_compared"] == len(cap["tokens"])


# -- divergence reporting ------------------------------------------------------
def test_tampered_capsule_reports_divergence(model):
    C.enable_capsule_capture()
    eng = _mk(model)
    _run(eng, "t", [5, 9, 2, 14], 10)
    cap = C.get_capsule_store().get("t")
    want = cap["tokens"][5]
    cap["tokens"][5] = (want + 1) % 100
    rep = C.replay_capsule(cap, eng)
    assert rep["first_divergence"] == 5
    assert rep["got"] == want and rep["expected"] == cap["tokens"][5]
    assert rep["logprob_delta"] is not None
    st = C.get_capsule_store().snapshot()
    assert st["divergent_replays_total"] == 1


# -- triggered capture ---------------------------------------------------------
def test_slow_ttft_and_deadline_trigger_capture(model):
    C.enable_capsule_capture()
    t = [0.0]
    sched = Scheduler(_mk(model), max_queue=8, slow_ttft=0.0,
                      clock=lambda: t[0])
    sched.submit("slow", [5, 9, 2], max_new_tokens=4, deadline=1.0)
    t[0] = 0.5                                # TTFT 0.5s > 0.0s
    sched.step()                              # admit + first token
    # the live /statusz request row cross-links the capsule id
    row = [r for r in sched.requests_overview()
           if r["rid"] == "slow"][0]
    assert row["capsule"] is not None
    t[0] = 5.0                                # past the deadline
    sched.run_until_idle(max_steps=100)
    cap = C.get_capsule_store().get("slow")
    assert "slow_ttft" in cap["persist_reasons"]
    assert "deadline_miss" in cap["persist_reasons"]
    assert row["capsule"] == cap["cap_id"]
    assert sched.request_timeline("slow")["capsule"] == cap["cap_id"]
    assert C.get_capsule_store().snapshot()["persisted_total"] == 1


def test_engine_error_persists_capsules(model, monkeypatch):
    C.enable_capsule_capture()
    eng = _mk(model)
    sched = Scheduler(eng, max_queue=8)
    sched.submit("boom", [5, 9, 2], max_new_tokens=8)
    sched.step()                              # admit + first window
    monkeypatch.setattr(eng, "step",
                        lambda: (_ for _ in ()).throw(
                            RuntimeError("chip fell over")))
    with pytest.raises(RuntimeError):
        sched.step()
    cap = C.get_capsule_store().get("boom")
    assert ["error:RuntimeError"] == cap["persist_reasons"]


def test_sentinel_trip_persists_active_capsules(model):
    C.enable_capsule_capture()
    H.enable_health()
    try:
        sched = Scheduler(_mk(model), max_queue=8)
        sched.submit("canary", [5, 9, 2], max_new_tokens=8)
        sched.step()
        H.get_health().sentinel.check(step=0, loss=float("nan"))
        sched.step()                          # trip noticed here
        cap = C.get_capsule_store().get("canary")
        assert "sentinel_trip" in cap["persist_reasons"]
    finally:
        H.disable_health()


# -- serving surface -----------------------------------------------------------
def test_http_capsule_endpoints_and_sse(model):
    C.enable_capsule_capture()
    sched = Scheduler(_mk(model), max_queue=8)
    fe = start_http_frontend(sched)
    try:
        def post(path, obj, headers=None):
            conn = http.client.HTTPConnection("127.0.0.1", fe.port,
                                              timeout=60)
            conn.request("POST", path, json.dumps(obj),
                         {"Content-Type": "application/json",
                          **(headers or {})})
            r = conn.getresponse()
            ctype, raw = r.getheader("Content-Type"), r.read()
            status = r.status
            conn.close()
            return status, ctype, raw

        # SSE framing: data:-framed events closed by data: [DONE]
        status, ctype, raw = post(
            "/v1/completions",
            {"id": "sse", "prompt": [5, 9, 2], "max_tokens": 6},
            {"Accept": "text/event-stream"})
        assert status == 200 and ctype == "text/event-stream"
        frames = [f for f in raw.decode().split("\n\n") if f.strip()]
        assert all(f.startswith("data: ") for f in frames)
        assert frames[-1] == "data: [DONE]"
        objs = [json.loads(f[6:]) for f in frames[:-1]]
        sse_toks = [t for o in objs if "tokens" in o
                    for t in o["tokens"]]
        assert objs[-1]["done"] and objs[-1]["state"] == "finished"

        # chunked NDJSON unchanged, same events through the one
        # shared encoder -> same tokens
        status, ctype, raw = post(
            "/v1/completions",
            {"id": "nd", "prompt": [5, 9, 2], "max_tokens": 6})
        assert status == 200 and ctype == "application/x-ndjson"
        lines = [json.loads(l) for l in raw.decode().splitlines() if l]
        assert [t for o in lines if "tokens" in o
                for t in o["tokens"]] == sse_toks

        # capsulez + full-capsule fetch (the store outlives _forget)
        cz = json.loads(urllib.request.urlopen(
            fe.url + "/capsulez").read())
        assert cz["enabled"] and cz["captured_total"] == 2
        c1 = json.loads(urllib.request.urlopen(
            fe.url + "/v1/capsule?rid=sse").read())
        assert c1["capsule"]["complete"] and \
            c1["capsule"]["tokens"] == sse_toks

        # replay: by rid, and by a capsule shipped in the body
        status, _, raw = post("/v1/replay", {"id": "sse"})
        assert status == 200
        assert json.loads(raw)["first_divergence"] is None
        status, _, raw = post("/v1/replay",
                              {"capsule": c1["capsule"]})
        assert status == 200
        assert json.loads(raw)["first_divergence"] is None

        # /statusz carries the store snapshot
        st = json.loads(urllib.request.urlopen(
            fe.url + "/statusz").read())
        assert st["capsules"]["captured_total"] == 2

        # error vocabulary: no body -> 400, unknown rid -> 400
        assert post("/v1/replay", {})[0] == 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(fe.url + "/v1/capsule?rid=nope")
        assert ei.value.code == 400
    finally:
        fe.shutdown()


# -- audit + federation --------------------------------------------------------
def test_divergence_audit_and_fleet_federation(model):
    C.enable_capsule_capture()
    eng = _mk(model)
    sched = Scheduler(eng, max_queue=8)
    router = ReplicaRouter([sched], sleep=lambda s: None)
    for i in range(3):
        router.submit(f"a{i}", [5 + i, 9, 2], max_new_tokens=6)
    sched.run_until_idle()
    other = _mk(model)                        # the audit replica
    summary = C.divergence_audit(other, n=2, seed=0)
    assert summary["replayed"] == 2
    assert summary["bit_exact"] == 2 and not summary["divergent"]
    snap = router.fleet_snapshot()
    assert snap["capsules"]["captured_total"] == 3
    assert snap["fleet"]["capsules"]["captured_total"] == 3
    assert snap["fleet"]["capsules"]["replays_total"] == 2
    assert snap["fleet"]["capsules"]["divergent_replays_total"] == 0
    assert C.get_capsule_store().snapshot()["audits"], \
        "the audit summary must land in the store snapshot"


# -- tier-1 budget guard -------------------------------------------------------
def test_tier1_budget_guard_capsule():
    """This module's fast tests stay bounded (the 870 s tier-1
    budget) and the disabled plane is one global read — identity-
    asserted so a refactor can't quietly break the contract."""
    assert C.get_capsule_store() is C.NULL_CAPSULE_STORE
    src = (Path(__file__).resolve().parent
           / "test_capsule.py").read_text()
    n_fast = 0
    for m in re.finditer(r"((?:@[\w.]+(?:\(.*?\))?\s*\n\s*)*)"
                         r"def (test_\w+)\(", src):
        if "pytest.mark.slow" not in m.group(1):
            n_fast += 1
    assert n_fast <= 16, (
        f"{n_fast} fast capsule tests — move heavy ones behind "
        f"@pytest.mark.slow to protect the 870 s tier-1 budget")
