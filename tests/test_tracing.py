"""End-to-end request tracing, crash flight recorder, and live debug
endpoints (ISSUE 9).

Contracts under test:
* tracer core: implicit thread-local nesting, explicit-context
  parenting, bounded span ring, injectable clock, Chrome-trace export
  schema, HTTP header inject/extract round trip;
* disabled-is-free: with no tracer installed every instrumentation
  site gets the shared ``NULL_SPAN`` singleton back (no allocation),
  and a traced serving run produces BIT-IDENTICAL tokens with
  ``prefill_compiles() == 1`` and decode compile counts unchanged;
* one connected trace per rid: direct scheduler runs, preemption/
  resume, router failover (eject-requeue), KV-migrating drain, and
  the remote HTTP hop (trace context in headers) all keep every span
  of a rid in ONE trace whose parent links resolve;
* ``Scheduler.request_timeline`` structured record + the frontend's
  slow-request log line;
* flight recorder: JSONL dumps parseable after explicit, fatal
  (``guard``), SIGTERM, and CheckpointManager-preemption triggers;
* ``/statusz`` / ``/tracez`` / ``/v1/timeline`` round-trip through
  ``json.loads``; the profiler bridge lands RecordEvent ranges and
  tracer spans in the ``export_chrome_tracing`` timeline;
* ``Histogram`` quantile estimates (p50/p95/p99 bucket
  interpolation).

Everything runs JAX_PLATFORMS=cpu; HTTP rigs are per-test and torn
down (the conftest thread-leak guard enforces it).
"""
import json
import logging
import os
import re
import signal
import threading
import urllib.request
from pathlib import Path

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.common.errors import EnforceError
from paddle_tpu.inference.engine import LLMEngine
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.observability import tracing as T
from paddle_tpu.observability.metrics import MetricRegistry
from paddle_tpu.serving import (Fault, FaultPlan, RemoteReplica,
                                ReplicaRouter, Scheduler,
                                start_http_frontend)

_NOSLEEP = lambda s: None                      # noqa: E731


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = LlamaForCausalLM(llama_tiny_config())
    m.eval()
    return m


@pytest.fixture(autouse=True)
def _reset_tracing():
    """Every test leaves the process-global tracer/recorder OFF — the
    disabled-is-free guarantees other modules assert depend on it."""
    yield
    T.disable_tracing()
    T.disable_flight_recorder()


def _mk_sched(model, **kw):
    kw.setdefault("max_queue", 8)
    return Scheduler(LLMEngine(model, max_seqs=4, max_len=64,
                               page_size=8), **kw)


def _direct(model, prompt, n):
    eng = LLMEngine(model, max_seqs=4, max_len=64, page_size=8)
    eng.add_request("ref", prompt, max_new_tokens=n)
    while eng.has_work():
        eng.step()
    return eng.result("ref")


def _until(cond, what, timeout=60.0):
    """Wait for a CONDITION another thread brings about (never a fixed
    sleep: a loaded machine stretches every guess)."""
    import time
    end = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < end, f"timed out waiting for {what}"
        time.sleep(0.005)


def _connected(tracer, rid):
    """Assert every finished span carrying ``rid`` lives in ONE trace
    whose parent links all resolve; returns that trace's spans."""
    spans = tracer.finished_spans()
    tids = {s["trace_id"] for s in spans
            if s["attrs"].get("rid") == str(rid)}
    assert len(tids) == 1, f"rid {rid}: spans in {len(tids)} traces"
    tid = next(iter(tids))
    tspans = [s for s in spans if s["trace_id"] == tid]
    ids = {s["span_id"] for s in tspans}
    for s in tspans:
        assert s["parent_id"] is None or s["parent_id"] in ids, (
            f"orphan span {s['name']} ({s['span_id']}): parent "
            f"{s['parent_id']} not in trace {tid}")
    return tspans


# -- tracer core ---------------------------------------------------------------
class TestTracerCore:
    def test_disabled_span_is_null_singleton(self):
        assert T.get_tracer() is None
        assert T.span("x") is T.NULL_SPAN
        assert T.start_span("x", activate=False) is T.NULL_SPAN
        # the singleton is inert end to end: context-manager, attrs,
        # context — nothing allocates, nothing records
        with T.span("x") as sp:
            assert sp.set_attr("k", 1) is sp
            assert sp.context() is None
        T.record_span("x", 0.5)        # no tracer: silently dropped
        assert T.current_context() is None

    def test_implicit_nesting_parents_per_thread(self):
        tr = T.enable_tracing()
        with T.span("outer") as a:
            with T.span("inner") as b:
                assert b.trace_id == a.trace_id
                assert b.parent_id == a.span_id
            assert tr.current() is a
        assert tr.current() is None
        spans = tr.finished_spans()
        assert [s["name"] for s in spans] == ["inner", "outer"]

    def test_explicit_ctx_overrides_and_held_spans(self):
        tr = T.enable_tracing()
        root = tr.start_span("root", activate=False)
        held = tr.start_span("held", ctx=root.context(),
                             activate=False)
        # held spans don't capture the thread stack
        assert tr.current() is None
        assert held.span_id in {s["span_id"]
                                for s in tr.open_spans()}
        held.end()
        held.end()                     # idempotent
        root.end()
        d = held.to_dict()
        assert d["parent_id"] == root.span_id
        assert d["trace_id"] == root.trace_id
        assert len(tr.finished_spans()) == 2

    def test_ring_bound_and_dropped_counter(self):
        tr = T.enable_tracing(max_spans=4)
        for i in range(7):
            with T.span(f"s{i}"):
                pass
        spans = tr.finished_spans()
        assert len(spans) == 4
        assert [s["name"] for s in spans] == ["s3", "s4", "s5", "s6"]
        assert tr.dropped == 3

    def test_injectable_clock(self):
        clk = [10.0]
        tr = T.enable_tracing(clock=lambda: clk[0])
        sp = tr.start_span("timed")
        clk[0] = 12.5
        sp.end()
        d = sp.to_dict()
        assert d["start"] == 10.0 and d["end"] == 12.5
        assert d["duration"] == pytest.approx(2.5)

    def test_chrome_trace_export_schema(self):
        clk = [1.0]
        tr = T.enable_tracing(clock=lambda: clk[0])
        with T.span("work", attrs={"rid": "r1"}):
            clk[0] = 1.25
        blob = json.dumps(tr.to_chrome_trace())
        out = json.loads(blob)         # round-trips
        evs = out["traceEvents"]
        assert len(evs) == 1
        ev = evs[0]
        assert ev["ph"] == "X" and ev["name"] == "work"
        assert isinstance(ev["ts"], int) and isinstance(ev["dur"], int)
        assert ev["dur"] == 250_000    # 0.25 s in microseconds
        assert ev["args"]["rid"] == "r1"
        assert "trace_id" in ev["args"] and "span_id" in ev["args"]

    def test_header_inject_extract_roundtrip(self):
        ctx = {"trace_id": "t1-2", "parent_id": "s1-3"}
        h = T.inject_headers(ctx, {"Content-Type": "application/json"})
        assert h["Content-Type"] == "application/json"
        assert T.extract_headers(h) == ctx
        assert T.extract_headers({}) is None
        assert T.inject_headers(None) == {}

    def test_slow_traces_threshold_and_order(self):
        clk = [0.0]
        tr = T.enable_tracing(clock=lambda: clk[0])
        for name, dur in (("fast", 0.01), ("slow", 0.5),
                          ("slower", 2.0)):
            sp = tr.start_span(name)
            clk[0] += dur
            sp.end()
        out = tr.slow_traces(0.1)
        assert [t["name"] for t in out] == ["slower", "slow"]
        assert out[0]["n_spans"] == 1
        assert out[0]["duration"] == pytest.approx(2.0)


# -- flight recorder -----------------------------------------------------------
class TestFlightRecorder:
    def test_record_and_dump_parseable(self, tmp_path):
        tr = T.enable_tracing()
        rec = T.enable_flight_recorder(str(tmp_path / "fr.jsonl"))
        with T.span("op"):
            pass
        open_sp = tr.start_span("inflight", activate=False)
        rec.record("checkpoint", step=7)
        rec.record_error("unit", RuntimeError("boom"))
        path = rec.dump(reason="test")
        lines = [json.loads(ln) for ln in open(path)]
        assert lines[0]["type"] == "flight_recorder"
        assert lines[0]["reason"] == "test"
        kinds = [ln.get("kind") for ln in lines
                 if ln["type"] == "event"]
        assert kinds == ["checkpoint", "error"]
        spans = [ln for ln in lines if ln["type"] == "span"]
        assert {s["name"] for s in spans} == {"op", "inflight"}
        assert any(s.get("open") for s in spans
                   if s["name"] == "inflight")
        assert rec.recent_errors()[0]["error"] == \
            "RuntimeError: boom"
        open_sp.end()

    def test_event_ring_bounded(self, tmp_path):
        rec = T.enable_flight_recorder(str(tmp_path / "fr.jsonl"),
                                       max_events=3)
        for i in range(6):
            rec.record("tick", i=i)
        assert [e["i"] for e in rec.recent()] == [3, 4, 5]

    def test_guard_dumps_on_injected_fatal(self, tmp_path):
        rec = T.enable_flight_recorder(str(tmp_path / "fatal.jsonl"))
        with pytest.raises(RuntimeError, match="injected"):
            with rec.guard("fatal"):
                raise RuntimeError("injected fatal")
        lines = [json.loads(ln)
                 for ln in open(tmp_path / "fatal.jsonl")]
        assert lines[0]["reason"] == "fatal"
        errs = [ln for ln in lines if ln.get("kind") == "error"]
        assert errs and "injected fatal" in errs[0]["error"]

    def test_dump_once_per_reason(self, tmp_path):
        rec = T.enable_flight_recorder(str(tmp_path / "w.jsonl"))
        assert rec.dump_once("wedged") is not None
        assert rec.dump_once("wedged") is None
        assert rec.dumps == 1

    def test_sigterm_hook_dumps_and_survives(self, tmp_path):
        rec = T.enable_flight_recorder(str(tmp_path / "term.jsonl"))
        rec.install_signal_hook()
        try:
            os.kill(os.getpid(), signal.SIGTERM)
        finally:
            rec.uninstall_signal_hook()
        lines = [json.loads(ln) for ln in open(tmp_path / "term.jsonl")]
        assert lines[0]["reason"] == f"signal_{int(signal.SIGTERM)}"
        assert any(ln.get("kind") == "signal" for ln in lines)

    def test_ckpt_preemption_hook_dumps(self, tmp_path):
        from paddle_tpu.distributed.ckpt_manager import CheckpointManager
        rec = T.enable_flight_recorder(str(tmp_path / "pre.jsonl"))
        mgr = CheckpointManager(str(tmp_path / "ckpts"))
        mgr.install_preemption_hook()
        try:
            os.kill(os.getpid(), signal.SIGTERM)
        finally:
            mgr.uninstall_preemption_hook()
        assert mgr.preempted
        lines = [json.loads(ln) for ln in open(tmp_path / "pre.jsonl")]
        assert lines[0]["reason"] == "preempted"
        assert any(ln.get("kind") == "preempted" for ln in lines)


# -- serving: zero-cost off, bit-identity + connectivity on --------------------
class TestServingTracing:
    def _run(self, model, prompts):
        sched = _mk_sched(model)
        for i, (p, n) in enumerate(prompts):
            sched.submit(f"r{i}", p, max_new_tokens=n)
        sched.run_until_idle()
        return {f"r{i}": sched.result(f"r{i}")
                for i in range(len(prompts))}, sched

    def test_tokens_bit_identical_and_compiles_unchanged(self, model):
        prompts = [([5, 9, 2, 14], 8), ([3, 3, 7], 6), ([11, 4], 5)]
        off, _ = self._run(model, prompts)
        pc, dc = LLMEngine.prefill_compiles(), LLMEngine.decode_compiles()
        T.enable_tracing()
        on, _ = self._run(model, prompts)
        assert on == off               # tracing cannot touch tokens
        # tracing adds ZERO compiles (counts are relative: tier-1 runs
        # every module in one process, so other geometries may already
        # hold cache entries; a fresh-process run measures exactly 1)
        assert LLMEngine.prefill_compiles() == pc >= 1
        assert LLMEngine.decode_compiles() == dc

    def test_connected_trace_per_rid_direct_scheduler(self, model):
        tr = T.enable_tracing()
        _, sched = self._run(model, [([5, 9, 2], 6), ([8, 1], 4)])
        for rid in ("r0", "r1"):
            tspans = _connected(tr, rid)
            names = {s["name"] for s in tspans}
            assert {"sched.request", "sched.queue_wait",
                    "sched.admit", "llm_engine.prefill",
                    "engine.prefill_chunk"} <= names

    def test_request_timeline_structured_record(self, model):
        t = [100.0]
        sched = Scheduler(LLMEngine(model, max_seqs=4, max_len=64,
                                    page_size=8), max_queue=8,
                          clock=lambda: t[0])
        sched.submit("x", [5, 9, 2], max_new_tokens=4)
        t[0] = 101.0
        sched.run_until_idle()
        tl = sched.request_timeline("x")
        assert tl["state"] == "finished"
        assert tl["submitted"] == 100.0
        assert tl["admitted"] == 101.0
        assert tl["queue_wait"] == pytest.approx(1.0)
        assert tl["ttft"] == pytest.approx(1.0)
        assert tl["preemptions"] == 0
        assert tl["n_tokens"] == len(sched.result("x"))
        events = [e["event"] for e in tl["timeline"]]
        assert events[0] == "submitted"
        assert "admitted" in events and "first_token" in events
        assert events[-1] == "finished"
        json.dumps(tl)                 # JSON-able end to end
        with pytest.raises(EnforceError):
            sched.request_timeline("nope")

    def test_preemption_timeline_and_trace(self, model):
        tr = T.enable_tracing()
        eng = LLMEngine(model, max_seqs=1, max_len=32, page_size=8,
                        n_pages=5, enable_prefix_caching=False)
        sched = Scheduler(eng, max_queue=8)
        sched.submit("lo", [1, 2, 3], max_new_tokens=16, priority=1)
        sched.step()
        sched.step()
        sched.submit("hi", [7, 8, 9], max_new_tokens=4, priority=0)
        sched.run_until_idle()
        tl = sched.request_timeline("lo")
        events = [e["event"] for e in tl["timeline"]]
        assert "preempted" in events
        assert any(e.startswith("resumed:") for e in events)
        assert tl["preemptions"] == 1
        tspans = _connected(tr, "lo")
        names = {s["name"] for s in tspans}
        assert {"sched.preempt", "sched.suspended",
                "sched.resume"} <= names
        _connected(tr, "hi")

    def test_requests_overview_live_states(self, model):
        sched = _mk_sched(model)
        sched.submit("a", [5, 9, 2], max_new_tokens=6)
        sched.step()
        rows = sched.requests_overview()
        assert len(rows) == 1 and rows[0]["rid"] == "a"
        assert rows[0]["state"] == "active"
        assert rows[0]["age"] >= 0
        sched.run_until_idle()
        assert sched.requests_overview() == []   # terminal: not live


# -- chaos: failover / migration keep one connected trace ----------------------
class TestTraceChaos:
    @pytest.mark.parametrize("kind", ["refuse", "timeout"])
    def test_router_fault_failover_single_trace(self, model, kind):
        """An injected submit fault on the first-pick replica fails
        the request over — every terminated rid still has ONE
        connected trace."""
        tr = T.enable_tracing()
        s0, s1 = _mk_sched(model), _mk_sched(model)
        router = ReplicaRouter([s0, s1], sleep=_NOSLEEP,
                               failure_threshold=1)
        plan = FaultPlan([Fault(op="submit", kind=kind, nth=1,
                                times=1)], sleep=_NOSLEEP)
        # the router tries replicas in load order; fault the first
        # submit regardless of which replica it lands on
        hook = plan.router_hook()
        router.set_fault(0, hook)
        router.set_fault(1, hook)
        router.submit("c", [5, 9, 2], max_new_tokens=6)
        router.run_until_idle()
        assert router.pop_result("c") == _direct(model, [5, 9, 2], 6)
        tspans = _connected(tr, "c")
        assert any(s["name"] == "router.request" for s in tspans)

    def test_eject_requeue_single_trace_two_replicas(self, model):
        tr = T.enable_tracing()
        s0, s1 = _mk_sched(model), _mk_sched(model)
        router = ReplicaRouter([s0, s1], sleep=_NOSLEEP)
        router.submit("e", [5, 9, 2, 14], max_new_tokens=10)
        src = router._owner["e"]
        router.replicas[src].step()
        router.eject(src)              # dead host: requeue on survivor
        router.run_until_idle()
        assert router.pop_result("e") == \
            _direct(model, [5, 9, 2, 14], 10)
        tspans = _connected(tr, "e")
        scheds = {s["attrs"]["sched"] for s in tspans
                  if "sched" in s["attrs"]}
        assert len(scheds) == 2        # spans from BOTH replicas

    def test_drain_migration_single_trace_two_replicas(self, model):
        tr = T.enable_tracing()
        s0, s1 = _mk_sched(model), _mk_sched(model)
        router = ReplicaRouter([s0, s1], sleep=_NOSLEEP)
        router.submit("m", [5, 9, 2, 14], max_new_tokens=12)
        src = router._owner["m"]
        router.replicas[src].step()
        router.replicas[src].step()
        assert router.drain_replica(src) == ["m"]
        router.run_until_idle()
        assert router.pop_result("m") == \
            _direct(model, [5, 9, 2, 14], 12)
        tspans = _connected(tr, "m")
        names = {s["name"] for s in tspans}
        assert "sched.migrate_out" in names
        assert any(s["name"] == "sched.resume" for s in tspans)
        scheds = {s["attrs"]["sched"] for s in tspans
                  if "sched" in s["attrs"]}
        assert len(scheds) == 2

    @pytest.mark.parametrize("schedule", ["disconnect", "crash"])
    def test_remote_chaos_connected_trace(self, model, schedule):
        """PR 6 chaos schedules at the transport seam: a lost-reply
        DISCONNECT (idempotent resubmit) and a backend CRASH (prober
        ejects, survivors adopt) — every rid that terminates finished
        still has ONE connected trace; under crash it spans both
        backends."""
        from paddle_tpu.serving import HealthProber
        tr = T.enable_tracing(max_spans=16384)
        scheds = [_mk_sched(model) for _ in range(2)]
        fes = [start_http_frontend(s) for s in scheds]
        parked = None
        try:
            reps = [RemoteReplica(fe.url, timeout=30, sleep=_NOSLEEP)
                    for fe in fes]
            router = ReplicaRouter(reps, sleep=_NOSLEEP)
            faults = {
                "disconnect": [Fault(op="submit", kind="disconnect",
                                     nth=1, times=1)],
                "crash": [Fault(op="poll", kind="crash", nth=4,
                                times=1, on_crash=fes[0].kill)],
            }[schedule]
            reps[0].set_fault_plan(FaultPlan(faults, sleep=_NOSLEEP))
            prober = HealthProber(router, dead_after=2, timeout=1.0,
                                  sleep=_NOSLEEP)
            rids = [f"x{i}" for i in range(3)]
            if schedule == "crash":
                # the crash falls on the main thread's 4th poll.  What
                # it must find, on any machine, is backend 0 with
                # ADMITTED work still in flight: its loop thread (which
                # owns all stepping) takes the requests through one
                # step and then stands still until it is killed
                arrived, stepped = threading.Event(), threading.Event()

                def one_step_then_stand():
                    assert arrived.wait(60)
                    scheds[0].step()
                    stepped.set()
                    fes[0]._stop.wait(60)
                parked = threading.Thread(
                    target=fes[0]._on_loop, args=(one_step_then_stand,))
                parked.start()
            for i, rid in enumerate(rids):
                router.submit(rid, [1 + i, 2, 3], max_new_tokens=8)
            if parked is not None:
                arrived.set()
                assert stepped.wait(60)
                assert all(r["state"] == "active"
                           for r in scheds[0].requests_overview())
            steps = 0
            while router.busy() and steps < 3000:
                router.step()
                steps += 1
                if steps % 10 == 0:
                    prober.probe_once()
            finished = [r for r in rids
                        if reps[router._owner[r]].status(r)
                        == "finished"] if schedule == "disconnect" \
                else [r for r in rids if r in router._owner]
            assert finished, "no rid terminated — rig broken"
            used = set()
            for rid in finished:
                tspans = _connected(tr, rid)
                used |= {s["attrs"]["sched"] for s in tspans
                         if "sched" in s["attrs"]}
            if schedule == "crash":
                # requeued work admitted on the survivor: the traces
                # collectively span both backends' schedulers
                assert len(used) == 2, used
        finally:
            for fe in fes:
                try:
                    fe.shutdown(drain=False)
                except Exception:
                    pass
            if parked is not None:
                parked.join(30)
                assert not parked.is_alive()

    def test_remote_hop_headers_connect_trace(self, model):
        """Trace context crosses the HTTP seam in HEADERS: a client
        span's context submitted through RemoteReplica parents the
        backend scheduler's spans."""
        tr = T.enable_tracing()
        sched = _mk_sched(model)
        fe = start_http_frontend(sched)
        try:
            rep = RemoteReplica(fe.url, timeout=30)
            root = tr.start_span("client.request", activate=False,
                                 attrs={"rid": "rr"})
            rep.submit("rr", [5, 9, 2], max_new_tokens=6,
                       trace_ctx=root.context())
            rep.run_until_idle(max_steps=2000)
            root.end()
            assert rep.pop_result("rr") == \
                _direct(model, [5, 9, 2], 6)
        finally:
            fe.shutdown()
        tspans = _connected(tr, "rr")
        names = {s["name"] for s in tspans}
        assert "client.request" in names
        assert "sched.admit" in names  # backend joined the trace


# -- live debug endpoints ------------------------------------------------------
class TestDebugEndpoints:
    def test_statusz_roundtrip(self, model):
        T.enable_tracing()
        rec = T.enable_flight_recorder()
        rec.record_error("unit", RuntimeError("seen"))
        sched = _mk_sched(model)
        # run one request BEFORE the frontend exists (its loop thread
        # owns all stepping once started — never step from two threads)
        sched.submit("done", [1, 2], max_new_tokens=2)
        sched.run_until_idle()
        fe = start_http_frontend(sched)
        try:
            def submit_and_fetch():
                # on the loop thread, which therefore cannot step: the
                # request is still live when the HTTP thread renders
                # the page, however fast 40 tokens decode
                sched.submit("s", [5, 9, 2], max_new_tokens=40)
                return urllib.request.urlopen(fe.url + "/statusz",
                                              timeout=30).read()
            raw = fe._on_loop(submit_and_fetch)
            out = json.loads(raw)      # round-trips
            assert out["status"] == "ok"
            assert out["uptime_seconds"] >= 0
            assert out["build"]["python"]
            assert out["build"]["jax"]
            rows = out["requests"]
            assert [r["rid"] for r in rows] == ["s"]
            assert rows[0]["state"] in ("waiting", "active")
            assert rows[0]["age"] >= 0
            assert out["target"]["kv_page_utilization"] is not None
            assert out["tracing"]["enabled"] is True
            assert out["recent_errors"][0]["error"] == \
                "RuntimeError: seen"
            sched.cancel("s")
        finally:
            fe.shutdown()

    def test_tracez_slow_traces_and_disabled(self, model):
        sched = _mk_sched(model)
        # populate the tracer BEFORE the frontend owns the stepping
        T.disable_tracing()
        fe0 = start_http_frontend(sched)
        try:
            out = json.loads(urllib.request.urlopen(
                fe0.url + "/tracez").read())
            assert out == {"enabled": False, "threshold_ms": 100.0,
                           "traces": []}
        finally:
            fe0.shutdown()             # drains: re-open admission
        sched.resume_admission()
        T.enable_tracing()
        sched.submit("z", [5, 9, 2], max_new_tokens=4)
        sched.run_until_idle()
        fe = start_http_frontend(sched)
        try:
            out = json.loads(urllib.request.urlopen(
                fe.url + "/tracez?threshold_ms=0&limit=5").read())
            assert out["enabled"] is True
            assert out["traces"], "expected at least one trace"
            t0 = out["traces"][0]
            assert t0["duration_ms"] >= 0
            assert t0["n_spans"] == len(t0["spans"])
            spans = {s["name"] for t in out["traces"]
                     for s in t["spans"]}
            assert "sched.admit" in spans
        finally:
            fe.shutdown()

    def test_timeline_endpoint_and_slow_request_log(self, model,
                                                    caplog):
        T.enable_tracing()
        sched = _mk_sched(model)
        fe = start_http_frontend(sched, slow_ttft=0.0)
        try:
            body = json.dumps({"prompt": [5, 9, 2], "max_tokens": 4,
                               "stream": False, "id": "slow1"}
                              ).encode()
            req = urllib.request.Request(
                fe.url + "/v1/completions", data=body,
                headers={"Content-Type": "application/json"})
            def slow():
                return [r for r in caplog.records
                        if "slow request" in r.getMessage()]
            with caplog.at_level(logging.WARNING,
                                 logger="paddle_tpu.serving"):
                out = json.loads(urllib.request.urlopen(req).read())
                # the handler thread logs AFTER it has answered: keep
                # the level until the line is there
                _until(slow, "the slow-request log line")
            assert out["state"] == "finished"
            msg = slow()[0].getMessage()
            assert "rid=slow1" in msg and "trace_id=" in msg

            def post(path, obj):
                req = urllib.request.Request(
                    fe.url + path, data=json.dumps(obj).encode(),
                    headers={"Content-Type": "application/json"})
                return json.loads(urllib.request.urlopen(req).read())

            # /v1/timeline through the control plane (the loop thread
            # owns all stepping; the client only submits and polls)
            assert post("/v1/submit", {"id": "tl", "prompt": [1, 2, 3],
                                       "max_tokens": 4})["accepted"]
            _until(lambda: post("/v1/poll", {"ids": ["tl"]})
                   ["requests"]["tl"]["state"] == "finished",
                   "request tl to finish")
            out = post("/v1/timeline", {"id": "tl"})
            assert out["timeline"]["state"] == "finished"
            assert out["timeline"]["ttft"] is not None
        finally:
            fe.shutdown()


# -- profiler bridge -----------------------------------------------------------
class TestProfilerBridge:
    def test_record_event_lands_in_tracer(self):
        from paddle_tpu.profiler import RecordEvent
        tr = T.enable_tracing()
        with T.span("parent") as p:
            with RecordEvent("user.range"):
                pass
        spans = {s["name"]: s for s in tr.finished_spans()}
        assert "user.range" in spans
        assert spans["user.range"]["parent_id"] == p.span_id

    def test_export_chrome_tracing_includes_tracer_spans(self,
                                                         tmp_path):
        from paddle_tpu import profiler
        T.enable_tracing()
        prof = profiler.Profiler(
            timer_only=True,
            on_trace_ready=profiler.export_chrome_tracing(
                str(tmp_path)))
        prof.start()
        with profiler.RecordEvent("bridge.range"):
            pass
        prof.step()
        prof.stop()
        out = json.loads(
            (tmp_path / "steps.chrome_trace.json").read_text())
        names = {e["name"] for e in out["traceEvents"]}
        assert "bridge.range" in names
        # the tracer's copy rides on its own track with span ids
        tids = {e.get("tid") for e in out["traceEvents"]
                if e["name"] == "bridge.range"}
        assert {1, 2} <= tids          # host-event AND tracer tracks


# -- histogram quantiles -------------------------------------------------------
class TestHistogramQuantiles:
    def test_bucket_interpolation(self):
        reg = MetricRegistry()
        h = reg.histogram("q", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5, n=2)
        h.observe(0.9)
        # ranks: q50 -> 2 of 4, inside (0.1, 1.0] holding 3 obs
        assert h.quantile(0.5) == pytest.approx(
            0.1 + 0.9 * (2 - 1) / 3)
        assert h.quantile(0.0) == 0.0 or h.quantile(0.0) <= 0.1
        h.observe(5.0)                 # overflow clamps to last bound
        assert h.quantile(0.99) == 1.0
        with pytest.raises(EnforceError):
            h.quantile(1.5)

    def test_snapshot_and_empty(self):
        reg = MetricRegistry()
        h = reg.histogram("q2", buckets=(1.0, 2.0))
        assert h.snapshot()["p95"] is None   # empty: no percentile
        h.observe(1.5, n=100)
        snap = h.snapshot()
        assert set(snap) >= {"count", "sum", "mean", "buckets",
                             "p50", "p95", "p99"}
        assert 1.0 <= snap["p50"] <= 2.0
        json.dumps(snap)


# -- training-side spans + tier-1 budget guard ---------------------------------
class TestTrainingSpans:
    def test_compiled_step_and_checkpoint_spans(self, tmp_path):
        import paddle_tpu.nn as nn
        from paddle_tpu.distributed.ckpt_manager import CheckpointManager
        from paddle_tpu.jit.train import CompiledTrainStep
        paddle.seed(3)
        model = nn.Linear(4, 2)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        step = CompiledTrainStep(
            model,
            lambda m, b: ((m(b["x"]) - b["y"]) ** 2).mean(), opt)
        batch = {"x": np.ones((2, 4), np.float32),
                 "y": np.zeros((2, 2), np.float32)}
        step(batch)                    # compile with tracing OFF
        tr = T.enable_tracing()
        step(batch)
        mgr = CheckpointManager(str(tmp_path / "ck"))
        mgr.save(step, 1)
        names = [s["name"] for s in tr.finished_spans()]
        assert "train.compiled_step" in names
        assert "train.checkpoint_save" in names
        assert step.step_compiles() == 1   # tracing added no compile

    def test_tier1_budget_guard_tracing_off_zero_cost(self, model):
        """The zero-cost contract tier-1 enforces: with no tracer,
        every instrumentation site returns the shared NULL_SPAN (no
        per-call allocation), a serving run records nothing, and the
        compile-count invariants hold; this module's fast tests stay
        bounded and soaks (none yet) must be slow-marked."""
        assert T.get_tracer() is None
        assert T.span("engine.decode") is T.NULL_SPAN
        assert T.start_span("x", activate=False) is T.NULL_SPAN
        pc = LLMEngine.prefill_compiles()
        sched = _mk_sched(model)
        sched.submit("g", [5, 9, 2], max_new_tokens=4)
        sched.run_until_idle()
        # nothing beyond the geometry's one program — whether this
        # process already compiled it (pc) or this was the first use
        assert LLMEngine.prefill_compiles() <= max(pc, 1)
        assert T.get_tracer() is None  # nothing enabled it midway
        src = (Path(__file__).resolve().parent
               / "test_tracing.py").read_text()
        n_fast = 0
        for m in re.finditer(r"((?:@[\w.]+(?:\(.*?\))?\s*\n\s*)*)"
                             r"def (test_\w+)\(", src):
            if "soak" in m.group(2):
                assert "pytest.mark.slow" in m.group(1), (
                    f"{m.group(2)} must be @pytest.mark.slow")
            if "pytest.mark.slow" not in m.group(1):
                n_fast += 1
        assert n_fast <= 40, (
            f"{n_fast} fast tracing tests — move heavy ones behind "
            f"@pytest.mark.slow to protect the 870 s tier-1 budget")
