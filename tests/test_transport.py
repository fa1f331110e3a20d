"""Fault-tolerant multi-host serving — remote transport, health
probing, KV-migrating drain, and the chaos harness (ISSUE 6).

Contracts under test:
* portable swap blobs: ``export_swap``/``import_swap`` round-trip
  byte-exact across caches (shared prefix pages materialized into the
  blob), refuse mismatched geometry, and degrade to recompute when
  the destination pool can't hold them;
* engine/scheduler/router migration: a drained replica's in-flight
  decodes resume on another replica BIT-IDENTICAL on both restore
  paths (swap-in and recompute), streams continue without duplicate
  or missing tokens;
* ``RemoteReplica``: the same duck-typed surface over HTTP, retried
  with bounded backoff, and IDEMPOTENT by rid — a lost-reply retry
  never double-admits;
* ``HealthProber``: slow opens the circuit (half-open probe decides
  recovery), dead ejects + requeues onto survivors;
* the chaos invariant: under every injected fault schedule
  (refused / timeout / slow / disconnect / crash), every submitted
  rid terminates in exactly one of finished / cancelled / shed
  (deadline expiry = shed reason ``deadline``, the timeout case) —
  no request is ever lost or left hanging;
* server satellites: oversized bodies → 413, ``/healthz`` → 503
  while draining or wedged, ``request_timeout`` becomes the
  scheduler deadline on submit.

Everything runs JAX_PLATFORMS=cpu; the HTTP rigs are per-test and
torn down by the fixture (the conftest thread-leak guard enforces
it).
"""
import http.client
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.common.errors import EnforceError, InvalidArgumentError
from paddle_tpu.inference.engine import LLMEngine
from paddle_tpu.inference.paged_cache import PagedKVCache
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.serving import (Fault, FaultPlan, HealthProber,
                                RejectedError, RemoteReplica,
                                ReplicaRouter, Scheduler,
                                start_http_frontend)

_NOSLEEP = lambda s: None                      # noqa: E731


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = LlamaForCausalLM(llama_tiny_config())
    m.eval()
    return m


def _direct(model, prompt, n, **ekw):
    eng = LLMEngine(model, max_seqs=4, max_len=64, page_size=8, **ekw)
    eng.add_request("ref", prompt, max_new_tokens=n)
    while eng.has_work():
        eng.step()
    return eng.result("ref")


def _mk_engine(model, **kw):
    cfg = dict(max_seqs=4, max_len=64, page_size=8)
    cfg.update(kw)
    return LLMEngine(model, **cfg)


def _hold_mid_decode(scheds, until, after_tokens=2):
    """Make "mid-decode" a condition and not a race against 48 tiny
    steps: every scheduler's engine stops making progress once its
    requests hold ``after_tokens`` generated tokens, until ``until()``
    is true.  The serving loop keeps turning (``step`` returns ``{}``),
    so polls are answered and marshalled commands — a drain — still
    run on the loop thread."""
    def hold(engine):
        step = engine.step

        def held():
            if not until() and sum(len(r.out) for r in
                                   engine.requests.values()) >= after_tokens:
                time.sleep(0.001)
                return {}
            return step()
        engine.step = held
    for sc in scheds:
        hold(sc.engine)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class Tracker:
    """Per-rid event log + terminal-state accounting for the chaos
    invariant (every rid exactly one terminal)."""

    def __init__(self):
        self.events = {}
        self.terminals = {}

    def cb(self, rid):
        def on_ev(ev):
            self.events.setdefault(rid, []).append(ev)
            if ev["type"] in ("finished", "cancelled", "shed"):
                self.terminals.setdefault(rid, []).append(ev)
        return on_ev

    def streamed(self, rid):
        return [t for ev in self.events.get(rid, [])
                if ev["type"] == "tokens" for t in ev["tokens"]]


# -- portable swap blobs -------------------------------------------------------
def _mk_cache(**kw):
    cfg = dict(n_pages=9, page_size=4, n_kv_heads=1, head_dim=4,
               max_seqs=2, max_len=16, num_layers=2,
               swap_pool_pages=8)
    cfg.update(kw)
    return PagedKVCache(**cfg)


def _fill(cache, slot, n_tok, seed=0):
    rng = np.random.default_rng(seed)
    L = cache.num_layers
    k = rng.standard_normal((L, n_tok, 1, 4)).astype(np.float32)
    v = rng.standard_normal((L, n_tok, 1, 4)).astype(np.float32)
    cache.write_prefill(slot, k, v)
    return k, v


def test_export_import_swap_roundtrip_bytes_exact():
    import jax
    src, dst = _mk_cache(), _mk_cache()
    slot = src.allocate(12)
    _fill(src, slot, 10, seed=3)
    before_k = np.asarray(jax.device_get(
        src.k_pages[:, :, src._pages[slot][:3]]))
    handle = src.swap_out(slot)
    blob = src.export_swap(handle)
    assert isinstance(blob, bytes) and len(blob) > 0
    assert src.swap_pool_used() == 0           # export consumed it
    assert src.export_swap(handle) is None     # and it stays consumed
    h2 = dst.import_swap(blob)
    assert h2 is not None
    assert dst.swap_pool_used() == 3           # 10 tok / P=4 -> 3 pages
    slot2 = dst.swap_in(h2, 12)
    assert slot2 is not None
    after_k = np.asarray(jax.device_get(
        dst.k_pages[:, :, dst._pages[slot2][:3]]))
    np.testing.assert_array_equal(before_k, after_k)
    assert dst.metrics_snapshot()["swap_imported_pages"] == 3
    assert src.metrics_snapshot()["swap_exported_pages"] == 3


def test_export_materializes_registered_prefix_pages():
    """Pages swap_out recorded by chain key (shared prefix — never
    copied locally) are read out of the device and shipped as DATA:
    a migration blob is self-contained, the destination need not hold
    this host's prefix index."""
    src, dst = _mk_cache(), _mk_cache()
    toks = list(range(8))                      # 2 full pages
    slot = src.allocate(10)
    _fill(src, slot, 8, seed=5)
    src.register_prefix(slot, toks)
    handle = src.swap_out(slot)
    assert src.swap_pool_used() == 0           # keys only: nothing copied
    blob = src.export_swap(handle)
    h2 = dst.import_swap(blob)
    assert h2 is not None
    assert dst.swap_pool_used() == 2           # materialized as data
    assert dst.swap_in(h2, 10) is not None
    assert dst.metrics_snapshot()["swap_fallbacks"] == 0


def test_import_swap_geometry_mismatch_raises():
    src = _mk_cache()
    slot = src.allocate(8)
    _fill(src, slot, 6)
    blob = src.export_swap(src.swap_out(slot))
    with pytest.raises(EnforceError):
        _mk_cache(page_size=8, max_len=32).import_swap(blob)
    with pytest.raises(EnforceError):
        _mk_cache(num_layers=1).import_swap(blob)


def test_import_swap_pool_full_degrades_to_none():
    src = _mk_cache()
    slot = src.allocate(8)
    _fill(src, slot, 6)
    blob = src.export_swap(src.swap_out(slot))
    dst = _mk_cache(swap_pool_pages=1)         # blob needs 2 pages
    before = dst.metrics_snapshot()["swap_fallbacks"]
    assert dst.import_swap(blob) is None       # recompute signal
    assert dst.metrics_snapshot()["swap_fallbacks"] == before + 1
    assert _mk_cache(swap_pool_pages=0).import_swap(blob) is None
    assert src.import_swap(None) is None       # no blob: recompute


# -- engine-level migration ----------------------------------------------------
def test_engine_export_import_resume_bit_identical(model):
    want = _direct(model, [5, 9, 2, 14], 12)
    e0, e1 = _mk_engine(model), _mk_engine(model)
    e0.add_request("x", [5, 9, 2, 14], max_new_tokens=12)
    e0.step()
    e0.step()
    e0.suspend("x")
    pkg = e0.export_request("x")
    assert "x" not in e0.requests              # it left this engine
    assert pkg["swap"] is not None
    e1.import_request(pkg)
    assert e1.resume("x") == "swap_in"         # pages travelled
    while e1.has_work():
        e1.step()
    assert e1.result("x") == want


def test_engine_export_recompute_fallback_bit_identical(model):
    """Source swap pool disabled: the package ships swap=None and the
    destination replays prompt + generated tokens — still
    bit-identical."""
    want = _direct(model, [3, 3, 7], 10)
    e0 = _mk_engine(model, swap_pool_pages=0)
    e1 = _mk_engine(model)
    e0.add_request("y", [3, 3, 7], max_new_tokens=10)
    e0.step()
    e0.suspend("y")
    pkg = e0.export_request("y")
    assert pkg["swap"] is None
    e1.import_request(pkg)
    assert e1.resume("y") == "recompute"
    while e1.has_work():
        e1.step()
    assert e1.result("y") == want


def test_engine_import_enforces_limits(model):
    e0, small = _mk_engine(model), _mk_engine(model, max_len=16)
    e0.add_request("z", list(range(1, 12)), max_new_tokens=12)
    e0.step()
    e0.suspend("z")
    pkg = e0.export_request("z")
    with pytest.raises(EnforceError):          # 23 tokens > max_len 16
        small.import_request(pkg)
    assert "z" not in small.requests
    e1 = _mk_engine(model)
    e1.import_request(pkg)                     # blob is reusable
    e1.resume("z")
    with pytest.raises(EnforceError):
        e1.import_request(pkg)                 # duplicate rid


# -- scheduler-level migration -------------------------------------------------
def test_sched_migrate_waiting_request_rebases_deadline(model):
    clock = FakeClock()
    e0 = _mk_engine(model, max_seqs=1, n_pages=3, page_size=8,
                    max_len=32, enable_prefix_caching=False)
    s0 = Scheduler(e0, max_queue=4, clock=clock)
    s1 = Scheduler(_mk_engine(model), max_queue=4, clock=clock)
    s0.submit("hog", [1, 2, 3], max_new_tokens=4)
    s0.step()                                  # hog takes the only slot
    clock.advance(2.0)
    s0.submit("w", [4, 5, 6], max_new_tokens=4, deadline=10.0)
    pkg = s0.migrate_out("w")
    assert pkg["admitted"] is False and pkg["tokens"] == []
    assert pkg["deadline_remaining"] == pytest.approx(10.0)
    assert s0.knows("w") is False
    clock.advance(1.0)
    s1.migrate_in(pkg)
    assert s1._reqs["w"].deadline == pytest.approx(13.0)  # re-based
    s0.run_until_idle()
    s1.run_until_idle()
    assert len(s1.result("w")) == 4
    assert s1.metrics_snapshot()["sched"] is not None
    assert int(s0.metrics_snapshot()["migrated_out"]) == 1
    assert int(s1.metrics_snapshot()["migrated_in"]) == 1


def test_sched_migrate_cancel_pending_resolves_cancel(model):
    s0 = Scheduler(_mk_engine(model), max_queue=4)
    s0.submit("c", [5, 9, 2], max_new_tokens=8)
    s0.step()
    s0.cancel("c")                             # active: abort is deferred
    assert s0.migrate_out("c") is None         # cancel wins, not a move
    assert s0.status("c") == "cancelled"
    s0.run_until_idle()


# -- router: drain + eject -----------------------------------------------------
def test_drain_replica_migrates_inflight_bit_identical(model):
    """Active AND waiting requests move; tokens bit-identical; the
    stream picks up with no duplicate or missing tokens."""
    want_a = _direct(model, [5, 9, 2, 14], 12)
    want_b = _direct(model, [3, 3, 7], 8)
    e0 = _mk_engine(model)
    e1 = _mk_engine(model)
    s0, s1 = Scheduler(e0, max_queue=8), Scheduler(e1, max_queue=8)
    router = ReplicaRouter([s0, s1], sleep=_NOSLEEP)
    tr = Tracker()
    # force both onto replica 0 so the drain moves an active + a
    # waiting-ish pair
    router.submit("a", [5, 9, 2, 14], max_new_tokens=12,
                  on_event=tr.cb("a"))
    src = router._owner["a"]
    router.replicas[src].step()
    router.replicas[src].step()
    moved = router.drain_replica(src)
    assert "a" in moved
    router.run_until_idle()
    assert router._owner["a"] == 1 - src
    assert router.pop_result("a") == want_a
    assert tr.streamed("a") == want_a          # seamless stream
    assert [e["type"] for e in tr.terminals["a"]] == ["finished"]
    # the drained replica refuses new work until reinstated
    with pytest.raises(RejectedError):
        router.replicas[src].submit("n", [1, 2], max_new_tokens=2)
    router.replicas[src].resume_admission()
    router.submit("b", [3, 3, 7], max_new_tokens=8,
                  on_event=tr.cb("b"))
    router.run_until_idle()
    assert router.pop_result("b") == want_b
    snap = router.metrics_snapshot()
    assert snap["replicas"][1 - src]["sched"]["migrated_in"] == 1


def test_drain_replica_recompute_fallback(model):
    """Source pool disabled AND destination pool disabled both land on
    the recompute path — bit-identical either way."""
    want = _direct(model, [5, 9, 2], 10)
    for src_kw, dst_kw in [({"swap_pool_pages": 0}, {}),
                           ({}, {"swap_pool_pages": 0})]:
        e0, e1 = _mk_engine(model, **src_kw), _mk_engine(model, **dst_kw)
        router = ReplicaRouter(
            [Scheduler(e0, max_queue=4), Scheduler(e1, max_queue=4)],
            sleep=_NOSLEEP)
        router.submit("r", [5, 9, 2], max_new_tokens=10)
        src = router._owner["r"]
        router.replicas[src].step()
        assert router.drain_replica(src) == ["r"]
        router.run_until_idle()
        assert router.pop_result("r") == want
        dst_eng = e1 if src == 0 else e0
        reg = dst_eng.metrics_snapshot()
        assert reg["kv_cache"]["swap_in_pages"] == 0   # recompute path


def test_eject_requeues_inflight_and_stream_continues(model):
    """A dead replica's requests replay on the survivor from the
    remembered prompt; the event tap suppresses the re-streamed
    prefix so the client sees each token exactly once."""
    want = _direct(model, [5, 9, 2, 14], 10)
    s0 = Scheduler(_mk_engine(model), max_queue=8)
    s1 = Scheduler(_mk_engine(model), max_queue=8)
    router = ReplicaRouter([s0, s1], sleep=_NOSLEEP)
    tr = Tracker()
    router.submit("e", [5, 9, 2, 14], max_new_tokens=10,
                  on_event=tr.cb("e"))
    src = router._owner["e"]
    router.replicas[src].step()
    router.replicas[src].step()
    delivered = len(tr.streamed("e"))
    assert delivered >= 1
    requeued = router.eject(src)
    assert requeued == ["e"]
    assert router.eject(src) == []             # idempotent
    assert router._owner["e"] == 1 - src
    assert not router._healthy(src)
    router.run_until_idle()
    assert router.pop_result("e") == want
    assert tr.streamed("e") == want            # no dupes, no gaps
    assert [e["type"] for e in tr.terminals["e"]] == ["finished"]
    snap = router.metrics_snapshot()
    assert snap["ejected"] == [src]
    text = paddle.observability.get_registry().expose_text()
    assert "serving_router_ejected_total" in text
    assert "serving_router_requeued_total" in text


def test_eject_with_no_survivor_sheds_not_hangs(model):
    s0 = Scheduler(_mk_engine(model), max_queue=4)
    router = ReplicaRouter([s0], sleep=_NOSLEEP)
    tr = Tracker()
    router.submit("x", [1, 2, 3], max_new_tokens=6,
                  on_event=tr.cb("x"))
    router.step()
    router.eject(0)
    assert [e["type"] for e in tr.terminals["x"]] == ["shed"]
    assert tr.terminals["x"][0]["reason"] == "replica_ejected"
    assert not router.busy()                   # nothing left to drive


def test_half_open_probe_races_concurrent_submits(model):
    """ISSUE 6 satellite: concurrent submits hitting the half-open
    window — every request admits exactly once, the circuit re-closes
    on the successful probe, and nothing raises."""
    clock = FakeClock()
    scheds = [Scheduler(_mk_engine(model), max_queue=16, clock=clock)
              for _ in range(2)]
    router = ReplicaRouter(scheds, failure_threshold=1, cooldown=5.0,
                           clock=clock, sleep=_NOSLEEP)
    down = {"on": True}

    def flaky(rid):
        if down["on"]:
            raise RuntimeError("injected: replica down")

    router.set_fault(0, flaky)
    router.submit("warm", [1, 2], max_new_tokens=2)
    assert router.healthy_replicas() == [1]    # circuit opened on 0
    down["on"] = False                         # replica recovers
    clock.advance(6.0)                         # past cooldown: half-open
    errs = []
    barrier = threading.Barrier(4)

    def worker(i):
        barrier.wait()
        try:
            router.submit(f"c{i}", [1 + i, 2, 3], max_new_tokens=2)
        except Exception as e:                 # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(4)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    assert errs == []
    assert router.healthy_replicas() == [0, 1]  # probe closed it
    router.run_until_idle()
    for i in range(4):
        assert len(router.result(f"c{i}")) == 2
    # exactly-once admission: each rid has exactly one owner record
    placed = sum(1 for s in scheds for r in s._reqs
                 if str(r).startswith("c"))
    assert placed == 4


# -- remote transport over HTTP ------------------------------------------------
@pytest.fixture()
def rig(model):
    made = []

    def make(n=2, sched_kw=None, engine_kw=None, **rep_kw):
        fes, scheds = [], []
        for _ in range(n):
            eng = _mk_engine(model, **(engine_kw or {}))
            sc = Scheduler(eng, max_queue=8, **(sched_kw or {}))
            scheds.append(sc)
            fes.append(start_http_frontend(sc))
        made.extend(fes)
        reps = [RemoteReplica(fe.url, timeout=30, sleep=_NOSLEEP,
                              **rep_kw) for fe in fes]
        router = ReplicaRouter(reps, sleep=_NOSLEEP)
        return fes, scheds, reps, router

    yield make
    for fe in made:
        try:
            fe.shutdown(drain=False)
        except Exception:
            pass


def test_remote_replica_matches_direct_engine(model, rig):
    want = _direct(model, [5, 9, 2, 14], 8)
    fes, scheds, reps, router = rig()
    tr = Tracker()
    router.submit("h1", [5, 9, 2, 14], max_new_tokens=8,
                  on_event=tr.cb("h1"))
    router.run_until_idle(max_steps=5000)
    assert router.pop_result("h1") == want
    assert tr.streamed("h1") == want
    assert [e["type"] for e in tr.terminals["h1"]] == ["finished"]
    # the control-plane surface works end to end
    snap = router.metrics_snapshot()
    assert snap["replicas"][0]["sched"]["sched"] is not None
    assert reps[0].load() >= 0
    assert reps[0].health()["status"] == "ok"


def test_remote_idempotent_resubmission_on_lost_reply(model, rig):
    """A disconnect AFTER the server admitted: the retry acks as a
    duplicate — admitted exactly once, tokens exactly once."""
    want = _direct(model, [5, 9, 2], 6)
    fes, scheds, reps, router = rig(n=1)
    plan = FaultPlan(
        [Fault(op="submit", kind="disconnect", nth=1, times=1)],
        sleep=_NOSLEEP)
    reps[0].set_fault_plan(plan)
    reps[0].submit("i1", [5, 9, 2], max_new_tokens=6)
    reps[0].run_until_idle(max_steps=5000)
    assert reps[0].pop_result("i1") == want
    assert plan.injected == {"disconnect": 1}
    assert scheds[0].metrics_snapshot()["admitted"] == 1  # not twice
    text = paddle.observability.get_registry().expose_text()
    assert "serving_transport_retries_total" in text
    assert "serving_transport_calls_total" in text


def test_remote_drain_migrates_mid_decode(model, rig):
    """The full multi-host hop: suspend on host A, blob over HTTP,
    swap-in on host B — bit-identical tokens, seamless stream, source
    healthz flips to 503 draining."""
    N = 48
    want = _direct(model, [5, 9, 2, 14], N)
    fes, scheds, reps, router = rig()
    drained = threading.Event()
    _hold_mid_decode(scheds, drained.is_set)
    tr = Tracker()
    idx = router.submit("m1", [5, 9, 2, 14], max_new_tokens=N,
                        on_event=tr.cb("m1"))
    router.step()                              # pull some tokens
    moved = router.drain_replica(idx)
    drained.set()
    assert moved == ["m1"]                     # still decoding: it moved
    router.run_until_idle(max_steps=8000)
    assert router.pop_result("m1") == want
    assert tr.streamed("m1") == want
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(fes[idx].url + "/healthz", timeout=30)
    assert ei.value.code == 503
    assert json.loads(ei.value.read())["status"] == "draining"
    reps[idx].resume_admission()
    assert reps[idx].health()["status"] == "ok"


def test_prober_kill_ejects_and_requeues(model, rig):
    """A crashed backend: the prober declares it dead, the router
    ejects + requeues, and the client stream continues without
    duplicates."""
    N = 48
    want = _direct(model, [3, 3, 7], N)
    fes, scheds, reps, router = rig()
    killed = threading.Event()
    _hold_mid_decode(scheds, killed.is_set)
    tr = Tracker()
    idx = router.submit("k1", [3, 3, 7], max_new_tokens=N,
                        on_event=tr.cb("k1"))
    router.step()
    prober = HealthProber(router, dead_after=1, timeout=1.0,
                          sleep=_NOSLEEP)
    fes[idx].kill()
    killed.set()
    out = prober.probe_once()
    assert out[idx] == "ejected"
    assert router._owner["k1"] == 1 - idx
    router.run_until_idle(max_steps=8000)
    assert router.pop_result("k1") == want
    assert tr.streamed("k1") == want           # tap suppressed replays
    assert [e["type"] for e in tr.terminals["k1"]] == ["finished"]
    text = paddle.observability.get_registry().expose_text()
    assert "serving_probe_checks_total" in text


def test_prober_slow_opens_circuit_then_recovers(model, rig):
    clock = FakeClock()
    fes, scheds, reps, router = rig()
    router._clock = clock
    plan = FaultPlan([Fault(op="health", kind="timeout", nth=1,
                            times=1)], sleep=_NOSLEEP)
    reps[0].set_fault_plan(plan)
    prober = HealthProber(router, dead_after=2, timeout=1.0,
                          sleep=_NOSLEEP, clock=clock)
    assert prober.probe_once()[0] == "slow"    # timeout != dead
    assert router.healthy_replicas() == [1]    # circuit opened
    assert not router.is_ejected(0)            # but NOT ejected
    clock.advance(router.cooldown + 1)         # half-open window
    assert 0 in router.healthy_replicas()
    assert prober.probe_once()[0] == "ok"      # fault exhausted


def test_prober_background_thread_start_stop(model, rig):
    fes, scheds, reps, router = rig(n=1)
    prober = HealthProber(router, interval=0.01, dead_after=3,
                          timeout=2.0).start()
    import time as _t
    _t.sleep(0.1)
    prober.stop()                              # guard checks no leak
    assert router.healthy_replicas() == [0]


# -- chaos suite ---------------------------------------------------------------
def _drive(router, prober=None, max_steps=3000, probe_every=10):
    steps = 0
    while router.busy() and steps < max_steps:
        router.step()
        steps += 1
        if prober is not None and steps % probe_every == 0:
            prober.probe_once()
    return steps


@pytest.mark.parametrize("schedule", ["refused", "timeout", "slow",
                                      "disconnect", "crash"])
def test_chaos_no_lost_requests(model, rig, schedule):
    """THE invariant: under every injected fault schedule, every
    submitted rid terminates in exactly one of finished / cancelled /
    shed (deadline-expired waiting = shed reason ``deadline``, the
    timeout case) — and finished rids' tokens are bit-identical to a
    faultless run."""
    N = 24
    want = {f"q{i}": _direct(model, [1 + i, 2, 3], N)
            for i in range(4)}
    fes, scheds, reps, router = rig()
    faults = {
        "refused": [Fault(op="submit", kind="refuse", nth=1, times=2),
                    Fault(op="poll", kind="refuse", nth=3, times=2)],
        "timeout": [Fault(op="submit", kind="timeout", nth=1, times=1),
                    Fault(op="poll", kind="timeout", nth=4, times=2)],
        "slow": [Fault(op="*", kind="slow", nth=1, times=None,
                       delay=0.01)],
        "disconnect": [
            Fault(op="submit", kind="disconnect", nth=1, times=1),
            Fault(op="poll", kind="disconnect", nth=5, times=1)],
        "crash": [Fault(op="poll", kind="crash", nth=6, times=1,
                        on_crash=fes[0].kill)],
    }[schedule]
    plan = FaultPlan(faults, sleep=_NOSLEEP)
    reps[0].set_fault_plan(plan)
    # no request finishes before the schedule's first fault has fired
    # (the crash waits for the sixth poll)
    _hold_mid_decode(scheds, lambda: bool(plan.injected))
    prober = HealthProber(router, dead_after=2, timeout=1.0,
                          sleep=_NOSLEEP)
    tr = Tracker()
    outcomes = {}
    for i in range(4):
        rid = f"q{i}"
        try:
            router.submit(rid, [1 + i, 2, 3], max_new_tokens=N,
                          on_event=tr.cb(rid))
            outcomes[rid] = "submitted"
        except (RejectedError, Exception):
            # refused at submit: the CLIENT knows immediately — that
            # is a terminal answer, not a lost request
            outcomes[rid] = "rejected_at_submit"
    # one cancel mid-flight exercises the cancelled terminal
    victim = next((r for r, o in outcomes.items()
                   if o == "submitted"), None)
    router.step()
    if victim is not None:
        try:
            router.cancel(victim)
        except Exception:
            pass
    _drive(router, prober=prober)
    assert plan.injected, "schedule injected nothing"
    for rid, o in outcomes.items():
        if o != "submitted":
            continue
        terms = tr.terminals.get(rid, [])
        assert len(terms) == 1, \
            f"{schedule}: rid {rid} saw terminals {terms} — " \
            f"the no-lost-request invariant is broken"
        kind = terms[0]["type"]
        assert kind in ("finished", "cancelled", "shed")
        if kind == "finished":
            assert tr.streamed(rid) == want[rid], \
                f"{schedule}: rid {rid} finished with wrong tokens"


def test_chaos_deadline_is_the_timeout_terminal(model, rig):
    """A request whose deadline expires while parked terminates as
    shed with reason ``deadline`` — the invariant's timeout case."""
    fes, scheds, reps, router = rig(
        n=1, engine_kw=dict(max_seqs=1, n_pages=5, max_len=32,
                            enable_prefix_caching=False))
    tr = Tracker()
    router.submit("hog", [1, 2, 3], max_new_tokens=24,
                  on_event=tr.cb("hog"))
    router.submit("late", [4, 5, 6], max_new_tokens=4,
                  deadline=0.0, on_event=tr.cb("late"))
    _drive(router)
    assert [e["type"] for e in tr.terminals["late"]] == ["shed"]
    assert tr.terminals["late"][0]["reason"] == "deadline"
    assert [e["type"] for e in tr.terminals["hog"]] == ["finished"]


# -- server satellites ---------------------------------------------------------
class _RecordingTarget:
    """Duck-typed scheduler that records submit kwargs and finishes
    instantly — deadline-propagation check without an engine."""

    def __init__(self):
        self.kw = None
        self.draining = False

    def submit(self, rid, prompt, **kw):
        self.kw = dict(kw)
        kw["on_event"]({"type": "finished", "rid": rid,
                        "tokens": [1, 2]})

    def status(self, rid):
        return "finished"

    def forget(self, rid):
        pass

    def cancel(self, rid):
        return False

    def busy(self):
        return False

    def step(self):
        return {}

    def drain(self):
        self.draining = True

    def metrics_snapshot(self):
        return {"waiting": 0, "draining": self.draining}


def test_request_timeout_propagates_as_deadline():
    tgt = _RecordingTarget()
    fe = start_http_frontend(tgt, request_timeout=7.5)
    try:
        body = json.dumps({"prompt": [1, 2, 3], "max_tokens": 4,
                           "stream": False}).encode()
        out = json.loads(urllib.request.urlopen(urllib.request.Request(
            fe.url + "/v1/completions", data=body,
            headers={"Content-Type": "application/json"}),
            timeout=30).read())
        assert out["state"] == "finished"
        assert tgt.kw["deadline"] == 7.5       # the satellite
        body = json.dumps({"prompt": [1, 2], "deadline": 2.0,
                           "stream": False}).encode()
        urllib.request.urlopen(urllib.request.Request(
            fe.url + "/v1/completions", data=body,
            headers={"Content-Type": "application/json"}),
            timeout=30).read()
        assert tgt.kw["deadline"] == 2.0       # explicit wins
    finally:
        fe.shutdown(drain=False)


def test_oversized_body_rejected_413():
    tgt = _RecordingTarget()
    fe = start_http_frontend(tgt, max_body_bytes=128)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", fe.port,
                                          timeout=30)
        big = json.dumps({"prompt": list(range(200))}).encode()
        conn.request("POST", "/v1/completions", big,
                     {"Content-Type": "application/json"})
        assert conn.getresponse().status == 413
        conn.close()
        # a hostile Content-Length alone (no body sent) is refused
        # from the header — nothing is read or buffered
        conn = http.client.HTTPConnection("127.0.0.1", fe.port,
                                          timeout=30)
        conn.putrequest("POST", "/v1/submit")
        conn.putheader("Content-Type", "application/json")
        conn.putheader("Content-Length", str(1 << 40))
        conn.endheaders()
        resp = conn.getresponse()
        assert resp.status == 413
        assert b"exceeds" in resp.read()
        conn.close()
        assert tgt.kw is None                  # nothing reached submit
    finally:
        fe.shutdown(drain=False)


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_healthz_503_when_wedged():
    class _WedgedTarget(_RecordingTarget):
        def busy(self):
            return True

        def step(self):
            raise RuntimeError("engine wedged")

    fe = start_http_frontend(_WedgedTarget())
    try:
        fe._loop_thread.join(timeout=10)       # loop dies on first step
        assert not fe._loop_thread.is_alive()
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(fe.url + "/healthz", timeout=30)
        assert ei.value.code == 503
        body = json.loads(ei.value.read())
        assert body["status"] == "wedged"
        assert "reason" in body
    finally:
        fe.shutdown(drain=False)
