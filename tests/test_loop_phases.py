"""The serving loop's phases on the profiler's clock (ISSUE 25).

Contracts under test:
* ``tracing.phase`` IS ``jax.profiler.TraceAnnotation``: with no
  profiler session open it reads no clock and feeds no ring, the
  tracer's or the profiler module's, and ``tracing.span()`` is still the
  ``NULL_SPAN`` singleton; ``RecordEvent`` without a ``Profiler`` session
  reads no clock either;
* under a real ``jax.profiler`` capture a tiny engine behind a
  ``Scheduler`` leaves, on ONE thread line, ``sched.step`` >
  ``engine.step`` > its leaves: nested, not overlapping, covering their
  parent but for microseconds, with the step's attributes;
* an MoE engine's ``engine.step.moe_counts`` is the fold of the step
  BEFORE's counts, behind this step's launch (ISSUE 29), there is one
  ``engine.step.wait`` a step, and every phase the benchmark's
  ``engine.idle_*`` specs sum is still written;
* the counters at the same boundary (``steps``, ``step_prefill_tokens``)
  are exact for a fixed request list on mixed steps, on windows and
  with prompts prefilled at admission.
"""
import glob
import statistics
import time

import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.inference.engine import LLMEngine
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.observability import tracing as T
from paddle_tpu.serving import Scheduler

ENGINE_LEAVES = {"engine.step.plan", "engine.step.pack",
                 "engine.step.launch", "engine.step.wait",
                 "engine.step.merge", "engine.step.account"}
SCHED_LEAVES = {"sched.step.intake", "sched.step.admit",
                "sched.step.emit", "engine.step"}
PROMPTS = [[5, 9, 2, 7, 1, 3, 8, 4, 6, 2, 9], [8, 1, 4], [3] * 19]


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = LlamaForCausalLM(llama_tiny_config())
    m.eval()
    return m


@pytest.fixture(autouse=True)
def _tracing_off():
    yield
    T.disable_tracing()


def _no_clock(*a):
    raise AssertionError("a clock was read")


# -- nobody traces: a phase is the annotation and nothing else ---------------------
def test_phase_is_the_profilers_annotation_and_feeds_no_ring(monkeypatch):
    import jax
    assert T.phase is jax.profiler.TraceAnnotation
    assert not T.phase.is_enabled()           # no session is open
    tr = T.enable_tracing()
    monkeypatch.setattr(time, "perf_counter", _no_clock)
    monkeypatch.setattr(time, "monotonic", _no_clock)
    with T.phase("engine.step", path="mixed") as sp:
        sp.set_metadata(decode_slots=3)
        with T.phase("engine.step.pack"):
            pass
    assert tr.finished_spans() == [] and tr.open_spans() == []
    assert not hasattr(profiler, "_HOST_EVENTS")
    T.disable_tracing()
    assert T.span("engine.step") is T.NULL_SPAN


def test_record_event_without_a_session_reads_no_clock(monkeypatch):
    monkeypatch.setattr(profiler.time, "perf_counter", _no_clock)
    assert profiler._SESSIONS == []
    with profiler.RecordEvent("user.range"):
        pass


def test_record_event_feeds_only_the_open_session():
    prof = profiler.Profiler(timer_only=True).start()
    try:
        with profiler.RecordEvent("inside"):
            pass
    finally:
        prof.stop()
    with profiler.RecordEvent("after"):
        pass
    assert [n for n, _, _ in prof._host_events] == ["inside"]
    assert profiler._SESSIONS == []


# -- a real capture on the CPU -------------------------------------------------------
def _capture(tmp_path, fn):
    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    lines = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            lines.append([(e.name, e.start_ns, e.start_ns + e.duration_ns,
                           dict(e.stats)) for e in line.events])
    return lines


def _children(evs, parent, names):
    _, p0, p1, _ = parent
    return sorted((e for e in evs
                   if e[0] in names and p0 <= e[1] and e[2] <= p1),
                  key=lambda e: e[1])


def _self_ns(parent, kids):
    for a, b in zip(kids, kids[1:]):
        assert a[2] <= b[1], f"{a[0]} overlaps {b[0]}"
    return (parent[2] - parent[1]) - sum(k[2] - k[1] for k in kids)


def test_a_capture_shows_the_loops_phases_nested_on_one_line(model,
                                                             tmp_path):
    sched = Scheduler(LLMEngine(model, max_seqs=4, max_len=64,
                                page_size=8, steps_per_sync=4,
                                enable_prefix_caching=False),
                      max_queue=8, chunked_prefill=True)
    sched.submit("warm", [1, 2, 3], max_new_tokens=6)
    sched.run_until_idle()                    # compiles stay outside
    for i, p in enumerate(PROMPTS):
        sched.submit(f"r{i}", p, max_new_tokens=9)
    lines = _capture(tmp_path, sched.run_until_idle)

    held = [ln for ln in lines if any(e[0] == "sched.step" for e in ln)]
    assert len(held) == 1, "the loop's phases are on ONE thread line"
    evs = held[0]
    assert {e[0] for e in evs if e[0].startswith(
        ("sched.", "engine.step", "serve."))} == \
        {"sched.step"} | SCHED_LEAVES | ENGINE_LEAVES
    steps = [e for e in evs if e[0] == "sched.step"]
    esteps = [e for e in evs if e[0] == "engine.step"]
    assert len(steps) >= 6 and len(esteps) == len(steps)

    sched_self, eng_self = [], []
    for st in steps:
        kids = _children(evs, st, SCHED_LEAVES)
        assert [k[0] for k in kids] == [
            "sched.step.intake", "sched.step.admit", "engine.step",
            "sched.step.emit", "sched.step.emit"]
        sched_self.append(_self_ns(st, kids))
    paths = set()
    for es in esteps:
        kids = _children(evs, es, ENGINE_LEAVES)
        names = [k[0] for k in kids]
        assert names[:3] == ["engine.step.plan", "engine.step.pack",
                             "engine.step.launch"]
        assert names[-2:] == ["engine.step.merge", "engine.step.account"]
        assert "engine.step.wait" in names
        eng_self.append(_self_ns(es, kids))
        attrs = es[3]
        assert set(attrs) == {"decode_slots", "prefill_tokens", "nsteps",
                              "path"}
        paths.add(attrs["path"])
    assert paths == {"mixed", "window"}
    assert sum(e[3]["prefill_tokens"] for e in esteps) == \
        sum(len(p) for p in PROMPTS)
    # every line of a step lies in a leaf: the parents' self time is the
    # spans' own cost (a descheduled thread may stretch a few)
    assert statistics.median(eng_self) < 1e6      # ns
    assert statistics.median(sched_self) < 1e6


def test_the_counts_fold_behind_the_next_steps_launch(tmp_path):
    """The loop thread's line of an MoE engine: step N's routed counts
    come back in step N's one read (``engine.step.wait``, once a step)
    and are folded in step N+1, after its launch and before its wait —
    the chip busy under them — but for the last step's, folded at once
    when the engine is left without work."""
    import json
    import pathlib
    from paddle_tpu.models.qwen2_moe import (Qwen2MoeForCausalLM,
                                             qwen2_moe_tiny_config)
    paddle.seed(0)
    moe = Qwen2MoeForCausalLM(qwen2_moe_tiny_config())
    moe.eval()
    sched = Scheduler(LLMEngine(moe, max_seqs=4, max_len=64, page_size=8,
                                steps_per_sync=4,
                                enable_prefix_caching=False),
                      max_queue=8, chunked_prefill=True)
    sched.submit("warm", [1, 2, 3], max_new_tokens=6)
    sched.run_until_idle()                    # compiles stay outside
    for i, p in enumerate(PROMPTS):
        sched.submit(f"r{i}", p, max_new_tokens=9)
    lines = _capture(tmp_path, sched.run_until_idle)
    (evs,) = [ln for ln in lines if any(e[0] == "sched.step" for e in ln)]
    esteps = sorted((e for e in evs if e[0] == "engine.step"),
                    key=lambda e: e[1])
    leaves = ENGINE_LEAVES | {"engine.step.moe_counts"}
    assert len(esteps) >= 6
    for i, es in enumerate(esteps):
        names = [k[0] for k in _children(evs, es, leaves)]
        want = ["engine.step.plan", "engine.step.pack",
                "engine.step.launch", "engine.step.moe_counts",
                "engine.step.wait", "engine.step.merge",
                "engine.step.account"]
        if i == 0:                            # nothing put aside yet
            want.remove("engine.step.moe_counts")
        if i == len(esteps) - 1:              # its own, at idle
            want.append("engine.step.moe_counts")
        assert names == want, (i, names)
    folds = sched.engine.count_folds
    assert folds["at_idle"] >= 1 and \
        folds["behind_launch"] >= len(esteps) - 1
    # what the benchmark's idle metrics sum is what the loop writes
    written = {e[0] for e in evs}
    specs = pathlib.Path(__file__).parent.parent / "perfbench" / \
        "layer_metrics"
    summed = set()
    for spec in specs.glob("engine.idle_*.json"):
        summed.update(json.loads(spec.read_text()).get("spans", ()))
    assert {"engine.step.wait", "engine.step.moe_counts",
            "engine.step.launch"} <= summed <= written


def test_the_front_ends_loop_adds_cmds_poll_and_wait(model, tmp_path):
    from paddle_tpu.serving import start_http_frontend
    sched = Scheduler(LLMEngine(model, max_seqs=4, max_len=64,
                                page_size=8), max_queue=8)
    fe = start_http_frontend(sched)
    try:
        def go():
            assert fe._on_loop(lambda: 7) == 7
            time.sleep(0.05)
        lines = _capture(tmp_path, go)
    finally:
        fe.shutdown()
    names = [{e[0] for e in ln} for ln in lines]
    loop = [n for n in names if "serve.loop.wait" in n]
    assert len(loop) == 1
    assert {"serve.loop.cmds", "serve.loop.poll"} <= loop[0]


# -- the counters at the same boundary --------------------------------------------
@pytest.mark.parametrize("kw,path", [
    (dict(steps_per_sync=1), "mixed"),
    (dict(steps_per_sync=4), "window"),
    (dict(steps_per_sync=4), "add"),       # prompts prefilled at admission
])
def test_step_counters_are_exact(model, kw, path):
    def run():
        eng = LLMEngine(model, max_seqs=4, max_len=64, page_size=8,
                        enable_prefix_caching=False, **kw)
        for i, p in enumerate(PROMPTS):
            if path == "add":
                eng.add_request(f"r{i}", p, max_new_tokens=9)
            else:
                eng.begin_request(f"r{i}", p, max_new_tokens=9)
        calls = 0
        while eng.has_work():
            eng.step()
            calls += 1
        assert eng.step() == {}               # nothing dispatched
        snap = eng.metrics_snapshot()
        toks = [eng.result(f"r{i}") for i in range(len(PROMPTS))]
        return calls, snap, toks

    calls, snap, toks = run()
    assert snap["steps"] == calls
    assert snap["prompt_tokens"] == sum(len(p) for p in PROMPTS)
    assert snap["step_prefill_tokens"] == \
        (0 if path == "add" else sum(len(p) for p in PROMPTS))
    assert snap["generated_tokens"] == sum(len(t) for t in toks) == 27
    calls2, snap2, toks2 = run()              # the counts repeat exactly
    assert (calls2, toks2) == (calls, toks)
    assert snap2["steps"] == snap["steps"]
    assert snap2["step_prefill_tokens"] == snap["step_prefill_tokens"]


def test_step_counters_are_on_the_registry(model):
    from paddle_tpu.observability import get_registry
    eng = LLMEngine(model, max_seqs=4, max_len=64, page_size=8)
    eng.begin_request("a", PROMPTS[0], max_new_tokens=3)
    while eng.has_work():
        eng.step()
    text = get_registry().expose_text()
    eid = eng.engine_id
    assert f'llm_engine_steps_total{{engine="{eid}"}}' in text
    assert f'llm_engine_step_prefill_tokens_total{{engine="{eid}"}} ' \
           f'{len(PROMPTS[0])}' in text
