"""The idle-by-phase reader (ISSUE 25): the attribution on synthetic
traces of plain tuples, the reader's arithmetic, and that what this PR
adds to the benchmark is consistent and needs no program to be new."""
import random

import pytest

from perfbench import manifest, trace_reduce, validate
from perfbench.readers import host_idle
from perfbench.readers.host_idle import NONE

NEW_METRICS = ["engine.idle_prepare_ms.sat", "engine.idle_readback_ms.sat",
               "engine.idle_finish_ms.sat", "sched.idle_ms.sat",
               "engine.idle_ms.steady", "sched.idle_ms.steady",
               "engine.rows_per_step.sat"]

# one loop thread: two iterations with a wait between them
LOOP = [("sched.step", 100, 300),            # 100-400
        ("sched.step.intake", 100, 20),      # 100-120
        ("engine.step", 130, 200),           # 130-330
        ("engine.step.pack", 140, 60),       # 140-200
        ("engine.step.launch", 200, 50),     # 200-250
        ("engine.step.wait", 260, 60),       # 260-320
        ("sched.step.emit", 340, 60),        # 340-400
        ("serve.loop.wait", 420, 30),        # 420-450
        ("sched.step", 500, 100),            # 500-600
        ("engine.step", 510, 80)]            # 510-590
OTHER = [("http.handler", 0, 1000), ("engine.step.pack", 0, 1000)]
# what JAX itself writes on the loop's line, inside the phases
JAXS = [("np.asarray(jax.Array)", 262, 50), ("PjitFunction(step)", 205, 40),
        ("pb.engine.step", 125, 210)]
PHASES = ("serve.loop.", "sched.step", "engine.step")


def test_innermost_segments_of_nested_spans():
    segs = host_idle.innermost_segments(LOOP[:7])
    assert segs == [
        (100, 120, "sched.step.intake"), (120, 130, "sched.step"),
        (130, 140, "engine.step"), (140, 200, "engine.step.pack"),
        (200, 250, "engine.step.launch"), (250, 260, "engine.step"),
        (260, 320, "engine.step.wait"), (320, 330, "engine.step"),
        (330, 340, "sched.step"), (340, 400, "sched.step.emit")]


def test_a_child_that_outlives_its_parent_is_cut():
    segs = host_idle.innermost_segments([("a", 0, 10), ("b", 5, 20)])
    assert segs == [(0, 5, "a"), (5, 10, "b")]


@pytest.mark.parametrize("gap,want", [
    # straddles two leaves and the parent's own stretch between them
    ((190, 265), {"engine.step.pack": 10, "engine.step.launch": 50,
                  "engine.step": 10, "engine.step.wait": 5}),
    # inside one leaf
    ((205, 215), {"engine.step.launch": 10}),
    # outside every span
    ((460, 490), {NONE: 30}),
    # from a wait, over nothing, into the next iteration
    ((440, 515), {"serve.loop.wait": 10, NONE: 50, "sched.step": 10,
                  "engine.step": 5}),
    # the parent's self time only
    ((120, 130), {"sched.step": 10}),
])
def test_a_gap_goes_to_the_innermost_span_over_it(gap, want):
    got = host_idle.attribute([gap], LOOP)
    assert got == want
    assert sum(got.values()) == gap[1] - gap[0]


def test_another_threads_spans_count_for_nothing():
    dev = {0: [("%fusion.1 = f32[] fusion()", 0, 150),
               ("%fusion.2 = f32[] fusion()", 210, 490)]}
    buckets, counts, detail, host = host_idle.table(
        dev, [OTHER, LOOP + JAXS], (0, 1000), PHASES)
    # gaps: 150-210 and 700-1000
    assert buckets == {"engine.step.pack": 50, "engine.step.launch": 10,
                       NONE: 300}
    # events that are not the program's phases show only in the detail
    assert detail == {"engine.step.pack": 50, "engine.step.launch": 5,
                      "PjitFunction(step)": 5, NONE: 300}
    assert counts == {"sched.step": 2, "engine.step": 2,
                      "sched.step.intake": 1, "engine.step.pack": 1,
                      "engine.step.launch": 1, "engine.step.wait": 1,
                      "sched.step.emit": 1, "serve.loop.wait": 1}
    # where the loop thread itself was, idle chip or not
    assert host["engine.step.pack"] == 60 and host["sched.step"] == 40
    assert host[NONE] == 570 and sum(host.values()) == 1000
    # a window that starts later counts only the spans that START in it
    late = host_idle.table(dev, [OTHER, LOOP + JAXS], (450, 1000),
                           PHASES)[1]
    assert late == {"sched.step": 1, "engine.step": 1}


def test_a_program_without_the_phases_puts_all_idle_under_none():
    dev = {0: [("%fusion.1 = f32[] fusion()", 100, 100)]}
    buckets, counts = host_idle.table(
        dev, [[("pb.engine.step", 0, 400)]], (0, 400), PHASES)[:2]
    assert buckets == {NONE: 300} and counts == {}
    assert host_idle.table({}, [LOOP], (0, 400)) is None
    assert host_idle.table(dev, [LOOP], None) is None


def test_buckets_sum_to_the_idle_exactly():
    rng = random.Random(25)
    for _ in range(50):
        spans, t = [], 0
        for _ in range(rng.randint(1, 6)):          # iterations
            t += rng.randint(0, 40)
            dur = rng.randint(50, 400)
            spans.append(("sched.step", t, dur))
            a = t + rng.randint(0, 20)
            while a < t + dur - 10:                 # leaves, some nested
                d = rng.randint(5, min(120, t + dur - a))
                spans.append((f"leaf{rng.randint(0, 3)}", a, d))
                if d > 20 and rng.random() < 0.5:
                    spans.append(("inner", a + 3, rng.randint(1, d - 6)))
                a += d + rng.randint(0, 15)
            t += dur
        ops, at = [], 0
        while at < t + 100:
            at += rng.randint(0, 90)
            d = rng.randint(1, 60)
            ops.append(("%op.1 = f32[] fusion()", at, d))
            at += d
        window = (rng.randint(0, 50), t + rng.randint(0, 120))
        buckets = host_idle.table({0: ops}, [spans], window)[0]
        r = trace_reduce.reduce_device(ops, window)
        assert sum(buckets.values()) == r["window"] - r["busy"]
        assert all(v > 0 for v in buckets.values())


class _Rec:
    trace_summary = {"idle_share": 10.0}


def test_read_sums_the_named_buckets_per_span(monkeypatch):
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: d + "/x")
    seen = []
    monkeypatch.setattr(
        host_idle, "table_of",
        lambda path, phases: seen.append((path, phases)) or (
            {"engine.step.pack": 6e6, "engine.step.launch": 2e6,
             "engine.step": 1e6, NONE: 5e6}, {"engine.step": 4}))
    spec = manifest.layer_metric("engine.idle_prepare_ms.sat")
    assert host_idle.read(_Rec, spec) == pytest.approx(2.0)       # ms
    assert seen == [(".perfbench_trace/moe_chat_overload/x", PHASES)]
    spec = manifest.layer_metric("engine.idle_finish_ms.sat")
    assert host_idle.read(_Rec, spec) == pytest.approx(0.25)
    # no sched.step in the trace, as on the parent: nothing to report
    spec = manifest.layer_metric("sched.idle_ms.sat")
    assert host_idle.read(_Rec, spec) is None


def test_read_off_the_chip_reports_nothing(monkeypatch):
    class Rec:
        trace_summary = None
    monkeypatch.setattr(host_idle, "table_of", None)    # never reached
    spec = manifest.layer_metric("engine.idle_ms.steady")
    assert host_idle.read(Rec, spec) is None


def test_a_counter_the_program_lacks_reads_as_nothing():
    from perfbench.readers import snapshot_present

    class Rec:
        snapshots = {"start": {"engine": {"generated_tokens": 10}},
                     "end": {"engine": {"generated_tokens": 90}}}
        spans = {"pb.engine.step": [(0.0, 1.0), (1.0, 2.0)]}

        @staticmethod
        def in_window(pairs):
            return list(pairs)
    spec = manifest.layer_metric("engine.rows_per_step.sat")
    assert snapshot_present.read(Rec, spec) is None     # the parent
    Rec.snapshots["start"]["engine"]["step_prefill_tokens"] = 100
    Rec.snapshots["end"]["engine"]["step_prefill_tokens"] = 320
    assert snapshot_present.read(Rec, spec) == (80 + 220) / 2


@pytest.mark.parametrize("name", NEW_METRICS)
def test_each_new_metric_file_loads_and_names_its_reader(name):
    bench = manifest.benchmark()
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    spec = manifest.layer_metric(name)
    assert {k: spec[k] for k in entry} == entry
    assert callable(manifest.module("readers", spec["reader"]).read)
    if spec["reader"] == "host_idle":
        assert spec["source"] == "device_trace" and spec["unit"] == "ms"
        assert spec["per"] in ("engine.step", "sched.step")
        assert len(spec["workloads"]) == 1 and spec["spans"]
        assert all(n == NONE or n.startswith(tuple(spec["phases"]))
                   for n in spec["spans"] + [spec["per"]])


def test_the_manifest_is_consistent_with_the_additions():
    bench = manifest.benchmark()
    assert validate.problems(bench, manifest.ROOT) == []
    # the additions stand together, in the order the issue gave them
    # (later PRs' entries follow them: the driver takes those at the end)
    names = [m["name"] for m in bench["per_layer"]]
    i = names.index(NEW_METRICS[0])
    assert names[i:i + 7] == NEW_METRICS
    # every idle second of a serving cell is read by exactly one of the
    # cell's metrics
    for cell in ("moe_chat_overload", "moe_chat_knee80"):
        seen = []
        for name in NEW_METRICS:
            spec = manifest.layer_metric(name)
            if spec["reader"] == "host_idle" and \
                    spec["workloads"] == [cell]:
                seen += spec["spans"]
        assert len(seen) == len(set(seen))
        assert NONE in seen and "engine.step.moe_counts" in seen
