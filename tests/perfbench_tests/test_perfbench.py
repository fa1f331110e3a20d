"""The benchmark's own yardstick, checked on the CPU.

Importing this file loads no accelerator library: everything under
``perfbench`` imports JAX lazily, inside the functions that need it.
"""
import copy
import io
import json
import math
import os
import shutil
import statistics

import pytest

from perfbench import flops, manifest, stats, trace_reduce, validate
from perfbench.drivers import open_loop

PALLAS = ' = f32[8] custom-call(), custom_call_target="tpu_custom_call"'


# -- trace reduction -------------------------------------------------------------

def synthetic_trace():
    """One chip, microseconds.  A ``while`` of 100 holds a fusion of 30
    and a Pallas kernel of 40; a copy of 10 follows a gap of 20; the
    window runs 10 past the last operation."""
    device = [("%while.1 = (s32[]) while(...)", 0, 100),
              ("%fusion.2 = f32[8] fusion(...)", 10, 30),
              ("%jvp_flash_fwd_.3" + PALLAS, 50, 40),
              ("%copy.4 = f32[8] copy(...)", 120, 10)]
    spans = [("pb.engine.step", 95, 20), ("pb.sched.admit", 135, 3)]
    return {0: device}, spans, (0, 140)


def test_trace_busy_idle_and_named_share():
    dev, spans, window = synthetic_trace()
    s = trace_reduce.summarize(dev, spans, window, unit=1.0)
    assert s["busy_s"] == 110 and s["window_s"] == 140
    assert s["idle_share"] == pytest.approx(100 * 30 / 140)
    assert s["named_share"] == pytest.approx(100 * 40 / 110)


def test_trace_self_time_by_name():
    dev, spans, window = synthetic_trace()
    ops = dict(trace_reduce.summarize(dev, spans, window, unit=1.0)
               ["device_ops"])
    assert ops == {"flash_fwd": 40, "while": 30, "fusion": 30, "copy": 10}


def test_trace_gaps_by_span():
    dev, spans, window = synthetic_trace()
    gaps = dict(trace_reduce.summarize(dev, spans, window, unit=1.0)
                ["idle_gaps"])
    # gap 100-120: 15 of it inside pb.engine.step (95-115); gap 130-140:
    # 3 inside pb.sched.admit
    assert gaps == {"pb.engine.step": 15, "between_spans": 12,
                    "pb.sched.admit": 3}


def test_trace_window_clips_and_averages_chips():
    dev, spans, _ = synthetic_trace()
    dev[1] = [("%copy.9 = f32[8] copy(...)", 0, 70)]
    s = trace_reduce.summarize(dev, [], (0, 70), unit=1.0)
    assert s["chips"] == 2
    assert s["busy_s"] == 70 and s["idle_share"] == 0
    assert trace_reduce.summarize({0: []}, [], (0, 1)) is None


@pytest.mark.parametrize("text,op,kernel", [
    ("%jvp_flash_fwd_.2 = x", "jvp_flash_fwd_", "flash_fwd"),
    ("%gmm.17 = x", "gmm", "gmm"),
    ("%dynamic-slice_bitcast_fusion.3 = x", "dynamic-slice_bitcast_fusion",
     "dynamic-slice_bitcast_fusion"),
    ("%transpose_jvp_flash_bwd_dq = x", "transpose_jvp_flash_bwd_dq",
     "flash_bwd_dq"),
])
def test_trace_names(text, op, kernel):
    assert trace_reduce.op_name(text) == op
    assert trace_reduce.kernel_name(text) == kernel


# -- arithmetic ------------------------------------------------------------------

@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0), ([1, 2, 3, 4], 50, 2.5),
    (list(range(1, 101)), 95, 95.05), ([7], 95, 7.0), ([3, 1], 100, 3.0),
])
def test_percentile(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)


def test_spread_is_the_contracts():
    v = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q1, q2, q3 = statistics.quantiles(v, n=4)
    assert stats.iqr_share(v) == pytest.approx((q3 - q1) / q2)


def test_due_times_and_mean_gap():
    assert stats.due_times([0.5, 0.25, 1.0], start=2.0) == [2.5, 2.75, 3.75]
    assert stats.mean_gap([1.0, 1.0, 1.0, 2.0, 3.0]) == pytest.approx(0.5)
    assert stats.mean_gap([1.0]) is None


def test_flops_against_a_hand_count():
    cfg = dict(hidden_size=8, intermediate_size=16, num_hidden_layers=2,
               num_attention_heads=2, num_key_value_heads=1, vocab_size=32)
    # head_dim 4: q 8*8, k+v 2*8*4, o 8*8, mlp 3*8*16 = 576 a layer
    assert flops.dense_matmul_params(cfg) == 2 * 576 + 8 * 32
    # causal attention, forward, one sequence of 10: 2 layers * 2 * 10^2
    # * 2 heads * 4
    assert flops.causal_attention_flops_fwd(cfg, 10) == 2 * 2 * 100 * 8
    per_token = 3 * (2 * 1408 + 3200 / 10)
    assert flops.train_flops_per_token(cfg, 10) == pytest.approx(per_token)
    assert flops.mfu_percent(per_token, 1000.0, 2, per_token * 1000) == 50


def test_flops_of_the_training_cell():
    """The issue's count: about 81 TFLOP a step (68 matmul + 13
    attention) for InternLM2-1.8B widths x 8 layers at 2 x 8192."""
    cfg = manifest.config(manifest.benchmark(), "internlm2_1p8b_8l")
    step = flops.train_flops_per_token(cfg, 8192) * 2 * 8192
    assert 80e12 < step < 82e12


def test_peaks_unknown_device_is_an_error():
    assert manifest.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        manifest.peaks("cpu")


# -- traffic ---------------------------------------------------------------------

def chat():
    """A mix of the tests' own, so that a later change of a cell's rate
    does not move these figures."""
    return {"driver": "open_loop",
            "arrival": {"process": "poisson", "rate_rps": 4.0},
            "prompt_len": {"dist": "lognormal", "median": 256, "sigma": 0.9,
                           "min": 16, "max": 1536},
            "output_len": {"dist": "lognormal", "median": 96, "sigma": 0.7,
                           "min": 8, "max": 384},
            "shared_prefix": None, "ramp_s": 8, "drain_s": 0}


def test_traffic_same_seed_same_requests():
    a = open_loop.make_requests(chat(), 2 ** 31 + 5, 20.0, 1000)
    b = open_loop.make_requests(chat(), 2 ** 31 + 5, 20.0, 1000)
    assert a == b and len(a) == 4 * (8 + 20)


def test_traffic_other_seed_same_cycle_from_another_start():
    a = open_loop.make_requests(chat(), 1, 20.0, 1000)
    b = open_loop.make_requests(chat(), 2, 20.0, 1000)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    shape = lambda r: (len(r["prompt"]), r["max_tokens"])   # noqa: E731
    cycle = 80                    # one window's worth: 4 req/s x 20 s
    assert list(map(shape, a[:cycle])) != list(map(shape, b[:cycle]))
    assert sorted(map(shape, a[:cycle])) == sorted(map(shape, b[:cycle]))
    # the cycle repeats, so every window holds the same requests
    assert list(map(shape, a[:32])) == list(map(shape, a[cycle:]))
    two = list(map(shape, b[:cycle])) * 2
    assert any(two[k:k + cycle] == list(map(shape, a[:cycle]))
               for k in range(cycle))
    assert a[cycle - 1]["due"] == pytest.approx(20.0) \
        == b[cycle - 1]["due"]


def test_traffic_every_stretch_carries_the_mix():
    reqs = open_loop.make_requests(chat(), 9, 40.0, 1000)
    tot = [sum(len(r["prompt"]) for r in reqs[k:k + 40])
           for k in range(0, 160, 40)]
    assert max(tot) / min(tot) < 1.15


def test_traffic_follows_its_parameters():
    mix = dict(chat(), ramp_s=0, drain_s=0)
    reqs = open_loop.make_requests(mix, 3, 100.0, 1000)
    plen = sorted(len(r["prompt"]) for r in reqs)
    assert plen[0] >= 16 and plen[-1] <= 1536
    assert abs(statistics.median(plen) - 256) <= 3
    out = sorted(r["max_tokens"] for r in reqs)
    assert out[0] >= 8 and out[-1] <= 384
    assert abs(statistics.median(out) - 96) <= 2
    due = [r["due"] for r in reqs]
    assert due == sorted(due)
    assert len(reqs) / due[-1] == pytest.approx(4.0)


def test_traffic_bursts_and_shared_prefixes():
    mix = dict(chat(), ramp_s=0, drain_s=0)
    mix["arrival"] = {"process": "constant", "rate_rps": 2.0,
                      "burst": {"on_s": 1.0, "off_s": 3.0}}
    mix["shared_prefix"] = {"groups": 2, "len": 32, "share": 1.0}
    reqs = open_loop.make_requests(mix, 4, 16.0, 1000)
    assert all(r["due"] % 4.0 <= 1.0 + 1e-9 for r in reqs)
    assert len({tuple(r["prompt"][:32]) for r in reqs}) == 2


def test_chunked_stream_parser():
    st = open_loop.Stream({"i": 0}, None, 0.0)

    def chunk(obj):
        data = (json.dumps(obj) + "\n").encode()
        return hex(len(data))[2:].encode() + b"\r\n" + data + b"\r\n"
    wire = (b"HTTP/1.1 200 OK\r\nTransfer-Encoding: chunked\r\n\r\n"
            + chunk({"id": "a", "tokens": [5]})
            + chunk({"id": "a", "tokens": [6, 7, 8]})
            + chunk({"id": "a", "done": True, "state": "finished"})
            + b"0\r\n\r\n")
    for k, i in enumerate(range(0, len(wire), 7)):    # arbitrary splits
        st.feed(wire[i:i + 7], float(k))
    assert len(st.token_times) == 4 and st.token_times[0] < st.token_times[1]
    assert st.token_times[1] == st.token_times[3]
    assert st.closed and st.finished and not st.failed
    bad = open_loop.Stream({"i": 1}, None, 0.0)
    bad.feed(b"HTTP/1.1 429 Too Many\r\nContent-Length: 2\r\n\r\n{}", 0.0)
    assert bad.failed and bad.finished


# -- the manifest ----------------------------------------------------------------

def test_manifest_is_consistent():
    assert validate.problems(manifest.benchmark(), manifest.ROOT) == []


def test_manifest_keys_are_the_contracts():
    bench = manifest.benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
    assert os.path.getsize(os.path.join(manifest.ROOT,
                                        "BENCHMARK.json")) < 64 << 10
    assert 1 <= bench["run_seconds"] <= 51


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  manifest.benchmark()["workloads"]])
def test_cell_files_load(cell):
    bench = manifest.benchmark()
    w = manifest.cell(bench, cell)
    cfg = manifest.config(bench, w["config"])
    mix = manifest.traffic(w["traffic"])
    assert manifest.module("builders", cfg["builder"]).build
    drv = manifest.module("drivers", mix["driver"])
    assert drv.prepare and drv.run
    for m in manifest.metrics_of(bench, cell, "per_layer"):
        spec = manifest.layer_metric(m["name"])
        assert manifest.module("readers", spec["reader"]).read
    small = manifest.at_size(cfg, True)
    assert small["hidden_size"] < cfg["hidden_size"]
    assert "rehearse" not in small and "rehearse" in cfg


def test_every_metric_names_cells_that_exist():
    """A retired cell leaves no metric pointing at it (PR 35)."""
    bench = manifest.benchmark()
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", ())) <= cells, m["name"]
    for m in bench["per_layer"]:
        assert set(manifest.layer_metric(m["name"])["workloads"]) <= cells


def test_every_traffic_file_is_a_cells():
    bench = manifest.benchmark()
    used = {w["traffic"] + ".json" for w in bench["workloads"]}
    there = set(os.listdir(os.path.join(manifest.HERE, "traffic")))
    assert there == used


RATED = [w["name"] for w in manifest.benchmark()["workloads"]
         if "share_of_knee" in manifest.traffic(w["traffic"])]


@pytest.mark.parametrize("cell", RATED)
def test_a_rated_mix_sits_at_its_share_of_the_knee(cell):
    """``perfbench/README.md``, "Finding a cell's rates again": under the
    knee the rate is the share rounded to 0.1 req/s, above it to 0.5;
    the cell's ``why`` quotes the rate the file holds."""
    w = manifest.cell(manifest.benchmark(), cell)
    mix = manifest.traffic(w["traffic"])
    share, knee = mix["share_of_knee"], mix["knee_rps"]
    step = 0.1 if share < 1 else 0.5
    rate = mix["arrival"]["rate_rps"]
    assert abs(rate - share * knee) <= step / 2 + 1e-9
    assert rate / step == pytest.approx(round(rate / step))
    assert (share < 1) == (mix["drain_s"] > 0)
    assert f"{rate:g} req/s" in w["why"] and f"{knee:g}" in w["why"]
    assert "chip call" in mix["what"]


def _thirds(*waiting):
    return {"by_third": [{"unanswered_at_end_of_third": n} for n in waiting]}


def _sweep_info(**over):
    """One run's driver info at 10 req/s over 40 s, nothing queued."""
    info = {"output_tokens_mean": 120.0, "window_output_tokens": 47600,
            "requests_finished_per_s_whole_run": 9.46,
            "requests_due_in_window": 400, "unanswered_in_window": 1,
            "generator_lateness_p95_ms": 5.0, **_thirds(2, 1, 1)}
    return dict(info, **over)


@pytest.mark.parametrize("over,why", [
    ({}, []),
    # the 21 requests in flight when a ``drain_s`` 0 run closes are not
    # held against the rate: nothing waits, the tokens offered came out
    ({"requests_finished_per_s_whole_run": 9.0}, []),
    ({"window_output_tokens": 46000}, ["tokens/s under offered"]),
    ({"unanswered_in_window": 5}, ["unanswered"]),
    (_thirds(13, 17, 20), ["thirds grow"]),
    (_thirds(0, 1, 2), []),              # one instant's count: 2 is noise
    ({"generator_lateness_p95_ms": 20.0}, ["generator late"]),
])
def test_the_sweeps_four_conditions(over, why):
    from perfbench.chip_calls import sweep_knee
    ok, reasons = sweep_knee.sustained(10.0, _sweep_info(**over), 40.0)
    assert reasons == why and ok == (not why)


def test_the_rated_cells_are_there():
    """What must stay true of PR 35's two cells; a later PR's cells, on
    one chip or four, rated or not, are none of this test's business."""
    assert {"moe_chat_knee80", "moe_chat_overload"} <= set(RATED)


def test_a_made_up_addition_needs_no_edit(tmp_path):
    """A later PR's cell, configuration, traffic mix, per-layer metric,
    reader and builder, added as files of their own."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(manifest.ROOT, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    pb = os.path.join(root, "perfbench")
    before = {p: os.path.getmtime(os.path.join(d, p))
              for d, _, fs in os.walk(pb) for p in fs}
    bench = copy.deepcopy(manifest.benchmark())

    def write(rel, obj):
        with open(os.path.join(pb, rel), "w") as f:
            f.write(obj if isinstance(obj, str) else json.dumps(obj))
    write("configs/made_up.json", {"builder": "made_up_builder",
                                   "reduced": ["num_hidden_layers"],
                                   "rehearse": {}})
    write("builders/made_up_builder.py", "def build(*a):\n    pass\n")
    write("traffic/made_up_mix.json", {"driver": "open_loop"})
    metric = {"name": "made_up.hit_share", "unit": "%", "better": "higher",
              "source": "program_counter", "layer": "scheduler",
              "moves": "tpot_p95_ms", "workloads": ["made_up_cell"]}
    write("layer_metrics/made_up.hit_share.json",
          dict(metric, reader="made_up_reader"))
    write("readers/made_up_reader.py", "def read(rec, spec):\n    pass\n")
    bench["configs"].append({
        "name": "made_up", "source": "https://example.org/config.json",
        "file": "perfbench/configs/made_up.json",
        "reduced": ["num_hidden_layers"], "why": "made up"})
    bench["workloads"].append({
        "name": "made_up_cell", "config": "made_up",
        "traffic": "made_up_mix", "chips": 1, "why": "made up"})
    bench["per_layer"].append(metric)
    for m in bench["end_to_end"]:
        if m["name"] == "tpot_p95_ms":
            m["workloads"].append("made_up_cell")
    assert validate.problems(bench, root) == []
    after = {p: os.path.getmtime(os.path.join(d, p))
             for d, _, fs in os.walk(pb) for p in fs}
    assert all(after[p] == t for p, t in before.items())
    # and the check does catch an addition that is wrong
    bench["per_layer"][-1] = dict(metric, moves="serve_tokens_per_s")
    assert any("does not report" in p or "differs" in p
               for p in validate.problems(bench, root))


# -- the reference against the framework -------------------------------------------

@pytest.mark.parametrize("config", ["internlm2_1p8b_8l",
                                    "deepseek_moe_16b_4l"])
def test_reference_agrees_with_the_framework(config):
    import jax
    import numpy as np
    from paddle_tpu.jit.train import traced_forward
    from perfbench import reference
    from perfbench.builders import models
    sz = manifest.at_size(manifest.config(manifest.benchmark(), config),
                          True)
    sz["dtype"] = "float32"             # compare mathematics, not rounding
    model = models.make_model(sz, 11, 64)
    if sz["arch"] == "qwen2_moe":
        # the training-style forward drops tokens past an expert's
        # capacity; the served path and the reference are dropless
        for layer in model.layers:
            layer.mlp.gate.capacity_factor = 64.0
    ids = np.random.default_rng(0).integers(0, sz["vocab_size"],
                                            size=(1, 40), dtype=np.int32)
    sd = model.raw_state_dict()
    got = np.asarray(traced_forward(
        model, lambda m, b: m(b["input_ids"]), sd, {"input_ids": ids},
        jax.random.key(0))[0], np.float32)
    want = reference.logits(
        reference.canonical(sz["arch"], sd, sz["num_hidden_layers"]), sz,
        ids[0].tolist())
    assert want.shape == got.shape == (40, sz["vocab_size"])
    assert reference.rel_l2(got, want) < 1e-4
    assert (got.argmax(-1) == want.argmax(-1)).all()


def test_tie_rule():
    from perfbench import reference
    import numpy as np
    assert reference.bf16_ulp(3.5) == 2.0 ** -6
    row = np.zeros((3, 8), np.float32)
    row[:, 2] = 3.5
    row[1, 5] = 3.5 - 2 * 2.0 ** -6          # two ulps under: a tie
    row[2, 5] = 3.5 - 3 * 2.0 ** -6          # three: not
    ok = reference.judge_served(row, 1, [2, 5])
    assert ok["ok"] and ok["equal"] == 1 and ok["tie_gaps_ulps"] == [2.0]
    bad = reference.judge_served(row, 2, [2, 5])
    assert not bad["ok"] and bad["not_ties"][0]["position"] == 1


# -- one rehearsal per driver, end to end ------------------------------------------

def last_line_of(workload, trace):
    import gc
    from paddle_tpu.observability.introspection import memory_brief
    from perfbench import run
    buf = io.StringIO()
    gc.collect()
    before = memory_brief()["device_pool_bytes"]
    rc = run.run_cell(workload, 2 ** 31 + 17, 3.0, trace, rehearse=True,
                      out=lambda s: buf.write(s + "\n"))
    assert rc == 0
    gc.collect()              # the engine's cycles: no pool outlives the run
    assert memory_brief()["device_pool_bytes"] <= before
    lines = buf.getvalue().strip().splitlines()
    assert len(lines) == 1
    return json.loads(lines[0])


# its TPOT tail spread 3.7 % in one of PR 35's two sets of six: recorded
# (the client's values reach no metric of the line), not judged
OVERLOAD_JUDGED = {"serve_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("workload,trace,metrics", [
    ("moe_chat_overload", False, OVERLOAD_JUDGED),
    ("moe_chat_knee80", True,
     {"sched.queue_wait_ms.steady", "engine.step_ms.steady",
      "ttft_p95_ms.steady"}),
    ("dense_train_8k", False, {"train_tokens_per_s_chip", "setup_s"}),
    # PR 35: each re-rated cell both ways
    ("moe_chat_knee80", False, {"tpot_p95_ms", "setup_s"}),
    ("moe_chat_overload", True,
     {"sched.batch_occupancy.sat", "ttft_p95_ms.sat", "engine.step_ms.sat",
      "engine.tokens_per_step.sat", "engine.rows_per_step.sat"}),
])
def test_rehearsal_prints_the_contracts_line(workload, trace, metrics):
    line = last_line_of(workload, trace)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    # off the chip no device metric is reported, under any name
    assert set(line["metrics"]) == metrics
    bench = manifest.benchmark()
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    for name, m in line["metrics"].items():
        assert m["unit"] == units[name]
        assert math.isfinite(m["value"]) and m["value"] > 0


@pytest.mark.parametrize("workload", ["moe_chat_overload", "dense_train_8k"])
def test_real_sizes_without_a_tpu_fail(workload, capsys):
    from perfbench import run
    printed = []
    rc = run.run_cell(workload, 1, 1.0, False, out=printed.append)
    assert rc == 2 and printed == []
    assert "Refusing to run" in capsys.readouterr().err
