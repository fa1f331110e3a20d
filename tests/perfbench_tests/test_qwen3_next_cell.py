"""The cell ``hybrid_serve_longctx`` and its configuration
``qwen3_next_80b_a3b_4l``, checked on the CPU at the configuration's
``rehearse`` sizes: the cell runs end to end, the benchmark's copy of the
plain reference agrees with the program's, the judge does what its
comment says, and the additions keep ``BENCHMARK.json`` valid.
"""
import io
import json
import math

import numpy as np
import pytest

from perfbench import manifest, validate

CELL, CONFIG = "hybrid_serve_longctx", "qwen3_next_80b_a3b_4l"

# the published config.json (the catalog's row), number for number
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4,
    "head_dim": 256, "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "moe_intermediate_size": 512, "num_attention_heads": 16,
    "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
    "vocab_size": 151936}


def rehearsal(trace):
    import gc
    from paddle_tpu.observability.introspection import memory_brief
    from perfbench import run
    buf = io.StringIO()
    gc.collect()
    before = memory_brief()["device_pool_bytes"]
    rc = run.run_cell(CELL, 2 ** 31 + 29, 3.0, trace, rehearse=True,
                      out=lambda s: buf.write(s + "\n"))
    assert rc == 0
    gc.collect()      # pools, state and weights: nothing outlives the run
    assert memory_brief()["device_pool_bytes"] <= before
    lines = buf.getvalue().strip().splitlines()
    assert len(lines) == 1
    return json.loads(lines[0])


@pytest.mark.parametrize("trace,metrics", [
    (False, {"serve_tokens_per_s", "setup_s"}),
    (True, {"engine.step_ms.longctx", "engine.rows_per_step.longctx",
            "sched.batch_occupancy.longctx", "ttft_p95_ms.longctx",
            "engine.tokens_per_step.longctx"}),
])
def test_the_cell_rehearses_on_the_cpu(trace, metrics):
    line = rehearsal(trace)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    # off the chip no device metric is reported, under any name
    assert set(line["metrics"]) == metrics
    bench = manifest.benchmark()
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    for name, m in line["metrics"].items():
        assert m["unit"] == units[name]
        assert math.isfinite(m["value"]) and m["value"] > 0


def test_real_size_without_a_tpu_fails(capsys):
    from perfbench import run
    printed = []
    assert run.run_cell(CELL, 1, 1.0, False, out=printed.append) == 2
    assert printed == [] and "Refusing to run" in capsys.readouterr().err


def test_both_copies_of_the_reference_give_the_same_logits():
    """``perfbench/reference_qwen3_next.py`` (blocks: one expert and one
    slice of the head at a time) against
    ``paddle_tpu/models/references/qwen3_next.py`` (one pass), on the
    served model's own weights, given the same share."""
    from paddle_tpu.models.references import qwen3_next as program_ref
    from perfbench import reference_qwen3_next as bench_ref
    from perfbench.builders import serve_qwen3_next as builder
    sz = manifest.at_size(manifest.config(manifest.benchmark(), CONFIG),
                          True)
    sz["dtype"] = "float32"
    model = builder.make_model(sz, 2 ** 31 + 5, 128)
    assert model.rope_cos.value.dtype == np.float32
    sd = model.raw_state_dict()
    ids = np.random.default_rng(0).integers(0, sz["vocab_size"],
                                            size=70).tolist()
    got = bench_ref.logits(bench_ref.canonical(sd, sz), sz, ids)
    want = np.asarray(program_ref.forward(
        program_ref.canonical(sd, sz), sz, ids,
        experts_held=program_ref.experts_held(sz)))
    assert got.shape == (70, sz["vocab_size"])
    np.testing.assert_allclose(got, want, atol=2e-6)
    # the lower-precision reading moves the logits (PERF.md's second
    # reading is not a no-op) and is never what a check computes
    import jax.numpy as jnp
    low = bench_ref.logits(bench_ref.canonical(sd, sz), sz, ids,
                           state_dtype=jnp.bfloat16)
    assert 1e-5 < np.abs(low - got).max() < 0.1 * np.abs(got).max()


def test_the_judge_keeps_its_band_and_goes_on_past_a_near_tie():
    from perfbench import reference_qwen3_next as ref
    top = 3.5
    ulp = ref.bf16_ulp(top)
    assert ulp == 2.0 ** -6
    row = np.zeros((4, 8), np.float32)
    row[:, 2] = top
    row[1, 5] = top - ref.TIE_ULPS * ulp            # on the band: a tie
    row[3, 5] = top - (ref.TIE_ULPS + 1) * ulp      # outside it
    ok = ref.judge_served(row, 1, [2, 5, 2])        # positions 0, 1, 2
    assert ok["ok"] and ok["equal"] == 2
    assert ok["tie_gaps_ulps"] == [float(ref.TIE_ULPS)]
    assert ok["max_gap_ulps"] == float(ref.TIE_ULPS)
    bad = ref.judge_served(row, 3, [2, 5])
    assert not bad["ok"] and bad["not_ties"][0]["position"] == 1


@pytest.fixture(scope="module")
def recurrence_case():
    """One seeded sequence at the published head geometry: its operands
    and the plain recurrence's outputs and final state."""
    import jax.numpy as jnp
    from perfbench import reference_qwen3_next as ref
    cfg = manifest.config(manifest.benchmark(), CONFIG)
    ops = ref.recurrence_inputs(cfg, 2 ** 31 + 11, 300)
    return ops, ref.recurrence(*map(jnp.asarray, ops))


def _the_programs(*a, **kw):
    from paddle_tpu.ops.pallas.gated_delta import ragged_gated_delta
    return ragged_gated_delta(*a, **kw)


def _a_bf16_pool(*a, **kw):
    import jax.numpy as jnp
    from perfbench import reference_qwen3_next as ref
    o, state = _the_programs(*a, **kw)
    return o, ref.round_to(state, jnp.bfloat16)


@pytest.mark.parametrize("fn,ok", [(_the_programs, True),
                                   (_a_bf16_pool, False)],
                         ids=["float32_state", "bf16_pool"])
def test_the_recurrence_limit_tells_a_bf16_state_from_float32(
        recurrence_case, fn, ok):
    """What no limit on served tokens can (the reference's section on
    the state's precision): the step programs' recurrence, handed a
    sequence as the engine hands a request over — a prompt in two steps,
    a step's rows as one descriptor a page, then one row a step —
    against the plain recurrence.  A state kept in bf16 between steps
    reads over the limit, the program's float32 state far under it."""
    from perfbench import reference_qwen3_next as ref
    from perfbench.builders.serve_qwen3_next import \
        through_the_step_recurrence
    ops, (o_ref, s_ref) = recurrence_case
    o, s = through_the_step_recurrence(fn, ops, page=128, budget=256,
                                       prompt_len=280)
    got = ref.judge_recurrence(o_ref, s_ref, o, s)
    assert got["ok"] is ok
    if ok:
        assert max(got["rel_l2_outputs"], got["rel_l2_state"]) \
            < ref.REC_REL_L2 / 10
    else:
        assert got["rel_l2_state"] > ref.REC_REL_L2 * 5


def test_the_reference_in_the_lower_precision_is_not_correct(
        recurrence_case):
    """The reading that has to come out as NOT correct: the plain
    recurrence with its state rounded to bf16 after every token fails
    both of the state's limits, with room."""
    import jax.numpy as jnp
    from perfbench import reference_qwen3_next as ref
    ops, (o_ref, s_ref) = recurrence_case
    o, s = ref.recurrence(*map(jnp.asarray, ops),
                          state_dtype=jnp.bfloat16)
    got = ref.judge_recurrence(o_ref, s_ref, o, s)
    assert not got["ok"]
    assert min(got["rel_l2_outputs"], got["rel_l2_state"]) \
        > 5 * ref.REC_REL_L2
    assert not ref.judge_state_bits(s)["ok"]
    assert ref.not_bf16_share(s) == 0.0
    sound = ref.judge_state_bits((s_ref, jnp.zeros_like(s_ref)))
    assert sound["ok"] and sound["not_bf16_share"] > 0.99


def test_the_configuration_holds_the_published_widths_and_says_its_cut():
    bench = manifest.benchmark()
    assert validate.problems(bench, manifest.ROOT) == []
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    cfg = manifest.config(bench, CONFIG)
    reduced = {"num_hidden_layers", "num_experts", "vocab_size"}
    assert set(entry["reduced"]) == set(cfg["reduced"]) == reduced
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    lo, hi = cfg["experts_held"]
    assert hi - lo == cfg["num_experts"] == 256
    assert cfg["vocab_slice"] == [0, cfg["vocab_size"]]
    assert cfg["num_hidden_layers"] == cfg["full_attention_interval"]
    for word in ("two chips share each layer", "expert-parallel",
                 "vocabulary-parallel", "pipeline"):
        assert word in cfg["deployment"]
    assert any("held ONCE" in d for d in cfg["departures"])
    cell = manifest.cell(bench, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "longctx_overload"
    tokens = next(m for m in bench["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert CELL in tokens["workloads"]
    mine = [m for m in bench["per_layer"]
            if m.get("workloads") == [CELL]]
    assert {m["moves"] for m in mine} == {"serve_tokens_per_s"}
    traffic = manifest.traffic(cell["traffic"])
    assert traffic["prompt_len"]["median"] == 4096
    assert traffic["prompt_len"]["max"] + traffic["output_len"]["max"] \
        <= cfg["engine"]["max_len"]
    assert f'{traffic["arrival"]["rate_rps"]} req/s' in cell["why"]
