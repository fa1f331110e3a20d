"""The cell ``ssm_moe_serve_reason`` and its configuration
``nemotron3_super_120b_a12b_11l``, checked on the CPU at the
configuration's ``rehearse`` sizes: the cell runs end to end, the
benchmark's copy of the plain reference agrees with the program's, each
judge passes the sound program and fails its controls, and the additions
keep ``BENCHMARK.json`` valid.
"""
import io
import json
import math

import numpy as np
import pytest

from perfbench import manifest, validate

CELL, CONFIG = "ssm_moe_serve_reason", "nemotron3_super_120b_a12b_11l"

# the published config.json's widths (the catalog's row), number for number
PUBLISHED = {
    "hidden_size": 4096, "num_attention_heads": 32,
    "num_key_value_heads": 2, "head_dim": 128, "mamba_num_heads": 128,
    "mamba_head_dim": 64, "ssm_state_size": 128, "n_groups": 8,
    "conv_kernel": 4, "chunk_size": 128, "moe_intermediate_size": 2688,
    "moe_latent_size": 1024, "moe_shared_expert_intermediate_size": 5376,
    "n_routed_experts": 512, "num_experts_per_tok": 22,
    "routed_scaling_factor": 5, "num_hidden_layers": 88,
    "vocab_size": 131072, "max_position_embeddings": 262144,
    "layer_norm_epsilon": 1e-05, "mlp_hidden_act": "relu2",
    "norm_topk_prob": True, "use_conv_bias": True}
NEW_METRICS = {
    "engine.step_ms.reason", "engine.rows_per_step.reason",
    "engine.tokens_per_step.reason", "sched.batch_occupancy.reason",
    "ttft_p95_ms.reason", "ssm.rows_per_step.reason",
    "ssm.state_bytes_per_step.reason", "moe.buffer_rows_per_step.reason"}
DEVICE_METRICS = {
    "kernel.named_share.reason", "device.idle_share.reason",
    "engine.idle_prepare_ms.reason", "engine.idle_readback_ms.reason",
    "engine.idle_finish_ms.reason", "sched.idle_ms.reason"}


def rehearsal(trace):
    import gc
    from paddle_tpu.observability.introspection import memory_brief
    from perfbench import run
    buf = io.StringIO()
    gc.collect()
    before = memory_brief()["device_pool_bytes"]
    rc = run.run_cell(CELL, 2 ** 31 + 31, 3.0, trace, rehearse=True,
                      out=lambda s: buf.write(s + "\n"))
    assert rc == 0
    gc.collect()      # pools, state and weights: nothing outlives the run
    assert memory_brief()["device_pool_bytes"] <= before
    lines = buf.getvalue().strip().splitlines()
    assert len(lines) == 1
    return json.loads(lines[0])


@pytest.mark.parametrize("trace,metrics", [
    (False, {"serve_tokens_per_s", "setup_s"}), (True, NEW_METRICS)])
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


@pytest.fixture(scope="module")
def small():
    """The rehearse sizes in float32, the model the builder makes and the
    benchmark's canonical parameters."""
    from perfbench import reference_nemotron_h as bench_ref
    from perfbench.builders import serve_nemotron_h as builder
    sz = manifest.at_size(manifest.config(manifest.benchmark(), CONFIG),
                          True)
    sz["dtype"] = "float32"
    model = builder.make_model(sz, 2 ** 31 + 5, 128)
    sd = model.raw_state_dict()
    return sz, model, sd, bench_ref.canonical(sd, sz)


def test_both_copies_of_the_reference_give_the_same_logits(small):
    """``perfbench/reference_nemotron_h.py`` (blocks: one expert and one
    slice of the head at a time) against
    ``paddle_tpu/models/references/nemotron_h.py`` (one pass), on the
    served model's own weights, given the same share."""
    from paddle_tpu.models.references import nemotron_h as program_ref
    from perfbench import reference_nemotron_h as bench_ref
    sz, _, sd, params = small
    ids = np.random.default_rng(0).integers(0, sz["vocab_size"],
                                            size=70).tolist()
    got = bench_ref.logits(params, sz, ids)
    want = np.asarray(program_ref.forward(
        program_ref.canonical(sd, sz), sz, ids,
        experts_held=program_ref.experts_held(sz)))
    assert got.shape == (70, sz["vocab_size"])
    np.testing.assert_allclose(got, want, atol=1e-5)
    # each lower reading moves the logits (PERF.md's second readings
    # are no no-ops) and is never what a check computes
    import jax.numpy as jnp
    for kw in (dict(state_dtype=jnp.bfloat16), dict(use_bias=False),
               dict(scale=1.0), dict(scoring="softmax")):
        low = bench_ref.logits(params, sz, ids, **kw)
        assert 1e-4 < np.abs(low - got).max(), kw


def test_the_judge_keeps_its_band_and_goes_on_past_a_near_tie():
    from perfbench import reference_nemotron_h as ref
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
    bad = ref.judge_served(row, 3, [2, 5])
    assert not bad["ok"] and bad["not_ties"][0]["position"] == 1


@pytest.fixture(scope="module")
def recurrence_case():
    """One seeded sequence at the published geometry's shape, at an
    eighth of its heads: the operands and the plain recurrence's outputs
    and final state."""
    import jax.numpy as jnp
    from perfbench import reference_nemotron_h as ref
    cfg = dict(manifest.config(manifest.benchmark(), CONFIG),
               mamba_num_heads=16, n_groups=2)
    ops = ref.recurrence_inputs(cfg, 2 ** 31 + 11, 300)
    return ops, ref.recurrence(*map(jnp.asarray, ops))


def _served(fn, ops):
    """``ops`` through ``fn`` as the engine hands a request over: a
    prompt of 280 in two steps of 256 rows, a descriptor a page, then one
    row a step."""
    import jax.numpy as jnp
    from perfbench.builders.serve_nemotron_h import \
        through_the_step_recurrence
    x, dt, a, b, c, d = ops

    def call(x_, dt_, b_, c_, state, *desc, page_size):
        return fn(x_, dt_, jnp.asarray(a), b_, c_, jnp.asarray(d), state,
                  *desc, page_size=page_size)
    return through_the_step_recurrence(
        call, (x, dt, b, c), (x.shape[1], x.shape[2], b.shape[2]),
        page=128, budget=256, prompt_len=280)


def _the_programs(*a, **kw):
    from paddle_tpu.ops.pallas.mamba2_ssd import ragged_ssd
    return ragged_ssd(*a, **kw)


def _a_bf16_pool(*a, **kw):
    import jax.numpy as jnp
    from perfbench import reference_nemotron_h as ref
    y, state = _the_programs(*a, **kw)
    return y, ref.round_to(state, jnp.bfloat16)


def _no_decay(x, dt, a, *rest, **kw):
    return _the_programs(x, dt, a * 0.0, *rest, **kw)


def _no_skip(x, dt, a, b, c, d, *rest, **kw):
    return _the_programs(x, dt, a, b, c, d * 0.0, *rest, **kw)


@pytest.mark.parametrize("fn,ok", [
    (_the_programs, True), (_a_bf16_pool, False), (_no_decay, False),
    (_no_skip, False)],
    ids=["float32_state", "bf16_pool", "decay_dropped", "D_dropped"])
def test_the_recurrence_limit_passes_the_program_and_fails_its_controls(
        recurrence_case, fn, ok):
    """The step programs' recurrence at 128-row chunks against the plain
    one: the program's float32 state far under the limit; a state kept in
    bf16 between steps, a dropped decay and a dropped ``D x`` over it."""
    from perfbench import reference_nemotron_h as ref
    ops, (y_ref, s_ref) = recurrence_case
    got = ref.judge_recurrence(y_ref, s_ref, *_served(fn, ops))
    assert got["ok"] is ok, got
    if ok:
        assert max(got["rel_l2_outputs"], got["rel_l2_state"]) \
            < ref.REC_REL_L2 / 10
    else:
        assert max(got["rel_l2_outputs"], got["rel_l2_state"]) \
            > ref.REC_REL_L2 * 5


def test_the_reference_in_the_lower_precision_is_not_correct(
        recurrence_case):
    """The plain recurrence with its state rounded to bf16 after every
    token fails both of the state's limits, with room."""
    import jax.numpy as jnp
    from perfbench import reference_nemotron_h as ref
    ops, (y_ref, s_ref) = recurrence_case
    y, s = ref.recurrence(*map(jnp.asarray, ops),
                          state_dtype=jnp.bfloat16)
    got = ref.judge_recurrence(y_ref, s_ref, y, s)
    assert not got["ok"] and got["rel_l2_state"] > 5 * ref.REC_REL_L2
    assert not ref.judge_state_bits(s)["ok"]
    assert ref.not_bf16_share(s) == 0.0
    sound = ref.judge_state_bits((s_ref, jnp.zeros_like(s_ref)))
    assert sound["ok"] and sound["not_bf16_share"] > 0.99


@pytest.mark.parametrize("kw,ok", [
    ({}, True), (dict(use_bias=False), False), (dict(scale=1.0), False),
    (dict(scoring="softmax"), False)],
    ids=["sound", "bias_dropped", "scale_dropped", "softmax"])
def test_the_expert_layer_limit_passes_the_program_and_fails_its_controls(
        small, kw, ok):
    """``moe_ffn`` (grouped dispatch) on seeded rows against the
    reference's expert layer in blocks: sound far under the limit; the
    reference with the correction bias left out of the selection, the
    route scale dropped, or a softmax router over it."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.inference.moe_dispatch import moe_ffn
    from perfbench import reference_nemotron_h as ref
    sz, model, _, params = small
    lay = next(l for l in params["layers"] if "router" in l)
    arch = model.moe_arch("grouped")
    h = jnp.asarray(ref.expert_rows(sz, 3, 40))
    live = jnp.ones(40, bool)
    whole = moe_ffn(h, lay, arch, live)[0]
    routed = moe_ffn(h, lay, arch._replace(shared=False), live)[0]
    got = ref.judge_expert_layer(
        ref.moe_in_blocks(h, lay, sz, shared=False, **kw),
        ref.moe_in_blocks(h, lay, sz, **kw), routed, whole)
    assert got["ok"] is ok, got
    if ok:
        assert got["rel_l2_routed"] < ref.EXPERT_REL_L2 / 100
    else:
        assert got["rel_l2_routed"] > ref.EXPERT_REL_L2 * 3


def test_the_configuration_holds_the_published_widths_and_says_its_cut():
    bench = manifest.benchmark()
    assert validate.problems(bench, manifest.ROOT) == []
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    cfg = manifest.config(bench, CONFIG)
    reduced = {"num_hidden_layers", "hybrid_override_pattern",
               "n_routed_experts", "vocab_size"}
    assert set(entry["reduced"]) == set(cfg["reduced"]) == reduced
    assert entry["source"] == cfg["source"] and "nvidia/NVIDIA-Nemotron-3" \
        in entry["source"]
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    lo, hi = cfg["experts_held"]
    assert hi - lo == cfg["n_routed_experts"] == 128
    assert cfg["vocab_slice"] == [0, cfg["vocab_size"]] == [0, 32768]
    pub = cfg["published"]["hybrid_override_pattern"]
    assert len(pub) == 88 and pub[27:38] == cfg["hybrid_override_pattern"]
    assert len(cfg["hybrid_override_pattern"]) == cfg["num_hidden_layers"]
    for word in ("four chips share each block", "expert-parallel",
                 "vocabulary-parallel", "pipeline"):
        assert word in cfg["deployment"]
    assert "no_rotary" in cfg["assumed"]
    assert any("held ONCE" in d for d in cfg["departures"])
    assert any("multi-token-prediction" in d for d in cfg["departures"])
    cell = manifest.cell(bench, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "reason_overload"
    assert bench["workloads"][-1] == cell
    tokens = next(m for m in bench["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert tokens["workloads"][-1] == CELL
    mine = [m for m in bench["per_layer"]
            if m.get("workloads") == [CELL]]
    assert {m["name"] for m in mine} == NEW_METRICS | DEVICE_METRICS
    assert bench["per_layer"][-len(mine):] == mine      # at the END
    assert {m["moves"] for m in mine} == {"serve_tokens_per_s"}
    traffic = manifest.traffic(cell["traffic"])
    assert (traffic["prompt_len"]["median"],
            traffic["output_len"]["median"]) == (256, 768)
    assert traffic["ramp_s"] == 30 and traffic["drain_s"] == 0
    assert traffic["prompt_len"]["max"] + traffic["output_len"]["max"] \
        <= cfg["engine"]["max_len"]
    assert f'{traffic["arrival"]["rate_rps"]} req/s' in cell["why"]
