"""The Nemotron-H hybrid backbone (blocks that are a Mamba-2 mixer, an
attention mixer with no position signal, or a latent sigmoid-routed
expert layer ALONE; a per-slot recurrent state beside the KV pages; an
expert share) against its plain float32 reference, at a small size on
the CPU: eager forward, the served path's LOGITS, the expert shares,
the controls, suspend/resume, the refused options, the counters, the
HTTP front end, the compile counts."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import engine as E
from paddle_tpu.inference.engine import LLMEngine
from paddle_tpu.inference.moe_dispatch import moe_ffn
from paddle_tpu.models.nemotron_h import (NemotronHForCausalLM,
                                          layer_weights,
                                          nemotron_h_tiny_config)
from paddle_tpu.models.references import nemotron_h as ref

ENGINE = dict(max_seqs=3, max_len=288, page_size=16, steps_per_sync=8,
              prefill_token_budget=48)
# every engine of this file has ONE geometry, so the file compiles one
# mixed-step program and three window programs, all traced with a spy on
# the logits they sample from (``SEEN`` fills while ``RECORD`` is set).
# The geometry (18 pages a sequence) is this file's OWN, so that no file
# run earlier in the same worker process has left these programs in the
# jit caches without the spy.
SEEN, RECORD, COMPILED = [], [False], {}
# The served path in float32 against the float32 reference: what is left
# is the order of summation (the chunked scan against the token loop,
# the sorted expert buffer against the per-expert sum, the paged
# attention against the dense one): a few float32 ulps of logits that
# reach 4.
LOGITS_ATOL = 3e-5


@pytest.fixture(scope="module", autouse=True)
def spy_on_the_logits():
    from paddle_tpu.inference import sampling
    real = sampling.sample_logits

    def keep(x):
        if RECORD[0]:
            SEEN.append(np.asarray(x))

    def spy(logits, *a, **kw):
        jax.debug.callback(keep, logits)
        return real(logits, *a, **kw)
    COMPILED.update(step=E._packed_mixed_step._cache_size(),
                    window=E._packed_mixed_window._cache_size())
    mp = pytest.MonkeyPatch()
    mp.setattr(sampling, "sample_logits", spy)
    yield
    mp.undo()


def make(**cfg_kw):
    """A tiny model whose norm weights are NOT at their initial ones, so
    that a norm taken the wrong way shows."""
    paddle.seed(7)
    cfg = nemotron_h_tiny_config(**cfg_kw)
    model = NemotronHForCausalLM(cfg)
    r = np.random.default_rng(1)
    sd = model.raw_state_dict()
    model.load_raw_state_dict({
        k: v + 0.3 * jnp.asarray(r.normal(size=v.shape), v.dtype)
        for k, v in sd.items() if "norm" in k})
    return model, cfg


@pytest.fixture(scope="module")
def tiny():
    model, cfg = make()
    cfgd = dataclasses.asdict(cfg)
    return model, cfg, cfgd, ref.canonical(model.raw_state_dict(), cfgd)


def ref_logits(tiny, ids, params=None):
    _, cfg, cfgd, own = tiny
    return np.asarray(ref.forward(params or own, cfgd, ids,
                                  experts_held=cfg.held))


def prompt(n, seed=0, vocab=128):
    return np.random.default_rng(seed).integers(0, vocab, size=n).tolist()


def run(engine, out=None):
    out = {} if out is None else out
    while engine.has_work():
        for rid, toks in engine.step().items():
            out.setdefault(rid, []).extend(toks)
    return out


def serve_alone(model, ids, n_new, **kw):
    eng = LLMEngine(model, **dict(ENGINE, **kw))
    eng.begin_request("solo", ids, max_new_tokens=n_new)
    return run(eng)["solo"]


def test_reference_equals_the_models_eager_forward(tiny):
    model = tiny[0]
    ids = prompt(70)              # crosses 16-row SSD chunks and 4 pages
    with paddle.no_grad():
        got = model(paddle.to_tensor(np.asarray([ids]))).numpy()[0]
    np.testing.assert_allclose(got, ref_logits(tiny, ids), atol=1e-5)


def test_block_pattern_and_share_follow_the_config():
    cfg = nemotron_h_tiny_config()
    assert cfg.layer_kinds == ("ssm", "ffn", "ssm", "full", "ffn")
    assert ref.layer_kinds(dataclasses.asdict(cfg)) == cfg.layer_kinds
    assert cfg.held == (0, 4) and cfg.conv_channels == 8 * 8 + 2 * 2 * 16
    from paddle_tpu.models.nemotron_h import NemotronHConfig
    pub = NemotronHConfig().layer_kinds
    assert (len(pub), pub.count("ssm"), pub.count("ffn"),
            pub.count("full")) == (88, 40, 40, 8)
    # the benchmark's eleven blocks are published blocks 27-37
    assert NemotronHConfig().hybrid_override_pattern[27:38] == \
        "MEMEMEMEM*E"


def served_rows(tiny, ids, n_new):
    """Serve one request, recording every row of logits the step
    programs sampled from; returns (tokens, rows, window sizes)."""
    eng = LLMEngine(tiny[0], **ENGINE)
    eng.begin_request("a", ids, max_new_tokens=n_new)
    windows = []
    del SEEN[:]
    RECORD[0] = True
    try:
        while eng.has_work():
            eng.step()
            windows.append(eng.last_window_steps)
        jax.effects_barrier()
    finally:
        RECORD[0] = False
    return eng.result("a"), np.concatenate(SEEN), windows


def worst_logit_error(rows, want):
    return max(np.abs(rows - want[pos][None]).max(axis=1).min()
               for pos in range(len(want)))


def test_served_logits_equal_the_references_full_forward(tiny):
    """Prefill in chunks (48 rows a step = three page chunks of ONE
    request in a step, then 16 + 11), then decode through windows of 8,
    4, 2 and 1: every position's logits, as the step programs computed
    them, against the reference's full forward pass."""
    ids, n_new = prompt(75, 3), 16
    served, rows, windows = served_rows(tiny, ids, n_new)
    assert windows[-4:] == [8, 4, 2, 1] and len(served) == n_new
    want = ref_logits(tiny, ids + served[:-1])
    assert worst_logit_error(rows, want) < LOGITS_ATOL
    assert served == want[len(ids) - 1:].argmax(-1).tolist()


def _without(sd, what):
    """The model's weights with one term of the equations taken out."""
    sd = dict(sd)
    for name in list(sd):
        if what == "decay" and name.endswith("mixer.A_log"):
            sd[name] = jnp.full_like(sd[name], -jnp.inf)   # exp(dt A) = 1
        if what == "D" and name.endswith("mixer.D"):
            sd[name] = jnp.zeros_like(sd[name])
        if what == "conv_bias" and name.endswith("mixer.conv_b"):
            sd[name] = jnp.zeros_like(sd[name])
    return sd


ROUTE_CONTROLS = {"correction_bias": dict(use_bias=False),
                  "route_scale": dict(scale=1.0),
                  "softmax": dict(scoring="softmax")}


@pytest.mark.parametrize("what", ["decay", "D", "conv_bias",
                                  *ROUTE_CONTROLS])
def test_each_control_fails_the_small_comparison(tiny, what):
    """The reference with one term dropped, or with another router, lies
    far outside the tolerance the served logits are held to."""
    model, cfg, cfgd, params = tiny
    ids = prompt(60, 4)
    want = ref_logits(tiny, ids)
    if what in ROUTE_CONTROLS:
        got = np.asarray(ref.forward(params, cfgd, ids,
                                     experts_held=cfg.held,
                                     **ROUTE_CONTROLS[what]))
    else:
        got = ref_logits(tiny, ids, ref.canonical(
            _without(model.raw_state_dict(), what), cfgd))
    assert np.abs(got - want).max() > 100 * LOGITS_ATOL, what


@pytest.mark.parametrize("what", ["bf16_state", "default_precision"])
def test_a_lower_precision_recurrence_fails_its_comparison(what):
    """The recurrence the step programs call, over a sequence handed
    over as the engine hands a request over, against the plain one:
    sound within 1e-5 (relative L2); with its pool rounded to bf16
    between calls far outside.  (The matmul-precision control has to be
    read on the chip: a CPU computes float32 products exactly.)"""
    from paddle_tpu.ops.pallas.mamba2_ssd import ragged_ssd
    r = np.random.default_rng(0)
    n, nh, p, g, ns, page = 75, 8, 8, 2, 16, 16
    f = np.float32
    ops = (r.normal(size=(n, nh, p)).astype(f),
           np.logaddexp(0, r.normal(size=(n, nh)) - 2).astype(f),
           -r.uniform(1, 16, size=nh).astype(f),
           r.normal(size=(n, g, ns)).astype(f),
           r.normal(size=(n, g, ns)).astype(f),
           r.uniform(0.5, 1.5, size=nh).astype(f))
    want, _ = ref.recurrence(*map(jnp.asarray, ops))

    def served(fn):
        state = jnp.zeros((3, nh, p, ns), f)
        out = []
        for pos in list(range(0, 64, page)) + list(range(64, n)):
            rows = min(page, 64 - pos) if pos < 64 else 1
            x, dt, a, b, c, d = ops
            buf = [np.zeros((page,) + v.shape[1:], f) for v in (x, dt, b, c)]
            for bb, v in zip(buf, (x, dt, b, c)):
                bb[:rows] = v[pos:pos + rows]
            y, state = fn(
                jnp.asarray(buf[0]), jnp.asarray(buf[1]), jnp.asarray(a),
                jnp.asarray(buf[2]), jnp.asarray(buf[3]), jnp.asarray(d),
                state, jnp.asarray([0], jnp.int32),
                jnp.asarray([rows], jnp.int32),
                jnp.asarray([pos], jnp.int32), jnp.asarray([1], jnp.int32),
                page_size=page)
            out.append(np.asarray(y)[:rows])
        return np.concatenate(out)

    def rel(got):
        return np.linalg.norm(got - np.asarray(want)) / np.linalg.norm(want)

    def bf16_pool(*a, **kw):
        y, s = ragged_ssd(*a, **kw)
        return y, s.astype(jnp.bfloat16).astype(jnp.float32)
    assert rel(served(ragged_ssd)) < 1e-5
    if what == "bf16_state":
        assert rel(served(bf16_pool)) > 1e-4
    else:
        # the control exists and runs (its reading is the chip's)
        with jax.default_matmul_precision("default"):
            assert rel(served(ragged_ssd.__wrapped__)) < 1e-5


def test_expert_shares_add_up_to_the_uncut_layer(tiny):
    """The two halves of the experts, each as the SERVED expert layer
    computes its share, with what both chips compute alike — the shared
    expert, and the projection out of the latent, which is linear, so
    the shares' latent sums may be added before OR after it — counted
    once, give the uncut reference's layer."""
    model, cfg, cfgd, params = tiny
    full_model, _ = make(experts_held=(0, 8))
    sd = full_model.raw_state_dict()
    lay = ref.canonical(sd, cfgd)["layers"][1]
    h = jnp.asarray(np.random.default_rng(2).normal(size=(40, 64)),
                    jnp.float32)
    whole = np.asarray(ref.moe(h, lay, cfgd))
    shared = np.asarray(ref.shared_expert(h, lay))
    w = layer_weights(sd, 1, "ffn")
    total, counts = -shared, []
    for lo in (0, 4):
        half = dict(w, **{k: w[k][lo:lo + 4] for k in (
            "experts_up", "experts_down")})
        arch = full_model.moe_arch("grouped")._replace(expert_lo=lo,
                                                       experts_held=4)
        y, cnt = moe_ffn(h, half, arch, jnp.ones(40, bool))
        total = total + np.asarray(y)
        counts.append(np.asarray(cnt))
        # and the reference, given the same share, gives the same part
        part = ref.moe(h, dict(lay, **{k: lay[k][lo:lo + 4] for k in (
            "experts_up", "experts_down")}), cfgd, held=(lo, 4))
        np.testing.assert_allclose(y, part, atol=3e-6)
        # grouped and dense dispatch agree on the share
        yd, cd = moe_ffn(h, half, arch._replace(dispatch="dense"),
                         jnp.ones(40, bool))
        np.testing.assert_allclose(y, yd, atol=3e-6)
        assert (np.asarray(cd) == counts[-1]).all()
    np.testing.assert_allclose(total, whole, atol=5e-6)
    # both shares count every routed slot of the router's full width
    assert (counts[0] == counts[1]).all() and counts[0].sum() == 40 * 3
    assert np.abs(whole - shared).max() > 1e-3      # the experts matter


def test_slot_reuse_after_retire_and_abort_starts_from_zero(tiny):
    model = tiny[0]
    want = serve_alone(model, prompt(40, 5), 6)
    eng = LLMEngine(model, **ENGINE)
    eng.begin_request("first", prompt(50, 6), max_new_tokens=5)
    run(eng)                                        # retires: slot 0 free
    eng.begin_request("gone", prompt(33, 8), max_new_tokens=30)
    eng.step()
    eng.step()
    assert eng.abort("gone")                        # mid-decode
    eng.begin_request("again", prompt(40, 5), max_new_tokens=6)
    assert eng.requests["again"].slot == 0
    assert run(eng)["again"] == want


@pytest.mark.parametrize("swap_pool_pages,path", [(None, "swap_in"),
                                                  (0, "recompute")])
def test_suspend_resume_gives_the_uninterrupted_tokens(tiny, swap_pool_pages,
                                                       path):
    model = tiny[0]
    ids = prompt(53, 9)
    want = serve_alone(model, ids, 14)
    eng = LLMEngine(model, **dict(ENGINE, swap_pool_pages=swap_pool_pages))
    eng.begin_request("a", ids, max_new_tokens=14)
    eng.begin_request("other", prompt(30, 10), max_new_tokens=20)
    out = {}
    while len(out.get("a", [])) < 5:
        for rid, t in eng.step().items():
            out.setdefault(rid, []).extend(t)
    assert eng.suspend("a") == (path == "swap_in")
    for _ in range(2):                   # the slot goes to someone else
        for rid, t in eng.step().items():
            out.setdefault(rid, []).extend(t)
    eng.begin_request("third", prompt(20, 11), max_new_tokens=4)
    for rid, t in eng.step().items():
        out.setdefault(rid, []).extend(t)
    assert eng.resume("a") == path
    run(eng, out)
    assert out["a"] == want
    snaps = eng.metrics_snapshot()["linear"]["state_snapshots"]
    assert snaps == (2 if path == "swap_in" else 0)


@pytest.mark.parametrize("kw,needle", [
    (dict(enable_prefix_caching=True), "enable_prefix_caching=True"),
    (dict(mesh="a mesh"), "mesh="),
    (dict(draft_model="a model"), "draft_model="),
    (dict(kv_dtype="int8"), "kv_dtype='int8'"),
    (dict(weight_dtype="int8"), "weight_dtype='int8'"),
    (dict(moe_dropless=False), "moe_dropless=False"),
])
def test_what_the_backbone_does_not_carry_is_refused_at_construction(
        tiny, kw, needle):
    with pytest.raises(ValueError) as err:
        LLMEngine(tiny[0], **dict(ENGINE, **kw))
    assert needle in str(err.value)
    assert "state-space" in str(err.value)
    assert "nemotron_h" in str(err.value)


def test_the_family_is_named_where_a_model_is_refused(tiny):
    from paddle_tpu.inference.backbone import resolve_backbone
    assert resolve_backbone(tiny[0]).arch == "nemotron_h"
    with pytest.raises(ValueError, match="Nemotron-H"):
        resolve_backbone(object())
    eng = LLMEngine(tiny[0], **ENGINE)
    assert eng.enable_prefix_caching is False
    with pytest.raises(Exception, match="begin_request"):
        eng.add_request("x", prompt(5), max_new_tokens=2)


def test_counters_are_exact_for_a_fixed_request_list(tiny):
    model, cfg = tiny[0], tiny[1]
    eng = LLMEngine(model, **ENGINE)
    eng.begin_request("a", prompt(75, 1), max_new_tokens=16)
    run(eng)
    snap = eng.metrics_snapshot()
    lin, moe = snap["linear"], snap["moe"]
    # 2 Mamba-2 blocks x (75 prompt rows; 15 decode rows; 15 decode
    # descriptors + chunks 16,16,16 | 16,11)
    assert (lin["prefill_rows"], lin["decode_rows"],
            lin["descriptors"]) == (150, 30, 40)
    assert lin["layers"] == 2 and lin["full_layers"] == 1
    # a slot a block: state 8 x 8 x 16 float32 + 3 conv rows of 128 f32
    per_layer = 8 * 8 * 16 * 4 + 3 * 128 * 4
    assert lin["state_bytes_per_slot"] == 2 * per_layer
    assert lin["state_bytes"] == 2 * per_layer * (ENGINE["max_seqs"] + 1)
    assert snap["kv_cache"]["state_bytes"] == lin["state_bytes"]
    # each live descriptor reads and writes one slot's state in a block
    assert lin["state_bytes_moved"] == 2 * 40 * per_layer
    # every routed slot of the TWO expert blocks is counted once; those
    # outside [0, 4) are absent
    tot = np.asarray(moe["expert_tokens"])
    assert tot.sum() == 2 * 3 * (75 + 15) and moe["dropped_tokens"] == 0
    assert moe["absent_slots"] == tot[4:].sum() > 0
    assert (moe["expert_lo"], moe["experts_held"]) == (0, 4)
    # rows of the sorted buffer, by the rows of the program that ran: a
    # mixed step's 51 (3 slots + 48 budget) x top-3 = 153 slots + 4
    # experts x the CPU's tile of 8, rounded up to the tile: 192 a block
    # a forward; a window forward's 3 (one a slot): 9 slots -> 16 + 32 =
    # 48.  3 mixed steps (48 + 27 prompt rows, and the last decode row
    # alone: a window of one IS the step program) + windows of 8 + 4 + 2
    assert moe["buffer_rows"] == 2 * (192 * 3 + 48 * 14)
    assert snap["forward_rows"] == {
        "mixed": {"capacity": 51 * 3, "live": 75 + 1},
        "window": {"capacity": 3 * 14, "live": 14}}
    assert moe["row_fill"] == pytest.approx(
        (tot.sum() - moe["absent_slots"]) / moe["buffer_rows"])
    from paddle_tpu.observability import get_registry
    text = get_registry().expose_text()
    eid = eng.engine_id
    for line in (
            f'llm_engine_linear_rows_total{{engine="{eid}",kind="prefill"}} 150',
            f'llm_engine_linear_rows_total{{engine="{eid}",kind="decode"}} 30',
            f'llm_engine_linear_descriptors_total{{engine="{eid}"}} 40',
            f'llm_engine_state_bytes{{engine="{eid}"}} {lin["state_bytes"]}',
            f'llm_engine_state_bytes_moved_total{{engine="{eid}"}} '
            f'{lin["state_bytes_moved"]}',
            f'llm_engine_expert_absent_slots_total{{engine="{eid}"}} '
            f'{moe["absent_slots"]}',
            f'llm_engine_expert_buffer_rows_total{{engine="{eid}"}} '
            f'{moe["buffer_rows"]}',
            f'llm_engine_forward_row_capacity_total{{engine="{eid}",'
            f'path="mixed"}} 153',
            f'llm_engine_forward_row_capacity_total{{engine="{eid}",'
            f'path="window"}} 42'):
        assert line in text, line


def test_scheduler_and_http_front_end_serve_the_hybrid(tiny):
    import json
    import urllib.request

    from paddle_tpu.serving.scheduler import Scheduler
    from paddle_tpu.serving.server import start_http_frontend
    model = tiny[0]
    ids = prompt(37, 30)
    want = serve_alone(model, ids, 9)
    fe = start_http_frontend(Scheduler(LLMEngine(model, **ENGINE),
                                       chunked_prefill=True))
    try:
        body = json.dumps({"id": "h", "prompt": ids, "max_tokens": 9,
                           "stream": False}).encode()
        req = urllib.request.Request(
            fe.url + "/v1/completions", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=300) as resp:
            got = json.loads(resp.read())
        with urllib.request.urlopen(fe.url + "/statusz", timeout=60) as r:
            status = r.read().decode()
    finally:
        fe.kill()
    assert got["tokens"] == want
    status = json.loads(status)
    assert status["target"]["moe"]["absent_slots"] > 0
    assert status["target"]["linear"]["state_bytes"] > 0
    assert status["target"]["linear"]["state_bytes_moved"] > 0


def test_one_mixed_step_program_and_the_declared_window_buckets(tiny):
    """LAST in the file: everything above — every mix of prompt chunks
    and decode rows, several engines, suspend / resume, the HTTP front
    end — ran on ONE compiled mixed-step program and the three declared
    window buckets (8, 4, 2)."""
    eng = LLMEngine(tiny[0], **ENGINE)
    for i, (n, new) in enumerate([(75, 16), (20, 9), (33, 3), (48, 12)]):
        eng.begin_request(i, prompt(n, 20 + i), max_new_tokens=new)
        if i % 2:
            run(eng)
    run(eng)
    assert E._packed_mixed_step._cache_size() - COMPILED["step"] == 1
    assert E._packed_mixed_window._cache_size() - COMPILED["window"] == 3
