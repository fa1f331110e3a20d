"""The Qwen3-Next hybrid backbone (Gated-DeltaNet layers with a per-slot
recurrent state beside gated full attention over KV pages, an expert
layer that holds a share) against its plain float32 reference, at a
small size on the CPU: eager forward, the served path's LOGITS, the
expert shares, slot reuse, suspend/resume and export/import, the refused
options, the counters, the compile counts."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import engine as E
from paddle_tpu.inference.engine import LLMEngine
from paddle_tpu.inference.moe_dispatch import moe_ffn
from paddle_tpu.models.qwen3_next import (Qwen3NextForCausalLM,
                                          layer_weights, moe_weights,
                                          qwen3_next_tiny_config)
from paddle_tpu.models.references import qwen3_next as ref

ENGINE = dict(max_seqs=3, max_len=272, page_size=16, steps_per_sync=8,
              prefill_token_budget=48)
# every engine of this file has ONE geometry, so the file compiles one
# mixed-step program and three window programs, all traced with a spy on
# the logits they sample from (``SEEN`` fills while ``RECORD`` is set).
# The geometry (17 pages a sequence) is this file's OWN: a file that had
# compiled the same model at the same geometry earlier in the same
# worker process (``tests/test_step_crossings.py`` serves this model at
# 16 pages) would leave programs without the spy in the jit caches.
SEEN, RECORD, COMPILED = [], [False], {}


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
    """A tiny model whose norm weights are NOT at their initial zeros /
    ones, so that a norm taken the wrong way shows."""
    paddle.seed(7)
    cfg = qwen3_next_tiny_config(**cfg_kw)
    model = Qwen3NextForCausalLM(cfg)
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


def ref_logits(tiny, ids):
    _, cfg, cfgd, params = tiny
    return np.asarray(ref.forward(params, cfgd, ids,
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
    ids = prompt(70)             # crosses a 64-row WY chunk and 4 pages
    with paddle.no_grad():
        got = model(paddle.to_tensor(np.asarray([ids]))).numpy()[0]
    np.testing.assert_allclose(got, ref_logits(tiny, ids), atol=2e-5)


def test_layer_pattern_and_share_follow_the_config():
    cfg = qwen3_next_tiny_config(num_hidden_layers=8)
    assert cfg.layer_kinds == ("linear", "linear", "linear", "full") * 2
    assert ref.layer_kinds(dataclasses.asdict(cfg)) == cfg.layer_kinds
    assert cfg.held == (0, 4) and cfg.rotary_dim == 4


def test_served_logits_equal_the_references_full_forward(tiny):
    """Prefill in chunks (48 rows a step = three page chunks of ONE
    request in a step, then 16 + 11), then decode through windows of 8,
    4, 2 and 1: every position's logits, as the step programs computed
    them, against the reference's full forward pass."""
    model, ids, n_new = tiny[0], prompt(75, 3), 16
    eng = LLMEngine(model, **ENGINE)
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
    served = eng.result("a")
    assert windows[-4:] == [8, 4, 2, 1] and len(served) == n_new
    want = ref_logits(tiny, ids + served[:-1])
    rows = np.concatenate(SEEN)                     # every row computed
    for pos in range(len(want)):
        err = np.abs(rows - want[pos][None]).max(axis=1).min()
        assert err < 3e-5, (pos, err)
    assert served == want[len(ids) - 1:].argmax(-1).tolist()


def test_expert_shares_add_up_to_the_uncut_layer(tiny):
    """The two halves of the experts, each as the SERVED expert layer
    computes its share, with the shared expert (which both chips
    compute alike) counted once, give the uncut reference's layer."""
    model, cfg, cfgd, params = tiny
    full_model, _ = make(experts_held=(0, 8))
    sd = full_model.raw_state_dict()
    lay = ref.canonical(sd, cfgd)["layers"][1]
    h = jnp.asarray(np.random.default_rng(2).normal(size=(40, 64)),
                    jnp.float32)
    whole = np.asarray(ref.moe(h, lay, cfgd))
    shared = np.asarray(ref.shared_expert(h, lay))
    w = layer_weights(sd, 1, "linear")
    total, counts = -shared, []
    for lo in (0, 4):
        half = dict(w, **{k: w[k][lo:lo + 4] for k in (
            "experts_gate", "experts_up", "experts_down")})
        arch = full_model.moe_arch("grouped")._replace(expert_lo=lo,
                                                       experts_held=4)
        y, cnt = moe_ffn(h, moe_weights(half), arch, jnp.ones(40, bool))
        total = total + np.asarray(y)
        counts.append(np.asarray(cnt))
        # and the reference, given the same share, gives the same part
        part = ref.moe(h, dict(lay, **{k: lay[k][lo:lo + 4] for k in (
            "experts_gate", "experts_up", "experts_down")}), cfgd,
            held=(lo, 4))
        np.testing.assert_allclose(y, part, atol=2e-6)
    np.testing.assert_allclose(total, whole, atol=3e-6)
    # both shares count every routed slot of the router's full width
    assert (counts[0] == counts[1]).all() and counts[0].sum() == 40 * 2
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


def test_export_import_carries_the_state_to_another_engine(tiny):
    model = tiny[0]
    ids = prompt(44, 12)
    want = serve_alone(model, ids, 12)
    src, dst = LLMEngine(model, **ENGINE), LLMEngine(model, **ENGINE)
    src.begin_request("m", ids, max_new_tokens=12)
    out = {}
    while len(out.get("m", [])) < 4:
        for rid, t in src.step().items():
            out.setdefault(rid, []).extend(t)
    assert src.suspend("m")
    pkg = src.export_request("m")
    assert pkg["swap"] is not None
    dst.begin_request("busy", prompt(25, 13), max_new_tokens=3)
    dst.step()
    dst.import_request(pkg)
    assert dst.resume("m") == "swap_in"
    run(dst, out)
    assert out["m"] == want


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
    assert "linear-attention" in str(err.value)


def test_synchronous_admission_is_refused_and_prefix_caching_is_off(tiny):
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
    # 3 linear layers x (75 prompt rows; 15 decode rows; 15 decode
    # descriptors + chunks 16,16,16 | 16,11)
    assert (lin["prefill_rows"], lin["decode_rows"],
            lin["descriptors"]) == (225, 45, 60)
    assert lin["layers"] == 3 and lin["full_layers"] == 1
    per_slot = 3 * (4 * 8 * 8 * 4 + 3 * (2 * 2 * 8 + 4 * 8) * 4)
    assert lin["state_bytes_per_slot"] == per_slot
    assert lin["state_bytes"] == per_slot * (ENGINE["max_seqs"] + 1)
    assert snap["kv_cache"]["state_bytes"] == lin["state_bytes"]
    # every routed slot is counted once; those outside [0, 4) are absent
    tot = np.asarray(moe["expert_tokens"])
    assert tot.sum() == 4 * 2 * (75 + 15) and moe["dropped_tokens"] == 0
    assert moe["absent_slots"] == tot[4:].sum() > 0
    assert (moe["expert_lo"], moe["experts_held"]) == (0, 4)
    from paddle_tpu.observability import get_registry
    text = get_registry().expose_text()
    eid = eng.engine_id
    for line in (
            f'llm_engine_linear_rows_total{{engine="{eid}",kind="prefill"}} 225',
            f'llm_engine_linear_rows_total{{engine="{eid}",kind="decode"}} 45',
            f'llm_engine_linear_descriptors_total{{engine="{eid}"}} 60',
            f'llm_engine_state_bytes{{engine="{eid}"}} {lin["state_bytes"]}',
            f'llm_engine_expert_absent_slots_total{{engine="{eid}"}} '
            f'{moe["absent_slots"]}'):
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


def test_one_mixed_step_program_and_the_declared_window_buckets(tiny):
    """LAST in the file: everything above — every mix of prompt chunks
    and decode rows, several engines, suspend / resume / import, the
    HTTP front end — ran on ONE compiled mixed-step program and the
    three declared window buckets (8, 4, 2)."""
    eng = LLMEngine(tiny[0], **ENGINE)
    for i, (n, new) in enumerate([(75, 16), (20, 9), (33, 3), (48, 12)]):
        eng.begin_request(i, prompt(n, 20 + i), max_new_tokens=new)
        if i % 2:
            run(eng)
    run(eng)
    assert E._packed_mixed_step._cache_size() - COMPILED["step"] == 1
    assert E._packed_mixed_window._cache_size() - COMPILED["window"] == 3
    assert eng.metrics_snapshot()["prefill_compiles"] == \
        E._paged_prefill_chunk._cache_size()
