"""MoE serving: expert-parallel paged decode with grouped-matmul
dispatch (ISSUE 19).

Contracts under test:
* backbone seam: ``resolve_backbone`` duck-types Llama AND Qwen2-MoE
  onto one ``BackboneSpec``; an unsupported model fails LOUDLY with
  the supported families and the ``register_backbone`` escape hatch;
* the ONE grouped_matmul dispatch per layer produces tokens
  BIT-IDENTICAL to the dense per-expert reference on every engine
  path — steps_per_sync x admission x KV dtype, int8 expert weights,
  capacity-factor dispatch — and through preempt -> resume on both
  restore paths (swap-in and recompute);
* token accounting: dropless drops NOTHING; a starved capacity
  factor drops tokens and says so; routed-slot totals reconcile
  between the two modes;
* capsules: an MoE capture replays bit-exactly, the ``moe`` router
  config gates replay (a tampered fingerprint is refused via
  ``fingerprint_mismatch``), while the dispatch MODE is deliberately
  absent — grouped captures replay on dense engines and vice versa;
* compile stability: churning batch mixes raise ZERO CompileWatch
  anomalies and zero new unified-program compiles (expert descriptors
  are traced data, not shapes);
* the per-expert load plane: ``metrics_snapshot()["moe"]``, the
  ``llm_engine_expert_tokens_total{layer,expert}`` registry family,
  and the /statusz target block;
* a tier-1 budget guard keeps this module's fast footprint flat.

Everything runs JAX_PLATFORMS=cpu on the tiny Qwen2-MoE config.
"""
import json
import re
import urllib.request
from pathlib import Path

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import engine as E
from paddle_tpu.inference.backbone import resolve_backbone
from paddle_tpu.inference.engine import LLMEngine
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.models.qwen2_moe import (Qwen2MoeForCausalLM,
                                         qwen2_moe_tiny_config)
from paddle_tpu.observability import capsule as C
from paddle_tpu.observability import introspection as I
from paddle_tpu.observability.metrics import get_registry
from paddle_tpu.serving import (ReplicaRouter, Scheduler,
                                start_http_frontend)

P = 8
PROMPTS = [[5, 9, 2, 14],                         # sub-page
           list(range(1, 20)),                    # 2.5 pages
           [7] * 33,                              # page-crossing
           [3, 1, 4, 1, 5, 9, 2, 6],              # exactly one page
           list(range(40, 51))]                   # 1.5 pages


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = Qwen2MoeForCausalLM(qwen2_moe_tiny_config())
    m.eval()
    return m


def _drain(eng):
    while eng.has_work():
        eng.step()


def _mk(model, **kw):
    kw.setdefault("max_seqs", 8)
    kw.setdefault("max_len", 64)
    kw.setdefault("page_size", P)
    kw.setdefault("n_pages", 64)
    return LLMEngine(model, **kw)


def _serve(model, prompts, max_new=6, admit="add", **kw):
    eng = _mk(model, **kw)
    for i, p in enumerate(prompts):
        (eng.begin_request if admit == "begin" else eng.add_request)(
            f"r{i}", p, max_new_tokens=max_new)
    _drain(eng)
    return [eng.result(f"r{i}") for i in range(len(prompts))], eng


# -- backbone seam -------------------------------------------------------------
def test_backbone_resolution_and_unsupported_error(model):
    spec = resolve_backbone(model)
    assert spec.arch == "qwen2_moe"
    assert spec.attn_bias is True and spec.moe is not None
    assert spec.moe["num_experts"] == 8 and spec.moe["top_k"] == 2
    paddle.seed(0)
    llama = LlamaForCausalLM(llama_tiny_config())
    lspec = resolve_backbone(llama)
    assert lspec.arch == "llama" and lspec.moe is None
    with pytest.raises(ValueError) as ei:
        resolve_backbone(object())
    msg = str(ei.value)
    assert "llama" in msg and "qwen2_moe" in msg
    assert "register_backbone" in msg


# -- grouped vs dense bit-identity ---------------------------------------------
@pytest.mark.parametrize("sps,admit,kv", [
    (1, "add", None), (4, "add", None), (8, "begin", None),
    (4, "begin", "int8")])
def test_grouped_matches_dense_grid(model, sps, admit, kv):
    """Acceptance: ONE grouped_matmul dispatch per layer produces the
    dense per-expert reference's tokens bit-for-bit in every program
    that routes: the step program alone, two window buckets, prompts
    prefilled by ``add_request``'s chunk program or packed into mixed
    steps beside decode rows, int8 KV pools."""
    kw = dict(steps_per_sync=sps, admit=admit, kv_dtype=kv)
    want, _ = _serve(model, PROMPTS, moe_dispatch="dense", **kw)
    got, _ = _serve(model, PROMPTS, moe_dispatch="grouped", **kw)
    assert got == want


def test_int8_experts_grouped_matches_dense(model):
    """Weight-only int8 expert stacks (per-channel absmax, scales
    applied POST-matmul in row order) keep the bit-identity.  The
    quantization is real — the expert/shared slots of the weight
    stack are (int8, scale) pairs, not fp arrays."""
    want, _ = _serve(model, PROMPTS[:3], max_new=8,
                     weight_dtype="int8", moe_dispatch="dense")
    got, eng = _serve(model, PROMPTS[:3], max_new=8,
                      weight_dtype="int8")
    assert got == want
    e_up, sh_dn = eng._stack[11], eng._stack[15]
    assert isinstance(e_up, tuple) and e_up[0].dtype == "int8"
    assert isinstance(sh_dn, tuple) and sh_dn[0].dtype == "int8"


@pytest.mark.parametrize("dispatch", ["grouped", "dense"])
@pytest.mark.parametrize("quant", [False, True])
def test_moe_ffn_reaches_a_layer_inside_flattened_stacks(dispatch, quant):
    """The unified step's layer loop hands ``moe_ffn`` EVERY layer's
    experts as one ``[L·E, ..]`` array (the engine's ``[L, E, ..]``
    stack, flattened) plus ``expert_base = layer · E`` — traced, as
    under the scan — so no layer is copied out for the grouped matmul.
    Output and counts are bit-equal to the same call on that layer's
    own ``[E, ..]`` stacks, grouped and dense, fp and int8 pairs."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.inference.moe_dispatch import MoEArch, moe_ffn
    from paddle_tpu.quantization.ops import quantize_absmax_raw
    rng = np.random.default_rng(11)
    n_layers, e, h, f, t, at = 3, 4, 16, 24, 10, 2
    arch = MoEArch(num_experts=e, top_k=2, norm_topk=False, capacity=0,
                   shared=False, shared_gate=False, attn_bias=False,
                   dispatch=dispatch)

    def stack(*shape):
        w = jnp.asarray(rng.normal(size=(n_layers, e) + shape), jnp.float32)
        return quantize_absmax_raw(w, axis=2) if quant else w

    def at_layer(w, i):
        return jax.tree_util.tree_map(lambda a: a[i], w)

    def flat(w):
        return jax.tree_util.tree_map(
            lambda a: a.reshape((-1,) + a.shape[2:]), w)
    experts = (stack(h, f), stack(h, f), stack(f, h))
    zed = jnp.zeros((1, 1), jnp.float32)
    rw = jnp.asarray(rng.normal(size=(h, e)), jnp.float32)
    hn = jnp.asarray(rng.normal(size=(t, h)), jnp.float32)
    live = jnp.arange(t) < t - 2

    def run(ws, base):
        return moe_ffn(hn, (rw,) + ws + (zed,) * 4, arch, live,
                       expert_base=base)
    want = jax.jit(lambda: run(tuple(at_layer(w, at) for w in experts),
                               0))()
    got = jax.jit(lambda i: run(tuple(flat(w) for w in experts),
                                i * e))(jnp.int32(at))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    assert int(got[1].sum()) == (t - 2) * arch.top_k


# -- capacity vs dropless accounting -------------------------------------------
def test_capacity_vs_dropless_accounting(model):
    """Dropless drops nothing; a starved capacity factor (0.5 -> one
    slot per expert per page group) drops tokens, says so in the
    snapshot, keeps grouped == dense, and the routed-slot totals
    reconcile: kept + dropped is the same physical slot count."""
    want, ed = _serve(model, PROMPTS, moe_dispatch="dense",
                      moe_dropless=False, moe_capacity_factor=0.5)
    got, ec = _serve(model, PROMPTS,
                     moe_dropless=False, moe_capacity_factor=0.5)
    assert got == want
    free, ef = _serve(model, PROMPTS)
    assert [len(t) for t in free] == [len(t) for t in got]
    mc, mf = ec.metrics_snapshot()["moe"], ef.metrics_snapshot()["moe"]
    assert mc["dropless"] is False and mc["capacity"] >= 1
    assert mc["dropped_tokens"] > 0
    assert mf["dropless"] is True and mf["dropped_tokens"] == 0
    assert sum(mc["expert_tokens"]) + mc["dropped_tokens"] == \
        sum(mf["expert_tokens"])


# -- preemption on the MoE path ------------------------------------------------
def test_preempt_resume_parity(model):
    """Mid-decode suspend -> resume through BOTH restore paths: the
    re-entered slot rejoins the grouped dispatch bit-identically."""
    prompt, n = PROMPTS[1], 8
    want, _ = _serve(model, [prompt], max_new=n)
    for swap_pages, path in ((32, "swap_in"), (0, "recompute")):
        eng = _mk(model, swap_pool_pages=swap_pages)
        eng.add_request("r", prompt, max_new_tokens=n)
        for _ in range(3):
            eng.step()
        eng.suspend("r")
        assert eng.resume("r") == path
        _drain(eng)
        assert eng.result("r") == want[0]


# -- capsule replay + router-config gate ---------------------------------------
def test_capsule_replay_and_fingerprint_gate(model):
    """An MoE capture replays bit-exactly; the dispatch MODE is
    deliberately outside the fingerprint (grouped capture replays on
    a dense engine: same bits, no mismatch); a tampered router config
    is refused via ``fingerprint_mismatch``."""
    C.enable_capsule_capture()
    eng = _mk(model)
    eng.add_request("g", PROMPTS[0], max_new_tokens=10)
    _drain(eng)
    cap = C.get_capsule_store().get("g")
    assert cap["fingerprint"]["moe"]["num_experts"] == 8
    assert "dispatch" not in cap["fingerprint"]["moe"]
    rep = C.replay_capsule(cap, eng)
    assert rep["first_divergence"] is None, rep
    assert not rep["fingerprint_mismatch"]
    dense = _mk(model, moe_dispatch="dense")
    rep = C.replay_capsule(cap, dense)
    assert rep["first_divergence"] is None, rep
    assert not rep["fingerprint_mismatch"]
    tampered = dict(cap, fingerprint=dict(
        cap["fingerprint"],
        moe=dict(cap["fingerprint"]["moe"], top_k=3)))
    rep = C.replay_capsule(tampered, eng)
    assert "moe" in rep["fingerprint_mismatch"]


# -- compile stability ---------------------------------------------------------
def test_compile_stability_across_mixes(model):
    """Expert routing is traced DATA: churning batch mixes through
    the unified MoE step raise zero CompileWatch anomalies and zero
    new compiles after warmup (delta form: the jit cache is
    process-global)."""
    w = I.enable_compile_watch()
    eng = _mk(model)                         # registers allowances
    eng.begin_request("w", [1, 2, 3], max_new_tokens=2)
    _drain(eng)
    base = LLMEngine.mixed_compiles()
    assert base >= 1
    rng = np.random.default_rng(0)
    eng2 = _mk(model)
    for i in range(6):                       # staggered admissions:
        plen = int(rng.integers(1, 40))      # every step sees a new
        eng2.begin_request(f"m{i}",          # decode/prefill mix
                           rng.integers(1, 200, plen).tolist(),
                           max_new_tokens=int(rng.integers(1, 8)))
        eng2.step()
    _drain(eng2)
    assert LLMEngine.mixed_compiles() == base, \
        "a batch-mix change recompiled the unified MoE program"
    assert not w.snapshot()["recompiles"]


# -- per-expert load plane -----------------------------------------------------
def test_expert_metrics_surface(model):
    """Per-expert routed-token counts surface in the engine snapshot,
    the registry counter family (engine, layer, expert), and the
    /statusz target block."""
    _, eng = _serve(model, PROMPTS[:2], max_new=4)
    moe = eng.metrics_snapshot()["moe"]
    assert moe["num_experts"] == 8 and len(moe["expert_tokens"]) == 8
    assert sum(moe["expert_tokens"]) > 0
    assert moe["imbalance"] >= 1.0
    assert moe["shared_experts"] is True
    text = get_registry().expose_text()
    eid = eng.engine_id
    assert f'llm_engine_expert_tokens_total{{engine="{eid}"' in text
    assert 'layer="0"' in text and 'expert="' in text
    assert f'llm_engine_expert_imbalance{{engine="{eid}"}}' in text
    # how full the grouped dispatch's sorted buffer ran (PR 31), exact
    # for a known routing: every step a mixed step of ONE forward over
    # the engine's t_cap rows (live or padding), so the buffer handed
    # to the kernels is m_pad x layers x steps, and dropless routing
    # keeps top-k slots a layer for every prompt token and for every
    # generated token but a request's last
    from paddle_tpu.inference.moe_dispatch import expert_buffer_rows
    from paddle_tpu.ops.pallas.grouped_matmul import padded_rows
    _, one = _serve(model, PROMPTS[:2], max_new=4, admit="begin",
                    steps_per_sync=1)
    snap = one.metrics_snapshot()
    m1, arch = snap["moe"], one._arch
    layers, t_cap = one._moe_counts.shape[0], one._step_geom[0]
    steps = snap["host_transfers"]["in"]
    m_pad = padded_rows(t_cap * arch.top_k, arch.num_experts, 8)
    assert expert_buffer_rows(arch, t_cap) == m_pad
    assert expert_buffer_rows(arch._replace(dispatch="dense"), t_cap) == 0
    kept = layers * arch.top_k * (len(PROMPTS[0]) + len(PROMPTS[1]) + 2 * 3)
    assert sum(m1["expert_tokens"]) == kept and m1["absent_slots"] == 0
    assert m1["buffer_rows"] == layers * steps * m_pad > kept
    assert m1["row_fill"] == kept / (layers * steps * m_pad)
    assert (f'llm_engine_expert_buffer_rows_total{{engine="'
            f'{one.engine_id}"}} {layers * steps * m_pad}') \
        in get_registry().expose_text()
    assert snap["forward_rows"] == {
        "mixed": {"capacity": steps * t_cap, "live": kept // (
            layers * arch.top_k)},
        "window": {"capacity": 0, "live": 0}}
    assert 0.0 < moe["row_fill"] < 1.0 and moe["buffer_rows"] > 0
    # ... and with windows, by the rows of the program that ran: a
    # window forward lays out a buffer for one row a slot, not for the
    # mixed step's slots + prefill budget (PR 33)
    _, two = _serve(model, PROMPTS[:2], max_new=12, admit="begin",
                    steps_per_sync=4)
    snap = two.metrics_snapshot()
    ran, m2 = snap["forward_rows"], snap["moe"]
    slots = two.max_seqs
    assert two._window_geom[0] == slots < t_cap
    mixed, window = (ran[p]["capacity"] // r for p, r in (
        ("mixed", t_cap), ("window", slots)))
    assert mixed > 0 and window >= 8
    assert ran["mixed"]["capacity"] == mixed * t_cap
    assert ran["window"]["capacity"] == window * slots
    w_pad = padded_rows(slots * arch.top_k, arch.num_experts, 8)
    assert expert_buffer_rows(arch, slots) == w_pad < m_pad
    assert m2["buffer_rows"] == layers * (mixed * m_pad + window * w_pad)
    # windows also route rows that have retired: live counts them
    assert sum(m2["expert_tokens"]) == layers * arch.top_k * (
        ran["mixed"]["live"] + ran["window"]["live"])
    assert m2["row_fill"] == sum(m2["expert_tokens"]) / m2["buffer_rows"]
    text = get_registry().expose_text()
    for path in ran:
        assert (f'llm_engine_forward_row_capacity_total{{engine="'
                f'{two.engine_id}",path="{path}"}} '
                f'{ran[path]["capacity"]}') in text
    sched = Scheduler(_mk(model), max_queue=8)
    sched.submit("s", PROMPTS[0], max_new_tokens=3)
    sched.run_until_idle(max_steps=100)
    fe = start_http_frontend(sched)
    try:
        st = json.loads(urllib.request.urlopen(
            fe.url + "/statusz").read())
        assert st["target"]["moe"]["num_experts"] == 8
        assert sum(st["target"]["moe"]["expert_tokens"]) > 0
    finally:
        fe.shutdown()
    router = ReplicaRouter([sched], sleep=lambda s: None)
    fleet = router.fleet_snapshot()["fleet"]["moe"]
    assert fleet["num_experts"] == 8
    assert fleet["expert_tokens"] == \
        sched.engine.metrics_snapshot()["moe"]["expert_tokens"]
    assert fleet["imbalance"] >= 1.0


# -- tier-1 budget guard -------------------------------------------------------
def test_tier1_budget_guard():
    """Adding MoE-serving tests must not blow the 870 s tier-1
    wall-clock budget on the 1-core CI box."""
    here = Path(__file__).resolve()
    src = here.read_text()
    n_fast = 0
    for m in re.finditer(r"((?:@[\w.]+(?:\(.*?\))?\s*\n)*)"
                         r"def test_\w+\(", src, re.S):
        if "pytest.mark.slow" not in m.group(1) \
                and "skipif" not in m.group(1):
            n_fast += 1
    assert n_fast <= 12, (
        f"{n_fast} fast MoE-serving tests — move the heavy ones "
        f"behind @pytest.mark.slow to protect the tier-1 budget")
