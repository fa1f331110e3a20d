"""Tensor-parallel serving over a GSPMD mesh (ISSUE 18).

Contracts under test, all on the forced 8-device CPU platform (the
root conftest's ``--xla_force_host_platform_device_count=8``):

* ``mesh=``/``tp_axis=`` shards the engine bit-exactly: greedy tokens
  on tp=2 are IDENTICAL to tp=1 on every path — fp, int8 KV, prefix
  hits, mixed steps, on-device windows — because only OUTPUT axes
  are ever sharded and every contraction input is explicitly gathered
  first (no cross-device float reduction anywhere);
* one compile per mesh shape: a second tp=2 engine with a different
  batch mix adds ZERO mixed/window compiles, and CompileWatch sees no
  recompile anomaly under churning mixed batches;
* the whole request lifecycle survives sharding: preempt -> resume on
  both restore paths, cross-mesh-shape migration (tp=1 <-> tp=2; the
  swap blob gathers to a portable host array and re-scatters on
  import), and capsule replay on — and ACROSS — tp variants;
* per-row stochastic draws: a sampling capsule captured while decoding
  in a NON-ZERO batch row replays bit-exactly (each window records its
  row; replay re-folds it via ``draw_base``) — the carried row>0
  stochastic-replay gap;
* per-shard memory honesty: ``memory_rows()`` reports
  ``device_bytes_per_shard == device_bytes / tp`` so a tp=N replica
  does not look N× cheaper than it is per chip.
"""
import numpy as np
import pytest

from conftest import requires_mesh

import paddle_tpu as paddle
from paddle_tpu.common.errors import EnforceError
from paddle_tpu.distributed.topology import serving_mesh
from paddle_tpu.inference.engine import LLMEngine
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.observability import capsule as C
from paddle_tpu.observability import introspection as I

pytestmark = requires_mesh(2)


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = LlamaForCausalLM(llama_tiny_config())
    m.eval()
    return m


def _mk(model, tp=None, **kw):
    cfg = dict(max_seqs=4, max_len=64, page_size=8, steps_per_sync=4)
    cfg.update(kw)
    mesh = serving_mesh(tp) if tp else None
    return LLMEngine(model, mesh=mesh, **cfg)


def _run(eng, reqs, admit="add"):
    """reqs: [(rid, prompt, max_new)] — staggered admission (each rid
    joins after one step) so batches churn, then drain."""
    for rid, prompt, n in reqs:
        (eng.begin_request if admit == "begin" else eng.add_request)(
            rid, prompt, max_new_tokens=n)
        eng.step()
    while eng.has_work():
        eng.step()
    return {rid: eng.result(rid) for rid, _, _ in reqs}


_REQS = [("a", [5, 9, 2, 14], 8), ("b", [3, 3, 7], 6),
         ("c", list(range(1, 14)), 5)]


# -- bit-identity: tp=2 vs tp=1 on every serving path -------------------------
@pytest.mark.parametrize("admit,kw", [
    ("add", {"steps_per_sync": 1}),           # the step program alone
    ("add", {}),                              # 4-step windows
    ("add", {"steps_per_sync": 8}),           # a second window bucket
    ("begin", {}),                            # chunks packed beside decode
    ("add", {"kv_dtype": "int8"}),
    ("begin", {"steps_per_sync": 1, "kv_dtype": "int8"}),
], ids=["step", "window4", "window8", "begin", "int8", "begin-int8"])
def test_tp2_greedy_bit_identical(model, admit, kw):
    want = _run(_mk(model, **kw), _REQS, admit)
    got = _run(_mk(model, tp=2, **kw), _REQS, admit)
    assert got == want, f"tp=2 diverged from tp=1 on {admit} {kw}"


def test_tp2_sampling_bit_identical(model):
    kw = dict(decode_strategy="sampling", top_k=5, temperature=0.8,
              seed=11)
    want = _run(_mk(model, **kw), _REQS)
    got = _run(_mk(model, tp=2, **kw), _REQS)
    assert got == want


def test_tp2_prefix_cache_hits_bit_identical(model):
    common = [7, 7, 3, 1, 9, 2, 8, 5, 5, 1]
    reqs = [("p1", common + [4], 6), ("p2", common + [11], 6)]
    e1 = _mk(model, enable_prefix_caching=True)
    e2 = _mk(model, tp=2, enable_prefix_caching=True)
    want, got = _run(e1, reqs), _run(e2, reqs)
    assert got == want
    # the second prompt actually HIT the shared prefix on the sharded
    # engine — we compared the hit path, not two misses
    assert e2.prefix_stats["hit_tokens"] > 0
    assert e2.prefix_stats["hit_tokens"] == e1.prefix_stats["hit_tokens"]


def test_tp_must_divide_kv_heads(model):
    # tiny config: 2 KV heads — tp=4 cannot hold whole heads per shard
    with pytest.raises(EnforceError, match="num_key_value_heads"):
        _mk(model, tp=4)


# -- the one-compile invariant per mesh shape ---------------------------------
def test_second_tp2_engine_adds_zero_compiles(model):
    """Warm the tp=2 step loop, then a SECOND tp=2 engine with a
    different batch mix must add zero mixed/window compiles — the
    sharded jits key on the (hashable) mesh, not the engine."""
    _run(_mk(model, tp=2), _REQS)
    base_m = LLMEngine.mixed_compiles()
    base_w = LLMEngine.window_compiles()
    base_p = LLMEngine.prefill_compiles()
    eng = _mk(model, tp=2)
    _run(eng, [("x", [9, 1, 4, 4, 2], 7), ("y", [2], 3)])
    assert LLMEngine.mixed_compiles() == base_m
    assert LLMEngine.window_compiles() == base_w
    assert LLMEngine.prefill_compiles() == base_p


def test_compile_watch_zero_recompiles_under_tp_mixed_churn(model):
    """CompileWatch must see churning mixed batches on a tp=2 engine
    as warmup within the declared allowances — zero recompile
    anomalies and zero ``jit_recompile_events_total``."""
    w = I.enable_compile_watch()
    eng = _mk(model, tp=2)
    _run(eng, _REQS)
    _run(eng, [("d", [8, 8, 1], 6), ("e", list(range(2, 19)), 4)])
    snap = w.snapshot()
    assert not snap["recompiles"], snap["recompiles"]
    assert eng.metrics_snapshot()["tp"] == 2


# -- lifecycle: preemption under tp -------------------------------------------
@pytest.mark.parametrize("pool,path", [(8, "swap_in"),
                                       (0, "recompute")])
def test_tp2_preempt_resume_bit_identical(model, pool, path):
    outs = []
    for tp in (None, 2):
        eng = _mk(model, tp=tp, swap_pool_pages=pool)
        eng.add_request("s", [5, 9, 2, 14], max_new_tokens=12)
        eng.step()
        eng.step()
        eng.suspend("s")
        assert eng.resume("s") == path
        while eng.has_work():
            eng.step()
        outs.append(eng.result("s"))
    assert outs[0] == outs[1]


# -- lifecycle: cross-mesh-shape migration ------------------------------------
@pytest.mark.parametrize("src_tp,dst_tp", [(None, 2), (2, None)])
def test_migration_across_mesh_shapes(model, src_tp, dst_tp):
    """A mid-decode request drains tp=1 -> tp=2 (and back): the swap
    blob is a portable HOST array (device_get gathers the sharded
    pages), import re-scatters it onto the destination's mesh, and the
    finished tokens match an unmigrated run exactly."""
    want = _run(_mk(model), [("mg", [5, 9, 2, 14], 12)])["mg"]
    src = _mk(model, tp=src_tp)
    src.add_request("mg", [5, 9, 2, 14], max_new_tokens=12)
    src.step()
    src.step()
    assert src.suspend("mg") is True
    pkg = src.export_request("mg")
    dst = _mk(model, tp=dst_tp)
    dst.import_request(pkg)
    assert dst.resume("mg") == "swap_in"     # blob fit: no recompute
    while dst.has_work():
        dst.step()
    assert dst.result("mg") == want


def test_migration_refuses_geometry_mismatch_not_mesh_shape(model):
    """Mesh shape is NOT part of the swap geometry: a tp=2 blob
    imports into a tp=1 cache (previous test), but a REAL geometry
    difference (page size) still refuses the package."""
    src = _mk(model, tp=2)
    src.add_request("mg", [5, 9, 2, 14], max_new_tokens=12)
    src.step()
    src.step()
    src.suspend("mg")
    pkg = src.export_request("mg")
    bad = _mk(model, page_size=16)           # different real geometry
    with pytest.raises(EnforceError, match="page_size"):
        bad.import_request(pkg)


# -- capsules under tp ---------------------------------------------------------
def test_capsule_replay_on_and_across_tp(model):
    """A capsule captured on a tp=2 engine replays divergence-free on
    the SAME engine and on a tp=1 engine (tp is fingerprinted but
    deliberately not token-affecting)."""
    C.enable_capsule_capture()
    eng = _mk(model, tp=2)
    eng.add_request("g", [5, 9, 2, 14], max_new_tokens=10)
    while eng.has_work():
        eng.step()
    cap = C.get_capsule_store().get("g")
    assert cap["fingerprint"]["tp"] == 2
    rep = C.replay_capsule(cap, eng)
    assert rep["first_divergence"] is None, rep
    rep = C.replay_capsule(cap, _mk(model))
    assert rep["first_divergence"] is None, rep


def test_stochastic_capsule_in_nonzero_row_replays(model):
    """The carried gap: a SAMPLING request decoded in batch row 1
    must replay bit-exactly — every window records its row, and the
    replay re-folds it (``draw_base``) while running the request in
    row 0."""
    C.enable_capsule_capture()
    kw = dict(decode_strategy="sampling", top_k=5, temperature=0.8,
              seed=11)
    eng = _mk(model, **kw)
    eng.add_request("row0", [1, 2, 3], max_new_tokens=14)
    eng.step()                               # row0 occupies slot 0
    eng.add_request("row1", [5, 9, 2, 14], max_new_tokens=10)
    while eng.has_work():
        eng.step()
    cap = C.get_capsule_store().get("row1")
    assert any(w.get("row", 0) > 0 for w in cap["windows"]), \
        "expected row1 to decode in a non-zero slot"
    rep = C.replay_capsule(cap, eng)
    assert rep["first_divergence"] is None, rep
    assert "sampling_replay_row0_only" not in rep["notes"]
    assert rep["steps_compared"] == len(eng.result("row1"))


# -- per-shard memory honesty --------------------------------------------------
def test_memory_rows_report_per_shard_bytes(model):
    e2 = _mk(model, tp=2, kv_dtype="int8")
    rows = e2.cache.memory_rows()
    assert rows["tp"] == 2
    assert rows["device_bytes_per_shard"] * 2 == rows["device_bytes"]
    r1 = _mk(model, kv_dtype="int8").cache.memory_rows()
    assert r1["tp"] == 1
    assert r1["device_bytes_per_shard"] == r1["device_bytes"]
    # same MODEL-side capacity: sharding splits bytes, never adds any
    assert rows["device_bytes"] == r1["device_bytes"]


def test_memory_brief_sums_per_shard(model):
    import gc
    from paddle_tpu.observability.introspection import memory_brief
    gc.collect()           # consumer registry holds WEAK refs; a
    # cyclic not-yet-collected engine from an earlier test would
    # contribute an unsharded pool row and skew the per-shard sum
    eng = _mk(model, tp=2)
    brief = memory_brief()
    assert brief["device_pool_bytes_per_shard"] * 2 == \
        brief["device_pool_bytes"]
    assert eng.cache.memory_rows()["tp"] == 2
