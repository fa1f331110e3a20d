"""A unified step crosses the host-device boundary once each way
(ISSUE 29): one packed upload, one read-back, the routed-expert counts
folded behind the next launch.

Contracts under test, all on the CPU at tiny sizes:
* the one-transfer wrappers (``_packed_mixed_step`` /
  ``_packed_mixed_window``) and the inner programs they wrap, fed the
  same plan, give identical tokens, pools, state, counts and keys — at
  EVERY dispatch of a mixed run, a window that exits early, a hybrid
  run whose last descriptor is dead;
* ``desc_tables[desc_of_row]`` is, row for row, the ``row_tables`` the
  host used to build and upload, over seeded random plans;
* ``host_transfers`` over ``steps`` is exactly 1.0 each way on mixed
  and window steps, and a spy on ``jax.device_put`` /
  ``jax.device_get`` agrees;
* the expert counters lose nothing: they equal the sum of the inner
  programs' counts, lag a running engine by one dispatch at most, are
  whole when the engine goes idle and before ``metrics_snapshot()``
  answers, also to a reader on another thread;
* greedy and sampled token streams equal a recording made with the
  inner programs and the key split on the host — and one made with NO
  window program at all: the inner step program, one token a
  dispatch, fed back on the host under the window's own key chain.
"""
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import engine as E
from paddle_tpu.inference.engine import LLMEngine
from paddle_tpu.inference.sampling import key_fingerprint
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.models.qwen2_moe import (Qwen2MoeForCausalLM,
                                         qwen2_moe_tiny_config)
from paddle_tpu.models.qwen3_next import (Qwen3NextForCausalLM,
                                          qwen3_next_tiny_config)
from paddle_tpu.observability.metrics import get_registry

PACKED = (E._packed_mixed_step, E._packed_mixed_window)
MOE = dict(max_seqs=8, max_len=64, page_size=8, n_pages=64,
           prefill_token_budget=20, enable_prefix_caching=False)
HYBRID = dict(max_seqs=3, max_len=256, page_size=16, steps_per_sync=8,
              prefill_token_budget=48)
PROMPTS = [[5, 9, 2, 14], list(range(1, 20)), [7] * 33,
           [3, 1, 4, 1, 5, 9, 2, 6], list(range(40, 51))]


@pytest.fixture(scope="module")
def moe():
    paddle.seed(0)
    m = Qwen2MoeForCausalLM(qwen2_moe_tiny_config())
    m.eval()
    return m


@pytest.fixture(scope="module")
def hybrid():
    paddle.seed(7)
    m = Qwen3NextForCausalLM(qwen3_next_tiny_config())
    m.eval()
    return m


@pytest.fixture(scope="module")
def dense():
    paddle.seed(0)
    m = LlamaForCausalLM(llama_tiny_config())
    m.eval()
    return m


def run(eng, out=None):
    out = {} if out is None else out
    while eng.has_work():
        for rid, toks in eng.step().items():
            out.setdefault(rid, []).extend(toks)
    return out


def begin(eng, prompts=PROMPTS, max_new=6, **kw):
    for i, p in enumerate(prompts):
        eng.begin_request(f"r{i}", p, max_new_tokens=max_new, **kw)


# -- the parent's launch, for the same plan --------------------------------------
def descriptors(f, key):
    """The unpacked upload as the inner programs take it, ``ids`` to
    ``draw_base`` (``row_tables`` gathered here from the descriptors'
    tables), and the state arguments' ``desc_slot``."""
    d = {k: jnp.asarray(v) for k, v in f.items()}
    return (d["ids"], d["positions"],
            jnp.asarray(f["desc_tables"][f["desc_of_row"]]),
            d["q_start"], d["q_len"], d["kv_len"], d["desc_tables"],
            d["desc_of_row"], d["off_of_row"], key, d["draw_base"]), \
        d.get("desc_slot")


def inner_call(fn, args, kw):
    """What the engine launched before the wrappers: the key split on
    the host, the inner program fed array by array (``row_tables``
    gathered here from the descriptors' tables; the planner's own is
    compared with that gather in the test of the plans below)."""
    packed, key, rec, conv = args[9:]
    kw = dict(kw)
    f = E._unpack_step(np.asarray(packed), kw.pop("geom"),
                       kw.get("hybrid") is not None)
    next_key, run_key = jax.random.split(key)
    desc, desc_slot = descriptors(f, run_key)
    common, state = args[:9] + desc, (rec, conv, desc_slot)
    if fn is E._packed_mixed_window:
        res = E._paged_mixed_window(
            *common, *(jnp.asarray(f[k]) for k in (
                "eos_ids", "budgets", "n_rows")), *state, **kw)
        toks, done, pools, rest = res[0], int(res[2]), res[3:7], res[8:]
    else:
        res = E._paged_mixed_step(*common, *state, **kw)
        toks, done, pools, rest = res[0], 1, res[1:5], res[6:]
    counts = None
    if kw.get("arch") is not None:
        counts, rest = np.asarray(rest[0]), rest[1:]
    return dict(toks=np.asarray(toks, np.int32).ravel(), done=done,
                words=key_fingerprint(run_key), counts=counts,
                pools=pools, next_key=next_key, lin=rest)


def per_token_call(fn, args, kw):
    """The window program's reference: NO on-device loop.  A window is
    served by the inner STEP program, one token a dispatch; the host
    feeds each live row's token back as its next input, bumps its
    position and length, walks the window key's ``split_step`` chain
    (the step program hands the chain key back) and stops once every
    live row has met its EOS or its budget — ``inner_call``'s result
    form.  A step launch is ``inner_call``'s."""
    if fn is not E._packed_mixed_window:
        return inner_call(fn, args, kw)
    weights, pools, (packed, key, rec, conv) = args[:5], args[5:9], args[9:]
    kw = dict(kw)
    n_steps = kw.pop("n_steps")
    f = E._unpack_step(np.array(packed), kw.pop("geom"),
                       kw.get("hybrid") is not None)
    next_key, chain = jax.random.split(key)
    words = key_fingerprint(chain)
    n, t = int(f["n_rows"]), f["ids"].size
    toks = np.zeros((n_steps, t), np.int32)
    emitted, live = np.zeros(n, np.int32), np.ones(n, bool)
    counts, done = None, 0
    while done < n_steps and live.any():
        desc, desc_slot = descriptors(f, chain)
        res = E._paged_mixed_step(*weights, *pools, *desc, rec, conv,
                                  desc_slot, **kw)
        nxt, pools, chain, rest = \
            np.asarray(res[0], np.int32), res[1:5], res[5], res[6:]
        if kw.get("arch") is not None:
            counts = np.asarray(rest[0]) + (0 if counts is None else counts)
            rest = rest[1:]
        if rest:
            rec, conv = rest
        toks[done] = nxt
        done += 1
        emitted += live
        live &= ~(((f["eos_ids"][:n] >= 0) & (nxt[:n] == f["eos_ids"][:n]))
                  | (emitted >= f["budgets"][:n]))
        f["ids"][:n] = nxt[:n]
        f["positions"][:n] += 1
        f["kv_len"][:n] += 1
    return dict(toks=toks.ravel(), done=done, words=words, counts=counts,
                pools=pools, next_key=next_key,
                lin=(rec, conv) if kw.get("hybrid") is not None else ())


def as_packed(want):
    """``inner_call``'s result in the wrappers' own output form."""
    parts = [want["toks"], np.int32([want["done"]]),
             np.asarray(want["words"], np.uint32).view(np.int32)]
    if want["counts"] is not None:
        parts.append(want["counts"].astype(np.int32).ravel())
    return (jnp.asarray(np.concatenate(parts)),) + tuple(want["pools"]) \
        + (want["next_key"],) + tuple(want["lin"])


def same(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(x, y)


@pytest.fixture
def shadow(monkeypatch):
    """Every launch of a wrapper also runs the inner program on copies
    of the donated pools and state, and the two are compared."""
    seen = []

    def call(program, fn, *args, **kw):
        if fn not in PACKED:
            return fn(*args, **kw)
        copies = jax.tree.map(jnp.copy, (args[5:9], args[11:13]))
        want = inner_call(
            fn, args[:5] + copies[0] + args[9:11] + copies[1], kw)
        got = fn(*args, **kw)
        n_toks = want["toks"].size
        toks, done, words, counts = E._unpack_result(
            np.asarray(got[0]), n_toks,
            None if want["counts"] is None else want["counts"].shape)
        assert done == want["done"] and words == want["words"]
        live = n_toks if fn is E._packed_mixed_step else \
            done * (n_toks // kw["n_steps"])
        np.testing.assert_array_equal(toks[:live], want["toks"][:live])
        if counts is not None:
            np.testing.assert_array_equal(counts, want["counts"])
        same(got[1:5], want["pools"])
        same(got[5], want["next_key"])
        same(got[6:], want["lin"])
        seen.append(dict(program=program, done=done, counts=counts,
                         packed=np.array(args[9]), geom=kw["geom"]))
        return got
    monkeypatch.setattr(E._insp, "watched_call", call)
    return seen


@pytest.fixture(params=[inner_call, per_token_call],
                ids=["inner_only", "per_token"])
def oracle(request, monkeypatch):
    """Once called, the engine runs on the inner programs alone, the
    key split on the host (``inner_only``), or without any window
    program at all (``per_token``)."""
    def call(program, fn, *args, **kw):
        if fn not in PACKED:
            return fn(*args, **kw)
        return as_packed(request.param(fn, args, kw))
    return lambda: monkeypatch.setattr(E._insp, "watched_call", call)


# -- (a) wrapper == inner, dispatch by dispatch ----------------------------------
def test_mixed_steps_equal_the_inner_program(moe, shadow):
    inner = E._paged_mixed_step._cache_size()
    eng = LLMEngine(moe, steps_per_sync=1, **MOE)
    begin(eng)
    run(eng)
    assert len(shadow) >= 8
    assert {s["program"] for s in shadow} == {"engine.mixed_step"}
    # prompt chunks and decode rows rode together, padding rows beside
    geom = eng._step_geom
    mixes = set()
    for s in shadow:
        f = E._unpack_step(s["packed"], geom, False)
        live = int(f["q_len"].sum())
        mixes.add((bool(f["n_rows"]), live > int(f["n_rows"])))
        assert live < geom[0]
    assert (True, True) in mixes and (True, False) in mixes
    # the shadow traced the inner program once, on its own account
    assert E._paged_mixed_step._cache_size() - inner == 1


def test_a_window_of_8_that_exits_early_equals_the_inner_program(
        moe, shadow):
    kw = dict(MOE, steps_per_sync=8)
    eng = LLMEngine(moe, **kw)
    eng.begin_request("free", PROMPTS[0], max_new_tokens=12)
    free = run(eng)["free"]
    del shadow[:]
    stop = free[4]                    # its fifth token ends the stream
    eng = LLMEngine(moe, **kw)
    eng.begin_request("eos", PROMPTS[0], max_new_tokens=12,
                      eos_token_id=stop)
    got = run(eng)["eos"]
    assert got == free[:free.index(stop) + 1]
    windows = [s for s in shadow if s["program"] == "engine.mixed_window"]
    assert windows and windows[0]["done"] < 8       # the early exit
    assert eng.last_window_steps == windows[-1]["done"]


def test_hybrid_steps_with_a_dead_descriptor_equal_the_inner_program(
        hybrid, shadow):
    eng = LLMEngine(hybrid, **HYBRID)
    rng = np.random.default_rng(3)
    for i, (n, new) in enumerate([(75, 10), (20, 9), (33, 3)]):
        eng.begin_request(i, rng.integers(0, 128, n).tolist(),
                          max_new_tokens=new)
    run(eng)
    t_cap, s_cap, _ = eng._step_geom
    assert s_cap < t_cap              # fewer descriptors than rows
    assert {s["program"] for s in shadow} == \
        {"engine.mixed_step", "engine.mixed_window"}
    for s in shadow:
        # each program at its own geometry: a window has one more
        # descriptor than rows, the dead one
        rows, descs, _ = s["geom"]
        assert (rows, descs) == (
            (eng.max_seqs, eng.max_seqs + 1)
            if s["program"] == "engine.mixed_window" else (t_cap, s_cap))
        f = E._unpack_step(s["packed"], s["geom"], True)
        assert f["q_len"][-1] == 0 and not f["desc_tables"][-1].any()
        assert f["desc_slot"][-1] == eng.max_seqs       # the pad slot
        pad = np.arange(rows) >= int(f["q_len"].sum())
        assert (f["desc_of_row"][pad] == descs - 1).all()


@pytest.mark.parametrize("model,cfg", [
    ("moe", dict(MOE, steps_per_sync=8)),
    ("dense", dict(max_seqs=4, max_len=64, page_size=8, steps_per_sync=4,
                   prefill_token_budget=12)),
    ("hybrid", HYBRID)])
def test_each_program_is_launched_at_its_own_geometry(
        request, shadow, model, cfg):
    """The mixed step runs slots + prefill budget rows, the decode
    window ONE row and one descriptor a slot (a hybrid backbone one
    descriptor more, dead, for its padding rows): the upload's size,
    the result's tokens and the row-capacity counter are the launched
    program's, and a step still crosses the boundary once each way."""
    eng = LLMEngine(request.getfixturevalue(model), **cfg)
    hy = model == "hybrid"
    slots, maxp = eng.max_seqs, eng.cache.page_table.shape[1]
    t_cap = slots + cfg["prefill_token_budget"]
    geoms = {
        "engine.mixed_step": (
            t_cap, slots + 3 + -(-cfg["prefill_token_budget"]
                                 // eng.cache.page_size) if hy else t_cap,
            maxp),
        "engine.mixed_window": (slots, slots + 1 if hy else slots, maxp)}
    assert (eng._step_geom, eng._window_geom) == \
        tuple(geoms[k] for k in sorted(geoms))
    begin(eng, (PROMPTS * 2)[:slots], max_new=11)     # every slot taken
    run(eng)
    assert {s["program"] for s in shadow} == set(geoms)
    ran = {"mixed": 0, "window": 0}
    live, fullest = dict(ran), 0
    for s in shadow:
        rows, descs, _ = geom = s["geom"]
        assert geom == geoms[s["program"]]
        assert s["packed"].shape == (E._step_layout(*geom, hy)[1],)
        f = E._unpack_step(s["packed"], geom, hy)
        n = int(f["n_rows"])
        path = "window" if s["program"] == "engine.mixed_window" \
            else "mixed"
        ran[path] += rows * s["done"]
        if path == "window":
            # pure decode: row i is descriptor i, the rest dead
            assert 1 <= n <= slots
            assert (f["q_len"][:n] == 1).all() and not f["q_len"][n:].any()
            assert (f["desc_of_row"][:n] == np.arange(n)).all()
            pad = descs - 1 if hy else np.arange(n, rows)
            assert (f["desc_of_row"][n:] == pad).all()
            live[path] += n * s["done"]
            fullest = max(fullest, n)
        else:
            live[path] += int(f["q_len"].sum())
    assert fullest == slots           # a window with no padding row
    snap = eng.metrics_snapshot()
    assert snap["forward_rows"] == {
        path: {"capacity": ran[path], "live": live[path]} for path in ran}
    assert 0 < live["mixed"] < ran["mixed"]
    assert 0 < live["window"] <= ran["window"] < ran["mixed"]
    assert snap["host_transfers"] == {"in": len(shadow),
                                      "out": len(shadow)}
    text = get_registry().expose_text()
    for path in ran:
        assert (f'llm_engine_forward_row_capacity_total{{engine="'
                f'{eng.engine_id}",path="{path}"}} {ran[path]}') in text


# -- (b) the gather on the device is the table the host used to build -----------
@pytest.mark.parametrize("kind,seed", [("dense", 0), ("dense", 1),
                                       ("hybrid", 2)])
def test_desc_tables_gathered_by_row_are_the_rows_own_tables(
        request, monkeypatch, kind, seed):
    """``row_tables`` as the parent packed it — each decode row its
    slot's page table, each chunk row its request's, padding rows zeros
    — rebuilt here from the REQUESTS' state (who was active in which
    slot, how far each prompt moved in the step), not from the
    descriptors, against ``desc_tables[desc_of_row]``."""
    model = request.getfixturevalue(kind)
    cfg = dict(HYBRID, steps_per_sync=1) if kind == "hybrid" else dict(
        max_seqs=6, max_len=96, page_size=8, n_pages=80,
        prefill_token_budget=20, steps_per_sync=1)
    eng = LLMEngine(model, enable_prefix_caching=False, **cfg)
    real, at_launch = E._insp.watched_call, []

    def call(program, fn, *args, **kw):
        if fn in PACKED:
            at_launch.append(dict(
                packed=np.array(args[9]),
                tables=eng.cache.page_table.copy(),
                active=[r.slot for r in eng._active],
                prefilling=[(r, r.slot, r.pf_pos)
                            for r in eng._prefilling]))
        return real(program, fn, *args, **kw)
    monkeypatch.setattr(E._insp, "watched_call", call)

    rng = np.random.default_rng(seed)
    t_cap, page = eng._step_geom[0], eng.cache.page_size
    kinds, i = set(), 0
    for _ in range(60):
        if rng.random() < 0.45 and eng.free_slots():
            eng.begin_request(
                i, rng.integers(1, 100, int(rng.integers(1, 45))).tolist(),
                max_new_tokens=int(rng.integers(1, 7)))
            i += 1
        del at_launch[:]
        eng.step()
        for rec in at_launch:
            want = np.zeros((t_cap, rec["tables"].shape[1]), np.int32)
            n = len(rec["active"])
            want[:n] = rec["tables"][rec["active"]]
            row = n
            for r, slot, before in rec["prefilling"]:
                moved = r.pf_pos - before
                want[row:row + moved] = rec["tables"][slot]
                if moved and before // page != (before + moved - 1) // page:
                    kinds.add("chunks_across_pages")
                row += moved
            f = E._unpack_step(rec["packed"], eng._step_geom,
                               kind == "hybrid")
            np.testing.assert_array_equal(
                f["desc_tables"][f["desc_of_row"]], want)
            assert row == int(f["q_len"].sum())
            kinds.add("padding" if row < t_cap else "full")
            if n and row == n:
                kinds.add("decode_only")
    assert {"decode_only", "chunks_across_pages", "padding"} <= kinds
    assert i >= 10


# -- (c) one transfer each way ----------------------------------------------------
@pytest.mark.parametrize("model,cfg,path", [
    ("moe", dict(MOE, steps_per_sync=1), "mixed"),
    ("moe", dict(MOE, steps_per_sync=8), "window"),
    ("dense", dict(max_seqs=4, max_len=64, page_size=8,
                   steps_per_sync=4), "window"),
    ("hybrid", HYBRID, "window")])
def test_a_step_makes_one_upload_and_one_blocking_read(
        request, monkeypatch, model, cfg, path):
    eng = LLMEngine(request.getfixturevalue(model), **cfg)
    eng.begin_request("warm", [1, 2, 3], max_new_tokens=10)
    run(eng)                                    # compiles stay outside
    before = dict(eng.host_transfers)
    steps0 = eng.metrics_snapshot()["steps"]
    calls = {"put": 0, "get": 0, "asarray": 0}

    def count(name, real):
        def spy(*a, **k):
            calls[name] += 1
            return real(*a, **k)
        return spy
    monkeypatch.setattr(jax, "device_put", count("put", jax.device_put))
    monkeypatch.setattr(jax, "device_get", count("get", jax.device_get))
    monkeypatch.setattr(jnp, "asarray", count("asarray", jnp.asarray))
    begin(eng, PROMPTS[:3], max_new=9)
    n_steps, longest = 0, 0
    while eng.has_work():
        eng.step()
        n_steps += 1
        longest = max(longest, eng.last_window_steps)
    monkeypatch.undo()
    snap = eng.metrics_snapshot()
    assert snap["steps"] - steps0 == n_steps >= 4
    moved = {k: snap["host_transfers"][k] - before[k] for k in before}
    assert moved == {"in": n_steps, "out": n_steps}
    assert calls == {"put": n_steps, "get": n_steps, "asarray": 0}
    assert (longest > 1) == (path == "window")
    text = get_registry().expose_text()
    for way in ("in", "out"):
        assert (f'llm_engine_host_transfers_total{{engine="'
                f'{eng.engine_id}",dir="{way}"}} '
                f'{snap["host_transfers"][way]}') in text


# -- (d) the expert counters lose nothing -----------------------------------------
def test_expert_counts_fold_one_dispatch_behind_and_whole_at_idle(
        moe, shadow):
    eng = LLMEngine(moe, steps_per_sync=1, **MOE)
    begin(eng)
    zero = np.zeros_like(eng._moe_counts)
    while eng.has_work():
        eng.step()
        through = sum((s["counts"] for s in shadow), zero)
        if eng.has_work():
            # the last dispatch's counts are put aside, all before it
            # are folded: one dispatch behind, never more
            assert len(eng._counts_aside) == 1
            np.testing.assert_array_equal(
                eng._moe_counts, through - shadow[-1]["counts"])
    # the step that left the engine idle folded at once
    assert not eng._counts_aside
    total = sum((s["counts"] for s in shadow), zero)
    np.testing.assert_array_equal(eng._moe_counts, total)
    assert eng.count_folds == {"behind_launch": len(shadow) - 1,
                               "at_idle": 1}
    snap = eng.metrics_snapshot()
    assert snap["count_folds"] == eng.count_folds       # nothing to drain
    assert snap["moe"]["expert_tokens"] == total.sum(axis=0).tolist()
    assert snap["moe"]["dropped_tokens"] == 0
    tot = total.sum(axis=0)
    assert snap["moe"]["imbalance"] == float(tot.max() / tot.mean())
    reg, eid = get_registry(), eng.engine_id
    tokens = reg.counter("llm_engine_expert_tokens_total", "",
                         ("engine", "layer", "expert"))
    for (l, e), v in np.ndenumerate(total):
        assert tokens.labels(eid, str(l), str(e)).value == v
    text = reg.expose_text()
    assert (f'llm_engine_count_folds_total{{engine="{eid}",'
            f'when="behind_launch"}} {len(shadow) - 1}') in text
    assert f'llm_engine_expert_imbalance{{engine="{eid}"}} ' \
           f'{float(tot.max() / tot.mean())}'[:60] in text


def test_a_snapshot_mid_run_drains_the_counts_first(moe, shadow):
    eng = LLMEngine(moe, steps_per_sync=8, **MOE)
    begin(eng, PROMPTS[:3], max_new=12)
    zero = np.zeros_like(eng._moe_counts)
    asked = 0
    while eng.has_work():
        eng.step()
        through = sum((s["counts"] for s in shadow), zero)
        moe_now = eng.metrics_snapshot()["moe"]
        asked += 1
        assert moe_now["expert_tokens"] == through.sum(axis=0).tolist()
        assert not eng._counts_aside
    # every dispatch's counts were folded by a reader, or at idle:
    # none was left for a launch to fold behind
    assert eng.count_folds == {"behind_launch": 0, "at_idle": asked}
    assert {s["program"] for s in shadow} == \
        {"engine.mixed_step", "engine.mixed_window"}


@pytest.mark.parametrize("admit,kw", [
    ("add", dict(steps_per_sync=4)),
    ("add", dict(moe_dropless=False, moe_capacity_factor=0.5,
                 steps_per_sync=4)),
    ("begin", dict(moe_dropless=False, moe_capacity_factor=0.5,
                   steps_per_sync=1))],
    ids=["add_window", "add_capacity_drops", "begin_capacity_drops"])
def test_every_path_counts_through_the_one_fold(moe, admit, kw, oracle):
    """Admission's own prefill-chunk program, windows and a capacity
    factor that drops, wherever the prompt was prefilled: totals equal
    whichever programs ran, and the accounting identity (kept + dropped
    = routed) holds."""
    cfg = {k: v for k, v in MOE.items() if k != "prefill_token_budget"}
    oracle()

    def serve(**extra):
        eng = LLMEngine(moe, **dict(cfg, **kw, **extra))
        for i, p in enumerate(PROMPTS):
            (eng.begin_request if admit == "begin" else eng.add_request)(
                f"r{i}", p, max_new_tokens=6)
        return run(eng), eng
    out, eng = serve()
    assert not eng._counts_aside
    folds = eng.count_folds
    assert folds["at_idle"] == 1 and folds["behind_launch"] >= 4
    moe_snap = eng.metrics_snapshot()["moe"]
    layers, top_k = eng._moe_counts.shape[0], moe_snap["top_k"]
    routed = (sum(len(p) for p in PROMPTS)
              + sum(len(t) - 1 for t in out.values())) * top_k * layers
    assert (moe_snap["dropped_tokens"] > 0) == ("moe_dropless" in kw)
    assert sum(moe_snap["expert_tokens"]) + moe_snap["dropped_tokens"] \
        >= routed                      # windows also route retired rows
    dense_out, dense_eng = serve(moe_dispatch="dense")
    assert dense_out == out
    np.testing.assert_array_equal(dense_eng._moe_counts, eng._moe_counts)


def test_a_reader_on_another_thread_loses_no_count(moe):
    """``/statusz`` reads ``metrics_snapshot()`` on a handler thread
    while the loop folds: more readers than cores, a short switch
    interval, and the totals still equal a run nobody read."""
    def serve(readers):
        eng = LLMEngine(moe, steps_per_sync=2, **MOE)
        begin(eng, PROMPTS + PROMPTS[:3], max_new=8)
        stop, errs = threading.Event(), []

        def read(mine):
            try:
                while not stop.is_set():
                    mine.append(sum(
                        eng.metrics_snapshot()["moe"]["expert_tokens"]))
            except Exception as e:          # read in the main thread
                errs.append(e)
        seen = [[] for _ in range(readers)]
        threads = [threading.Thread(target=read, args=(mine,))
                   for mine in seen]
        for t in threads:
            t.start()
        try:
            out = run(eng)
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=30)
        assert not any(t.is_alive() for t in threads) and not errs
        return out, eng, seen
    want_out, want, _ = serve(0)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        out, eng, seen = serve(16)
    finally:
        sys.setswitchinterval(interval)
    assert out == want_out
    np.testing.assert_array_equal(eng._moe_counts, want._moe_counts)
    assert eng._moe_dropped == want._moe_dropped == 0
    assert sum(eng.count_folds.values()) <= sum(want.count_folds.values())
    for mine in filter(None, seen):     # no reader sees a count taken back
        assert mine == sorted(mine)
        assert mine[-1] <= int(want._moe_counts.sum())
    tokens = get_registry().counter(
        "llm_engine_expert_tokens_total", "",
        ("engine", "layer", "expert"))
    assert sum(tokens.labels(eng.engine_id, str(l), str(e)).value
               for l, e in np.ndindex(*eng._moe_counts.shape)) == \
        int(want._moe_counts.sum())


# -- (e) the key split in the program is the host's, bit for bit ------------------
@pytest.mark.parametrize("sampling", [
    dict(),
    dict(decode_strategy="sampling", top_k=5, temperature=0.8, seed=11),
    dict(decode_strategy="sampling", top_p=0.9, seed=5)],
    ids=["greedy", "sampled_top_k", "sampled_top_p"])
@pytest.mark.parametrize("model,cfg", [
    ("moe", dict(MOE, steps_per_sync=4)), ("hybrid", HYBRID)])
def test_token_streams_equal_a_recording_made_with_the_inner_programs(
        request, oracle, model, cfg, sampling):
    from paddle_tpu.observability import capsule as C
    net = request.getfixturevalue(model)

    def serve():
        C.enable_capsule_capture()
        try:
            eng = LLMEngine(net, **dict(cfg, **sampling))
            begin(eng, PROMPTS[:3], max_new=10)
            out = run(eng)
            keys = [[w["key"] for w in
                     C.get_capsule_store().get(f"r{i}")["windows"]]
                    for i in range(3)]
        finally:
            C.disable_capsule_capture()
        return out, keys, np.asarray(eng._key)
    got = serve()
    oracle()
    want = serve()
    assert got[0] == want[0]
    # the windows' keys, as the capsules recorded them, and the key the
    # engine is left with
    assert got[1] == want[1] and any(got[1])
    np.testing.assert_array_equal(got[2], want[2])
