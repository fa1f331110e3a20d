"""PR 33: a decode forward alone, at the window's own geometry (one row
and one descriptor a slot) against the mixed step's (slots + prefill
budget rows), with the SAME live rows — so the per-forward prediction is
judged apart from traffic — and the ragged kernel at the three shapes a
window now runs it at, against attention computed by hand.

    chiprun --chips 1 --timeout 1800 -- python3 perfbench/chip_calls/pr33_window_rows.py all
    python3 perfbench/chip_calls/pr33_window_rows.py ragged
    python3 perfbench/chip_calls/pr33_window_rows.py forward <configuration> [live rows ...]
    JAX_PLATFORMS=cpu python3 perfbench/chip_calls/pr33_window_rows.py rehearse

``forward`` builds a configuration's model and ``LLMEngine`` as its cell
does (no scheduler, no HTTP), admits ``live`` requests (every slot, then
a few), steps them through their prompts, and then times decode windows
of 8 forwards — alternating the engine's window geometry with the mixed
step's (``engine._window_geom`` pointed at ``_step_geom``: the program
the parent launched) — on the host's clock round the blocking step and,
for a few windows, under the profiler (``perfbench/trace_reduce.py``:
device busy a forward and the top operations).  ``all`` runs ``ragged``
and one ``forward`` a serving configuration, each in a process of its
own (a parent that has touched JAX would hold the chip), and writes
every result to ``chiprun_out/pr33_window_rows.json``.
A watchdog ends a hang in stacks and a non-zero exit.
"""
import faulthandler
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))             # the checkout's root
sys.path.insert(0, ROOT)

WATCHDOG_S = 840
OUT = os.path.join(ROOT, "chiprun_out", "pr33_window_rows.json")
TRACE_DIR = os.path.join(ROOT, ".perfbench_trace", "pr33_window_rows")
# live rows timed in each configuration: every slot, and what its cell
# holds (steady: 3-8 rows; the hybrid cells run full)
LIVE = {"deepseek_moe_16b_4l": (32, 6),
        "qwen3_next_80b_a3b_4l": (64, 8),
        "nemotron3_super_120b_a12b_11l": (128, 16)}
# prompt tokens a request: about its cell's mean context while decoding
CONTEXT = {"deepseek_moe_16b_4l": 300, "qwen3_next_80b_a3b_4l": 2048,
           "nemotron3_super_120b_a12b_11l": 700}


# -- the ragged kernel at a window's shapes ----------------------------------------
def _on_the_cpu(q, kp, vp, kn, vn, q_start, q_len, kv_len, tables, layer):
    """The rehearsal's stand-in for the kernel (Mosaic has no CPU): the
    program's own per-row mirror, given the kernel's output form."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.paged_attention import \
        ragged_paged_append_attend_reference
    s_max, t = q_start.shape[0], q.shape[0]
    live = jnp.arange(t) < jnp.sum(q_len)       # decode rows come first
    pad = jnp.minimum(jnp.arange(t), s_max - 1)
    o, k1, v1 = ragged_paged_append_attend_reference(
        q, kp[0], vp[0], kn, vn, jnp.where(live, kv_len[pad], 0),
        jnp.where(live[:, None], tables[pad], 0))
    out = jnp.zeros((s_max, kp.shape[3]) + q.shape[1:], q.dtype)
    out = out.at[pad, 0].add(jnp.where(live[:, None, None], o, 0))
    return out, k1[None], v1[None]


def ragged_decode(h, kvh, d, maxp, slots, hybrid, n_live, max_ctx, seed,
                  on_chip=True):
    """One call of the ragged kernel as a decode window makes it: ``t`` =
    ``slots`` rows, one descriptor a row (and the hybrid backbones' dead
    one), ``n_live`` of them live over contexts up to ``max_ctx``,
    checked against attention by hand for a sample of descriptors and
    heads; the same rows at the mixed step's shape for the time."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.paged_attention import \
        ragged_paged_append_attend_raw
    r = np.random.default_rng(seed)
    P, bf = 128, jnp.bfloat16
    n_pages = slots * maxp + 1
    key = jax.random.key(seed)
    kp = jax.random.normal(key, (1, kvh, n_pages, P, d), bf)
    vp = jax.random.normal(jax.random.fold_in(key, 1),
                           (1, kvh, n_pages, P, d), bf)
    kp0, vp0 = np.asarray(kp[0]), np.asarray(vp[0])      # before any call
    ctx = r.integers(1, max_ctx, size=n_live)
    ctx[0], ctx[-1] = P - 1, min(2 * P, max_ctx)    # fills / opens a page
    fn = jax.jit(lambda *a: ragged_paged_append_attend_raw(
        *a[:-1], layer=a[-1]) if on_chip else _on_the_cpu(*a),
        donate_argnums=(1, 2))
    g = h // kvh
    res = {}
    for name, t, s_max in (
            ("window", slots, slots + 1 if hybrid else slots),
            ("mixed", slots + 512 if hybrid else slots + P,
             slots + 3 + 512 // P if hybrid else slots + P)):
        q = jax.random.normal(jax.random.fold_in(key, 2), (t, h, d), bf)
        kn = jax.random.normal(jax.random.fold_in(key, 3), (t, kvh, d), bf)
        vn = jax.random.normal(jax.random.fold_in(key, 4), (t, kvh, d), bf)
        q_start = np.zeros(s_max, np.int32)
        q_len = np.zeros(s_max, np.int32)
        kv_len = np.zeros(s_max, np.int32)
        tables = np.zeros((s_max, maxp), np.int32)
        for s in range(n_live):
            tables[s] = 1 + s * maxp + np.arange(maxp)
            q_start[s], q_len[s], kv_len[s] = s, 1, ctx[s]
        args = [jnp.asarray(x) for x in (q_start, q_len, kv_len, tables)]
        t0 = time.perf_counter()
        out, kp, vp = fn(q, kp, vp, kn, vn, *args, jnp.int32(0))
        out = np.asarray(jax.block_until_ready(out), np.float32)
        first = time.perf_counter() - t0
        state = [kp, vp]

        def again():
            o2, state[0], state[1] = fn(q, state[0], state[1], kn, vn,
                                        *args, jnp.int32(0))
            return o2
        ts = []
        for _ in range(7):
            t0 = time.perf_counter()
            jax.block_until_ready(again())
            ts.append(time.perf_counter() - t0)
        kp, vp = state
        qf, knf, vnf = (np.asarray(x, np.float32) for x in (q, kn, vn))
        worst = 0.0
        for s in sorted({0, n_live - 1, *range(0, n_live, 7)}):
            kl = int(kv_len[s])
            kk = np.zeros((kvh, kl + 1, d), np.float32)
            vv = np.zeros_like(kk)
            for pos in range(0, kl, P):
                pg, n = tables[s][pos // P], min(P, kl - pos)
                kk[:, pos:pos + n] = kp0[:, pg, :n].astype(np.float32)
                vv[:, pos:pos + n] = vp0[:, pg, :n].astype(np.float32)
            kk[:, kl], vv[:, kl] = knf[s], vnf[s]
            for hh in sorted({0, g - 1, h // 2, h - 1}):
                sc = kk[hh // g] @ qf[s, hh] / np.sqrt(d)
                p = np.exp(sc - sc.max())
                want = (p / p.sum()) @ vv[hh // g]
                worst = max(worst, float(np.abs(out[s, 0, hh] - want)
                                         .max()))
        # a dead descriptor's block comes back zeroed
        dead_zero = bool(not out[n_live:].any()) if n_live < s_max else True
        res[name] = {"t": t, "descriptors": s_max, "first_call_s": first,
                     "call_ms_median": 1e3 * float(np.median(ts)),
                     "max_abs_err": worst, "dead_blocks_zero": dead_zero,
                     "ok": worst < 0.05 and dead_zero}
    return {"heads": [h, kvh, d], "live": n_live,
            "max_context": int(ctx.max()), **res,
            "ok": all(v["ok"] for v in res.values())}


def ragged(rehearse=False):
    shapes = [  # heads, KV heads, head, pages a sequence, slots, hybrid
        ("deepseek_16_16x128", 16, 16, 128, 16, 32, False),
        ("qwen3_next_16_2x256", 16, 2, 256, 128, 64, True),
        ("nemotron_32_2x128", 32, 2, 128, 64, 128, True)]
    out = {}
    for i, (name, h, kvh, d, maxp, slots, hybrid) in enumerate(shapes):
        if rehearse:
            maxp, slots, h, kvh, d = 4, 4, h // 4, max(kvh // 4, 1), 32
        for n_live in sorted({slots, max(slots // 8, 2)}):
            key = f"{name}.live{n_live}"
            out[key] = ragged_decode(h, kvh, d, maxp, slots, hybrid,
                                     n_live, maxp * 128 - 2, 10 * i + 1,
                                     on_chip=not rehearse)
            print("ragged", key, json.dumps(out[key]), flush=True)
    out["ok"] = all(v["ok"] for v in out.values())
    return out


# -- a decode forward of a whole configuration -------------------------------------
def build_engine(name, rehearse):
    import jax.numpy as jnp
    from paddle_tpu.inference.engine import LLMEngine
    from perfbench import manifest
    sz = manifest.at_size(manifest.config(manifest.benchmark(), name),
                          rehearse)
    builder = manifest.module("builders", sz["builder"])
    make = getattr(builder, "make_model", None)
    if make is None:
        from perfbench.builders.models import make_model as make
    model = make(sz, 2147489301, sz["engine"]["max_len"])
    return sz, LLMEngine(model, dtype=getattr(jnp, sz["dtype"]),
                         **sz["engine"])


def traced_windows(eng, n_windows):
    """``n_windows`` decode windows under the profiler: device busy ms a
    forward and the top device operations, ms a forward."""
    import jax
    from perfbench import trace_reduce
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    forwards = 0
    with jax.profiler.TraceAnnotation("pb.trace.window"):
        for _ in range(n_windows):
            eng.step()
            assert eng.last_window_steps == 8, eng.last_window_steps
            forwards += eng.last_window_steps
    jax.profiler.stop_trace()
    s = trace_reduce.summarize_dir(TRACE_DIR, "pb.trace.window")
    if s is None:                   # no device plane: the CPU rehearsal
        return {"forwards": forwards}
    return {"forwards": forwards,
            "busy_ms_a_forward": 1e3 * s["busy_s"] / forwards,
            "named_share": s["named_share"],
            "ops_ms_a_forward": [[k, 1e3 * v / forwards]
                                 for k, v in s["device_ops"]]}


def forward(name, lives=(), rehearse=False):
    import jax
    sz, eng = build_engine(name, rehearse)
    slots = eng.max_seqs
    lives = [int(v) for v in lives] or (
        [slots, 2] if rehearse else list(LIVE[name]))
    ctx = 40 if rehearse else CONTEXT[name]
    n_win, n_traced = (2, 1) if rehearse else (6, 3)
    geoms = {"window": eng._window_geom, "mixed": eng._step_geom}
    rng = np.random.default_rng(33)
    res = {"configuration": name, "slots": slots, "context": ctx,
           "geometry": {k: list(v) for k, v in geoms.items()}, "live": {}}
    serial = 0
    for live in lives:
        # enough tokens for every window timed below, and for the mixed
        # steps that carry the others' prompts while a request decodes
        budget = eng._pf_budget_static
        need = 8 * (2 * (n_win + n_traced) + 4) + 2 \
            + live * (-(-ctx // budget) + 1)
        for _ in range(live):
            eng.begin_request(
                f"r{serial}", rng.integers(0, sz["vocab_size"], ctx)
                .tolist(), max_new_tokens=need)
            serial += 1
        while eng._prefilling:
            eng.step()
        assert len(eng._active) == live, (len(eng._active), live)
        rows = {}
        for path in ("window", "mixed", "window", "mixed"):
            eng._window_geom = geoms[path]
            eng.step()                       # this geometry's program
            assert eng.last_window_steps == 8, eng.last_window_steps
            got = rows.setdefault(path, {"ms_a_forward": []})
            if "traced" not in got:
                got["traced"] = traced_windows(eng, n_traced)
                continue
            for _ in range(n_win):
                t0 = time.perf_counter()
                eng.step()
                dt = time.perf_counter() - t0
                assert eng.last_window_steps == 8, eng.last_window_steps
                got["ms_a_forward"].append(
                    1e3 * dt / eng.last_window_steps)
        eng._window_geom = geoms["window"]
        for path, got in rows.items():
            got["ms_a_forward_median"] = float(
                np.median(got["ms_a_forward"]))
        res["live"][str(live)] = rows
        print("forward", name, "live", live, json.dumps(rows), flush=True)
        for rid in [r.rid for r in eng._active]:
            eng.abort(rid)
        while eng.has_work():
            eng.step()
    snap = eng.metrics_snapshot()
    res["forward_rows"] = snap["forward_rows"]
    res["window_compiles"] = snap["window_compiles"]
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    res["memory_peak_bytes"] = peak
    res["ok"] = True
    return res


# -- the parts, one process each ---------------------------------------------------
def save(key, value):
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    try:
        with open(OUT, encoding="utf-8") as f:
            done = json.load(f)
    except (OSError, ValueError):
        done = {}
    done[key] = value
    with open(OUT, "w", encoding="utf-8") as f:
        json.dump(done, f, indent=1)


def main(argv):
    part = argv[0] if argv else "all"
    rehearse = "rehearse" in argv
    argv = [a for a in argv[1:] if a != "rehearse"]
    if part in ("all", "rehearse"):
        # no JAX in this process: each part takes the chip in its own
        parts = [["ragged"]] + [["forward", name] for name in LIVE]
        rcs = []
        for p in parts:
            cmd = [sys.executable, os.path.abspath(__file__)] + p + (
                ["rehearse"] if part == "rehearse" else [])
            print("pr33_window_rows:", " ".join(cmd[2:]), flush=True)
            rcs.append(subprocess.call(cmd, cwd=ROOT))
        print("pr33_window_rows: exit codes", rcs, flush=True)
        return max(rcs)
    import jax
    from paddle_tpu.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not rehearse:
        print("pr33_window_rows: no TPU", file=sys.stderr)
        return 2
    if part == "ragged":
        key, res = "ragged", ragged(rehearse)
    elif part == "forward":
        key, res = "forward." + argv[0], forward(argv[0], argv[1:],
                                                 rehearse)
    else:
        print(__doc__, file=sys.stderr)
        return 2
    res["device"] = dev.device_kind
    save(key + (".rehearsal" if rehearse else ""), res)
    print(json.dumps(res), flush=True)
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
