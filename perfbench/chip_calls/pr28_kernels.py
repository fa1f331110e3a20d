"""PR 28's first chip call: the two attention paths the new cell rests on,
each ALONE against a plain recurrence, before anything is built on them.

    chiprun --chips 1 --timeout 900 -- python3 perfbench/chip_calls/pr28_kernels.py [ragged|gdn|gmm] [descriptors]

1. The ragged paged kernel at head 256, 16:2 heads, ``maxp`` 128, 576
   descriptors (it had run on the chip at head 128 only; a compile that
   passes is not a run): 64 decode rows over up to 12k of context and a
   512-row prompt in four page chunks, against attention computed by
   hand in float32 for a sample of descriptors.
3. ``gmm`` at a row tile of 32 (what an expert share takes).
2. The ragged Gated-DeltaNet path (``ops/pallas/gated_delta.py``) at
   32 heads x 128 x 128, 65 slots: the same batch against the
   token-by-token recurrence, and its time a call.
A watchdog ends a hang in stacks and a non-zero exit.
"""
import faulthandler
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))            # the checkout's root

WATCHDOG_S = 240


def ragged_at_head_256():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.paged_attention import \
        ragged_paged_append_attend_raw
    r = np.random.default_rng(0)
    h, kvh, d, P, maxp, slots, budget = 16, 2, 256, 128, 128, 64, 512
    t = slots + budget
    n_pages = slots * maxp + 1
    bf = jnp.bfloat16
    key = jax.random.key(0)
    kp = jax.random.normal(key, (1, kvh, n_pages, P, d), bf)
    vp = jax.random.normal(jax.random.fold_in(key, 1),
                           (1, kvh, n_pages, P, d), bf)
    q = jax.random.normal(jax.random.fold_in(key, 2), (t, h, d), bf)
    kn = jax.random.normal(jax.random.fold_in(key, 3), (t, kvh, d), bf)
    vn = jax.random.normal(jax.random.fold_in(key, 4), (t, kvh, d), bf)
    q_start = np.zeros(t, np.int32)
    q_len = np.zeros(t, np.int32)
    kv_len = np.zeros(t, np.int32)
    tables = np.zeros((t, maxp), np.int32)
    for s in range(slots):
        tables[s] = 1 + s * maxp + np.arange(maxp)
        q_start[s], q_len[s] = s, 1
        kv_len[s] = int(r.integers(1, 12000))
    pre_slot = slots - 1                 # the prompt reuses the last table
    kv_len[pre_slot], q_len[pre_slot] = 0, 0          # not decoding
    for c in range(4):
        dsc = slots + c
        tables[dsc] = tables[pre_slot]
        q_start[dsc], q_len[dsc] = slots + c * P, P
        kv_len[dsc] = 4096 + c * P
    kp0, vp0 = np.asarray(kp[0], np.float32), np.asarray(vp[0], np.float32)
    fn = jax.jit(lambda *a: ragged_paged_append_attend_raw(
        *a[:-1], layer=a[-1]), donate_argnums=(1, 2))
    args = [jnp.asarray(x) for x in (q_start, q_len, kv_len, tables)]
    t0 = time.perf_counter()
    out, kp, vp = fn(q, kp, vp, kn, vn, *args, jnp.int32(0))
    out = np.asarray(jax.block_until_ready(out), np.float32)
    first = time.perf_counter() - t0
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        o2, kp, vp = fn(q, kp, vp, kn, vn, *args, jnp.int32(0))
        jax.block_until_ready(o2)
        times.append(time.perf_counter() - t0)
    qf, knf, vnf = (np.asarray(x, np.float32) for x in (q, kn, vn))
    worst = 0.0
    g = h // kvh
    for dsc in list(range(0, slots - 1, 9)) + [slots + c for c in range(4)]:
        n, kl, qs = int(q_len[dsc]), int(kv_len[dsc]), int(q_start[dsc])
        # the context as it was BEFORE the call plus rows appended by
        # earlier descriptors of the same table (the prompt's chunks)
        ctx_k = np.zeros((kvh, kl + n, d), np.float32)
        ctx_v = np.zeros_like(ctx_k)
        for pos in range(kl + n):
            pg = tables[dsc][pos // P]
            ctx_k[:, pos] = kp0[:, pg, pos % P]
            ctx_v[:, pos] = vp0[:, pg, pos % P]
        for e in range(slots, slots + 4):          # appended this call
            if (tables[e] == tables[dsc]).all() and q_len[e]:
                a, b = int(kv_len[e]), int(kv_len[e] + q_len[e])
                if a < kl + n:
                    rows = slice(int(q_start[e]), int(q_start[e]) + b - a)
                    ctx_k[:, a:b] = np.swapaxes(knf[rows], 0, 1)
                    ctx_v[:, a:b] = np.swapaxes(vnf[rows], 0, 1)
        ctx_k[:, kl:kl + n] = np.swapaxes(knf[qs:qs + n], 0, 1)
        ctx_v[:, kl:kl + n] = np.swapaxes(vnf[qs:qs + n], 0, 1)
        for j in range(0, n, max(n // 4, 1)):
            for hh in (0, h - 1):
                kk, vv = ctx_k[hh // g, :kl + j + 1], ctx_v[hh // g,
                                                             :kl + j + 1]
                sc = kk @ qf[qs + j, hh] / np.sqrt(d)
                p = np.exp(sc - sc.max())
                want = (p / p.sum()) @ vv
                worst = max(worst, float(np.abs(out[dsc, j, hh] - want)
                                         .max()))
    return {"first_call_s": first, "call_ms_median":
            1e3 * float(np.median(times)), "max_abs_err": worst,
            "ok": worst < 0.05}


def gated_delta_at_real_widths(n_desc=None):
    """``n_desc``: descriptors handed over (the engine's cap for this
    backbone, slots + budget / page + 3, when ``None``)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.gated_delta import \
        ragged_gated_delta_reference
    r = np.random.default_rng(1)
    hv, dk, dv, P, slots, budget = 32, 128, 128, 128, 64, 512
    t = slots + budget
    n_desc = n_desc or slots + budget // P + 3
    q = r.normal(size=(t, hv, dk)).astype(np.float32)
    k = r.normal(size=(t, hv, dk)).astype(np.float32)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(dk)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.normal(size=(t, hv, dv)).astype(np.float32)
    g = -np.abs(r.normal(size=(t, hv))).astype(np.float32) * 0.3
    b = r.uniform(size=(t, hv)).astype(np.float32)
    state = r.normal(size=(slots + 1, hv, dk, dv)).astype(np.float32)
    state[slots] = 0
    q_start = np.zeros(n_desc, np.int32)
    q_len = np.zeros(n_desc, np.int32)
    kv_len = np.zeros(n_desc, np.int32)
    slot = np.full(n_desc, slots, np.int32)
    for s in range(slots - 1):
        q_start[s], q_len[s], kv_len[s], slot[s] = s, 1, 100 + s, s
    for c in range(4):
        dsc = slots - 1 + c
        q_start[dsc], q_len[dsc] = slots - 1 + c * P, P
        kv_len[dsc], slot[dsc] = c * P, slots - 1
    fn = jax.jit(ragged_gated_delta_reference, static_argnames="page_size",
                 donate_argnums=(5,))
    dargs = [jnp.asarray(x) for x in (q, k, v, g, b)]
    desc = [jnp.asarray(x) for x in (q_start, q_len, kv_len, slot)]
    t0 = time.perf_counter()
    o, new = fn(*dargs, jnp.asarray(state), *desc, page_size=P)
    o = np.asarray(jax.block_until_ready(o))
    new_np = np.asarray(new)
    first = time.perf_counter() - t0

    def timed(descs):
        nonlocal new
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            o2, new = fn(*dargs, new, *descs, page_size=P)
            jax.block_until_ready(o2)
            ts.append(time.perf_counter() - t0)
        return 1e3 * float(np.median(ts))
    mixed_ms = timed(desc)
    only_decode = [jnp.asarray(x) for x in (
        q_start, np.where(np.arange(n_desc) < slots - 1, q_len, 0),
        kv_len, slot)]
    decode_ms = timed(only_decode)

    def step(S, i):
        S = S * np.exp(g[i])[:, None, None]
        d = b[i][:, None] * (v[i] - np.einsum("hkv,hk->hv", S, k[i]))
        S = S + k[i][:, :, None] * d[:, None, :]
        return S, np.einsum("hkv,hk->hv", S, q[i])
    worst_o = worst_s = 0.0
    for s in (0, 17, 62):
        S, want = step(state[s].astype(np.float64), s)
        worst_o = max(worst_o, float(np.abs(o[s] - want).max()))
        worst_s = max(worst_s, float(np.abs(new_np[s] - S).max()))
    S = np.zeros((hv, dk, dv))
    for i in range(slots - 1, slots - 1 + 4 * P):
        S, want = step(S, i)
        worst_o = max(worst_o, float(np.abs(o[i] - want).max()))
    worst_s = max(worst_s, float(np.abs(new_np[slots - 1] - S).max()))
    return {"descriptors": n_desc, "first_call_s": first,
            "mixed_call_ms": mixed_ms,
            "decode_only_call_ms": decode_ms, "max_abs_err_o": worst_o,
            "max_abs_err_state": worst_s,
            "ok": worst_o < 2e-4 and worst_s < 2e-4}


def gmm_small_tiles(e=256, k=2048, n=512, slots=5760, tm=32,
                    interpret=False):
    """The grouped matmul at the row tile an expert SHARE takes (32:
    256 held experts with ~11 live rows each), float32 rows against bf16
    expert matrices as ``moe_ffn`` hands them over, against a plain
    product for a sample of experts; and its time a call against the
    tile of 128 it had."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.grouped_matmul import (
        gmm, make_dropless_plan_rows)
    r = np.random.default_rng(2)
    # half of the slots are routed to experts held elsewhere (id == e)
    row_expert = np.where(r.random(slots) < 0.5, r.integers(0, e, slots),
                          e).astype(np.int32)
    x = r.normal(size=(slots, k)).astype(np.float32)
    w = jax.random.normal(jax.random.key(3), (e, k, n), jnp.bfloat16)
    out = {}
    for tile in (tm, 128):
        def run(xs, w, row_expert, tile=tile):
            order, dest, valid, te, cnt, m_pad = make_dropless_plan_rows(
                row_expert, e, tile)
            buf = jnp.zeros((m_pad, k), jnp.float32).at[dest].set(
                xs[order], mode="drop")
            y = gmm(buf, w, te, cnt, tm=tile, interpret=interpret)
            got = jnp.where(valid[:, None],
                            y[jnp.minimum(dest, m_pad - 1)], 0.0)
            return jnp.zeros((slots, n), jnp.float32).at[order].set(got)
        fn = jax.jit(run)
        y = np.asarray(jax.block_until_ready(fn(x, w, row_expert)))
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(x, w, row_expert))
            ts.append(time.perf_counter() - t0)
        out[f"tm{tile}_call_ms"] = 1e3 * float(np.median(ts))
        if tile == tm:
            worst = 0.0
            for ex in (0, 7, e // 2, e - 1):
                rows = np.nonzero(row_expert == ex)[0]
                want = x[rows] @ np.asarray(w[ex], np.float32)
                worst = max(worst, float(np.abs(y[rows] - want).max()
                                         / np.abs(want).max()))
            absent = np.nonzero(row_expert == e)[0]
            out["max_rel_err"] = worst
            out["absent_rows_zero"] = bool((y[absent] == 0).all())
    out["ok"] = out["max_rel_err"] < 0.02 and out["absent_rows_zero"]
    return out


def main(which="both", n_desc=None):
    import jax
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("pr28_kernels: no TPU", file=sys.stderr)
        return 2
    res = {"device": dev.device_kind}
    parts = {"ragged": ("ragged_head_256", ragged_at_head_256),
             "gdn": ("gated_delta", lambda: gated_delta_at_real_widths(
                 int(n_desc) if n_desc else None)),
             "gmm": ("gmm_tile_32", gmm_small_tiles)}
    for key in (("ragged", "gdn") if which == "both" else (which,)):
        name, fn = parts[key]
        res[name] = fn()
        print(name, json.dumps(res[name]), flush=True)
    res["ok"] = all(v["ok"] for v in res.values() if isinstance(v, dict))
    print(json.dumps(res))
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:3]))
