"""PR 32's first chip call: the device paths the new cell rests on, each
ALONE, before anything is built on them.

    chiprun --chips 1 --timeout 900 -- python3 perfbench/chip_calls/pr32_kernels.py [ragged|ssd|moe|all]

1. ``ragged``: the ragged paged kernel at 32 : 2 heads of 128, ``maxp``
   64, 135 descriptors (it had run on the chip at 16 : 16 x 128 and
   16 : 2 x 256; PR 21's row DMAs HUNG at an untried head ratio, and a
   compile that passes is not a run): 128 decode rows over up to 8k of
   context and a 512-row prompt in four page chunks, against attention
   computed by hand in float32 for a sample of descriptors.
2. ``ssd``: the ragged Mamba-2 path (``ops/pallas/mamba2_ssd.py``,
   ``jax.numpy`` on the chip too) at 128 heads x 64 x 128, 129 slots:
   the same batch against the token-by-token recurrence, and its time a
   call, mixed and decode-only, against the state's bytes at 819 GB/s.
3. ``moe``: the expert layer (``moe_ffn``: sigmoid top-22 of 512, 128
   held, ``gmm`` at K 1024 / N 2688 and K 2688 / N 1024 — shapes the
   tile and block choosers had not seen) over 640 and over 128 live
   rows: its time a call against the held experts' bytes.
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

WATCHDOG_S = 420


def _median_ms(call, n=5):
    import jax
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(call())
        ts.append(time.perf_counter() - t0)
    return 1e3 * float(np.median(ts))


def ragged_at_32_to_2():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.paged_attention import \
        ragged_paged_append_attend_raw
    r = np.random.default_rng(0)
    h, kvh, d, P, maxp, slots, budget = 32, 2, 128, 128, 64, 128, 512
    t = slots + budget
    n_desc = slots + 3 + budget // P
    n_pages = slots * maxp + 1
    bf = jnp.bfloat16
    key = jax.random.key(0)
    kp = jax.random.normal(key, (1, kvh, n_pages, P, d), bf)
    vp = jax.random.normal(jax.random.fold_in(key, 1),
                           (1, kvh, n_pages, P, d), bf)
    q = jax.random.normal(jax.random.fold_in(key, 2), (t, h, d), bf)
    kn = jax.random.normal(jax.random.fold_in(key, 3), (t, kvh, d), bf)
    vn = jax.random.normal(jax.random.fold_in(key, 4), (t, kvh, d), bf)
    q_start = np.zeros(n_desc, np.int32)
    q_len = np.zeros(n_desc, np.int32)
    kv_len = np.zeros(n_desc, np.int32)
    tables = np.zeros((n_desc, maxp), np.int32)
    for s in range(slots - 1):
        tables[s] = 1 + s * maxp + np.arange(maxp)
        q_start[s], q_len[s] = s, 1
        kv_len[s] = int(r.integers(1, 8000))
    pre = slots - 1                      # the prompt's own table
    chunks = [slots - 1 + c for c in range(4)]
    for c, dsc in enumerate(chunks):
        tables[dsc] = 1 + pre * maxp + np.arange(maxp)
        q_start[dsc], q_len[dsc] = slots - 1 + c * P, P
        kv_len[dsc] = 2048 + c * P
    kp0, vp0 = np.asarray(kp[0], np.float32), np.asarray(vp[0], np.float32)
    fn = jax.jit(lambda *a: ragged_paged_append_attend_raw(
        *a[:-1], layer=a[-1]), donate_argnums=(1, 2))
    args = [jnp.asarray(x) for x in (q_start, q_len, kv_len, tables)]
    t0 = time.perf_counter()
    out, kp, vp = fn(q, kp, vp, kn, vn, *args, jnp.int32(0))
    out = np.asarray(jax.block_until_ready(out), np.float32)
    first = time.perf_counter() - t0
    state = [kp, vp]

    def again():
        o2, state[0], state[1] = fn(q, state[0], state[1], kn, vn, *args,
                                    jnp.int32(0))
        return o2
    call_ms = _median_ms(again)
    qf, knf, vnf = (np.asarray(x, np.float32) for x in (q, kn, vn))
    worst = 0.0
    g = h // kvh
    for dsc in list(range(0, slots - 1, 13)) + chunks:
        n, kl, qs = int(q_len[dsc]), int(kv_len[dsc]), int(q_start[dsc])
        # the context as it was BEFORE the call plus rows appended by
        # earlier descriptors of the same table (the prompt's chunks)
        ctx_k = np.zeros((kvh, kl + n, d), np.float32)
        ctx_v = np.zeros_like(ctx_k)
        for pos in range(kl + n):
            pg = tables[dsc][pos // P]
            ctx_k[:, pos] = kp0[:, pg, pos % P]
            ctx_v[:, pos] = vp0[:, pg, pos % P]
        for e in chunks:                           # appended this call
            if (tables[e] == tables[dsc]).all():
                a, b = int(kv_len[e]), int(kv_len[e] + q_len[e])
                if a < kl + n:
                    rows = slice(int(q_start[e]), int(q_start[e]) + b - a)
                    ctx_k[:, a:b] = np.swapaxes(knf[rows], 0, 1)
                    ctx_v[:, a:b] = np.swapaxes(vnf[rows], 0, 1)
        ctx_k[:, kl:kl + n] = np.swapaxes(knf[qs:qs + n], 0, 1)
        ctx_v[:, kl:kl + n] = np.swapaxes(vnf[qs:qs + n], 0, 1)
        for j in range(0, n, max(n // 4, 1)):
            for hh in (0, 15, 16, h - 1):
                kk = ctx_k[hh // g, :kl + j + 1]
                vv = ctx_v[hh // g, :kl + j + 1]
                sc = kk @ qf[qs + j, hh] / np.sqrt(d)
                p = np.exp(sc - sc.max())
                want = (p / p.sum()) @ vv
                worst = max(worst, float(np.abs(out[dsc, j, hh] - want)
                                         .max()))
    return {"heads": [h, kvh, d], "descriptors": n_desc,
            "first_call_s": first, "call_ms_median": call_ms,
            "max_abs_err": worst, "ok": worst < 0.05}


def ssd_at_real_widths():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.mamba2_ssd import ragged_ssd_reference
    r = np.random.default_rng(1)
    nh, p, g, n, P, slots, budget = 128, 64, 8, 128, 128, 128, 512
    t = slots + budget
    n_desc = slots + budget // P + 3
    f = np.float32

    def silu(v):
        return v / (1.0 + np.exp(-v))
    x = silu(r.normal(size=(t, nh, p))).astype(f)
    b = silu(r.normal(size=(t, g, n))).astype(f)
    c = silu(r.normal(size=(t, g, n))).astype(f)
    dt = np.exp(r.uniform(np.log(1e-3), np.log(0.1), size=(t, nh))).astype(f)
    a = -np.exp(r.uniform(0, np.log(16.0), size=nh)).astype(f)
    d = r.uniform(0.5, 1.5, size=nh).astype(f)
    state = jax.random.normal(jax.random.key(5), (slots + 1, nh, p, n),
                              jnp.float32)
    state = state.at[slots].set(0.0)
    state0 = {s: np.asarray(state[s], np.float64) for s in (0, 17, 126)}
    q_start = np.zeros(n_desc, np.int32)
    q_len = np.zeros(n_desc, np.int32)
    kv_len = np.zeros(n_desc, np.int32)
    slot = np.full(n_desc, slots, np.int32)
    for s in range(slots - 1):
        q_start[s], q_len[s], kv_len[s], slot[s] = s, 1, 100 + s, s
    for ci in range(4):
        dsc = slots - 1 + ci
        q_start[dsc], q_len[dsc] = slots - 1 + ci * P, P
        kv_len[dsc], slot[dsc] = ci * P, slots - 1
    fn = jax.jit(ragged_ssd_reference, static_argnames="page_size",
                 donate_argnums=(6,))
    dargs = [jnp.asarray(v) for v in (x, dt, a, b, c, d)]
    desc = [jnp.asarray(v) for v in (q_start, q_len, kv_len, slot)]
    t0 = time.perf_counter()
    y, new = fn(*dargs, state, *desc, page_size=P)
    y = np.asarray(jax.block_until_ready(y))
    first = time.perf_counter() - t0
    new_np = {s: np.asarray(new[s]) for s in (0, 17, 126, slots - 1)}
    hold = [new]

    def timed(descs):
        def call():
            y2, hold[0] = fn(*dargs, hold[0], *descs, page_size=P)
            return y2
        return _median_ms(call)
    mixed_ms = timed(desc)
    only_decode = [jnp.asarray(v) for v in (
        q_start, np.where(np.arange(n_desc) < slots - 1, q_len, 0),
        kv_len, slot)]
    decode_ms = timed(only_decode)
    rep = nh // g

    def step(S, i):
        bh, ch = np.repeat(b[i], rep, 0), np.repeat(c[i], rep, 0)
        S = S * np.exp(dt[i] * a)[:, None, None] \
            + (dt[i][:, None] * x[i])[:, :, None] * bh[:, None, :]
        return S, np.einsum("hpn,hn->hp", S, ch) + d[:, None] * x[i]
    worst_y = worst_s = 0.0
    for s in (0, 17, 126):
        S, want = step(state0[s], s)
        worst_y = max(worst_y, float(np.abs(y[s] - want).max()))
        worst_s = max(worst_s, float(np.abs(new_np[s] - S).max()))
    S = np.zeros((nh, p, n))
    for i in range(slots - 1, slots - 1 + 4 * P):
        S, want = step(S, i)
        worst_y = max(worst_y, float(np.abs(y[i] - want).max()))
    worst_s = max(worst_s, float(np.abs(new_np[slots - 1] - S).max()))
    pool = (slots + 1) * nh * p * n * 4
    return {"descriptors": n_desc, "first_call_s": first,
            "mixed_call_ms": mixed_ms, "decode_only_call_ms": decode_ms,
            "pool_bytes": pool,
            "read_plus_write_floor_ms": 2e3 * pool / 819e9,
            "max_abs_err_y": worst_y, "max_abs_err_state": worst_s,
            "ok": worst_y < 5e-4 and worst_s < 5e-4}


def expert_layer_at_real_widths():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.inference.moe_dispatch import (MoEArch,
                                                   expert_buffer_rows,
                                                   moe_ffn)
    h, z, f, fs, held, e, k = 4096, 1024, 2688, 5376, 128, 512, 22
    bf = jnp.bfloat16
    key = jax.random.key(7)

    def w(i, *shape, std=0.02):
        return (std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                        jnp.float32)).astype(bf)
    lay = dict(router=w(0, h, e), router_bias=w(1, e, std=0.1),
               latent_in=w(2, h, z), latent_out=w(3, z, h),
               experts_up=w(4, held, z, f), experts_down=w(5, held, f, z),
               shared_up=w(6, h, fs), shared_down=w(7, fs, h))
    arch = MoEArch(num_experts=e, top_k=k, norm_topk=True, capacity=0,
                   shared=True, shared_gate=False, attn_bias=False,
                   dispatch="grouped", expert_lo=0, experts_held=held,
                   scoring="sigmoid", route_scale=5.0, expert_act="relu2")
    t = 640
    x = jax.random.normal(jax.random.fold_in(key, 9), (t, h), bf)
    from paddle_tpu.inference import moe_dispatch
    chosen = moe_dispatch._row_tile
    out = {"tile_chosen": chosen(arch, t)}
    for tile in (32, 64):
        # the tile the dispatch takes, steered for this reading only
        moe_dispatch._row_tile = lambda arch, t, tile=tile: tile
        fn = jax.jit(lambda x, lay, live, arch: moe_ffn(x, lay, arch, live),
                     static_argnums=3)
        for name, n_live in (("640_rows_live", t), ("128_rows_live", 128)):
            live = jnp.arange(t) < n_live
            y, cnt = jax.block_until_ready(fn(x, lay, live, arch))
            ms = _median_ms(lambda: fn(x, lay, live, arch)[0])
            ms_routed = _median_ms(lambda: fn(
                x, lay, live, arch._replace(shared=False))[0])
            cnt = np.asarray(cnt)
            out[f"tile{tile}_{name}"] = {
                "buffer_rows": expert_buffer_rows(arch, t),
                "call_ms": ms, "call_ms_without_shared": ms_routed,
                "routed_slots": int(cnt.sum()),
                "held_slots": int(cnt[:held].sum()),
                "finite": bool(np.isfinite(np.asarray(
                    y, np.float32)).all())}
    moe_dispatch._row_tile = chosen
    weights = held * 2 * z * f * 2
    out["held_expert_bytes"] = weights
    out["weights_floor_ms"] = 1e3 * weights / 819e9
    out["ok"] = all(v["finite"] and v["routed_slots"] ==
                    (640 if "640" in n else 128) * k
                    for n, v in out.items() if isinstance(v, dict))
    return out


def main(which="all"):
    import jax
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("pr32_kernels: no TPU", file=sys.stderr)
        return 2
    res = {"device": dev.device_kind}
    parts = {"ragged": ("ragged_32_to_2", ragged_at_32_to_2),
             "ssd": ("mamba2_ssd", ssd_at_real_widths),
             "moe": ("expert_layer", expert_layer_at_real_widths)}
    for key in (tuple(parts) if which == "all" else (which,)):
        name, fn = parts[key]
        res[name] = fn()
        print(name, json.dumps(res[name]), flush=True)
    res["ok"] = all(v["ok"] for v in res.values() if isinstance(v, dict))
    print(json.dumps(res))
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:2]))
