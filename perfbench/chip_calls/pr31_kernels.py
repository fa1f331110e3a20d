"""PR 31's first chip call: the expert layer's two kernels ALONE at both
serving cells' shapes, before anything is measured through them.

    chiprun --chips 1 --timeout 900 -- python3 perfbench/chip_calls/pr31_kernels.py [deepseek|hybrid|both]

One layer's gate|up + down over the sorted buffer at
``(slots, K, F, E)`` = (960, 2048, 1408, 64) and (5760, 2048, 512, 256):

- the route the parent took — float32 rows, one ``gmm`` a projection at
  the parent's blocks and row tile, ``silu(hg) * hu`` in XLA — as the
  baseline, timed call by call;
- the fused ``gmm_glu`` on bf16 rows and the down ``gmm``, float32 out,
  over a sweep of row tiles and (K, N) blocks, each against the floor
  its weights set at 819 GB/s, and against the baseline's values (the
  same to float32 rounding) and a float64 product for a sample of
  experts;
- two routings a shape: every row live, and a decode window's handful
  (the rest of the buffer is empty tiles).

A compile that passes is not a run (PR 21): this runs them, under a
watchdog that ends a hang in stacks and a non-zero exit.
"""
import faulthandler
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))            # the checkout's root

WATCHDOG_S = 840
HBM_BYTES_PER_S = 819e9

SHAPES = {
    # name: slots (rows x top-k), K, F, E held, share of slots held here,
    # the parent's row tile, live rows of a decode window x top-k
    "deepseek": dict(slots=960, k=2048, f=1408, e=64, held=1.0,
                     tm_parent=128, decode_slots=12 * 6),
    "hybrid": dict(slots=5760, k=2048, f=512, e=256, held=0.5,
                   tm_parent=32, decode_slots=30 * 10),
}


def _timed(fn, *args, n=20):
    import jax
    jax.block_until_ready(fn(*args))
    best = []
    for _ in range(3):
        t0 = time.perf_counter()
        out = None
        for _ in range(n):
            out = fn(*args)
        jax.block_until_ready(out)
        best.append((time.perf_counter() - t0) / n)
    return 1e3 * min(best)


def one_shape(name, interpret=False, small=False):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.grouped_matmul import (
        _gmm_call, _gmm_glu_call, _glu_cfg, _auto_tm, gate_up, gmm,
        make_dropless_plan_rows)
    sh = dict(SHAPES[name])
    if small:                      # the CPU rehearsal
        sh.update(slots=96, k=256, f=128, e=8, decode_slots=12)
    slots, k, f, e = sh["slots"], sh["k"], sh["f"], sh["e"]
    r = np.random.default_rng(31)
    bf, f32 = jnp.bfloat16, jnp.float32
    key = jax.random.key(31)
    wg = jax.random.normal(key, (e, k, f), bf) * 0.05
    wu = jax.random.normal(jax.random.fold_in(key, 1), (e, k, f), bf) * 0.05
    wd = jax.random.normal(jax.random.fold_in(key, 2), (e, f, k), bf) * 0.05
    x = jax.random.normal(jax.random.fold_in(key, 3), (slots, k), bf)
    res = {"shape": sh, "floor_ms": {
        "gate_up": 1e3 * 2 * e * k * f * 2 / HBM_BYTES_PER_S,
        "down": 1e3 * e * k * f * 2 / HBM_BYTES_PER_S}}

    def routing(live_slots):
        re_ = np.where(r.random(slots) < sh["held"],
                       r.integers(0, e, slots), e)
        re_[live_slots:] = e                      # not live: dropped
        return jnp.asarray(re_, jnp.int32)

    def plan(row_expert, tm, dtype):
        order, dest, valid, te, cnt, m_pad = make_dropless_plan_rows(
            row_expert, e, tm)
        xs = jnp.zeros((m_pad, k), dtype).at[dest].set(
            x[order].astype(dtype), mode="drop")
        return xs, te, cnt, dest, valid, order

    for rname, live in (("all_live", slots), ("decode",
                                              sh["decode_slots"])):
        row_expert = routing(live)
        out = {}
        # -- the parent's route: f32 rows, three calls, its blocks ---------
        tm0 = sh["tm_parent"] if not small else 8
        xs0, te0, cnt0, dest0, valid0, _ = plan(row_expert, tm0, f32)
        kw = dict(interpret=interpret)
        tn_down = min(1024, k)
        gu0 = jax.jit(lambda a, w, te: _gmm_call(
            a, w, te, transpose_w=False, tm=tm0, tc=k, tj=f, **kw))
        dn0 = jax.jit(lambda a, w, te: _gmm_call(
            a, w, te, transpose_w=False, tm=tm0, tc=f, tj=tn_down, **kw))
        act0 = jax.jit(lambda g, u: jax.nn.silu(g) * u)
        hg0, hu0 = gu0(xs0, wg, te0), gu0(xs0, wu, te0)
        hs0 = act0(hg0, hu0)
        ys0 = dn0(hs0, wd, te0)
        out["parent"] = {
            "tm": tm0, "m_pad": int(xs0.shape[0]),
            "gate_ms": _timed(gu0, xs0, wg, te0),
            "up_ms": _timed(gu0, xs0, wu, te0),
            "silu_mul_ms": _timed(act0, hg0, hu0),
            "down_ms": _timed(dn0, hs0, wd, te0)}
        base_rows = np.asarray(jnp.where(
            valid0[:, None], hs0[jnp.minimum(dest0, xs0.shape[0] - 1)], 0))
        base_ys = np.asarray(jnp.where(
            valid0[:, None], ys0[jnp.minimum(dest0, xs0.shape[0] - 1)], 0))

        # -- the fused route over a sweep ---------------------------------
        tiles = (8,) if small else (32, 64, 128)
        for tm in tiles:
            xs, te, cnt, dest, valid, order = plan(row_expert, tm, bf)
            m_pad = int(xs.shape[0])
            kblocks = sorted({k, min(k, 1024), min(k, 512)}, reverse=True)
            for tc in kblocks:
                tag = f"tm{tm}_glu_tc{tc}"
                glu = jax.jit(lambda a, g, u, te, tc=tc, tm=tm:
                              _gmm_glu_call(a, g, u, te, tm=tm, tc=tc,
                                            tj=f, save_pre=False,
                                            out_dtype=f32, **kw)[0])
                try:
                    hs = glu(xs, wg, wu, te)
                    rows = np.asarray(jnp.where(
                        valid[:, None],
                        hs[jnp.minimum(dest, m_pad - 1)], 0))
                    scale = float(np.abs(base_rows).max()) or 1.0
                    out[tag] = {
                        "m_pad": m_pad, "ms": _timed(glu, xs, wg, wu, te),
                        "max_rel_diff_vs_parent": float(
                            np.abs(rows - base_rows).max() / scale)}
                except Exception as ex:  # noqa: BLE001
                    out[tag] = {"error": repr(ex)[:300]}
            for tj in sorted({k, min(k, 1024)}, reverse=True):
                tag = f"tm{tm}_down_tj{tj}"
                hs_in = jnp.zeros((m_pad, f), f32).at[dest].set(
                    jnp.asarray(base_rows), mode="drop")
                dn = jax.jit(lambda a, w, te, tj=tj, tm=tm: _gmm_call(
                    a, w, te, transpose_w=False, tm=tm, tc=f, tj=tj,
                    out_dtype=f32, **kw))
                try:
                    ys = dn(hs_in, wd, te)
                    rows = np.asarray(jnp.where(
                        valid[:, None],
                        ys[jnp.minimum(dest, m_pad - 1)], 0))
                    scale = float(np.abs(base_ys).max()) or 1.0
                    out[tag] = {
                        "ms": _timed(dn, hs_in, wd, te),
                        "max_rel_diff_vs_parent": float(
                            np.abs(rows - base_ys).max() / scale)}
                except Exception as ex:  # noqa: BLE001
                    out[tag] = {"error": repr(ex)[:300]}

        # -- what the wrappers pick, end to end, against float64 ----------
        tm = _auto_tm(e, slots) if not small else 8
        xs, te, cnt, dest, valid, order = plan(row_expert, tm, bf)
        m_pad = int(xs.shape[0])
        picked = jax.jit(lambda a, g, u, d, te, cnt: gmm(
            gate_up(a, g, u, te, cnt, tm=tm, out_dtype=f32, **kw),
            d, te, cnt, tm=tm, out_dtype=f32, **kw))
        ys = picked(xs, wg, wu, wd, te, cnt)
        got = np.asarray(jnp.where(
            valid[:, None], ys[jnp.minimum(dest, m_pad - 1)], 0))
        ordn = np.asarray(order)
        rex = np.asarray(row_expert)[ordn]
        xn = np.asarray(x.astype(f32), np.float64)[ordn]
        worst = 0.0
        for ex_ in (0, e // 3, e - 1):
            rows = np.nonzero(rex == ex_)[0]
            if not len(rows):
                continue
            g = xn[rows] @ np.asarray(wg[ex_].astype(f32), np.float64)
            u = xn[rows] @ np.asarray(wu[ex_].astype(f32), np.float64)
            want = (g / (1 + np.exp(-g)) * u) @ np.asarray(
                wd[ex_].astype(f32), np.float64)
            worst = max(worst, float(np.abs(got[rows] - want).max()
                                     / np.abs(want).max()))
        out["picked"] = {
            "tm": tm, "m_pad": m_pad,
            "glu_blocks": _glu_cfg(tm, k, f, bf, bf, f32),
            "ms": _timed(picked, xs, wg, wu, wd, te, cnt),
            "max_rel_err_vs_float64": worst,
            "max_rel_diff_vs_parent": float(
                np.abs(got - base_ys).max()
                / (float(np.abs(base_ys).max()) or 1.0)),
            "dropped_rows_zero": bool(
                (got[rex == e] == 0).all())}
        res[rname] = out
        print(name, rname, json.dumps(out), flush=True)
    res["ok"] = all(
        res[rn]["picked"]["max_rel_err_vs_float64"] < 5e-3
        and res[rn]["picked"]["max_rel_diff_vs_parent"] < 1e-4
        and res[rn]["picked"]["dropped_rows_zero"]
        for rn in ("all_live", "decode"))
    return res


def main(which="both", rehearse=""):
    import jax
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    dev = jax.devices()[0]
    small = rehearse == "rehearse"
    if dev.platform != "tpu" and not small:
        print("pr31_kernels: no TPU", file=sys.stderr)
        return 2
    res = {"device": dev.device_kind}
    for name in (("deepseek", "hybrid") if which == "both" else (which,)):
        res[name] = one_shape(name, interpret=small, small=small)
    res["ok"] = all(v["ok"] for v in res.values() if isinstance(v, dict))
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/pr31_kernels.json", "w") as fh:
        json.dump(res, fh, indent=1)
    print(json.dumps({"ok": res["ok"], "device": res["device"]}))
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:3]))
