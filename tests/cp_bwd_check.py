"""Real-TPU check of the context-parallel flash chunk backward
(subprocess; exits 86 when no TPU is reachable).

1. PARITY: ``_chunk_bwd``'s Pallas path (flash _bwd_impl with GLOBAL
   out/lse statistics) against the f32 einsum oracle, for both the
   causal diagonal block and a full off-diagonal block — the two
   patterns the ring backward dispatches.
2. MICROBENCH: one (q-chunk, kv-chunk) backward, flash vs einsum, as
   an in-graph ``lax.scan`` (host dispatch latency cannot contaminate
   in-graph timing; marginal time over two scan lengths cancels the
   fixed per-call cost).

Prints ONE json line.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

try:
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"skip": f"platform {dev.platform}"}))
        sys.exit(86)
except Exception as e:  # noqa: BLE001
    print(json.dumps({"skip": str(e)[:200]}))
    sys.exit(86)

import jax.numpy as jnp
from jax import lax

from paddle_tpu.distributed.context_parallel import (_chunk_bwd,
                                                     _chunk_bwd_jnp)

B, H, HK, D = 1, 16, 4, 128
LQ = LK = 2048


def _data(seed=0):
    rng = np.random.default_rng(seed)

    def t(*shape):
        return jnp.asarray(rng.standard_normal(shape) * 0.5,
                           jnp.bfloat16)
    s = 2 * LQ
    q = t(B, s, H, D)
    k = t(B, s, HK, D)
    v = t(B, s, HK, D)
    do = t(B, s, H, D)
    return q, k, v, do


def _global_stats(q, k, v):
    """f32 full causal attention over the 2-chunk sequence -> the
    GLOBAL normalized out + lse the ring would have saved."""
    b, s, h, d = q.shape
    hk = k.shape[2]
    kf = jnp.repeat(k, h // hk, axis=2).astype(jnp.float32)
    vf = jnp.repeat(v, h // hk, axis=2).astype(jnp.float32)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                    kf) / np.sqrt(d)
    mask = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(mask[None, None], sc, -jnp.inf)
    m = jnp.max(sc, axis=-1)
    p = jnp.exp(sc - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p / l[..., None], vf)
    lse = m + jnp.log(l)
    return o, lse


def parity():
    q, k, v, do = _data()
    out, lse = jax.jit(_global_stats)(q, k, v)
    # q-chunk = second half; its global out/lse slices
    q1 = q[:, LQ:]
    out1 = out[:, LQ:].astype(jnp.bfloat16)
    lse1 = lse[:, :, LQ:]
    do1 = do[:, LQ:]
    res = {}
    for name, kc, vc, diag, koff in (
            ("diag", k[:, LQ:], v[:, LQ:], True, LQ),
            ("full", k[:, :LQ], v[:, :LQ], False, 0)):
        f = jax.jit(lambda *a, d=diag, ko=koff: _chunk_bwd(
            *a, d, jnp.int32(LQ), jnp.int32(ko)))
        g = jax.jit(lambda *a, d=diag, ko=koff: _chunk_bwd_jnp(
            *a, d, jnp.int32(LQ), jnp.int32(ko)))
        fl = f(q1, kc, vc, out1, lse1, do1)
        or_ = g(q1, kc, vc, out1, lse1, do1)
        errs = []
        for a, b_ in zip(fl, or_):
            a = np.asarray(a, np.float32)
            b_ = np.asarray(b_, np.float32)
            denom = np.maximum(np.abs(b_).max(), 1e-6)
            errs.append(float(np.abs(a - b_).max() / denom))
        res[name] = {"max_rel_err": max(errs)}
        assert max(errs) < 5e-2, (name, errs)   # bf16 kernel vs f32
    return res


def _scan_time(fn, args, n_long=24, n_short=8):
    """Marginal in-graph time per iteration (dispatch-proof)."""
    def run(n):
        def body(c, _):
            outs = fn(*((c,) + args[1:]))
            # feed a slice of the output back to serialize iterations
            c2 = (c + outs[0].astype(c.dtype) * 1e-6).astype(c.dtype)
            return c2, ()
        final, _ = lax.scan(body, args[0], None, length=n)
        return jnp.sum(final.astype(jnp.float32))
    jl = jax.jit(lambda: run(n_long))
    js = jax.jit(lambda: run(n_short))
    float(jax.device_get(jl()))   # compile+warm
    float(jax.device_get(js()))
    ts = []
    for j in (js, jl):
        t0 = time.perf_counter()
        float(jax.device_get(j()))
        ts.append(time.perf_counter() - t0)
    return (ts[1] - ts[0]) / (n_long - n_short)


def bench():
    q, k, v, do = _data(1)
    out, lse = jax.jit(_global_stats)(q, k, v)
    q1, kc, vc = q[:, LQ:], k[:, :LQ], v[:, :LQ]
    out1 = out[:, LQ:].astype(jnp.bfloat16)
    lse1, do1 = lse[:, :, LQ:], do[:, LQ:]
    args = (q1, kc, vc, out1, lse1, do1)
    t_flash = _scan_time(
        lambda *a: _chunk_bwd(*a, False, jnp.int32(LQ), jnp.int32(0)),
        args)
    t_jnp = _scan_time(
        lambda *a: _chunk_bwd_jnp(*a, False, jnp.int32(LQ),
                                  jnp.int32(0)), args)
    return {"flash_ms": round(t_flash * 1e3, 3),
            "einsum_ms": round(t_jnp * 1e3, 3),
            "speedup": round(t_jnp / t_flash, 2),
            "shape": f"b{B} h{H}/kv{HK} d{D} chunk {LQ}x{LK} bf16"}


if __name__ == "__main__":
    out = {"parity": parity(), "bench": bench()}
    print(json.dumps(out))
