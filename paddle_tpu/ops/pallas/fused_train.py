"""Fused train-step regions (Pallas/Mosaic) — MPK-style mega-kernelization.

BENCH_r03–r07 pin overall training MFU at ~0.51 while the flash kernel
alone reaches 0.62: the gap is the long tail of element-wise ops and
inter-op overhead around attention (PAPERS.md, MPK arxiv 2512.22219).
This module fuses the three worst offenders into single kernel regions,
each with a jnp reference path mirroring the kernel math bit-for-bit —
the CI-covered path, exactly as the INT8 paged-attention kernels do:

1. **Fused optimizer update** (`fused_update_flat`): one pass over each
   (param, grad, slot) triple — the global-norm clip scale, lr and
   beta-correction are folded into the update, weight decay stays
   decoupled for AdamW.  On TPU the params and moments are
   input_output_aliased so the update is in-place: read p/g/m/v once,
   write p/m/v once, no clipped-grad materialization and no second
   HBM pass (the unfused clip→update chain reads the grads twice and
   round-trips the clipped copy through HBM).

2. **add+norm chains** (`add_rms_norm_raw` / `add_layer_norm_raw`):
   ``h = residual + x; y = norm(h)`` in one pass — the residual write
   and the norm read share one VMEM tile instead of two HBM trips.

3. **matmul+rotary** (`matmul_rope_raw` / `qkv_rope_raw`): the rotary
   embedding is applied in-register to the q/k projection's output tile
   before it is ever written, removing the pre-rope q/k HBM round-trip.

Bit-identity contract: every reference here is op-for-op the math of
the unfused path it replaces (``Optimizer.apply_gradients``'s per-leaf
loop, ``_nn.rms_norm``/``_nn.layer_norm``, ``F.linear`` + llama's
``_apply_rope_raw``), so flipping ``fused_step``/``fuse_norm_rope`` off
reproduces the same trajectory bit-for-bit; tests/test_fused_train.py
locks this.  The kernels never execute in CPU CI — they are verified by
keeping their math in lockstep with these references.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ShapeNotCovered

__all__ = [
    "kernels_active", "SLOT_KEYS", "fused_update_flat",
    "fused_update_reference", "update_flop_estimate",
    "add_rms_norm_raw", "add_rms_norm_reference",
    "add_layer_norm_raw", "add_layer_norm_reference",
    "matmul_rope_raw", "matmul_rope_reference", "qkv_rope_raw",
]

_LANES = 128


def kernels_active() -> bool:
    """Pallas kernels run only on real TPU with the flag on AND no active
    GSPMD mesh (a pallas_call inside a pjit'd sharded program would force
    a gather — sharded steps take the reference math, whose collectives
    GSPMD places; a shard_map'd kernel variant is future work)."""
    from ...common.flags import get_flag
    from ...runtime.device import is_compiled_with_tpu
    if not (get_flag("use_pallas") and is_compiled_with_tpu()):
        return False
    from ...distributed.auto_parallel import get_mesh
    return get_mesh() is None


# ---------------------------------------------------------------------------
# 1. fused optimizer update: global-norm clip folded into one update pass
# ---------------------------------------------------------------------------

SLOT_KEYS = {"sgd": (), "momentum": ("velocity",),
             "adam": ("moment1", "moment2")}

# analytic per-element FLOP estimates (mul+add counted separately) for
# the MFU numerator when the update runs inside the kernel — XLA's cost
# analysis cannot see into a pallas_call, so CompiledTrainStep.step_flops
# adds these back to keep pre/post-fusion MFU comparable.
_UPDATE_FLOPS = {"sgd": 2, "momentum": 5, "adam": 16}
_CLIP_FLOPS = 4      # square+accumulate on the norm pass, scale+round fold


def update_flop_estimate(kind: str, n_elems: int, has_clip: bool) -> float:
    per = _UPDATE_FLOPS.get(kind, 6)
    if has_clip:
        per += _CLIP_FLOPS
    return float(per) * float(n_elems)


def _clip_fold_f32(gf, clip_scale, grad_dtype):
    """Fold the global-norm clip scale into the f32 grad IN-REGISTER.
    The unfused path (ClipGradByGlobalNorm.transform) materializes the
    clipped grad in the grad's dtype before apply_gradients re-casts it
    to f32 — replay that rounding here so fused == unfused bitwise."""
    return (gf * clip_scale).astype(grad_dtype).astype(jnp.float32)


def _bias_corrections(kind, hp, step_f):
    """Adam's ``1 - beta ** t`` pair (None for the other kinds).  Scalars,
    so they are computed ONCE outside the kernel body and ride its SMEM
    scalar operand: Mosaic has no lowering for a scalar ``math.powf``."""
    if kind != "adam":
        return None
    return 1 - hp["beta1"] ** step_f, 1 - hp["beta2"] ** step_f


def _update_math(kind, hp, pf, gf, slots, lr, bias_corr):
    """The single source of optimizer math: called by the Pallas kernel
    body and the reference path with the same f32 operands (``bias_corr``
    from ``_bias_corrections``).  Mirrors
    ``Optimizer.apply_gradients``'s per-leaf ``upd()`` op-for-op (note:
    like that path, L1Decay is applied in its L2 form — the compiled
    path has never special-cased L1)."""
    wd = hp.get("weight_decay", 0.0)
    if wd and not hp.get("decoupled", False):
        gf = gf + wd * pf
    if kind == "sgd":
        return pf - lr * gf, {}
    if kind == "momentum":
        mu = hp["momentum"]
        v = mu * slots["velocity"] + gf
        if hp.get("nesterov", False):
            new_p = pf - lr * (gf + mu * v)
        else:
            new_p = pf - lr * v
        return new_p, {"velocity": v}
    if kind == "adam":
        b1, b2, eps = hp["beta1"], hp["beta2"], hp["epsilon"]
        m = b1 * slots["moment1"] + (1 - b1) * gf
        v = b2 * slots["moment2"] + (1 - b2) * jnp.square(gf)
        bc1, bc2 = bias_corr
        mhat = m / bc1
        vhat = v / bc2
        new_p = pf - lr * mhat / (jnp.sqrt(vhat) + eps)
        if wd and hp.get("decoupled", False):
            new_p = new_p - lr * wd * pf
        return new_p, {"moment1": m, "moment2": v}
    raise ShapeNotCovered(f"no fused update for optimizer kind {kind!r}")


def fused_update_reference(kind, p, g, slots, *, lr, step_f, clip_scale,
                           hyper):
    """CPU/debug path: the kernel math as one jnp expression chain per
    (param, grad, slot) triple — bit-identical to the kernel AND to the
    unfused clip→update loop (the clip rounding is replayed in
    _clip_fold_f32)."""
    gf = g.astype(jnp.float32)
    if clip_scale is not None:
        gf = _clip_fold_f32(gf, clip_scale, g.dtype)
    pf = p.astype(jnp.float32)
    new_p, new_slots = _update_math(
        kind, hyper, pf, gf, slots, lr,
        _bias_corrections(kind, hyper, step_f))
    return new_p.astype(p.dtype), new_slots


_OPT_TILE_ROWS = 512          # per-grid-step tile: 512 x 128 (256 KB f32)


def _opt_kernel_body(kind, hp, has_clip, slot_keys, scal_ref, p_ref, g_ref,
                     *refs):
    n = len(slot_keys)
    slot_in = refs[:n]
    outs = refs[n:]
    lr = scal_ref[0]
    gf = g_ref[...].astype(jnp.float32)
    if has_clip:
        gf = _clip_fold_f32(gf, scal_ref[1], g_ref.dtype)
    pf = p_ref[...].astype(jnp.float32)
    slots = {k: slot_in[i][...] for i, k in enumerate(slot_keys)}
    new_p, new_slots = _update_math(kind, hp, pf, gf, slots, lr,
                                    (scal_ref[2], scal_ref[3]))
    outs[0][...] = new_p.astype(outs[0].dtype)
    for i, k in enumerate(slot_keys):
        outs[1 + i][...] = new_slots[k]


def _fused_update_kernel(kind, p, g, slots, *, lr, step_f, clip_scale,
                         hyper):
    """One kernel launch over the flattened triple.  The param and slot
    buffers are input_output_aliased: each tile streams HBM→VMEM once,
    the clipped f32 grad and the new param/moments are produced
    in-register, and the results overwrite the inputs in the same pass."""
    if p.dtype not in (jnp.float32, jnp.bfloat16, jnp.float16):
        raise ShapeNotCovered(f"fused update: dtype {p.dtype}")
    slot_keys = SLOT_KEYS[kind]
    n = p.size
    tile = _OPT_TILE_ROWS * _LANES
    pad = (-n) % tile

    def prep(a):
        a = a.reshape(-1)
        if pad:
            a = jnp.concatenate([a, jnp.zeros((pad,), a.dtype)])
        return a.reshape(-1, _LANES)

    p2, g2 = prep(p), prep(g)
    s2 = [prep(slots[k]) for k in slot_keys]
    bias_corr = _bias_corrections(kind, hyper, step_f) or (1.0, 1.0)
    scal = jnp.stack([
        jnp.asarray(x, jnp.float32)
        for x in (lr, 1.0 if clip_scale is None else clip_scale,
                  *bias_corr)])
    blk = pl.BlockSpec((_OPT_TILE_ROWS, _LANES), lambda i: (i, 0))
    n_in = 2 + len(slot_keys)
    outs = pl.pallas_call(
        functools.partial(_opt_kernel_body, kind, hyper,
                          clip_scale is not None, slot_keys),
        name=f"fused_update_{kind}",
        grid=(p2.shape[0] // _OPT_TILE_ROWS,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [blk] * n_in,
        out_specs=[blk] * (1 + len(slot_keys)),
        out_shape=[jax.ShapeDtypeStruct(p2.shape, p2.dtype)]
        + [jax.ShapeDtypeStruct(p2.shape, jnp.float32)
           for _ in slot_keys],
        input_output_aliases={1: 0, **{3 + i: 1 + i
                                       for i in range(len(slot_keys))}},
    )(scal, p2, g2, *s2)
    new_p = outs[0].reshape(-1)[:n].reshape(p.shape)
    new_slots = {k: outs[1 + i].reshape(-1)[:n].reshape(p.shape)
                 for i, k in enumerate(slot_keys)}
    return new_p, new_slots


def fused_update_flat(kind, p, g, slots, *, lr, step_f, clip_scale, hyper):
    """Fused clip→update over one (param, grad, slots) triple of any
    shape (Optimizer.apply_gradients_fused packs the small-leaf tail
    into flat per-dtype buffers before calling this).  Kernel on TPU,
    bit-identical jnp reference elsewhere."""
    from ...observability import introspection as _insp
    # runs at TRACE time (inside the enclosing step's jit), i.e.
    # exactly when the surrounding program compiles — which is what a
    # subprogram note should count
    _insp.get_compile_watch().note_subprogram(
        "pallas.fused_update_flat", kind=kind,
        kernel=bool(kernels_active()))
    if kernels_active():
        try:
            return _fused_update_kernel(kind, p, g, slots, lr=lr,
                                        step_f=step_f,
                                        clip_scale=clip_scale, hyper=hyper)
        except ShapeNotCovered:     # param dtype / optimizer kind
            pass
    return fused_update_reference(kind, p, g, slots, lr=lr, step_f=step_f,
                                  clip_scale=clip_scale, hyper=hyper)


# ---------------------------------------------------------------------------
# 2. fused residual-add + norm chains
# ---------------------------------------------------------------------------

def add_rms_norm_reference(x, residual, weight, epsilon=1e-6):
    """h = residual + x; y = rms_norm(h, weight) — op-for-op the
    ``x + attn`` followed by ``_nn.rms_norm`` chain.  Returns (h, y)."""
    h = residual + x
    dt = h.dtype
    xf = h.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = (xf * lax.rsqrt(ms + epsilon)).astype(dt)
    if weight is not None:
        out = out * weight
    return h, out


def add_layer_norm_reference(x, residual, weight, bias, epsilon=1e-5):
    """h = residual + x; y = layer_norm(h) over the LAST axis — op-for-op
    ``_nn.layer_norm`` with a length-1 normalized_shape.  Returns (h, y)."""
    h = residual + x
    dt = h.dtype
    xf = h.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mean) * lax.rsqrt(var + epsilon)
    out = out.astype(dt)
    if weight is not None:
        out = out * weight
    if bias is not None:
        out = out + bias
    return h, out


def _norm_rows_tile(n_rows, dtype):
    """Largest row-tile dividing n_rows that respects the dtype's sublane
    multiple; None when no legal tile exists (→ reference path)."""
    min_rows = 16 if dtype == jnp.bfloat16 else 8
    for cand in (256, 128, 64, 32, 16, 8):
        if cand >= min_rows and n_rows % cand == 0:
            return cand
    return None


def _add_norm_eligible(x, weight):
    h = x.shape[-1]
    if weight is None or x.ndim < 2:
        return None
    if h % _LANES or h > 8192:
        return None
    if x.dtype not in (jnp.float32, jnp.bfloat16):
        return None
    rows = 1
    for d in x.shape[:-1]:
        rows *= d
    return _norm_rows_tile(rows, x.dtype)


def _add_rms_kernel_body(eps, x_ref, r_ref, w_ref, h_ref, o_ref):
    h = r_ref[...] + x_ref[...]
    h_ref[...] = h
    xf = h.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    o_ref[...] = ((xf * lax.rsqrt(ms + eps)).astype(h.dtype)
                  * w_ref[...]).astype(o_ref.dtype)


def _add_ln_kernel_body(eps, has_bias, x_ref, r_ref, w_ref, *rest):
    if has_bias:
        b_ref, h_ref, o_ref = rest
    else:
        h_ref, o_ref = rest
    h = r_ref[...] + x_ref[...]
    h_ref[...] = h
    xf = h.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    d = xf - mean
    var = jnp.mean(d * d, axis=-1, keepdims=True)   # == jnp.var
    out = (d * lax.rsqrt(var + eps)).astype(h.dtype) * w_ref[...]
    if has_bias:
        out = out + b_ref[...]
    o_ref[...] = out.astype(o_ref.dtype)


def _add_norm_call(body, x, residual, weight, bias, out_dt, tile_r):
    h_dim = x.shape[-1]
    rows = x.size // h_dim
    x2 = x.reshape(rows, h_dim)
    r2 = residual.reshape(rows, h_dim)
    w2 = weight.reshape(1, h_dim)
    blk = pl.BlockSpec((tile_r, h_dim), lambda i: (i, 0))
    wblk = pl.BlockSpec((1, h_dim), lambda i: (0, 0))
    ins = [x2, r2, w2]
    in_specs = [blk, blk, wblk]
    if bias is not None:
        ins.append(bias.reshape(1, h_dim))
        in_specs.append(wblk)
    h, out = pl.pallas_call(
        body,
        name="add_norm",
        grid=(rows // tile_r,),
        in_specs=in_specs,
        out_specs=[blk, blk],
        out_shape=[jax.ShapeDtypeStruct((rows, h_dim), x.dtype),
                   jax.ShapeDtypeStruct((rows, h_dim), out_dt)],
    )(*ins)
    return h.reshape(x.shape), out.reshape(x.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _add_rms_norm_k(x, residual, weight, epsilon):
    tile_r = _add_norm_eligible(x, weight)
    out_dt = jnp.promote_types(x.dtype, weight.dtype)
    return _add_norm_call(functools.partial(_add_rms_kernel_body, epsilon),
                          x, residual, weight, None, out_dt, tile_r)


def _add_rms_fwd(x, residual, weight, epsilon):
    return _add_rms_norm_k(x, residual, weight, epsilon), \
        (x, residual, weight)


def _add_rms_bwd(epsilon, res, cts):
    x, residual, weight = res
    _, vjp = jax.vjp(
        lambda a, r, w: add_rms_norm_reference(a, r, w, epsilon),
        x, residual, weight)
    return vjp(cts)


_add_rms_norm_k.defvjp(_add_rms_fwd, _add_rms_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _add_ln_k(x, residual, weight, bias, epsilon):
    tile_r = _add_norm_eligible(x, weight)
    out_dt = jnp.promote_types(x.dtype, weight.dtype)
    if bias is not None:
        out_dt = jnp.promote_types(out_dt, bias.dtype)
    body = functools.partial(_add_ln_kernel_body, epsilon, bias is not None)
    return _add_norm_call(body, x, residual, weight, bias, out_dt, tile_r)


def _add_ln_fwd(x, residual, weight, bias, epsilon):
    return _add_ln_k(x, residual, weight, bias, epsilon), \
        (x, residual, weight, bias)


def _add_ln_bwd(epsilon, res, cts):
    x, residual, weight, bias = res
    if bias is None:
        _, vjp = jax.vjp(
            lambda a, r, w: add_layer_norm_reference(a, r, w, None,
                                                     epsilon),
            x, residual, weight)
        return vjp(cts) + (None,)
    _, vjp = jax.vjp(
        lambda a, r, w, b: add_layer_norm_reference(a, r, w, b, epsilon),
        x, residual, weight, bias)
    return vjp(cts)


_add_ln_k.defvjp(_add_ln_fwd, _add_ln_bwd)


def add_rms_norm_raw(x, residual, weight, epsilon=1e-6):
    """Fused residual-add + RMSNorm: returns ``(h, y)`` with
    ``h = residual + x`` and ``y = rms_norm(h, weight)``.  One VMEM pass
    on TPU (backward runs the reference math via custom_vjp); the jnp
    reference elsewhere — bit-identical to the unfused chain."""
    if kernels_active() and _add_norm_eligible(x, weight) is not None:
        return _add_rms_norm_k(x, residual, weight, epsilon)
    return add_rms_norm_reference(x, residual, weight, epsilon)


def add_layer_norm_raw(x, residual, weight, bias, epsilon=1e-5):
    """Fused residual-add + last-axis LayerNorm: returns ``(h, y)``.
    Same dispatch contract as :func:`add_rms_norm_raw`."""
    if kernels_active() and _add_norm_eligible(x, weight) is not None:
        return _add_ln_k(x, residual, weight, bias, epsilon)
    return add_layer_norm_reference(x, residual, weight, bias, epsilon)


# ---------------------------------------------------------------------------
# 3. fused matmul + rotary (the rotary→QKV chain)
# ---------------------------------------------------------------------------

def _rotate_half(x):
    # kept in lockstep with models/llama.py::_rotate_half (tests pin it)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([-x2, x1], axis=-1)


def _rotate_half_interleaved(x):
    # lockstep with models/llama.py::_rotate_half_interleaved
    x1 = x[..., 0::2]
    x2 = x[..., 1::2]
    return jnp.stack([-x2, x1], axis=-1).reshape(x.shape)


def matmul_rope_reference(x, w, cos, sin, n_heads, head_dim,
                          interleaved=False):
    """``reshape(x @ w) → rope`` for ONE projection operand — op-for-op
    the ``F.linear`` + reshape + ``_apply_rope_raw`` chain from
    models/llama.py (rope applied to q and k is independent per
    operand, so per-projection fusion preserves bit-identity)."""
    b, s = x.shape[0], x.shape[1]
    acc = jnp.float32 if x.dtype in (jnp.bfloat16, jnp.float16) else None
    y = jnp.matmul(x, w, preferred_element_type=acc)
    if acc is not None:
        y = y.astype(x.dtype)
    y = y.reshape(b, s, n_heads, head_dim)
    if interleaved:
        half = cos.shape[-1] // 2
        cos = jnp.repeat(cos[..., :half], 2, axis=-1)
        sin = jnp.repeat(sin[..., :half], 2, axis=-1)
    rot = _rotate_half_interleaved if interleaved else _rotate_half
    cosb = cos[None, :, None, :]
    sinb = sin[None, :, None, :]
    yf = y.astype(jnp.float32)
    return (yf * cosb + rot(yf) * sinb).astype(y.dtype)


def _mmr_tile_rows(s, hidden, dtype):
    """Row tile for the matmul+rope kernel: must divide the sequence
    length (so a tile never crosses a batch boundary and the cos/sin
    block index is i % (S // tile)) and keep the x tile under ~4 MB."""
    budget = 4 * 2**20
    for cand in (256, 128, 64, 32):
        if s % cand:
            continue
        if cand * hidden * jnp.dtype(dtype).itemsize <= budget:
            return cand
    return None


def _mmr_eligible(x, w, cos, head_dim, interleaved):
    if interleaved or x.ndim != 3:
        return None             # strided lane access — reference path
    b, s, hidden = x.shape
    if head_dim % _LANES or hidden % _LANES:
        return None
    if x.dtype not in (jnp.float32, jnp.bfloat16):
        return None
    if cos.shape != (s, head_dim):
        return None
    return _mmr_tile_rows(s, hidden, x.dtype)


def _mmr_kernel_body(half, x_ref, w_ref, cos_ref, sin_ref, o_ref):
    acc = jnp.dot(x_ref[...], w_ref[...],
                  preferred_element_type=jnp.float32)
    # mirror F.linear: accumulate f32, round to the input dtype, then
    # rope in f32 — keeps the kernel in lockstep with the reference
    y = acc.astype(x_ref.dtype)
    yf = y.astype(jnp.float32)
    y1, y2 = yf[:, :half], yf[:, half:]
    rot = jnp.concatenate([-y2, y1], axis=-1)
    out = yf * cos_ref[...] + rot * sin_ref[...]
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _matmul_rope_k(x, w, cos, sin, n_heads, head_dim, interleaved):
    b, s, hidden = x.shape
    tile_r = _mmr_eligible(x, w, cos, head_dim, interleaved)
    rows = b * s
    x2 = x.reshape(rows, hidden)
    cosf = cos.astype(jnp.float32)
    sinf = sin.astype(jnp.float32)
    s_blocks = s // tile_r
    out = pl.pallas_call(
        functools.partial(_mmr_kernel_body, head_dim // 2),
        name="matmul_rope",
        grid=(rows // tile_r, n_heads),
        in_specs=[
            pl.BlockSpec((tile_r, hidden), lambda i, j: (i, 0)),
            pl.BlockSpec((hidden, head_dim), lambda i, j: (0, j)),
            pl.BlockSpec((tile_r, head_dim),
                         lambda i, j: (i % s_blocks, 0)),
            pl.BlockSpec((tile_r, head_dim),
                         lambda i, j: (i % s_blocks, 0)),
        ],
        out_specs=pl.BlockSpec((tile_r, head_dim), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, n_heads * head_dim),
                                       x.dtype),
    )(x2, w, cosf, sinf)
    return out.reshape(b, s, n_heads, head_dim)


def _mmr_fwd(x, w, cos, sin, n_heads, head_dim, interleaved):
    return _matmul_rope_k(x, w, cos, sin, n_heads, head_dim, interleaved), \
        (x, w, cos, sin)


def _mmr_bwd(n_heads, head_dim, interleaved, res, ct):
    x, w, cos, sin = res
    _, vjp = jax.vjp(
        lambda a, b, c, d: matmul_rope_reference(
            a, b, c, d, n_heads, head_dim, interleaved), x, w, cos, sin)
    return vjp(ct)


_matmul_rope_k.defvjp(_mmr_fwd, _mmr_bwd)


def matmul_rope_raw(x, w, cos, sin, *, n_heads, head_dim,
                    interleaved=False):
    """One q/k projection with the rotary embedding fused into the
    matmul's output write.  Kernel on TPU when the shape is eligible
    (backward = reference math via custom_vjp); reference elsewhere."""
    if kernels_active() and _mmr_eligible(x, w, cos, head_dim,
                                          interleaved) is not None:
        return _matmul_rope_k(x, w, cos, sin, n_heads, head_dim,
                              interleaved)
    return matmul_rope_reference(x, w, cos, sin, n_heads, head_dim,
                                 interleaved)


def qkv_rope_raw(x, wq, wk, wv, cos, sin, *, n_heads, n_kv, head_dim,
                 interleaved=False):
    """The rotary→QKV chain: q and k projections each fused with rope
    (one pass per projection — the pre-rope q/k never round-trip HBM),
    v a plain projection left to the MXU.  Returns (q, k, v) shaped
    [B, S, heads, head_dim], bit-identical to the unfused
    project→reshape→rope chain."""
    q = matmul_rope_raw(x, wq, cos, sin, n_heads=n_heads,
                        head_dim=head_dim, interleaved=interleaved)
    k = matmul_rope_raw(x, wk, cos, sin, n_heads=n_kv,
                        head_dim=head_dim, interleaved=interleaved)
    b, s = x.shape[0], x.shape[1]
    acc = jnp.float32 if x.dtype in (jnp.bfloat16, jnp.float16) else None
    v = jnp.matmul(x, wv, preferred_element_type=acc)
    if acc is not None:
        v = v.astype(x.dtype)
    return q, k, v.reshape(b, s, n_kv, head_dim)
