"""Grouped (per-expert) matmul for TPU — the MoE expert-compute kernel.

Reference parity: phi/kernels/fusion moe grouped-GEMM kernels (the
reference's fused expert FFN path, SURVEY.md §2.3 EP row).

TPU-native design (megablox-class, built independently): tokens are
pre-sorted by expert and padded so every ``tm``-row tile belongs to
exactly ONE expert; a scalar-prefetched ``tile_expert`` map then lets
each grid step DMA the right expert's weight block, so the whole MoE
FFN is dense MXU matmuls over the ragged token groups — no [T, E, C]
capacity-padded dispatch tensors, no wasted FLOPs on empty capacity
slots, and dropless routing (no token dropping) for free.

Three kernels:
- ``_gmm_kernel``      out[i] = lhs[i] @ w[e(i)]      (fwd, and dX with
                       ``transpose_w`` contracting w's last dim)
- ``_gmm_dw_kernel``   dw[e] += lhs[i].T @ dout[i]    (weight grad; the
                       m grid dim is innermost so each (e, k, n) output
                       block is visited in one contiguous run)

The public entry :func:`grouped_matmul` wires these into a
``jax.custom_vjp``; :func:`make_dropless_plan` builds the sorted,
tile-aligned token layout from router top-k indices (all jit-safe,
static shapes).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .vma import out_sds

__all__ = ["grouped_matmul", "glu_grouped", "gate_up", "gmm_reference",
           "make_dropless_plan",
           "make_dropless_plan_rows", "dropless_moe_ffn",
           "dropless_moe_ffn_rows"]


class GmmCfg(NamedTuple):
    """The static argument of the two custom-vjp entries: row tile,
    K block, N block, interpret mode, and what the forward's float32
    accumulators are rounded to on the way out (``None``: the rows'
    dtype; gradients always take their primal's)."""
    tm: int
    tk: int
    tn: int
    interpret: bool = False
    out_dtype: object = None


def _pick_tile(dim: int, cap: int) -> int:
    """Largest divisor of ``dim`` that is <= cap AND a multiple of 128
    (Mosaic lane constraint for minor block dims); the full dim
    (always legal) wins when the best divisor would make tiny tiles —
    e.g. 1408 = 11*128 has only the 128 divisor, and an 11x larger
    grid costs far more in per-step overhead than the bigger block
    costs in VMEM (measured r4 at the DeepSeekMoE shape: tn=128 ran
    3520 grid steps at 16.7 TF/s; tn=1408-full is ~2x faster)."""
    t = (min(cap, dim) // 128) * 128
    while t >= 128:
        if dim % t == 0:
            break
        t -= 128
    else:
        return dim
    # the full-dim override stays VMEM-bounded: past ~1.5k lanes a
    # full-dim block on BOTH operands can blow the 16M scoped budget
    if t < 512 and dim <= 1536:
        return dim
    return t


# A call's blocks live in VMEM twice over (Pallas double-buffers every
# input and output block) beside its float32 accumulators.  Mosaic
# scopes a call to 16 MiB of a v5e's 128 MiB unless the call asks for
# more, so a call whose blocks need more says so — and the block
# choosers below never plan past ``_VMEM_BUDGET``.
_SCOPED_VMEM = 16 * 2 ** 20
_VMEM_HEADROOM = 2 * 2 ** 20       # Mosaic's own scratch beside the blocks
_VMEM_BUDGET = 40 * 2 ** 20
# Row tiles this small mean a handful of rows an expert (serving): the
# call is bound by the expert matrices it streams, not by the MXU.
_WEIGHT_BOUND_TM = 64


def _vmem_bytes(blocks, acc_elems):
    """``blocks``: (elements, dtype) of every input and output block."""
    return sum(2 * n * jnp.dtype(dt).itemsize for n, dt in blocks) \
        + 4 * acc_elems


def _vmem_params(need):
    """``compiler_params`` for a call whose blocks take ``need`` bytes:
    nothing under Mosaic's default scope, else the limit it needs."""
    if need + _VMEM_HEADROOM <= _SCOPED_VMEM:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=need + _VMEM_HEADROOM)


def _whole_matrix_fits(tm, k, n, n_w, row_dt, w_dt, out_dt):
    """Whether ``n_w`` WHOLE [k, n] expert matrices fit the budget as
    one block each beside a weight-bound row tile.  With one block a
    matrix, a tile of the same expert as the tile before it finds its
    weights in VMEM (Pallas skips a DMA whose block index did not
    change): each expert's matrix crosses HBM once a call, and the
    tiles past the last live one — all mapped to the last expert —
    fetch nothing.  Split along K or N, every tile fetches every block
    again, the empty ones too."""
    if tm > _WEIGHT_BOUND_TM:
        return False
    need = _vmem_bytes([(tm * k, row_dt), (tm * n, out_dt)]
                       + [(k * n, w_dt)] * n_w, n_w * tm * n)
    return need <= _VMEM_BUDGET


# ---------------------------------------------------------------------------
# out[i] = lhs[i] @ w[e(i)]    (and the dX variant via transpose_w)
# ---------------------------------------------------------------------------

def _mxu_pair(a, b):
    """The two operands of a kernel's dot.  A bf16 pair goes to the MXU
    as it is: a product of two bf16 values is exact in float32 and the
    accumulators are float32, so nothing is rounded that a widened pair
    would not round, at a fraction of the passes.  Anything else is
    widened to float32 first."""
    if a.dtype == b.dtype == jnp.bfloat16:
        return a, b
    return a.astype(jnp.float32), b.astype(jnp.float32)


def _gmm_kernel(te_ref, lhs_ref, w_ref, out_ref, acc_ref, *, nc,
                transpose_w):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a, b = _mxu_pair(lhs_ref[...], w_ref[0])     # [tm, tc], [tc,tj]|[tj,tc]
    dims = (((1,), (1,)), ((), ())) if transpose_w \
        else (((1,), (0,)), ((), ()))
    acc_ref[...] += jax.lax.dot_general(
        a, b, dims, preferred_element_type=jnp.float32)

    @pl.when(ic == nc - 1)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _gmm_call(lhs, w, tile_expert, *, transpose_w, tm, tc, tj,
              interpret=False, out_dtype=None):
    """``out_dtype`` (default ``lhs.dtype``): what the float32
    accumulator is rounded to on its way out."""
    m, _ = lhs.shape
    if transpose_w:      # w [E, J, C], contract C
        j_dim = w.shape[1]
        w_block = (1, tj, tc)
        w_imap = lambda i, j, c, te: (te[i], j, c)
    else:                # w [E, C, J]
        j_dim = w.shape[2]
        w_block = (1, tc, tj)
        w_imap = lambda i, j, c, te: (te[i], c, j)
    nm, nj, nc = m // tm, j_dim // tj, lhs.shape[1] // tc
    out_dtype = out_dtype or lhs.dtype
    need = _vmem_bytes([(tm * tc, lhs.dtype), (tc * tj, w.dtype),
                        (tm * tj, out_dtype)], tm * tj)
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, nc=nc, transpose_w=transpose_w),
        name="gmm",
        compiler_params=_vmem_params(need),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nm, nj, nc),
            in_specs=[
                pl.BlockSpec((tm, tc), lambda i, j, c, te: (i, c)),
                pl.BlockSpec(w_block, w_imap),
            ],
            out_specs=pl.BlockSpec((tm, tj), lambda i, j, c, te: (i, j)),
            scratch_shapes=[pltpu.VMEM((tm, tj), jnp.float32)],
        ),
        out_shape=out_sds((m, j_dim), out_dtype, tile_expert, lhs, w),
        interpret=interpret,
    )(tile_expert.astype(jnp.int32), lhs, w)
    return out


# ---------------------------------------------------------------------------
# fused gate|up GLU: hs = silu(lhs @ wg[e]) * (lhs @ wu[e]) in ONE pass
# ---------------------------------------------------------------------------

def _gmm_glu_kernel(te_ref, lhs_ref, wg_ref, wu_ref, *refs, nc,
                    save_pre):
    """Two dots per tile visit — the lhs block is loaded ONCE for both
    the gate and up projections, and the silu*mul epilogue runs on the
    accumulators in VMEM (no hg/hu round-trip through HBM on the
    forward-only path).  ``save_pre`` additionally emits the
    pre-activation hg/hu (the training path's backward needs them)."""
    if save_pre:
        hs_ref, hg_ref, hu_ref, accg_ref, accu_ref = refs
    else:
        hs_ref, accg_ref, accu_ref = refs
        hg_ref = hu_ref = None
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _():
        accg_ref[...] = jnp.zeros_like(accg_ref)
        accu_ref[...] = jnp.zeros_like(accu_ref)

    lhs = lhs_ref[...]                                     # [tm, tc]
    for w_ref, acc_ref in ((wg_ref, accg_ref), (wu_ref, accu_ref)):
        a, b = _mxu_pair(lhs, w_ref[0])
        acc_ref[...] += jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ic == nc - 1)
    def _():
        g = accg_ref[...]
        u = accu_ref[...]
        hs_ref[...] = (jax.nn.silu(g) * u).astype(hs_ref.dtype)
        if save_pre:
            hg_ref[...] = g.astype(hg_ref.dtype)
            hu_ref[...] = u.astype(hu_ref.dtype)


def _gmm_glu_call(lhs, wg, wu, tile_expert, *, tm, tc, tj, save_pre,
                  interpret=False, out_dtype=None):
    m, _ = lhs.shape
    out_dtype = out_dtype or lhs.dtype
    f_dim = wg.shape[2]
    nm, nj, nc = m // tm, f_dim // tj, lhs.shape[1] // tc
    row_spec = pl.BlockSpec((tm, tj), lambda i, j, c, te: (i, j))
    out_specs = [row_spec] + ([row_spec, row_spec] if save_pre else [])
    out_shape = [out_sds((m, f_dim), out_dtype, tile_expert, lhs, wg)] \
        * (3 if save_pre else 1)
    need = _vmem_bytes(
        [(tm * tc, lhs.dtype), (tc * tj, wg.dtype), (tc * tj, wu.dtype)]
        + [(tm * tj, out_dtype)] * len(out_shape), 2 * tm * tj)
    outs = pl.pallas_call(
        functools.partial(_gmm_glu_kernel, nc=nc, save_pre=save_pre),
        name="gmm_glu",
        compiler_params=_vmem_params(need),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nm, nj, nc),
            in_specs=[
                pl.BlockSpec((tm, tc), lambda i, j, c, te: (i, c)),
                pl.BlockSpec((1, tc, tj), lambda i, j, c, te: (te[i], c, j)),
                pl.BlockSpec((1, tc, tj), lambda i, j, c, te: (te[i], c, j)),
            ],
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((tm, tj), jnp.float32),
                            pltpu.VMEM((tm, tj), jnp.float32)],
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(tile_expert.astype(jnp.int32), lhs, wg, wu)
    # pallas_call returns a list when out_shape is a list (even len 1)
    return tuple(outs) if isinstance(outs, (list, tuple)) else (outs,)


def _glu_cfg(tm, k, n, row_dt=jnp.bfloat16, w_dt=jnp.bfloat16,
             out_dt=jnp.float32):
    """Blocks (tm, tk, tn) for the two-weight kernel, or None when no
    safe tiling exists.  A weight-bound row tile takes both matrices
    whole when they fit (``_whole_matrix_fits``).  At training's tiles
    both weight blocks live in VMEM together under Mosaic's default
    scope, so the K block halves vs the single-weight gmm (two
    [tc, tj] bf16 blocks double-buffered + two f32 accumulators must
    stay under the ~16M scoped budget).  _pick_tile's full-dim
    fallback can exceed the cap (e.g. K=1408 has no >=128 divisor
    <= 512) — those shapes keep the two-gmm path."""
    if _whole_matrix_fits(tm, k, n, 2, row_dt, w_dt, out_dt):
        return (tm, k, n)
    tk = _pick_tile(k, 512)
    tn = _pick_tile(n, 1024)
    if tk > 512 or tn > 1408:
        return None
    return (tm, tk, tn)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def glu_grouped(lhs, wg, wu, tile_expert, counts, cfg):
    """Fused silu(lhs @ wg[e]) * (lhs @ wu[e]) over the sorted
    tile-aligned layout.  ``cfg`` is a :class:`GmmCfg`."""
    (hs,) = _gmm_glu_call(lhs, wg, wu, tile_expert, tm=cfg.tm, tc=cfg.tk,
                          tj=cfg.tn, save_pre=False,
                          interpret=cfg.interpret, out_dtype=cfg.out_dtype)
    return hs


def _glu_grouped_fwd(lhs, wg, wu, tile_expert, counts, cfg):
    hs, hg, hu = _gmm_glu_call(lhs, wg, wu, tile_expert, tm=cfg.tm,
                               tc=cfg.tk, tj=cfg.tn, save_pre=True,
                               interpret=cfg.interpret,
                               out_dtype=cfg.out_dtype)
    return hs, (lhs, wg, wu, tile_expert, counts, hg, hu)


def _glu_grouped_bwd(cfg, res, dhs):
    lhs, wg, wu, tile_expert, counts, hg, hu = res
    tm, tk, tn, interp = cfg[:4]
    g = hg.astype(jnp.float32)
    sg = jax.nn.sigmoid(g)
    silu_g = g * sg
    dhs_f = dhs.astype(jnp.float32)
    dhg = (dhs_f * hu.astype(jnp.float32)
           * (sg * (1 + g * (1 - sg)))).astype(lhs.dtype)
    dhu = (dhs_f * silu_g).astype(lhs.dtype)
    # dX via the transposed gmm for each branch; dW via the dw kernel
    dlhs = _gmm_call(dhg, wg, tile_expert, transpose_w=True, tm=tm,
                     tc=tn, tj=tk, interpret=interp)
    dlhs = dlhs + _gmm_call(dhu, wu, tile_expert, transpose_w=True,
                            tm=tm, tc=tn, tj=tk, interpret=interp)
    e = wg.shape[0]
    dwg = _gmm_dw_call(lhs, dhg, tile_expert, counts, e, tm=tm, tk=tk,
                       tn=tn, interpret=interp)
    dwu = _gmm_dw_call(lhs, dhu, tile_expert, counts, e, tm=tm, tk=tk,
                       tn=tn, interpret=interp)
    return (dlhs.astype(lhs.dtype), dwg.astype(wg.dtype),
            dwu.astype(wu.dtype), None, None)


glu_grouped.defvjp(_glu_grouped_fwd, _glu_grouped_bwd)


# ---------------------------------------------------------------------------
# dw[e] = sum over e's tiles of lhs[i].T @ dout[i]
# ---------------------------------------------------------------------------

def _gmm_dw_kernel(te_ref, lhs_ref, dout_ref, dw_ref, acc_ref, *, nm):
    i = pl.program_id(2)
    e = te_ref[i]
    first = jnp.logical_or(i == 0, te_ref[jnp.maximum(i - 1, 0)] != e)
    last = jnp.logical_or(i == nm - 1,
                          te_ref[jnp.minimum(i + 1, nm - 1)] != e)

    @pl.when(first)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = lhs_ref[...].astype(jnp.float32)                    # [tm, tk]
    g = dout_ref[...].astype(jnp.float32)                   # [tm, tn]
    acc_ref[...] += jax.lax.dot_general(
        a, g, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                 # [tk, tn]

    @pl.when(last)
    def _():
        dw_ref[0] = acc_ref[...].astype(dw_ref.dtype)


def _gmm_dw_call(lhs, dout, tile_expert, counts, num_experts, *, tm, tk,
                 tn, interpret=False):
    m, k = lhs.shape
    n = dout.shape[1]
    nm, nk, nn = m // tm, k // tk, n // tn
    need = _vmem_bytes([(tm * tk, lhs.dtype), (tm * tn, dout.dtype),
                        (tk * tn, lhs.dtype)], tk * tn)
    dw = pl.pallas_call(
        functools.partial(_gmm_dw_kernel, nm=nm),
        name="gmm_dw",
        compiler_params=_vmem_params(need),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            # m innermost: each (e, kk, j) output block is one contiguous
            # visit run, zero-initialised on the run's first tile
            grid=(nk, nn, nm),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda kk, j, i, te: (i, kk)),
                pl.BlockSpec((tm, tn), lambda kk, j, i, te: (i, j)),
            ],
            out_specs=pl.BlockSpec((1, tk, tn),
                                   lambda kk, j, i, te: (te[i], kk, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        out_shape=out_sds((num_experts, k, n), lhs.dtype, tile_expert,
                          lhs, dout),
        interpret=interpret,
    )(tile_expert.astype(jnp.int32), lhs, dout)
    # experts with zero tiles were never visited — their blocks are
    # uninitialised memory, not zeros
    return jnp.where((counts > 0)[:, None, None], dw,
                     jnp.zeros_like(dw))


# ---------------------------------------------------------------------------
# public custom-vjp entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def grouped_matmul(lhs, w, tile_expert, counts, cfg):
    """lhs [M, K] @ w[tile_expert[i]] -> [M, N], rows pre-grouped so each
    tm-row tile maps to one expert.  ``cfg`` is a :class:`GmmCfg`."""
    return _gmm_call(lhs, w, tile_expert, transpose_w=False, tm=cfg.tm,
                     tc=cfg.tk, tj=cfg.tn, interpret=cfg.interpret,
                     out_dtype=cfg.out_dtype)


def _grouped_matmul_fwd(lhs, w, tile_expert, counts, cfg):
    return grouped_matmul(lhs, w, tile_expert, counts, cfg), \
        (lhs, w, tile_expert, counts)


def _grouped_matmul_bwd(cfg, res, dout):
    lhs, w, tile_expert, counts = res
    tm, tk, tn, interp = cfg[:4]
    dlhs = _gmm_call(dout, w, tile_expert, transpose_w=True, tm=tm,
                     tc=tn, tj=tk, interpret=interp)
    dw = _gmm_dw_call(lhs, dout, tile_expert, counts, w.shape[0],
                      tm=tm, tk=tk, tn=tn, interpret=interp)
    return dlhs.astype(lhs.dtype), dw.astype(w.dtype), None, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def gmm(lhs, w, tile_expert, counts, *, tm=512, interpret=False,
        out_dtype=None):
    """Convenience wrapper picking legal tile sizes for [M,K]@[E,K,N];
    the result is in ``out_dtype`` (default: the rows' dtype).

    Measured on v5e (36864×1024 @ 8×1024×704, bf16): tm=512 with the
    full K as one block beats tm=256/tk=512 by ~1.5× and beats XLA's
    dense batched einsum by ~1.36× (26.9 vs 19.8 TFLOP/s in a
    serialized scan microbench).  Small row tiles free VMEM for a
    full-K block (r5 sweep at the 64-expert shape: tm=256/tk=2048 hit
    140 TF/s vs tm=384/tk=1024's 121; tk=2048 at tm>=384 overflows
    VMEM)."""
    k, n = w.shape[1], w.shape[2]
    if _whole_matrix_fits(tm, k, n, 1, lhs.dtype, w.dtype,
                          out_dtype or lhs.dtype):
        tk, tn = k, n
    else:
        tk = _pick_tile(k, 2048 if tm <= 256 else 1024)
        tn = _pick_tile(n, 1024)
    cfg = GmmCfg(tm, tk, tn, interpret, out_dtype)
    return grouped_matmul(lhs, w, tile_expert, counts, cfg)


def gmm_reference(lhs, w, tile_expert, counts=None, *, tm=128):
    """Pure-jnp oracle: per-row expert gather then row-wise matmul."""
    row_expert = jnp.repeat(tile_expert, tm)               # [M]
    wr = w[row_expert]                                     # [M, K, N]
    return jnp.einsum("mk,mkn->mn", lhs.astype(jnp.float32),
                      wr.astype(jnp.float32)).astype(lhs.dtype)


# ---------------------------------------------------------------------------
# dropless layout: sorted-by-expert, tile-aligned
# ---------------------------------------------------------------------------

def make_dropless_plan(expert_idx, num_experts: int, tm: int):
    """From router top-k ``expert_idx`` [T, k] build the tile-aligned
    sorted layout (all static shapes, jit-safe):

    - ``order``   [T*k]  slot ids sorted by expert (stable)
    - ``dest``    [T*k]  destination row of sorted slot i in the padded
                         buffer (each expert starts at a tm boundary)
    - ``tile_expert`` [M//tm] expert owning each row tile
    - ``counts``  [E]    tokens routed to each expert
    - ``m_pad``   int    static padded row count
    """
    order, dest, _, tile_expert, counts, m_pad = \
        make_dropless_plan_rows(expert_idx.reshape(-1), num_experts, tm)
    return order, dest, tile_expert, counts, m_pad


def padded_rows(n_rows: int, num_experts: int, tm: int) -> int:
    """Rows of the sorted buffer (``m_pad``): a static bound on
    ``n_rows`` rows laid out with every expert's share padded to a
    whole ``tm``-row tile."""
    return -(-n_rows // tm) * tm + num_experts * tm


def make_dropless_plan_rows(row_expert, num_experts: int, tm: int):
    """Rows-level variant of :func:`make_dropless_plan` for pre-routed
    buffers (the EP all-to-all receive side): ``row_expert`` [M] holds
    each row's LOCAL expert id, with invalid/padding rows marked by any
    id >= ``num_experts``.  Invalid rows get an out-of-bounds ``dest``
    (scatter ``mode='drop'`` skips them).  Returns
    (order, dest, valid_sorted, tile_expert, counts, m_pad)."""
    m = row_expert.shape[0]
    key = jnp.clip(row_expert, 0, num_experts)             # E == invalid
    order = jnp.argsort(key, stable=True)
    sorted_e = key[order]
    valid_sorted = sorted_e < num_experts
    counts = jnp.bincount(key, length=num_experts + 1)[:num_experts]
    padded = ((counts + tm - 1) // tm) * tm
    pad_start = jnp.concatenate(
        [jnp.zeros(1, padded.dtype), jnp.cumsum(padded)[:-1]])
    start = jnp.concatenate(
        [jnp.zeros(1, counts.dtype), jnp.cumsum(counts)[:-1]])
    safe_e = jnp.clip(sorted_e, 0, num_experts - 1)
    rank = jnp.arange(m) - start[safe_e]
    m_pad = padded_rows(m, num_experts, tm)
    dest = jnp.where(valid_sorted, pad_start[safe_e] + rank, m_pad)
    tile_start = jnp.arange(m_pad // tm) * tm
    tile_expert = jnp.searchsorted(pad_start, tile_start,
                                   side="right") - 1
    tile_expert = jnp.clip(tile_expert, 0, num_experts - 1)
    return order, dest, valid_sorted, tile_expert, counts, m_pad


def _auto_tm(e: int, n_rows: int) -> int:
    """Measured (v5e, round 4) row-tile table.  Big tiles win until
    per-expert padding dominates: at 8 experts (qwen2 shape, F=704)
    tm=512 with full-K blocks is best (26.9 TF/s, 1.36x XLA's dense
    comparator); at 64 experts (DeepSeekMoE-16B widths) tm=256 halves
    tm=512's padding bound and runs 16.6 vs 11.2 TF/s (tm=256 with a
    full-K=2048 block: 140 TF/s vs tm=384/tk=1024's 121 and tm=512's
    80); the round-3 heuristic's tm=128 was 1.39x SLOWER than the
    dense comparator.  Tiny buffers fall back so the padding bound stays
    sane.

    Every expert's rows are padded to a whole tile, so the tile halves
    while ``e`` tiles would outnumber the rows, down to 32 (bf16 rows
    tile by 16, float32 rows by 8).  Where ``e * tm <= n_rows`` — every
    training shape above — nothing halves.  Where it does the padding
    is the buffer: 256 held experts x 11 live rows each at tm=128 made
    38.5k rows of 5.8k (my chip runs, PR 28: a serving step 49.7 ->
    42.9 ms at 32), and 64 experts serving 160 rows x top-6 made 9,216
    rows of at most 960 (PR 31: 3,008 at 32).  The floor used to be 128
    up to 64 experts, from round 4's table — but that table was
    measured at training's row counts, where the MXU binds; a buffer
    this empty is bound by the expert matrices it streams whatever the
    tile, and by the rows it reads and writes."""
    tm = 512 if e <= 16 else 256
    while tm > 32 and e * tm > n_rows:
        tm //= 2
    return tm


def gate_up(xs, wg, wu, tile_expert, counts, *, tm, interpret=False,
            act=jax.nn.silu, out_dtype=None):
    """act(xs @ wg[e]) * (xs @ wu[e]) over the sorted layout, in
    ``out_dtype`` (default: the rows' dtype).  silu-GLU goes through
    the fused two-dot kernel (one lhs stream, epilogue on the float32
    accumulators in VMEM); any other activation, or a shape it has no
    safe blocks for, keeps the two-gmm path."""
    cfg = _glu_cfg(tm, wg.shape[1], wg.shape[2], xs.dtype, wg.dtype,
                   out_dtype or xs.dtype) if act is jax.nn.silu else None
    if cfg is not None:
        return glu_grouped(xs, wg, wu, tile_expert, counts,
                           GmmCfg(*cfg, interpret, out_dtype))
    hg = gmm(xs, wg, tile_expert, counts, tm=tm, interpret=interpret,
             out_dtype=out_dtype)
    hu = gmm(xs, wu, tile_expert, counts, tm=tm, interpret=interpret,
             out_dtype=out_dtype)
    return (act(hg.astype(jnp.float32)) *
            hu.astype(jnp.float32)).astype(hg.dtype)


def dropless_moe_ffn_rows(x_rows, row_expert, wg, wu, wd, *, tm=None,
                          interpret=False, act=jax.nn.silu):
    """Per-row dropless SwiGLU expert FFN: x_rows [M, H] where row i
    belongs to LOCAL expert ``row_expert[i]`` (ids >= E mark invalid
    rows, which produce zero output).  This is the per-shard compute of
    the expert-parallel path (distributed/expert_parallel.py) — three
    grouped matmuls on the sorted tile-aligned layout, no top-k
    combine."""
    m, h = x_rows.shape
    e = wg.shape[0]
    if tm is None:
        tm = _auto_tm(e, m)
    order, dest, valid_sorted, tile_expert, counts, m_pad = \
        make_dropless_plan_rows(row_expert, e, tm)
    xs = jnp.zeros((m_pad, h), x_rows.dtype).at[dest].set(
        x_rows[order], mode="drop")

    hs = gate_up(xs, wg, wu, tile_expert, counts, tm=tm,
                 interpret=interpret, act=act)
    ys = gmm(hs, wd, tile_expert, counts, tm=tm, interpret=interpret)

    dest_safe = jnp.minimum(dest, m_pad - 1)
    y_sorted = jnp.where(valid_sorted[:, None], ys[dest_safe], 0)
    return jnp.zeros((m, h), ys.dtype).at[order].set(y_sorted)


def dropless_moe_ffn(x, gate_vals, expert_idx, wg, wu, wd, *, tm=None,
                     interpret=False, act=jax.nn.silu):
    """Full dropless MoE FFN: route x [T, H] through per-expert SwiGLU
    experts (wg/wu [E, H, F], wd [E, F, H]) with top-k combine weights
    gate_vals [T, k] — three grouped matmuls on the sorted layout.

    ``tm=None`` picks the row tile adaptively: as large as possible
    (512 is fastest on v5e) while keeping the per-expert tile padding
    under ~25% of the slot count (matters at 60+ experts)."""
    t, h = x.shape
    k = expert_idx.shape[1]
    e = wg.shape[0]
    if tm is None:
        tm = _auto_tm(e, t * k)
    order, dest, tile_expert, counts, m_pad = make_dropless_plan(
        expert_idx, e, tm)
    # scatter token rows into the padded sorted buffer (dup per slot)
    rows = x[order // k]                                   # [T*k, H]
    xs = jnp.zeros((m_pad, h), x.dtype).at[dest].set(rows)

    hs = gate_up(xs, wg, wu, tile_expert, counts, tm=tm,
                 interpret=interpret, act=act)
    ys = gmm(hs, wd, tile_expert, counts, tm=tm, interpret=interpret)

    y_slots = ys[dest]                                     # [T*k, H] sorted
    y = jnp.zeros((t * k, h), ys.dtype).at[order].set(y_slots)
    out = jnp.einsum("tk,tkh->th", gate_vals.astype(jnp.float32),
                     y.reshape(t, k, h).astype(jnp.float32))
    return out.astype(x.dtype)
