"""Expert-parallel dropless MoE: ragged all-to-all token exchange +
per-shard Pallas grouped matmul.

Reference parity: the reference runs its fused MoE kernels and the EP
all-to-all *together* — incubate moe_layer's alltoall dispatch feeding
the phi/kernels/fusion grouped expert GEMMs (SURVEY.md §2.3 EP row).
Round-3 of this build had the two halves separately; round-4 composed
them with a capacity-PADDED ``lax.all_to_all`` (each peer chunk padded
to a fixed per-peer capacity, overflow beyond it silently dropped).

Round-5 design (this file): the exchange is **ragged** —
``jax.lax.ragged_all_to_all`` moves exactly the routed rows, no padded
payload.  Per shard:

1. route local tokens (router weights replicated; the aux loss is
   reassembled EXACTLY from fold-``pmean``'d per-shard means, so it
   equals the dense path's global aux),
2. sort the (token, expert) slots by owner shard — the sorted rows ARE
   the send buffer (no per-peer padding slots),
3. all-gather the tiny per-peer count vector into the global count
   matrix ``C`` (n² ints over ICI), from which every shard derives the
   same exchange plan: send offsets/sizes, each chunk's landing offset
   in its receiver's buffer, and — when a receive bound ``R`` is set —
   the clamped matrix ``C_eff`` (sender-order prefix of each receiver
   column),
4. exchange rows + expert ids with ``ragged_all_to_all`` (rides ICI;
   payload = actual routed rows, not capacity padding),
5. run the dropless grouped-matmul SwiGLU on the received rows against
   the LOCAL expert shard (ops/pallas/grouped_matmul.py
   ``dropless_moe_ffn_rows``; Megatron row-parallel ``psum`` over
   ``mp`` when the FFN dim is tensor-sharded),
6. reverse-exchange the rows (transposed plan, landing back at each
   sender's unclamped chunk starts — undelivered slots stay zero and
   contribute nothing to the combine), and combine with the local
   top-k gates.

Capacity semantics (better than round-4's): ``capacity_factor`` bounds
each shard's TOTAL receive buffer at ``factor * s`` rows (``s`` = local
slots), not each per-peer chunk — drops happen only when a shard's
total routed load exceeds ``factor``× balanced, never because one
peer's chunk is skewed.  ``capacity_factor=None`` sizes the buffer at
the full global slot count: **zero drops at any router skew** (XLA
shapes are static, so strict droplessness must still allocate the
worst case — but the ragged exchange only ever MOVES the actual rows,
and the drop count is exact and observable either way; see
``return_drops`` and ``FLAGS_moe_log_drops``).

XLA:CPU has no ragged-all-to-all thunk (verified: "HLO opcode
`ragged-all-to-all` is not supported by XLA:CPU ThunkEmitter"), so on
CPU meshes (the 8-virtual-device test/dryrun platform) the SAME plan
drives a gather-based emulation with identical semantics; the real
primitive lowers on TPU.  ``tests/test_moe.py`` additionally checks
the plan algebra against a numpy model of the primitive's documented
contract, so the TPU path's offsets are covered without multi-chip
hardware.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

__all__ = ["moe_grouped_ep_raw", "expert_fold_axes",
           "ep_grouped_compatible", "EP_FOLD", "exchange_plan"]

# single source of the expert-dim fold order (this module loads lazily
# from MoELayer.forward, after nn.moe is fully imported)
from ..nn.moe import EP_AXES as EP_FOLD  # noqa: E402


def expert_fold_axes(mesh) -> Tuple[str, ...]:
    """Mesh axes (>1) the expert dim folds over, in fold order."""
    return tuple(a for a in EP_FOLD if mesh.shape.get(a, 1) > 1)


def ep_grouped_compatible(mesh, num_experts: int,
                          num_tokens: int) -> bool:
    """True when the grouped EP path can run: an active expert fold
    whose size divides both the expert count and the token count.  The
    ONE divisibility predicate shared by MoELayer._resolve_dispatch and
    the dryrun's forced-mode gate."""
    fold = expert_fold_axes(mesh)
    if not fold:
        return False
    n = int(np.prod([mesh.shape[a] for a in fold]))
    return n > 1 and num_experts % n == 0 and num_tokens % n == 0


def _fused_index(fold: Tuple[str, ...]):
    """Row-major linear index over the fold axes — matches both the
    PartitionSpec fold ordering and tuple-axis collectives.  The ONE
    source of the fused shard index (plan rows/columns and the
    emulation's buffer selection must agree on it)."""
    me = jnp.int32(0)
    for a in fold:
        me = me * lax.axis_size(a) + lax.axis_index(a)
    return me


# ---------------------------------------------------------------------------
# Exchange plan: every shard derives the SAME plan from the global count
# matrix, so sender-side and receiver-side views always agree.
# ---------------------------------------------------------------------------

def exchange_plan(C, R: int):
    """From the global count matrix ``C`` ([n, n] int32, ``C[j, i]`` =
    rows shard j routes to shard i) and the receive bound ``R``, derive
    the clamped matrix ``C_eff`` (each receiver column keeps the
    sender-order prefix that fits in ``R``) and both directions' offset
    vectors, as functions of the caller's shard index ``me``:

    forward (tokens -> expert shards), for shard ``me``:
      - ``in_off[i]``   start of peer i's chunk in my sorted send rows
                        (UNCLAMPED cumsum — that is where the rows sit)
      - ``send_sz[i]``  rows actually delivered to peer i (clamped)
      - ``out_off[i]``  where my chunk starts in peer i's buffer
                        (= sum of earlier senders' delivered rows)
      - ``recv_sz[j]``  rows I receive from peer j

    reverse (processed rows -> back to their senders) is the transpose:
    chunk starts on the return side are the UNCLAMPED ``in_off`` of the
    original sender, so undelivered slots stay at the buffer fill.
    """
    n = C.shape[0]
    C = C.astype(jnp.int32)
    # receiver-column prefix clamp: sender j's chunk for receiver i is
    # cut to what fits after senders < j
    recv_cum = jnp.cumsum(C, axis=0) - C            # [n, n] excl. over j
    C_eff = jnp.clip(jnp.int32(R) - recv_cum, 0, C)
    send_start = jnp.cumsum(C, axis=1) - C          # [n, n] excl. over i
    out_start = jnp.cumsum(C_eff, axis=0) - C_eff   # [n, n] excl. over j
    return C_eff, send_start, out_start


def _ragged_a2a(operand, out_buf, in_off, send_sz, out_off, recv_sz,
                fold, use_primitive: bool):
    """One ragged exchange.  ``use_primitive`` lowers to the XLA
    ragged-all-to-all (TPU); otherwise an all-gather + gather emulation
    with identical semantics runs (XLA:CPU lacks the thunk).  Chunks
    may be non-contiguous in ``out_buf`` (reverse direction lands at
    unclamped starts); positions no chunk covers keep ``out_buf``'s
    fill values."""
    if use_primitive:
        return lax.ragged_all_to_all(
            operand, out_buf, in_off.astype(jnp.int32),
            send_sz.astype(jnp.int32), out_off.astype(jnp.int32),
            recv_sz.astype(jnp.int32), axis_name=fold)
    g_op = lax.all_gather(operand, fold)            # [n, S, ...]
    g_in = lax.all_gather(in_off, fold)             # [n, n]
    g_out = lax.all_gather(out_off, fold)           # [n, n]
    g_send = lax.all_gather(send_sz, fold)          # [n, n]
    # my column index == my fused index (row-major over fold — the same
    # ordering tuple-axis all_gather concatenates in)
    idx = _fused_index(fold)
    # receiver view of sender j's chunk for me: starts at g_out[j, idx]
    # locally, at g_in[j, idx] in j's buffer, size g_send[j, idx]
    starts = g_out[:, idx]                          # [n] chunk starts here
    sizes_ = g_send[:, idx]                         # [n] chunk sizes
    srcs = g_in[:, idx]                             # [n] starts at sender
    r = jnp.arange(out_buf.shape[0])
    # last chunk starting at or before r (zero-size chunks share starts
    # with their successor; 'right' picks the covering one)
    j_of_r = jnp.searchsorted(starts, r, side="right") - 1
    j_of_r = jnp.clip(j_of_r, 0, starts.shape[0] - 1)
    within = r - starts[j_of_r]
    valid = (within >= 0) & (within < sizes_[j_of_r])
    src_row = jnp.clip(srcs[j_of_r] + within, 0, operand.shape[0] - 1)
    picked = g_op[j_of_r, src_row]
    mask = valid.reshape((-1,) + (1,) * (operand.ndim - 1))
    return jnp.where(mask, picked, out_buf)


def _ep_local(x, router_w, wg, wu, wd, *, fold, sizes, k, balance_coef,
              z_coef, norm_topk, tm, interpret, recv_rows, use_mp,
              use_primitive):
    """Per-shard body (manual over ``fold`` + optionally ``mp``).
    x [T_l, H] local tokens; wg/wu [E_l, H, F(/mp)], wd [E_l, F(/mp), H]
    local experts.  Returns (out [T_l, H], aux scalar, dropped rows)."""
    from ..nn.moe import _assemble_aux, _router_parts
    from ..ops.pallas.grouped_matmul import dropless_moe_ffn_rows

    n = int(np.prod(sizes))
    e_l = wg.shape[0]
    t_l, h = x.shape
    me = _fused_index(fold)

    gate_vals, expert_idx, density, proxy, zsq = _router_parts(
        x, router_w, k=k, norm_topk=norm_topk)
    # exact global aux: per-shard token means pmean'd over the fold
    density = lax.pmean(density, fold)
    proxy = lax.pmean(proxy, fold)
    zsq = lax.pmean(zsq, fold)
    aux = _assemble_aux(density, proxy, zsq, balance_coef=balance_coef,
                        z_coef=z_coef)

    s = t_l * k
    flat_e = expert_idx.reshape(s)
    dshard = flat_e // e_l                              # owner shard
    order = jnp.argsort(dshard, stable=True)
    counts = jnp.bincount(dshard, length=n)

    # the sorted rows ARE the send buffer — no per-peer padding slots
    rows = x[order // k]                                # [s, H]
    ids = flat_e[order]                                 # [s]

    C = lax.all_gather(counts, fold)                    # [n, n]
    C_eff, send_start, out_start = exchange_plan(C, recv_rows)
    in_off = send_start[me]
    send_sz = C_eff[me]
    out_off = out_start[me]
    recv_sz = C_eff[:, me]

    recv_x = _ragged_a2a(rows, jnp.zeros((recv_rows, h), x.dtype),
                         in_off, send_sz, out_off, recv_sz, fold,
                         use_primitive)
    recv_e = _ragged_a2a(ids, jnp.full((recv_rows,), -1, ids.dtype),
                         in_off, send_sz, out_off, recv_sz, fold,
                         use_primitive)

    # ids < 0 mark empty buffer rows -> local id e_l (zero output)
    loc_e = jnp.where(recv_e >= 0, recv_e - me * e_l, e_l)
    y = dropless_moe_ffn_rows(recv_x, loc_e, wg, wu, wd, tm=tm,
                              interpret=interpret)
    if use_mp:
        y = lax.psum(y, "mp")                           # row-parallel F

    # reverse exchange: transposed plan; undelivered slots stay zero
    y_back = _ragged_a2a(y, jnp.zeros((s, h), y.dtype),
                         out_start[:, me], C_eff[:, me],
                         send_start[:, me], C_eff[me], fold,
                         use_primitive)
    y_flat = jnp.zeros((s, h), y_back.dtype).at[order].set(y_back)
    out = jnp.einsum("tk,tkh->th", gate_vals,
                     y_flat.reshape(t_l, k, h).astype(jnp.float32))
    dropped = jnp.sum(C) - jnp.sum(C_eff)               # exact, global
    return out.astype(x.dtype), aux, dropped


@functools.lru_cache(maxsize=64)
def _mapped_ep(mesh, fold, use_mp, k, balance_coef, z_coef, norm_topk,
               tm, interpret, recv_rows):
    sizes = tuple(mesh.shape[a] for a in fold)
    use_primitive = mesh.devices.flat[0].platform == "tpu"
    body = functools.partial(
        _ep_local, fold=fold, sizes=sizes, k=k,
        balance_coef=balance_coef, z_coef=z_coef, norm_topk=norm_topk,
        tm=tm, interpret=interpret, recv_rows=recv_rows, use_mp=use_mp,
        use_primitive=use_primitive)
    mp = "mp" if use_mp else None
    x_spec = P(fold, None)
    specs = (x_spec, P(None, None), P(fold, None, mp),
             P(fold, None, mp), P(fold, mp, None))
    mapped = jax.shard_map(
        body, mesh=mesh, axis_names=frozenset(fold) | (
            {"mp"} if use_mp else set()),
        in_specs=specs, out_specs=(x_spec, P(), P()), check_vma=False)
    # partial-manual shard_map only lowers under jit; the jit wrapper
    # inlines under an outer jit and caches the eager compile
    return jax.jit(mapped)


def moe_grouped_ep_raw(x, router_w, wg, wu, wd, *, k, balance_coef,
                       z_coef, norm_topk, tm, interpret, mesh,
                       capacity_factor: Optional[float] = 2.0,
                       return_drops: bool = False):
    """Grouped MoE over GLOBAL arrays: x [T, H], router_w [H, E],
    wg/wu [E, H, F], wd [E, F, H] -> (out [T, H], aux[, dropped]).

    ``capacity_factor`` bounds each shard's TOTAL receive buffer at
    ``factor * s`` rows (s = local slots = T/n * k); drops happen only
    when a shard's whole routed load exceeds that — never from one
    skewed peer chunk.  ``None`` sizes the buffer at the global slot
    count: strictly dropless at any skew.  Either way the exchange
    payload is ragged (actual rows only) and ``dropped`` (returned when
    ``return_drops``; also see ``FLAGS_moe_log_drops``) counts exactly
    the rows the bound cut.

    Callers must pre-check :func:`ep_grouped_compatible` (MoELayer's
    dispatch resolution does); the NotImplementedErrors below are the
    backstop for direct raw-level misuse.
    """
    fold = expert_fold_axes(mesh)
    if not fold:
        raise NotImplementedError("no expert-parallel fold axis > 1")
    n = int(np.prod([mesh.shape[a] for a in fold]))
    t, _ = x.shape
    e = wg.shape[0]
    if e % n:
        raise NotImplementedError(f"{e} experts not divisible by "
                                  f"expert fold {n}")
    if t % n:
        raise NotImplementedError(f"{t} tokens not divisible by "
                                  f"expert fold {n}")
    mp = mesh.shape.get("mp", 1)
    f_dim = wg.shape[2]
    use_mp = mp > 1 and f_dim % mp == 0
    t_l = t // n
    s = t_l * k
    if capacity_factor is None:
        recv_rows = n * s                               # dropless
    else:
        recv_rows = min(n * s, max(8, int(math.ceil(
            capacity_factor * s))))
    fn = _mapped_ep(mesh, fold, use_mp, k, float(balance_coef),
                    float(z_coef), bool(norm_topk), tm, bool(interpret),
                    int(recv_rows))
    out, aux, dropped = fn(x, router_w, wg, wu, wd)
    if return_drops:
        return out, aux, dropped
    return out, aux
