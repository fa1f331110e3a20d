"""SPMD wrapping for the Mosaic flash kernel under GSPMD meshes.

XLA cannot auto-partition a Mosaic (pallas) kernel: compiling a flash
call whose operands are sharded over mesh axes fails with "Mosaic
kernels cannot be automatically partitioned" (surfaced by the detached
v5p-64 AOT compile of the 8B plans — single-chip runs never partition,
so the gap was latent until round 5).  The TPU-native fix is the one
the error message prescribes: run the kernel inside ``shard_map`` over
the axes that shard its operands, so each shard runs the kernel on its
local block and GSPMD never sees the pallas call.

Structure: a ``custom_vjp`` whose forward and backward are EACH their
own explicit ``shard_map`` (mirroring the kernel's own _fwd/_bwd_impl
attach-grad design, including the flash_out/flash_lse checkpoint tags
for flash-aware remat).  Letting jax auto-transpose one nested
shard_map instead trips partial-manual lowering bugs in both
partitioners (shardy: "manual axes must come before free axes";
GSPMD: an unshard assertion), so the backward never transposes a
shard_map — it IS one.

Axis layout (the recipes' canonical attention sharding): batch over
the data axes (``dp``, ``sharding``), heads over tensor-parallel
(``mp``); sequence is handled elsewhere (``sep`` context parallelism
wraps its own shard_map).  Axes of size 1, axes already manual in the
caller's context (the 1F1B engine's ``pp``), and axes that don't
divide the corresponding dim are skipped; with no active axes the
wrapper degrades to a direct ``flash_attention_raw`` call, so
single-chip behavior is bit-identical.  In-kernel dropout perturbs the
seed per shard by the fused index of the active axes — identically in
forward and backward, so the regenerated PRNG bits match.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from . import ShapeNotCovered

__all__ = ["flash_attention_spmd", "flash_attention_spmd_ext",
           "active_wrap_axes"]

_BATCH_AXES = ("dp", "sharding")
_HEAD_AXES = ("mp",)


from .vma import vma_union as _manual_axes


def active_wrap_axes(mesh, q_shape, kv_heads, *arrays
                     ) -> Tuple[Tuple[str, ...], Tuple[str, ...]]:
    """(batch_axes, head_axes) the kernel should be manual over: mesh
    axes > 1, not already manual on the operands, evenly dividing the
    batch / head dims."""
    manual = _manual_axes(*arrays)
    b, _, h, _ = q_shape
    batch = []
    acc = 1
    for a in _BATCH_AXES:
        n = mesh.shape.get(a, 1)
        if n > 1 and a not in manual and b % (acc * n) == 0:
            batch.append(a)
            acc *= n
    heads = []
    for a in _HEAD_AXES:
        n = mesh.shape.get(a, 1)
        if n > 1 and a not in manual and h % n == 0 \
                and kv_heads % n == 0:
            heads.append(a)
    return tuple(batch), tuple(heads)


@dataclass(frozen=True)
class _Meta:
    mesh: object = field(hash=False, compare=False)
    axis_names: frozenset
    axes: Tuple[str, ...]            # seed-perturb order
    qkv_spec: object
    lse_spec: object
    mask_spec: object                # None when no mask
    mask_bcast: Tuple[str, ...]      # axes dmask must psum over
    causal: bool
    bq: int
    bk: int
    dropout_p: float
    mask_grad: bool

    def __hash__(self):
        # mesh deliberately excluded (matches the generated __eq__'s
        # compare=False): equal metas must hash equal even when
        # fleet.reset()/init() rebuilt an equivalent Mesh object
        return hash((self.axis_names, self.axes,
                     str(self.qkv_spec), str(self.mask_spec),
                     self.causal, self.bq, self.bk, self.dropout_p,
                     self.mask_grad))


def _ctx_mesh(meta):
    # inside an enclosing shard_map (e.g. the 1F1B engine's pp axis)
    # the nested shard_map must be built on the CONTEXT abstract mesh
    # (which carries the outer axes' Manual types)
    ctx = jax.sharding.get_abstract_mesh()
    if ctx is not None and not ctx.empty \
            and ctx.shape == meta.mesh.shape:
        return ctx
    return meta.mesh


def _perturbed(meta, seed):
    idx = jnp.int32(0)
    for a in meta.axes:
        idx = idx * lax.axis_size(a) + lax.axis_index(a)
    return seed + idx


def _fwd_shard_map(meta, q, k, v, mask, seed):
    from .flash_attention import _fwd

    has_mask = mask is not None

    def body(q_, k_, v_, *rest):
        m_ = rest[0] if has_mask else None
        s_ = _perturbed(meta, rest[-1])
        qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q_, k_, v_))
        out, lse = _fwd(qt, kt, vt, causal=meta.causal, bq=meta.bq,
                        bk=meta.bk, mask=m_, dropout_p=meta.dropout_p,
                        seed=s_)
        return jnp.swapaxes(out, 1, 2), lse

    in_specs = [meta.qkv_spec] * 3
    args = [q, k, v]
    if has_mask:
        in_specs.append(meta.mask_spec)
        args.append(mask)
    in_specs.append(P())
    args.append(seed)
    mapped = jax.shard_map(
        body, mesh=_ctx_mesh(meta), axis_names=meta.axis_names,
        in_specs=tuple(in_specs),
        out_specs=(meta.qkv_spec, meta.lse_spec), check_vma=False)
    return mapped(*args)


def _bwd_shard_map(meta, q, k, v, mask, seed, out, lse, do):
    from .flash_attention import _bwd_dmask, _bwd_impl

    has_mask = mask is not None

    def body(q_, k_, v_, out_, lse_, do_, *rest):
        m_ = rest[0] if has_mask else None
        s_ = _perturbed(meta, rest[-1])
        qt, kt, vt, ot, dot = (jnp.swapaxes(x, 1, 2)
                               for x in (q_, k_, v_, out_, do_))
        dq, dk, dv = _bwd_impl(qt, kt, vt, ot, lse_, dot,
                               causal=meta.causal, bq=meta.bq,
                               bk=meta.bk, mask=m_,
                               dropout_p=meta.dropout_p, seed=s_)
        outs = [jnp.swapaxes(dq, 1, 2), jnp.swapaxes(dk, 1, 2),
                jnp.swapaxes(dv, 1, 2)]
        if meta.mask_grad:
            dm = _bwd_dmask(qt, kt, vt, ot, lse_, dot, m_,
                            causal=meta.causal, bq=meta.bq, bk=meta.bk,
                            dropout_p=meta.dropout_p, seed=s_)
            if meta.mask_bcast:
                # mask broadcast over sharded dims: partial sums
                dm = lax.psum(dm, meta.mask_bcast)
            outs.append(dm)
        return tuple(outs)

    in_specs = [meta.qkv_spec] * 3 + [meta.qkv_spec, meta.lse_spec,
                                      meta.qkv_spec]
    args = [q, k, v, out, lse, do]
    if has_mask:
        in_specs.append(meta.mask_spec)
        args.append(mask)
    in_specs.append(P())
    args.append(seed)
    out_specs = [meta.qkv_spec] * 3
    if meta.mask_grad:
        out_specs.append(meta.mask_spec)
    mapped = jax.shard_map(
        body, mesh=_ctx_mesh(meta), axis_names=meta.axis_names,
        in_specs=tuple(in_specs), out_specs=tuple(out_specs),
        check_vma=False)
    return mapped(*args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _spmd_attach(meta, q, k, v, mask, seed, out, lse):
    return out


def _spmd_attach_fwd(meta, q, k, v, mask, seed, out, lse):
    return out, (q, k, v, mask, seed, out, lse)


def _spmd_attach_bwd(meta, res, do):
    q, k, v, mask, seed, out, lse = res
    grads = _bwd_shard_map(meta, q, k, v, mask, seed, out, lse, do)
    dq, dk, dv = grads[:3]
    dmask = grads[3] if meta.mask_grad else None
    return dq, dk, dv, dmask, None, None, None


_spmd_attach.defvjp(_spmd_attach_fwd, _spmd_attach_bwd)


def flash_attention_spmd(q, k, v, causal=False, mask=None,
                         dropout_p: float = 0.0, seed=None,
                         mask_grad: bool = False):
    """flash_attention_raw ([B, S, H, D] layout) made safe under GSPMD
    meshes — see module docstring.  Raises NotImplementedError exactly
    where the raw kernel would (per-shard shapes), so callers'
    jnp-fallback handling is unchanged."""
    from ...distributed.auto_parallel import get_mesh
    from .flash_attention import _tag, flash_attention_raw

    pm = get_mesh()
    mesh = pm.mesh if pm is not None else None
    if mesh is not None:
        batch_axes, head_axes = active_wrap_axes(
            mesh, q.shape, k.shape[2], q, k, v)
    else:
        batch_axes = head_axes = ()
    axes = batch_axes + head_axes
    free_axes = (frozenset(mesh.shape) - _manual_axes(q, k, v)
                 if mesh is not None else frozenset())
    if not axes and not free_axes:
        # no mesh, or every axis already manual in the caller's
        # context: pallas lowers directly
        return flash_attention_raw(q, k, v, causal=causal, mask=mask,
                                   dropout_p=dropout_p, seed=seed,
                                   mask_grad=mask_grad)

    b, sq, h, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    nh = int(np.prod([mesh.shape[a] for a in head_axes], dtype=np.int64))
    # the kernel's shared shape gate, on per-shard LOCAL shapes
    from .flash_attention import check_eligibility
    bq, bk = check_eligibility(sq, sk, h // nh, hk // nh, d,
                               causal=causal, dropout_p=dropout_p,
                               mask_grad=mask_grad)

    bspec = tuple(batch_axes) if batch_axes else None
    hspec = tuple(head_axes) if head_axes else None
    qkv_spec = P(bspec, None, hspec, None)
    lse_spec = P(bspec, hspec, None, None)

    mask_spec = None
    mask_bcast: Tuple[str, ...] = ()
    if mask is not None:
        mask = jnp.asarray(mask.value if hasattr(mask, "value")
                           else mask)
        while mask.ndim < 4:
            mask = mask[None]
        mb, mh, msq, msk = mask.shape
        if (msk != sk or mb not in (1, b) or mh not in (1, h)
                or msq not in (1, sq)):
            raise ShapeNotCovered(
                f"flash mask shape {mask.shape} not broadcastable to "
                f"[{b},{h},{sq},{sk}]")
        if mask_grad and msq != sq:
            raise ShapeNotCovered(
                "trainable bias needs full Sq (no query broadcast)")
        mask_spec = P(bspec if mb > 1 else None,
                      hspec if mh > 1 else None, None, None)
        mask_bcast = tuple(
            (batch_axes if mb == 1 else ())
            + (head_axes if mh == 1 else ()))

    if seed is None:
        seed = jnp.zeros((), jnp.int32)
    seed = jnp.asarray(seed, jnp.int32)

    manual = _manual_axes(q, k, v)
    # pallas_call refuses to lower while ANY mesh axis is still Auto —
    # claim every non-manual axis (size-1 ones are free; specs only
    # reference the really-sharded ones)
    axis_names = frozenset(a for a in mesh.shape if a not in manual)

    meta = _Meta(mesh=mesh, axis_names=axis_names, axes=axes,
                 qkv_spec=qkv_spec, lse_spec=lse_spec,
                 mask_spec=mask_spec, mask_bcast=mask_bcast,
                 causal=causal, bq=bq, bk=bk,
                 dropout_p=float(dropout_p), mask_grad=bool(mask_grad))

    sg = lax.stop_gradient
    out, lse = _fwd_shard_map(
        meta, sg(q), sg(k), sg(v),
        sg(mask) if mask is not None else None, sg(seed))
    out, lse = _tag(out, lse)
    return _spmd_attach(meta, q, k, v, mask, seed, out, lse)


def flash_attention_spmd_ext(q, k, v, mask, seed, *, causal=False,
                             dropout_p=0.0, mask_grad=False):
    """apply_op-friendly positional variant (mask and seed traced)."""
    return flash_attention_spmd(q, k, v, causal=causal, mask=mask,
                                dropout_p=dropout_p, seed=seed,
                                mask_grad=mask_grad)
