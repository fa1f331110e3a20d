"""Pipeline parallelism.

Reference parity: fleet/meta_parallel/parallel_layers/pp_layers.py
(LayerDesc, SharedLayerDesc, PipelineLayer — layer-list segmentation) and
fleet/meta_parallel/pipeline_parallel.py (PipelineParallel.train_batch:
python 1F1B microbatch loop over NCCL p2p, SURVEY.md §3.3).

TPU-native design: the reference's python-level schedule loop becomes ONE
compiled SPMD program — ``gpipe_spmd`` runs a GPipe-style circulating
pipeline inside ``jax.shard_map`` manual over ONLY the ``pp`` mesh axis
(dp/sharding/mp stay auto, so GSPMD still lays out data/tensor/FSDP
parallelism inside each stage).  Stage params are stacked on a leading
axis sharded over ``pp``; activations rotate between stages with
``lax.ppermute`` over ICI.

Two backward strategies:

* ``pipeline_train_1f1b`` (training default, n_virtual==1): a TRUE
  1F1B schedule — ONE fused loop interleaves each microbatch's
  backward with the forwards (B_s(m) fires at tick m + 2S-1-s, F_s(m)
  at m + s), holding stage inputs in a ring buffer of 2S slots.  Peak
  live activation memory is bounded by the in-flight microbatch count
  (∝ pp), NOT by n_micro — the reference 1F1B's memory bound
  (fleet PipelineParallel.train_batch), delivered as a jax.custom_vjp
  whose backward replays nothing: grads are accumulated inside the
  same loop via per-tick jax.vjp at the saved stage inputs.
* ``gpipe_spmd`` + jax.grad (eval / interleaved v>1): backward derived
  by AD through the loop (all-forward-then-all-backward), with stage
  remat; residual memory ∝ n_micro.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..common.errors import enforce
from ..nn.layer import Layer
from ..nn.container import LayerList

__all__ = ["LayerDesc", "SharedLayerDesc", "PipelineLayer", "gpipe_spmd",
           "pipeline_train_1f1b"]


# ---------------------------------------------------------------------------
# The compiled SPMD pipeline engine
# ---------------------------------------------------------------------------

def _pvary(x, axis):
    # no-op when already varying over this axis (pcast rejects that);
    # any OTHER ValueError (bad axis name etc.) must surface here, not
    # as an opaque vma mismatch deep in the scan
    aval = jax.typeof(x)
    if axis in getattr(aval, "vma", ()):
        return x
    return jax.lax.pcast(x, (axis,), to="varying")


def _mesh_platform(mesh) -> str:
    try:
        return list(mesh.devices.flat)[0].platform
    except Exception:
        return "cpu"


@functools.lru_cache(maxsize=64)
def _jitted_pipeline(stage_fn: Callable, mesh, pp_axis: str,
                     n_params: int, n_extra: int, remat: bool,
                     n_virtual: int, tail_fn: Optional[Callable] = None,
                     n_tail_params: int = 0, n_tail_idx: int = 0,
                     tail_cond: Optional[bool] = None):
    """Build + cache the jitted shard_map engine (keyed on a *stable*
    stage_fn object so eager loops don't re-trace every step).

    Schedule: circulating pipeline.  With ``n_virtual == 1`` this is
    GPipe (each device owns one contiguous chunk; microbatch m enters
    stage 0 at tick m).  With ``n_virtual = v > 1`` it is the
    interleaved / virtual-stage schedule (Megatron "virtual pipeline"):
    device d owns chunks d, d+S, …, d+(v-1)·S and microbatches cycle the
    ring v times in rounds of S, shrinking the fill bubble from
    (S-1)·T_stage to (S-1)·T_stage/v.

    Output contract — two modes:

    * no ``tail_fn``: each device returns its own [n_micro, …] buffer
      (only the last stage's is meaningful) with out_specs sharded over
      ``pp_axis`` — the caller slices the last stage's shard.
    * ``tail_fn`` (the training path): the loss head runs *inside* the
      pipeline on each completed microbatch (the reference computes the
      loss on the last stage — fleet PipelineParallel ``_loss_fn``) and
      only the accumulated scalars are psum'd over pp.  This removes
      the round-1 zero-fill + psum of the full [n_micro, batch, …]
      activation buffer AND never materializes whole-batch logits.
    """
    fn = jax.checkpoint(stage_fn) if remat else stage_fn
    tfn = (jax.checkpoint(tail_fn) if (remat and tail_fn is not None)
           else tail_fn)
    # cond-guard the loss tail on TPU; XLA:CPU keeps the masked path
    # (grad-of-cond-in-scan aborts there, jax 0.9).  Callers that never
    # differentiate through the loop (the 1F1B primal) force it on.
    if tail_cond is None:
        tail_cond = _mesh_platform(mesh) == "tpu"

    def inner(params_local, xm, *rest):
        extra_local = rest[:n_extra]
        tail_local = rest[n_extra:n_extra + n_tail_params]
        tail_idx = rest[n_extra + n_tail_params:]
        # local slab: [1, v, per_chunk, ...] -> [v, per_chunk, ...]
        locals_ = [p[0] for p in params_local]
        n_micro = xm.shape[0]
        stage = jax.lax.axis_index(pp_axis)
        nstage = jax.lax.axis_size(pp_axis)
        v = n_virtual
        rounds = -(-n_micro // nstage) if v > 1 else 1
        total = (rounds * v * nstage + nstage - 1) if v > 1 \
            else (n_micro + nstage - 1)
        carry = _pvary(jnp.zeros(xm.shape[1:], xm.dtype), pp_axis)
        xmv = _pvary(xm, pp_axis)   # feed index is stage-dependent
        if tfn is None:
            acc0 = _pvary(jnp.zeros(xm.shape, xm.dtype), pp_axis)
        else:
            shapes = jax.eval_shape(
                tail_fn, tail_local, xm[0], *(ti[0] for ti in tail_idx))
            acc0 = jax.tree_util.tree_map(
                lambda s: _pvary(jnp.zeros(s.shape, s.dtype), pp_axis),
                shapes)

        def step(t, state):
            carry, acc = state
            u = t - stage                     # device-local schedule tick
            if v > 1:
                uc = jnp.clip(u, 0, rounds * v * nstage - 1)
                r, uu = uc // (v * nstage), uc % (v * nstage)
                lap = uu // nstage
                m = r * nstage + uu % nstage  # microbatch index
            else:
                lap = jnp.zeros((), u.dtype)
                m = jnp.clip(u, 0, n_micro - 1)
            mc = jnp.minimum(m, n_micro - 1)
            feed = xmv[mc]
            inp = jnp.where((stage == 0) & (lap == 0), feed, carry)
            chunk = [jax.lax.dynamic_index_in_dim(p, lap, 0, False)
                     for p in locals_]
            y = fn(chunk, inp, *extra_local)
            keep = ((stage == nstage - 1) & (u >= 0) & (m < n_micro)
                    & (lap == v - 1))
            if tfn is None:
                acc = jax.lax.dynamic_update_index_in_dim(
                    acc, jnp.where(keep, y, acc[mc]), mc, 0)
            elif tail_cond:
                # TPU path: lax.cond skips the dead tail evaluations
                # (norm + lm-head matmul over the full vocab!) on every
                # stage/tick where keep is False — the round-2 "loss
                # tail runs on every stage every tick" waste
                tout = jax.lax.cond(
                    keep,
                    lambda: jax.tree_util.tree_map(
                        lambda o: _pvary(o, pp_axis),
                        tfn(tail_local, y, *(ti[mc] for ti in
                                             tail_idx))),
                    lambda: jax.tree_util.tree_map(
                        lambda a: jnp.zeros_like(a), acc))
                acc = jax.tree_util.tree_map(lambda a, o: a + o, acc,
                                             tout)
            else:
                # XLA:CPU fallback: the tail runs every tick on every
                # stage and is masked (SPMD lockstep) — grad-of-cond
                # inside scan inside shard_map aborts XLA:CPU (jax 0.9)
                tout = tfn(tail_local, y, *(ti[mc] for ti in tail_idx))
                acc = jax.tree_util.tree_map(
                    lambda a, o: a + jnp.where(keep, o, jnp.zeros_like(o)),
                    acc, tout)
            nxt = jax.lax.ppermute(
                y, pp_axis, [(i, (i + 1) % nstage) for i in range(nstage)])
            return nxt, acc

        carry, acc = jax.lax.fori_loop(0, total, step, (carry, acc0))
        if tfn is None:
            return acc[None]                 # [1, n_micro, ...] per stage
        # scalars (loss sums/counts): psum over pp is O(1) traffic
        return jax.tree_util.tree_map(
            lambda a: jax.lax.psum(a, pp_axis), acc)

    in_specs = (tuple(P(pp_axis) for _ in range(n_params)), P(),
                *(P() for _ in range(n_extra + n_tail_params + n_tail_idx)))
    out_specs = P() if tail_fn is not None else P(pp_axis)
    mapped = jax.shard_map(inner, mesh=mesh, axis_names={pp_axis},
                           in_specs=in_specs, out_specs=out_specs)
    # jit wrapper: eager evaluation of checkpoint/scan inside shard_map is
    # unsupported; under an outer jit this inlines
    return jax.jit(mapped)


def gpipe_spmd(params: Sequence[jax.Array], x_micro: jax.Array,
               stage_fn: Callable, *extra,
               mesh, pp_axis: str = "pp", remat: bool = True,
               n_virtual: int = 1, tail_fn: Optional[Callable] = None,
               tail_params: Sequence[jax.Array] = (),
               tail_indexed: Sequence[jax.Array] = (),
               tail_cond: Optional[bool] = None):
    """Run ``stage_fn`` as a circulating SPMD pipeline.

    params:   v==1: arrays stacked [n_chunks, per, ...]; v>1: the
              interleaved [S, v, per, ...] device-major layout (chunk
              l*S+d at [d, l] — device d's lap-l virtual stage), so
              pp shards dim 0 with no cross-shard relayout.
    x_micro:  [n_micro, micro_batch, ...] input microbatches (replicated
              over pp; may be sharded over data axes).
    stage_fn: (local_params_list, h, *extra) -> h, applied by every
              stage.  Pass a STABLE callable (module-level or cached) —
              the compiled engine is cached keyed on it.
    extra:    broadcast side inputs (e.g. rope tables), replicated.
    n_virtual: virtual stages per device (interleaved schedule).
    tail_fn:  optional (tail_params, y, *per_micro) -> pytree of arrays;
              runs on each completed microbatch at the last stage (loss
              head); results are summed over microbatches.  Must be a
              STABLE callable, like stage_fn.
    tail_params: side parameters for tail_fn (e.g. final norm + lm head
              weights), replicated over pp (mp/dp shardings still apply).
    tail_indexed: arrays with a leading [n_micro] dim, indexed per
              microbatch and passed to tail_fn (e.g. labels).

    Returns [n_micro, micro_batch, ...] outputs of the final stage, or
    the summed tail pytree when ``tail_fn`` is given.
    """
    nstage = mesh.shape[pp_axis]
    n_chunks = params[0].shape[0] if n_virtual == 1 \
        else params[0].shape[0] * params[0].shape[1]
    enforce(n_chunks == nstage * n_virtual,
            f"stacked chunk dims {tuple(params[0].shape)} != mesh "
            f"'{pp_axis}' size {nstage} * n_virtual {n_virtual}")
    # interleaved placement: stacks arrive ALREADY [S, v, per, ...]
    # (device-major storage — see models' pipe classes): dim 0 shards
    # over pp, dim 1 indexes the device's laps.  A global-chunk-order
    # [v*S, ...] layout would need a cross-shard relayout here (SPMD
    # involuntary full rematerialization of every stack, every step).
    # v==1 gains a singleton lap dim (free — dim 0 stays sharded) so
    # the engine slab is uniformly [S, v, per, ...].
    if n_virtual > 1:
        for p in params:
            enforce(p.shape[0] == nstage and p.shape[1] == n_virtual,
                    f"interleaved stacks must be [S={nstage}, "
                    f"v={n_virtual}, per, ...]; got {p.shape}")
        stacked = list(params)
    else:
        stacked = [p[:, None] for p in params]
    fn = _jitted_pipeline(stage_fn, mesh, pp_axis, len(params),
                          len(extra), remat, n_virtual, tail_fn,
                          len(tail_params), len(tail_indexed),
                          tail_cond)
    out = fn(tuple(stacked), x_micro, *extra, *tail_params, *tail_indexed)
    if tail_fn is not None:
        return out
    return out[nstage - 1]                   # last stage's buffer


# ---------------------------------------------------------------------------
# 1F1B: fused forward+backward schedule (training path, n_virtual == 1)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _jitted_1f1b(stage_fn: Callable, tail_fn: Callable, mesh,
                 pp_axis: str, n_params: int, n_extra: int,
                 n_tail_params: int, n_tail_idx: int,
                 stash: bool = False, n_virtual: int = 1):
    """The fused 1F1B loop (fleet PipelineParallel.train_batch's
    schedule, compiled): at tick t, stage s runs forward on microbatch
    ``t - s`` and backward on microbatch ``t - (2S-1) + s``.  Stage
    inputs wait in a ring buffer of 2S slots (max in-flight is 2S-1 at
    stage 0), so peak activation memory is ∝ S in-flight microbatches
    — independent of n_micro.  Gradients come from per-tick jax.vjp at
    the saved inputs (no AD through the loop, so lax.cond may skip
    inactive ramp ticks and the per-stage branch on every backend).

    ``n_virtual = v > 1`` is the INTERLEAVED 1F1B (Megatron virtual
    pipeline, fleet's interleaved schedule): device d owns chunks
    d, d+S, …, d+(v-1)S; microbatches run in rounds of S per lap.
    Forward of chunk c = lap·S + d on microbatch m = r·S + j fires at
    tick t = r·vS + lap·S + j + d; backward mirrors it with delay
    D = vS at t = D + r·vS + (v-1-lap)·S + j + (S-1-d) — the mirror
    keeps every producer exactly one tick ahead of its consumer
    (chain gap 1 at the loss chunk, ring gap < 2vS everywhere, both
    provable from the algebra), so the ring needs 2vS CHUNK slots —
    each 1/v of a stage, i.e. the same total bytes as v=1's 2S stage
    slots: memory stays ∝ pp.  Fill+drain bubble shrinks from 2S-1
    stage-units (v=1) to S + (S-1)/v.

    ``stash=False`` (remat schedule): the ring holds stage INPUTS and
    every backward tick re-runs the stage forward inside jax.vjp —
    minimal memory (2S input slots), ~1 extra forward of FLOPs per
    microbatch.  ``stash=True`` (the reference 1F1B's memory/compute
    point — fleet PipelineParallel saves in-flight activations): the
    forward tick runs jax.vjp and the ring holds the VJP RESIDUALS
    (weight leaves are filtered by tracer identity and re-injected at
    backward, so parameters are never duplicated per slot); backward
    ticks apply the saved vjp — no recompute (measured 1.26x faster
    per microbatch-stage on v5e).  With ``n_virtual > 1`` the capture
    and rebuild run as ``lax.switch`` over per-lap STATIC chunk
    slices, so identity filtering still holds per branch.  Residual
    size per slot is whatever ``stage_fn``'s own checkpoint policy
    leaves saveable, so model-level recompute flags still control the
    memory/FLOPs trade inside a stage.  Rings are 2vS chunk slots —
    memory stays ∝ pp either way.

    Returns (loss_sum, count, grads_stacked, dxm, grads_tail) with the
    grads UNSCALED (cotangent 1.0 on loss_sum); the custom_vjp wrapper
    scales by the incoming cotangent and 1/count.
    """
    nstage = mesh.shape[pp_axis]
    # XLA:CPU aborts on lax.cond inside a loop inside shard_map (jax
    # 0.9) — fall back to computing both branches + select there; TPU
    # gets real conds (ramp ticks and the last-stage branch cost ~0)
    use_cond = _mesh_platform(mesh) == "tpu"

    def _branch(pred, true_fn, false_fn, operand):
        if use_cond:
            return jax.lax.cond(pred, true_fn, false_fn, operand)
        t = true_fn(operand)
        f = false_fn(operand)
        return jax.tree_util.tree_map(
            lambda a, b: jnp.where(pred, a, b), t, f)

    v = n_virtual

    def inner(params_local, xm, *rest):
        extra = rest[:n_extra]
        tail_params = rest[n_extra:n_extra + n_tail_params]
        tail_idx = rest[n_extra + n_tail_params:]
        # v==1: local slab [1, per, ...] -> [per, ...]
        # v>1:  local slab [1, v, per, ...] -> [v, per, ...] (lap dim)
        locals_ = [p[0] for p in params_local]
        n_micro = xm.shape[0]
        stage = jax.lax.axis_index(pp_axis)
        s_count = nstage
        rounds = -(-n_micro // s_count)             # ceil, v>1 rounds
        ring_n = 2 * v * s_count
        span = rounds * v * s_count                 # F-tick count (v>1)
        total = (n_micro + 2 * s_count - 1) if v == 1 \
            else (span + v * s_count + s_count - 1)
        is_last = stage == s_count - 1
        chunk_shapes = [tuple(p.shape[(2 if v > 1 else 1):])
                        for p in params_local]

        def fwd_fn(chunk, inp):
            return stage_fn(chunk, inp, *extra)

        def last_fn(chunk, inp, tailp, lbls):
            return tail_fn(tailp, stage_fn(chunk, inp, *extra), *lbls)

        act = jax.eval_shape(lambda x: x[0], xm)
        zero_act = _pvary(jnp.zeros(act.shape, act.dtype), pp_axis)
        xmv = _pvary(xm, pp_axis)
        tail_idx_v = tuple(_pvary(t, pp_axis) for t in tail_idx)
        # tail params must be VARYING here: a vjp wrt a replicated
        # (unvaried) input makes jax transpose-insert a psum over pp on
        # its cotangent at every tick — wrong (it mixes the other
        # stages' masked-out branch values) and a collective per tick.
        # Varying inputs keep cotangents device-local; the single psum
        # at the end does the cross-stage reduction.
        tail_params = tuple(_pvary(t, pp_axis) for t in tail_params)
        # per-lap STATIC chunk slices: stable tracer identities, so the
        # residual weight-leaf filter works per lap (for v>1 the laps
        # are lax.switch branches — each branch closes over its own
        # static chunk, never a dynamically-indexed copy)
        if v == 1:
            chunks_static = [locals_]
        else:
            chunks_static = [[p[l] for p in locals_] for l in range(v)]
        const_pools = [list(ch) + list(extra) for ch in chunks_static]
        const_pool = const_pools[0]
        box: dict = {}
        if stash:
            # trace-time probe: residual shapes + which leaves are just
            # re-reads of the (tick-invariant) weights/extras — those
            # are re-injected at backward instead of ring-buffered
            def _probe(ip):
                _, vjp = jax.vjp(lambda ch, i: stage_fn(ch, i, *extra),
                                 chunks_static[0], ip)
                flat, _ = jax.tree_util.tree_flatten(vjp)
                box["const_ix"] = [
                    next((j for j, c in enumerate(const_pool)
                          if l is c), -1) for l in flat]
                box["res_sd"] = [(tuple(l.shape), l.dtype)
                                 for l in flat]
                return 0

            # probe with zero_act (not the act template): its aval
            # carries the {pp} varying annotation the scan carries need
            jax.eval_shape(_probe, zero_act)
            const_ix = box["const_ix"]
            # identity filtering is heuristic (vjp residual leaves that
            # ARE the weight tracers) — if it matched nothing, the full
            # weight set would be ring-buffered 2S times per device.
            # Make that degradation loud instead of a silent HBM blowup.
            import numpy as _np
            stored_b = sum(
                int(_np.prod(sh)) * _np.dtype(dt).itemsize
                for (sh, dt), ci in zip(box["res_sd"], const_ix)
                if ci < 0)
            act_b = int(_np.prod(act.shape)) * _np.dtype(
                act.dtype).itemsize
            weight_b = sum(int(_np.prod(c.shape)) * _np.dtype(
                c.dtype).itemsize for c in locals_)
            if all(ci < 0 for ci in const_ix) and \
                    stored_b > 4 * act_b + weight_b:
                import warnings
                warnings.warn(
                    "1F1B stash: no vjp residual leaf matched a weight "
                    f"tracer; ring-buffering {stored_b >> 20} MiB per "
                    "slot (includes per-slot weight copies). Set "
                    "stash=False or simplify the stage fn.",
                    RuntimeWarning, stacklevel=2)
            ring0 = (
                tuple(_pvary(jnp.zeros((ring_n,) + sh, dt), pp_axis)
                      for (sh, dt), ci in zip(box["res_sd"], const_ix)
                      if ci < 0),
                _pvary(jnp.zeros((ring_n,) + act.shape, act.dtype),
                       pp_axis),                             # stage outs
            )
        else:
            ring0 = _pvary(jnp.zeros((ring_n,) + act.shape, act.dtype),
                           pp_axis)                          # stage inputs
        state = (
            zero_act,                                        # fwd carry
            zero_act,                                        # bwd carry
            ring0,
            tuple(_pvary(jnp.zeros(c.shape, jnp.float32), pp_axis)
                  for c in locals_),                         # param grads
            tuple(_pvary(jnp.zeros(t.shape, jnp.float32), pp_axis)
                  for t in tail_params),                     # tail grads
            _pvary(jnp.zeros(xm.shape, jnp.float32), pp_axis),  # dxm
            _pvary(jnp.zeros((), jnp.float32), pp_axis),     # loss sum
            _pvary(jnp.zeros((), jnp.float32), pp_axis),     # count
        )

        def step(t, st):
            fcarry, bcarry, ring, gp, gt, dxm, lsum, cnt = st

            # ---- forward ------------------------------------------------
            if v == 1:
                # F_s(m) at t = m + s
                mf = t - stage
                active_f = (mf >= 0) & (mf < n_micro)
                mfc = jnp.clip(mf, 0, n_micro - 1)
                slot_f = mfc % ring_n
                lap_f = jnp.zeros((), t.dtype)
                chunk_f = locals_
                feed_f = stage == 0
            else:
                # interleaved: F of chunk lap·S+d on microbatch r·S+j at
                # t = r·vS + lap·S + j + d  (device tick u = t - d)
                uf = t - stage
                ufc = jnp.clip(uf, 0, span - 1)
                r_f = ufc // (v * s_count)
                q_f = ufc % (v * s_count)
                lap_f = q_f // s_count
                mf = r_f * s_count + q_f % s_count
                active_f = (uf >= 0) & (uf < span) & (mf < n_micro)
                mfc = jnp.clip(mf, 0, n_micro - 1)
                slot_f = ufc % ring_n
                chunk_f = [jax.lax.dynamic_index_in_dim(p, lap_f, 0,
                                                        False)
                           for p in locals_]
                feed_f = (stage == 0) & (lap_f == 0)
            inp = jnp.where(feed_f, xmv[mfc], fcarry)

            if stash:
                def _capture(chunk):
                    """vjp-capture branch for one lap's static chunk:
                    returns (y, stored residual leaves)."""
                    def br(ip):
                        y, vjp = jax.vjp(
                            lambda ch, i: fwd_fn(ch, i), chunk, ip)
                        flat, td = jax.tree_util.tree_flatten(vjp)
                        box["td"] = td
                        return y, tuple(
                            l for l, ci in zip(flat, const_ix)
                            if ci < 0)
                    return br

                def do_f(rs):
                    res_rings, y_ring = rs
                    if v == 1:
                        y, stored = _capture(chunks_static[0])(inp)
                    else:
                        y, stored = jax.lax.switch(
                            lap_f, [_capture(ch)
                                    for ch in chunks_static], inp)
                    res_rings = tuple(
                        jax.lax.dynamic_update_index_in_dim(
                            r, v_, slot_f, 0)
                        for r, v_ in zip(res_rings, stored))
                    y_ring = jax.lax.dynamic_update_index_in_dim(
                        y_ring, y, slot_f, 0)
                    return y, (res_rings, y_ring)
            else:
                def do_f(ring):
                    y = fwd_fn(chunk_f, inp)
                    ring = jax.lax.dynamic_update_index_in_dim(
                        ring, inp, slot_f, 0)
                    return y, ring

            y, ring = _branch(
                active_f, do_f, lambda ring: (inp, ring), ring)

            # ---- backward ----------------------------------------------
            if v == 1:
                # B_s(m) at t = m + 2S-1-s
                mb = t - (2 * s_count - 1) + stage
                active_b = (mb >= 0) & (mb < n_micro)
                mbc = jnp.clip(mb, 0, n_micro - 1)
                slot_b = mbc % ring_n
                chunk_b = locals_
                lap_b = jnp.zeros((), t.dtype)
                is_last_chunk = is_last
            else:
                # mirror schedule with delay D = vS: B of chunk lap·S+d
                # at t = D + r·vS + (v-1-lap)·S + j + (S-1-d)
                ub = t - v * s_count - (s_count - 1 - stage)
                ubc = jnp.clip(ub, 0, span - 1)
                r_b = ubc // (v * s_count)
                q_b = ubc % (v * s_count)
                lap_b = v - 1 - q_b // s_count
                j_b = q_b % s_count
                mb = r_b * s_count + j_b
                active_b = (ub >= 0) & (ub < span) & (mb < n_micro)
                mbc = jnp.clip(mb, 0, n_micro - 1)
                # ring slot keyed on the F tick of the same (chunk, m)
                slot_b = (r_b * v * s_count + lap_b * s_count
                          + j_b) % ring_n
                chunk_b = [jax.lax.dynamic_index_in_dim(p, lap_b, 0,
                                                        False)
                           for p in locals_]
                is_last_chunk = is_last & (lap_b == v - 1)
            sinp = None if stash else ring[slot_b]

            def _apply_saved_vjp(ct):
                """Rebuild the forward tick's vjp from ring residuals +
                re-injected constant leaves and apply it (stash mode).
                For v>1 the constants are the BACKWARD lap's static
                chunk — selected with lax.switch so identities stay
                per-branch."""
                res_rings, _ = ring
                stored_b = [jax.lax.dynamic_index_in_dim(r, slot_b, 0,
                                                         False)
                            for r in res_rings]

                def _rebuild(pool):
                    def br(args):
                        stored, ct_ = args
                        it = iter(stored)
                        re_flat = [pool[ci] if ci >= 0 else next(it)
                                   for ci in const_ix]
                        vjp_saved = jax.tree_util.tree_unflatten(
                            box["td"], re_flat)
                        return vjp_saved(ct_)
                    return br

                if v == 1:
                    return _rebuild(const_pools[0])(
                        (tuple(stored_b), ct))
                return jax.lax.switch(
                    lap_b, [_rebuild(p) for p in const_pools],
                    (tuple(stored_b), ct))

            def seed(p, fill):
                ct = jnp.full(p.shape, fill, p.dtype)
                if pp_axis in getattr(jax.typeof(p), "vma", ()):
                    ct = _pvary(ct, pp_axis)
                return ct

            def bwd_last(_):
                lbls = tuple(ti[mbc] for ti in tail_idx_v)
                if stash:
                    y_saved = jax.lax.dynamic_index_in_dim(
                        ring[1], slot_b, 0, False)
                    (s_, c_), tvjp = jax.vjp(
                        lambda tp, yy: tail_fn(tp, yy, *lbls),
                        tuple(tail_params), y_saved)
                    dtp, dy = tvjp((seed(s_, 1.0), seed(c_, 0.0)))
                    dch, dip = _apply_saved_vjp(dy)
                else:
                    (s_, c_), vjp = jax.vjp(
                        lambda ch, ip, tp: last_fn(ch, ip, tp, lbls),
                        chunk_b, sinp, tuple(tail_params))
                    dch, dip, dtp = vjp((seed(s_, 1.0), seed(c_, 0.0)))
                # cotangents of replicated (unvaried) inputs come back
                # unvaried — align vma/pytree with the other branches
                dch = tuple(_pvary(g, pp_axis) for g in dch)
                dip = _pvary(dip, pp_axis)
                dtp = tuple(_pvary(g, pp_axis) for g in dtp)
                return (dch, dip, dtp,
                        _pvary(s_.astype(jnp.float32), pp_axis),
                        _pvary(c_.astype(jnp.float32), pp_axis))

            def bwd_mid(_):
                if stash:
                    dch, dip = _apply_saved_vjp(bcarry)
                else:
                    _, vjp = jax.vjp(
                        lambda ch, ip: fwd_fn(ch, ip), chunk_b, sinp)
                    dch, dip = vjp(bcarry)
                dch = tuple(_pvary(g, pp_axis) for g in dch)
                zt = tuple(_pvary(jnp.zeros(t.shape, t.dtype), pp_axis)
                           for t in tail_params)
                z = _pvary(jnp.zeros((), jnp.float32), pp_axis)
                return dch, _pvary(dip, pp_axis), zt, z, z

            def do_b(_):
                return _branch(is_last_chunk, bwd_last, bwd_mid, None)

            def skip_b(_):
                zc = tuple(_pvary(jnp.zeros(sh, p.dtype), pp_axis)
                           for sh, p in zip(chunk_shapes, locals_))
                zt = tuple(_pvary(jnp.zeros(t.shape, t.dtype), pp_axis)
                           for t in tail_params)
                z = _pvary(jnp.zeros((), jnp.float32), pp_axis)
                return zc, zero_act, zt, z, z

            dch, dip, dtp, ds, dc = _branch(active_b, do_b, skip_b,
                                            None)
            if v == 1:
                gp = tuple(g + d.astype(jnp.float32)
                           for g, d in zip(gp, dch))
            else:
                # scatter-add the chunk grad into its lap slot
                gp = tuple(
                    jax.lax.dynamic_update_index_in_dim(
                        g, jax.lax.dynamic_index_in_dim(g, lap_b, 0,
                                                        False)
                        + d.astype(jnp.float32), lap_b, 0)
                    for g, d in zip(gp, dch))
            gt = tuple(g + d.astype(jnp.float32)
                       for g, d in zip(gt, dtp))
            lsum = lsum + ds
            cnt = cnt + dc
            # stage 0's (lap 0's) dinp is this microbatch's input grad
            dxm = jnp.where(
                active_b & (stage == 0) & (lap_b == 0),
                jax.lax.dynamic_update_index_in_dim(
                    dxm, dip.astype(jnp.float32), mbc, 0),
                dxm)

            # ---- rotate: y forward, dinp backward ----------------------
            fcarry = jax.lax.ppermute(
                y, pp_axis,
                [(i, (i + 1) % s_count) for i in range(s_count)])
            bcarry = jax.lax.ppermute(
                dip.astype(act.dtype), pp_axis,
                [(i, (i - 1) % s_count) for i in range(s_count)])
            return fcarry, bcarry, ring, gp, gt, dxm, lsum, cnt

        _, _, _, gp, gt, dxm, lsum, cnt = jax.lax.fori_loop(
            0, total, step, state)
        lsum = jax.lax.psum(lsum, pp_axis)
        cnt = jax.lax.psum(cnt, pp_axis)
        dxm = jax.lax.psum(dxm, pp_axis)          # stage 0 contributed
        gt = tuple(jax.lax.psum(g, pp_axis) for g in gt)   # last stage
        gp = tuple(g[None] for g in gp)           # [1, per, ...]
        return lsum, cnt, gp, dxm, gt

    in_specs = (tuple(P(pp_axis) for _ in range(n_params)), P(),
                *(P() for _ in range(n_extra + n_tail_params
                                     + n_tail_idx)))
    out_specs = (P(), P(), tuple(P(pp_axis) for _ in range(n_params)),
                 P(), tuple(P() for _ in range(n_tail_params)))
    mapped = jax.shard_map(inner, mesh=mesh, axis_names={pp_axis},
                           in_specs=in_specs, out_specs=out_specs)
    return jax.jit(mapped)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 9, 10))
def pipeline_train_1f1b(stage_fn, tail_fn, mesh, pp_axis, stacked,
                        x_micro, extra, tail_params, tail_indexed,
                        stash: bool = False, n_virtual: int = 1):
    """Mean loss of the pipelined model+loss-head under the 1F1B
    schedule (interleaved when ``n_virtual > 1``).  ``tail_fn`` must
    return ``(loss_sum, valid_count)``; the result is
    Σloss_sum / max(Σcount, 1) over all microbatches.

    Differentiable via custom_vjp: under jax.grad the fwd rule runs the
    fused 1F1B loop ONCE, producing loss and all gradients together
    (ring buffers ⇒ activation memory ∝ pp, not n_micro); without grad,
    the plain forward pipeline runs (cond-guarded tail).
    stacked: v==1: tuple of [S, per_chunk, ...] arrays; v>1: the
    interleaved [S, v, per_chunk, ...] device-major layout (chunk
    l*S+d at [d, l]) — never global chunk order, so no cross-shard
    relayout happens.  ``stash``: ring-buffer VJP residuals so backward
    ticks skip the forward recompute (see _jitted_1f1b)."""
    loss_sum, count = gpipe_spmd(
        list(stacked), x_micro, stage_fn, *extra, mesh=mesh,
        pp_axis=pp_axis, n_virtual=n_virtual, tail_fn=tail_fn,
        tail_params=tuple(tail_params),
        tail_indexed=tuple(tail_indexed), tail_cond=True)
    return loss_sum / jnp.maximum(count, 1.0)


def _ptrain_1f1b_fwd(stage_fn, tail_fn, mesh, pp_axis, stacked, x_micro,
                     extra, tail_params, tail_indexed,
                     stash: bool = False, n_virtual: int = 1):
    eng = _jitted_1f1b(stage_fn, tail_fn, mesh, pp_axis, len(stacked),
                       len(extra), len(tail_params), len(tail_indexed),
                       stash, n_virtual)
    # v>1 stacks arrive already in [S, v, per, ...] engine layout;
    # gradients come back in the same layout — no relayout either way
    if n_virtual > 1:
        nstage = mesh.shape[pp_axis]
        for p in stacked:
            enforce(p.shape[0] == nstage and p.shape[1] == n_virtual,
                    f"interleaved stacks must be [S={nstage}, "
                    f"v={n_virtual}, per, ...]; got {p.shape}")
    lsum, cnt, gp, dxm, gt = eng(tuple(stacked), x_micro, *extra,
                                 *tail_params, *tail_indexed)
    denom = jnp.maximum(cnt, 1.0)
    loss = lsum / denom
    # cotangents must come back in the primal dtypes; scale-by-ct in
    # the bwd rule preserves each grad's dtype
    gp = tuple(g.astype(p.dtype) for g, p in zip(gp, stacked))
    dxm = dxm.astype(x_micro.dtype)
    gt = tuple(g.astype(t.dtype) for g, t in zip(gt, tail_params))
    return loss, (gp, dxm, gt, denom)


def _ptrain_1f1b_bwd(stage_fn, tail_fn, mesh, pp_axis, stash, n_virtual,
                     res, ct):
    gp, dxm, gt, denom = res
    scale = ct / denom
    dstacked = tuple((g * scale).astype(g.dtype) for g in gp)
    dx = (dxm * scale).astype(dxm.dtype)
    dtail = tuple((g * scale).astype(g.dtype) for g in gt)
    return dstacked, dx, None, dtail, None


pipeline_train_1f1b.defvjp(_ptrain_1f1b_fwd, _ptrain_1f1b_bwd)


# ---------------------------------------------------------------------------
# Paddle-parity layer-list API
# ---------------------------------------------------------------------------

def _balance_partition(costs: Sequence[int], s: int) -> List[int]:
    """Contiguous partition of ``costs`` into ``s`` parts minimizing the
    max part sum (classic DP; n and s are tiny — layer counts)."""
    n = len(costs)
    enforce(n >= s, f"cannot split {n} layers into {s} stages")
    prefix = [0]
    for c in costs:
        prefix.append(prefix[-1] + c)
    INF = float("inf")
    # best[k][i] = minimal max-part-sum splitting costs[:i] into k parts
    best = [[INF] * (n + 1) for _ in range(s + 1)]
    cut = [[0] * (n + 1) for _ in range(s + 1)]
    best[0][0] = 0.0
    for k in range(1, s + 1):
        for i in range(k, n + 1):
            for j in range(k - 1, i):
                val = max(best[k - 1][j], prefix[i] - prefix[j])
                if val < best[k][i]:
                    best[k][i] = val
                    cut[k][i] = j
    bounds = [n]
    k, i = s, n
    while k > 0:
        i = cut[k][i]
        bounds.append(i)
        k -= 1
    return list(reversed(bounds))

class LayerDesc:
    """Deferred layer constructor (fleet pp_layers.LayerDesc parity)."""

    def __init__(self, layer_cls, *args, **kwargs):
        self.layer_cls = layer_cls
        self.args = args
        self.kwargs = kwargs
        enforce(issubclass(layer_cls, Layer) or callable(layer_cls),
                "LayerDesc needs a Layer subclass")

    def build_layer(self) -> Layer:
        return self.layer_cls(*self.args, **self.kwargs)


class SharedLayerDesc(LayerDesc):
    """Layer whose parameters are shared across stages (e.g. tied
    embedding/lm-head).  Under single-program SPMD the sharing is simply
    object identity — the first build is reused."""

    def __init__(self, key, layer_cls, *args, forward_func=None,
                 shared_weight_attr="weight", **kwargs):
        super().__init__(layer_cls, *args, **kwargs)
        self.layer_name = key
        self.forward_func = forward_func
        self.shared_weight_attr = shared_weight_attr


class PipelineLayer(Layer):
    """fleet.meta_parallel.PipelineLayer parity.

    Holds the full layer list (single-program SPMD: every process owns
    the whole model; stage placement is a sharding concern, not an
    ownership concern).  ``forward`` runs the stack sequentially — the
    semantics the reference's PipelineParallel produces.  The pipelined
    *execution* is the compiled path: models with a uniform decoder
    stack (e.g. LlamaForCausalLMPipe) lower it through gpipe_spmd.
    """

    def __init__(self, layers, num_stages: Optional[int] = None,
                 topology=None, loss_fn=None, seg_method="uniform",
                 recompute_interval: int = 0, **kwargs):
        super().__init__()
        self._loss_fn = loss_fn
        self._seg_method = seg_method
        self._shared: dict = {}
        built: List[Layer] = []
        self.descs = list(layers)
        for d in self.descs:
            if isinstance(d, SharedLayerDesc):
                if d.layer_name in self._shared:
                    built.append(self._shared[d.layer_name])
                else:
                    lyr = d.build_layer()
                    self._shared[d.layer_name] = lyr
                    built.append(lyr)
            elif isinstance(d, LayerDesc):
                built.append(d.build_layer())
            else:
                enforce(isinstance(d, Layer),
                        "PipelineLayer accepts Layers or LayerDescs")
                built.append(d)
        self.run_function = LayerList(built)
        if num_stages is None and topology is not None:
            num_stages = topology.get_dim("pipe") if hasattr(
                topology, "get_dim") else 1
        self._num_stages = num_stages or 1
        self._segment()

    def _segment(self):
        """Compute stage boundaries per ``seg_method`` (fleet
        PipelineLayer ``seg_method`` parity):

        - ``"uniform"``: equal layer counts per stage;
        - ``"layer:<Class>"``: stage boundaries only at occurrences of
          the named layer class (the reference's way of keeping e.g. a
          decoder block plus its surrounding glue on one stage);
        - ``"flops"``: balance per-stage cost using parameter count as
          the FLOPs proxy (for dense layers FLOPs ≈ 2·params·tokens, so
          param totals rank transformer blocks correctly).
        """
        n = len(self.run_function)
        s = self._num_stages
        method = self._seg_method or "uniform"
        if method == "uniform":
            base, extra = divmod(n, s)
            bounds = [0]
            for i in range(s):
                bounds.append(bounds[-1] + base + (1 if i < extra else 0))
        elif method.startswith("layer:"):
            name = method[len("layer:"):]
            marks = [i for i, lyr in enumerate(self.run_function)
                     if type(lyr).__name__ == name]
            enforce(len(marks) >= s,
                    f"seg_method '{method}': found {len(marks)} "
                    f"'{name}' layers < {s} stages")
            # first stage starts at 0; later stages begin at evenly
            # strided marker layers
            bounds = [0]
            base, extra = divmod(len(marks), s)
            idx = 0
            for i in range(s - 1):
                idx += base + (1 if i < extra else 0)
                bounds.append(marks[idx])
            bounds.append(n)
        elif method == "flops":
            costs = [max(1, sum(int(np.prod(p.shape))
                                for p in lyr.parameters()))
                     for lyr in self.run_function]
            bounds = _balance_partition(costs, s)
        else:
            enforce(False, f"unknown seg_method '{method}'")
        self.segment_parts = bounds

    def get_stage_layers(self, stage: int) -> List[Layer]:
        lo, hi = self.segment_parts[stage], self.segment_parts[stage + 1]
        return list(self.run_function[lo:hi])

    @property
    def num_stages(self) -> int:
        return self._num_stages

    def forward(self, x, *args, **kwargs):
        # side inputs (e.g. rope cos/sin) are forwarded to every layer —
        # dropping them silently diverged from the sequential-parity
        # contract (ADVICE.md round-1)
        for lyr in self.run_function:
            x = lyr(x, *args, **kwargs)
        return x
