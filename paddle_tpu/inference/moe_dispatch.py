"""MoE FFN step path for LLMEngine serving (ISSUE: ROADMAP item 3).

One traced function, :func:`moe_ffn`, replaces the dense SwiGLU FFN
inside every serving program's decoder-layer body when the engine's
backbone is an MoE family (Qwen2-MoE/DeepSeekMoE geometry): top-k
router → token→expert dispatch → per-expert SwiGLU → top-k combine,
plus the always-on shared expert.  The router's scoring (softmax, or
sigmoid with a correction bias and a route scale), the experts' form
(gated SiLU, or ``relu^2`` with no gate matrix) and a narrow latent the
routed experts live in are static fields of the arch and what the
layer's weights bring (:class:`MoEArch`, :func:`moe_ffn`).  All routing tensors are TRACED data
— descriptors never surface to the host — so the engine's one-compile
invariants (``mixed_compiles() == 1`` per geometry) survive untouched.

The static part of the configuration is ONE hashable :class:`MoEArch`
jit argument; everything else (which tokens, which experts) is data.

Two dispatch modes, BIT-IDENTICAL on CPU by construction:

- ``grouped`` — the production shape: sort routed slots by expert into
  the tile-aligned dropless layout (ops/pallas/grouped_matmul.py's
  ``make_dropless_plan_rows``), a buffer in the rows' own dtype, and
  run TWO Pallas calls a layer over it (no per-expert programs):
  ``gmm_glu`` — gate and up in one pass over the rows, ``silu(g) * u``
  on the float32 accumulators in VMEM — and ``gmm`` for the down
  projection, both writing float32.  (Int8 expert pairs keep one
  ``gmm`` a projection: their scale must land before ``silu``.)  On
  CPU the per-row gathered-einsum
  oracle (``gmm_reference``'s idiom) does — which is exactly the
  row-wise math the dense mode runs, so the two modes agree bit for
  bit off-TPU (each row's contraction is independent of every other
  row's placement).
- ``dense`` — the per-row reference: gather each slot's expert weights
  and contract row-wise, no sorting.  The comparator for tests.

Token dropping: ``arch.capacity == 0`` is dropless (every routed slot
computes).  ``capacity > 0`` is the capacity-factor mode: within each
page-group (a prefill chunk; decode rows are singleton groups and can
never drop, since ``jax.lax.top_k`` returns distinct experts), an
expert keeps at most ``capacity`` slots in slot order and the rest
contribute exactly +0.0 to the combine — deterministic across the
split/unified/scanned paths because the group boundaries are page
chunks on every path (the unified planner packs whole page chunks in
capacity mode).

INT8 expert weights ride the quantization absmax path: stacks arrive
as ``(int8 values, f32 scale)`` pairs with per-(expert, out-channel)
scales that multiply the contraction OUTPUT — same fold the engine's
``_mm`` uses — so both dispatch modes stay bit-identical quantized.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

__all__ = ["MoEArch", "moe_ffn", "expert_buffer_rows"]


class MoEArch(NamedTuple):
    """Hashable static-jit MoE dispatch configuration.  ``capacity`` is
    the per-page-group per-expert slot cap (0 = dropless); ``dispatch``
    is ``"grouped"`` or ``"dense"`` (bit-identical on CPU — excluded
    from the capsule fingerprint like tp).

    ``num_experts`` is the ROUTER's width.  An expert layer that holds
    a share of them is told which: ``experts_held`` experts starting at
    ``expert_lo`` (0 held = all of them).  The router still scores all
    ``num_experts``, top-k and the renormalisation run over all k, and a
    slot whose expert lies outside ``[expert_lo, expert_lo +
    experts_held)`` computes nothing here and adds +0 to the combine —
    on one chip the layer runs without its exchange."""
    num_experts: int
    top_k: int
    norm_topk: bool
    capacity: int
    shared: bool
    shared_gate: bool
    attn_bias: bool
    dispatch: str
    expert_lo: int = 0
    experts_held: int = 0
    scoring: str = "softmax"
    route_scale: float = 1.0
    expert_act: str = "silu_glu"

    @property
    def n_held(self) -> int:
        return self.experts_held or self.num_experts


# ``moe_ffn``'s weights by name: the scanned backbones hand them over as
# a tuple in this order, a layer-dict backbone as its dict (which may
# leave out what its layer has not — a gate matrix, a token gate on the
# shared expert — and bring ``router_bias``, ``latent_in``,
# ``latent_out``)
_MW = ("router", "experts_gate", "experts_up", "experts_down",
       "shared_gate", "shared_up", "shared_down", "shared_expert_gate")


def _mm(x, w):
    """x @ w for fp or weight-only-int8 (values, per-out-channel scale)
    stacked weights — the engine's fold, restated here to avoid a
    circular import."""
    import jax.numpy as jnp
    if isinstance(w, tuple):
        qw, sc = w
        return jnp.matmul(x, qw.astype(x.dtype)) * sc.astype(x.dtype)
    return jnp.matmul(x, w)


def _expert_rows_mm(x, w, row_expert):
    """Row-wise expert contraction: row i of ``x`` [M, K] against
    ``w[row_expert[i]]`` ([E, K, N] or int8 pair), f32 accumulate.
    Each output row depends only on its own inputs — row-order
    independent bitwise, which is the whole grouped≡dense argument."""
    import jax.numpy as jnp
    if isinstance(w, tuple):
        qw, sc = w
        wr = qw[row_expert]
        y = jnp.einsum("mk,mkn->mn", x.astype(jnp.float32),
                       wr.astype(jnp.float32))
        return y * sc[row_expert]
    wr = w[row_expert]
    return jnp.einsum("mk,mkn->mn", x.astype(jnp.float32),
                      wr.astype(jnp.float32))


def _row_tile(arch, t):
    """The sorted buffer's row tile for a dispatch over ``t`` rows: by
    the rows the experts held here can expect, their share of the
    routed slots (all of them without a share)."""
    from ..ops.pallas.grouped_matmul import _auto_tm
    from ..runtime.device import is_compiled_with_tpu
    return _auto_tm(arch.n_held,
                    t * arch.top_k * arch.n_held // arch.num_experts) \
        if is_compiled_with_tpu() else 8


def expert_buffer_rows(arch, t):
    """Rows of the sorted buffer ONE grouped dispatch over ``t`` token
    rows hands its kernels in a layer (``m_pad``: static, live or
    not) — what the engine's ``row_fill`` divides the kept slots by.
    The dense reference has no buffer: 0."""
    from ..ops.pallas.grouped_matmul import padded_rows
    if arch.dispatch != "grouped":
        return 0
    return padded_rows(t * arch.top_k, arch.n_held, _row_tile(arch, t))


def _on_shards(fn, shardings, n_args):
    """``fn`` as it is, or — under a tp mesh, where the expert stacks
    are replicated — run whole by every shard (a Mosaic call cannot be
    partitioned by GSPMD: ``TPShardings.per_shard``)."""
    if shardings is None:
        return fn
    return lambda *a: shardings.per_shard(
        lambda *b: (fn(*b),), (None,) * n_args, (None,))(*a)[0]


def _gmm_apply(xs, w, tile_expert, gcounts, tm, on_tpu, shardings, base,
               e):
    """One grouped matmul over the sorted tile-aligned buffer, float32
    out whatever the rows' dtype: the Pallas kernel on TPU, the per-row
    oracle (same rows, same math as dense mode) on CPU.

    ``base`` (see ``moe_ffn``'s ``expert_base``) is where this layer's
    ``e`` experts start in ``w``: it rides the kernel's tile→expert
    map, so the kernel's own block DMAs reach into the layer-stacked
    weights and XLA never copies a layer out for the custom call."""
    import jax
    import jax.numpy as jnp

    from ..ops.pallas import grouped_matmul
    if not on_tpu:
        row_e = jnp.repeat(tile_expert, tm) + base
        return _expert_rows_mm(xs, w, row_e)

    gmm = _on_shards(functools.partial(
        grouped_matmul.gmm, tm=tm, out_dtype=jnp.float32), shardings, 4)
    if isinstance(w, tuple):
        # the kernel streams one weight dtype; upcast feeds the MXU
        # copy XLA fuses into the kernel's input stream, and the
        # per-out-channel scale folds into the output like _mm's
        qw, sc = w
        if qw.shape[0] != e:
            # the upcast is a copy anyway: take this layer's rows first
            qw = jax.lax.dynamic_slice_in_dim(qw, base, e)
            sc = jax.lax.dynamic_slice_in_dim(sc, base, e)
        y = gmm(xs, qw.astype(xs.dtype), tile_expert, gcounts)
        return y * sc[jnp.repeat(tile_expert, tm)]
    return gmm(xs, w, tile_expert + base, gcounts)


def _gate_up_apply(xs, wg, wu, tile_expert, gcounts, tm, on_tpu, shardings,
                   base, e):
    """``silu(xs @ wg[e]) * (xs @ wu[e])`` over the sorted buffer,
    float32.  Float expert stacks on a TPU: ONE ``gmm_glu`` call — the
    rows stream once for both projections and the epilogue runs on the
    float32 accumulators in VMEM, so neither product goes to HBM —
    reaching into the stacks through ``tile_expert + base`` as ``gmm``
    does.  Int8 pairs (their per-channel scale must land before the
    ``silu``) and the CPU oracle keep a call a projection."""
    import jax
    import jax.numpy as jnp

    from ..ops.pallas import grouped_matmul
    if on_tpu and not isinstance(wg, tuple) and not isinstance(wu, tuple):
        glu = _on_shards(functools.partial(
            grouped_matmul.gate_up, tm=tm, out_dtype=jnp.float32),
            shardings, 5)
        return glu(xs, wg, wu, tile_expert + base, gcounts)
    hg = _gmm_apply(xs, wg, tile_expert, gcounts, tm, on_tpu, shardings,
                    base, e)
    hu = _gmm_apply(xs, wu, tile_expert, gcounts, tm, on_tpu, shardings,
                    base, e)
    return jax.nn.silu(hg) * hu


def _relu2(x):
    import jax
    x = jax.nn.relu(x)
    return x * x


def moe_ffn(hn, mw, arch, live, group_start=None, shardings=None,
            expert_base=0):
    """The MoE decoder-layer FFN for one serving dispatch.

    hn [T, H] post-attention-layernorm rows; ``mw`` the per-layer
    weight tuple ``(rw, egw, euw, edw, sgw, suw, sdw, seg)`` (router
    [H, E] fp; expert stacks [E, H, F]/[E, F, H], fp or int8 pairs;
    shared-expert Linears, placeholder [1, 1] zeros when
    ``arch.shared`` is off) or the same by name (``_MW``), a dict.  A
    dict that brings ``latent_in`` [H, Z] / ``latent_out`` [Z, H] puts
    the routed experts in a ``Z``-wide latent: ONE shared projection of
    the rows before the dispatch and one of the combined sum after it
    (slots held elsewhere add +0 BEFORE it); the router and the shared
    expert read the full-width rows.  ``live`` [T] bool masks padding rows out
    of routing (their FFN output is unread); ``group_start`` [T] int32
    maps each row to its capacity page-group's first row (``None`` =
    every row its own group — the decode programs, where top-k's
    distinct experts make the in-group rank identically 0).
    ``expert_base`` (int or traced int32 scalar): the row of the
    expert stacks at which THIS layer's experts start — 0 for one
    layer's ``[E, ..]`` stacks; ``layer * E`` when the caller hands
    over every layer's experts as one ``[L·E, ..]`` array (the
    engine's ``[L, E, ..]`` stack, flattened — a bitcast), so a layer
    loop can use them where they lie.

    Returns ``(ffn_out [T, H], counts [E] int32)`` — counts are the
    KEPT routed slots per expert of the router's width (the
    observability plane's per-expert load; dropless ⇒ sum == live·k).
    With a held share (``arch.experts_held``) the counts of experts
    outside it are the slots routed to the absent chips: counted, not
    computed."""
    import jax
    import jax.numpy as jnp

    from ..ops.pallas.grouped_matmul import make_dropless_plan_rows
    from ..runtime.device import is_compiled_with_tpu

    if not isinstance(mw, dict):
        mw = dict(zip(_MW, mw))
    rw, euw, edw = mw["router"], mw["experts_up"], mw["experts_down"]
    glu = arch.expert_act == "silu_glu"
    t = hn.shape[0]
    e, k = arch.num_experts, arch.top_k
    # the experts whose matrices are here: all ``e``, or a share
    n_held, lo = arch.n_held, arch.expert_lo
    share = n_held != e
    f32 = jnp.float32
    xf = hn.astype(f32)

    logits = jnp.dot(xf, rw.astype(f32))
    if arch.scoring == "softmax":
        # router (nn/moe.py _router_parts math, serving subset): softmax
        # over ALL experts, then top-k; HF Qwen2-MoE ships norm_topk off
        probs = jax.nn.softmax(logits, axis=-1)             # [T, E]
        gate_vals, expert_idx = jax.lax.top_k(probs, k)     # [T, k]
        if arch.norm_topk:
            gate_vals = gate_vals / jnp.clip(
                jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    else:
        # independent scores: chosen by the bias-corrected ones, weighed
        # by the uncorrected ones
        scores = jax.nn.sigmoid(logits)
        pick = scores + mw["router_bias"].astype(f32)[None, :] \
            if "router_bias" in mw else scores
        _, expert_idx = jax.lax.top_k(pick, k)
        gate_vals = jnp.take_along_axis(scores, expert_idx, axis=1)
        if arch.norm_topk:
            gate_vals = gate_vals / (
                jnp.sum(gate_vals, axis=-1, keepdims=True) + 1e-20)
    if arch.route_scale != 1.0:
        gate_vals = gate_vals * arch.route_scale
    # the rows the experts read: ``hn``, or its shared projection into
    # the experts' latent
    rows = _mm(hn, mw["latent_in"]) if "latent_in" in mw else hn
    h = rows.shape[1]

    live_slot = jnp.repeat(live, k)                         # [T*k]
    eidx = expert_idx.reshape(-1)
    if arch.capacity and group_start is not None:
        # in-group rank of each slot = live same-expert slots before it
        # within its page group, via ONE exclusive cumsum over the flat
        # slot order minus the value at the group's first slot (slots
        # before the group cancel, so groups never contaminate each
        # other — the split-prefill chunk and the unified planner's
        # whole-page chunk rank identically)
        onehot = (jax.nn.one_hot(expert_idx, e, dtype=jnp.int32)
                  * live[:, None, None].astype(jnp.int32)
                  ).reshape(t * k, e)
        ex_cum = jnp.cumsum(onehot, axis=0) - onehot        # exclusive
        first_slot = jnp.repeat(group_start, k) * k
        base = jnp.take(ex_cum, first_slot, axis=0)
        rank = jnp.take_along_axis(ex_cum - base,
                                   eidx[:, None], axis=1)[:, 0]
        keep = live_slot & (rank < arch.capacity)
    else:
        # dropless — or decode rows (singleton groups): top_k returns
        # distinct experts, so every in-group rank is 0 < capacity
        keep = live_slot
    if share:
        # slots routed to experts held elsewhere take the dropped lane;
        # the held ones index the stacks from ``expert - lo``
        here = keep & (eidx >= lo) & (eidx < lo + n_held)
        row_expert = jnp.where(here, eidx - lo, n_held)
    else:
        here = keep
        row_expert = jnp.where(keep, eidx, e)               # e = dropped
    counts = jnp.sum(
        jax.nn.one_hot(eidx, e, dtype=jnp.int32)
        * keep[:, None].astype(jnp.int32), axis=0)          # [E]

    if arch.dispatch == "grouped":
        on_tpu = is_compiled_with_tpu()
        tm = _row_tile(arch, t)
        order, dest, valid_sorted, tile_expert, gcounts, m_pad = \
            make_dropless_plan_rows(row_expert, n_held, tm)
        # the routed rows travel in their own dtype (``xf`` is an exact
        # widening of ``hn``: the kernels read the same values at half
        # the bytes); what the kernels write is float32, as before
        xs = jnp.zeros((m_pad, h), rows.dtype).at[dest].set(
            rows[order // k], mode="drop")
        if glu:
            hs = _gate_up_apply(xs, mw["experts_gate"], euw, tile_expert,
                                gcounts, tm, on_tpu, shardings,
                                expert_base, n_held)
        else:
            hs = _relu2(_gmm_apply(xs, euw, tile_expert, gcounts, tm,
                                   on_tpu, shardings, expert_base, n_held))
        ys = _gmm_apply(hs, edw, tile_expert, gcounts, tm, on_tpu,
                        shardings, expert_base, n_held)
        dest_safe = jnp.minimum(dest, m_pad - 1)
        y_sorted = jnp.where(valid_sorted[:, None], ys[dest_safe], 0.0)
        y = jnp.zeros((t * k, h), f32).at[order].set(y_sorted)
    else:
        # dense per-expert reference: the same row-wise contractions
        # on the unsorted slot rows, dropped slots zeroed after
        safe = jnp.clip(eidx - lo, 0, n_held - 1) + expert_base
        xdup = jnp.repeat(rows.astype(f32), k, axis=0)      # [T*k, H]
        hu = _expert_rows_mm(xdup, euw, safe)
        if glu:
            hg = _expert_rows_mm(xdup, mw["experts_gate"], safe)
            hs = (jax.nn.silu(hg.astype(f32))
                  * hu.astype(f32)).astype(xdup.dtype)
        else:
            hs = _relu2(hu.astype(f32))
        ys = _expert_rows_mm(hs, edw, safe)
        y = jnp.where(here[:, None], ys.astype(f32), 0.0)

    out = jnp.einsum("tk,tkh->th", gate_vals.astype(f32),
                     y.reshape(t, k, h))                    # [T, H]
    if "latent_out" in mw:
        out = _mm(out.astype(hn.dtype), mw["latent_out"]).astype(f32)

    if arch.shared:
        # shared-expert SwiGLU (+ optional sigmoid token gate) — the
        # Qwen2-MoE composition (nn/moe.py MoELayer) — or relu^2
        suw, sdw = mw["shared_up"], mw["shared_down"]
        sh = jax.nn.silu(_mm(xf, mw["shared_gate"])) * _mm(xf, suw) \
            if glu else _relu2(_mm(xf, suw))
        shared = _mm(sh, sdw)
        if arch.shared_gate:
            shared = shared * jax.nn.sigmoid(
                _mm(xf, mw["shared_expert_gate"]))
        out = out + shared

    return out.astype(hn.dtype), counts
