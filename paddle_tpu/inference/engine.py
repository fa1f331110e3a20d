"""LLMEngine — continuous-batching serving loop over the paged KV cache.

Reference parity: the reference's serving story (AnalysisPredictor +
PaddleNLP's llm serving loops); kernel blueprint per PAPERS.md ragged
paged attention.  TPU-native design: requests of ragged lengths share
one physical page pool; each engine step decodes ONE token for every
active request as a single jitted program — a lax.scan over the stacked
decoder layers whose attention is the Pallas ragged-paged kernel and
whose K/V append is a vectorized page scatter.  Host-side work per step
is only page-table bookkeeping (allocate/extend/release).  Admission
(add_request) prefills through the model's standard cache path and
bulk-writes the prompt K/V into the request's pages.

The dense jitted ``generate()`` remains the single-tenant fast path;
this engine is the multi-tenant path where requests join and leave
between steps (continuous batching).

Serving-shape discipline: admission runs prompts through page-size
**chunks** of ONE compiled prefill program (each chunk fills exactly
one KV page in-graph, and its queries attend over the sequence's
pages so far under a position mask), so a mixed-length request stream
costs a single prefill compile total — no length buckets at all.
``prefill_compiles()`` / ``decode_compiles()`` expose the jit cache
sizes so ops can assert the no-recompile property.

Quantized serving (the quantization subsystem's engine knobs):
``kv_dtype="int8"`` stores the paged KV pools int8 with per-token
scales — the Pallas decode kernel streams int8 pages and dequantizes
in VMEM, roughly halving decode HBM traffic and doubling page capacity
per chip vs fp16.  ``weight_dtype="int8"`` runs the decoder matmuls
against int8 weights (per-output-channel absmax scales folded into the
matmul outputs); models already converted by
``paddle_tpu.quantization.quantize_model`` are picked up as-is.  Both
knobs keep the no-recompile property: the quantized programs' shapes
are still fixed by the engine geometry alone.

Preemption (``suspend``/``resume``): a request can be evicted from the
decode batch mid-generation — its KV pages swap into the paged cache's
bounded host pool (``swap_pool_pages=``) and its slot frees — and
later re-admitted.  Resume restores the pages host-side (swap-in) or,
when the pool could not hold them or the entry was dropped, REPLAYS
the prompt through the same chunked-prefill program and the
already-generated tokens through the same compiled decode program —
either way the request continues with bit-identical tokens to an
unpreempted run (greedy decoding; the sampling strategy's key stream
is global per step, so preemption reshuffles it by construction) and
no new prefill compilations.

One step loop: ``step()`` dispatches ONE compiled mixed-batch program
(``_paged_mixed_step``) that packs every active decode slot (compacted
host-side — retired slots cost nothing) plus up to
``prefill_token_budget`` tokens of pending ``begin_request`` prefill
chunks.  Descriptors are traced scalars, so ``mixed_compiles() == 1``
across arbitrary batch mixes, and a long prompt does not stall
in-flight decodes.  ``add_request`` remains the synchronous admission
path (its own chunked-prefill program).  A step crosses the
host-device boundary once each way: its descriptors go up as ONE packed
buffer, its tokens, ``steps_done``, window key and routed counts come
back as ONE array whose copy is issued at launch
(``_packed_mixed_step`` / ``_packed_mixed_window``;
``llm_engine_host_transfers_total`` over ``llm_engine_steps_total``
reads 1.0 each way).

On-device decode windows: with no prefill pending, a
``steps_per_sync > 1`` window runs as ONE compiled ``lax.while_loop``
program (``_paged_mixed_window``) — attend (ragged Pallas kernel, pools
aliased in place), sample, KV-append, token feed-back chained
in-graph — syncing the host only at the window boundary, with early
exit once every row has hit EOS or its budget (``steps_done`` comes
back so the host merge stays exact).  Window lengths bucket to
powers of two (one compile per bucket, declared to the CompileWatch
at construction); the per-step body IS the single-step program's
body and the key sequence is the same ``inference.sampling``
``split_step`` chain, so a window's tokens equal the per-token
stream's (``steps_per_sync=1``) on every path — plain, int8 KV,
prefix hits, preempt→resume, migration.  The window program runs at
a geometry of its own, one row and one descriptor a slot (the mixed
step's rows are the slots plus the prefill budget, which a pure-decode
window cannot use): ``llm_engine_forward_row_capacity_total{path}``
counts the rows each path's programs ran.

Automatic prefix caching (``enable_prefix_caching=``, default on):
admission looks up the longest cached page-aligned prefix of the
prompt in the paged cache's chain-hash index, maps those pages into
the new slot's table (host-side only), and runs the chunked prefill
over the uncached tail — shared system prompts / few-shot templates
prefill ONCE and cost one set of pages however many requests carry
them.  Sharing is page-table indirection only: the prefill/decode
programs are unchanged, so ``prefill_compiles() == 1`` still holds.

MoE serving (Qwen2-MoE/DeepSeekMoE backbones): the model resolves
through the backbone seam (inference/backbone.py) instead of the old
hardwired ``model.llama.*`` reads, and every serving program gains a
static ``arch`` argument — ``None`` keeps the Llama trace byte
identical; an :class:`~.moe_dispatch.MoEArch` switches the decoder
FFN to the top-k routed + shared-expert path (inference/
moe_dispatch.py): ONE grouped matmul dispatch per projection per
layer over the sorted dropless layout, or the dense per-row
reference (``moe_dispatch="dense"``), bit-identical on CPU.  Routing
descriptors are traced data, so every one-compile invariant above
survives; the programs additionally return per-layer-per-expert
routed-token counts feeding the ``llm_engine_expert_tokens_total``
observability plane (folded on the host one dispatch later, behind the
next launch: ``_note_expert_counts`` / ``_fold_expert_counts``).
Capacity-factor dispatch (``moe_dropless=False``) drops per
page-group deterministically whichever way a prompt was admitted
(the step's planner packs whole page chunks in that mode, as
``add_request``'s chunked prefill does); decode rows are singleton
groups and never drop.
"""
from __future__ import annotations

import functools
import itertools
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from ..common.errors import enforce
from ..observability import get_registry
from ..observability import capsule as _capsule
from ..observability import health as _health
from ..observability import introspection as _insp
from ..observability import tracing as _tracing
from ..observability.tracing import phase as _phase
from ..profiler import RecordEvent
from . import sampling as _sampling
from .moe_dispatch import expert_buffer_rows
from .paged_cache import PagedKVCache

__all__ = ["LLMEngine", "GenRequest"]

_ENGINE_IDS = itertools.count()

# serving-latency bucket ladders (seconds): TTFT spans prefill compiles
# and multi-chunk prompts; TPOT is per decoded token
_TTFT_BUCKETS = (.01, .025, .05, .1, .25, .5, 1.0, 2.5, 5.0, 10.0,
                 30.0, 60.0)
_TPOT_BUCKETS = (.0005, .001, .0025, .005, .01, .025, .05, .1, .25,
                 .5, 1.0)
# accepted-draft-length ladder (speculative windows): covers k up to
# 32; fixed so engines with different spec_k share the family
_SPEC_LEN_BUCKETS = (0., 1., 2., 3., 4., 5., 6., 7., 8., 12., 16.,
                     24., 32.)


class GenRequest:
    def __init__(self, rid, prompt_ids, max_new_tokens, eos_token_id):
        self.rid = rid
        self.prompt = list(prompt_ids)
        self.max_new = max_new_tokens
        self.eos = eos_token_id
        self.out: List[int] = []
        self.slot: Optional[int] = None
        self.done = False
        self.cancelled = False
        # preemption: suspended requests hold no slot or device pages,
        # only (maybe) a host swap-pool entry
        self.suspended = False
        self.swap_handle: Optional[int] = None
        # unified-step chunked admission (begin_request): next prompt
        # position to prefill, and the submit time TTFT measures from
        self.pf_pos = 0
        self.t_submit: Optional[float] = None
        # resume by recompute on the unified path: the token history
        # (prompt + generated but the last) re-prefills through the
        # step's chunk stream; the token it samples at the end is the
        # one the request already has, and is not delivered again
        self.replay: Optional[List[int]] = None
        # speculative decoding: the request's DRAFT KV slot in the
        # engine's second paged cache — attached lazily at its first
        # speculative window, released on retire/suspend/abort
        self.draft_slot: Optional[int] = None

    @property
    def pf_seq(self) -> List[int]:
        """What the chunk stream prefills: the prompt, or the replayed
        history of a request resumed by recompute."""
        return self.prompt if self.replay is None else self.replay


def _wout(w) -> int:
    """Output width of a stacked weight — fp array [.., in, out] or
    weight-only-int8 (values, scale) pair."""
    return w[0].shape[-1] if isinstance(w, tuple) else w.shape[-1]


def _win(w) -> int:
    """Input width of a weight [.., in, out] (fp array or int8 pair)."""
    return w[0].shape[-2] if isinstance(w, tuple) else w.shape[-2]


# the names of a scanned layer's weights, in stack order (the expert
# matrices are not scanned: ``_mixed_forward`` closes over them)
_DENSE_LAYER = ("in_norm", "q", "k", "v", "o", "post_norm", "gate", "up",
                "down")
_MOE_LAYER = ("in_norm", "q", "q_bias", "k", "k_bias", "v", "v_bias", "o",
              "post_norm", "router", "shared_gate", "shared_up",
              "shared_down", "shared_expert_gate")


def _mm(x, w):
    """x @ w for fp or weight-only-int8 stacked weights.  The int8
    scale is per-OUTPUT-channel, so it folds into the matmul result —
    the MXU pass consumes the int8 weight upcast in registers, never a
    materialized fp copy."""
    import jax.numpy as jnp
    if isinstance(w, tuple):
        qw, sc = w
        return jnp.matmul(x, qw.astype(x.dtype)) * sc.astype(x.dtype)
    return jnp.matmul(x, w)


def _per_shard(shardings, kernel, in_dims, out_dims):
    """A Pallas kernel call under the engine's tp mesh: wrapped in a
    ``shard_map`` so every shard runs it on its own heads (GSPMD cannot
    partition a Mosaic call; see ``TPShardings.per_shard``).  Without a
    mesh the kernel is returned untouched."""
    if shardings is None:
        return kernel
    return shardings.per_shard(kernel, in_dims, out_dims)


def _tpc(x, shardings, dim=None):
    """Tensor-parallel sharding constraint: shard ``dim`` over the tp
    axis (``None`` = fully replicated) when the engine carries a mesh,
    identity otherwise — so the no-mesh trace is byte-identical to the
    pre-sharding programs.

    The placement discipline that keeps tp=N BIT-IDENTICAL to tp=1 on
    greedy: only OUTPUT axes are ever sharded (head axes, MLP hidden,
    the o/down projections' H outputs), and every contraction input is
    constrained REPLICATED first.  A contraction over a sharded axis
    would lower to partial-sum + psum — a cross-device float reduction
    whose order differs from the single-device dot — while gathering
    the inputs (all-gather moves bits, never adds floats) keeps every
    matmul's reduction on one device in one order."""
    if shardings is None:
        return x
    return shardings.constrain(x, dim)


@functools.partial(
    __import__("jax").jit,
    static_argnames=("eps", "kvh", "head_dim", "transpose_head",
                     "shardings", "arch"),
    donate_argnames=("k_pages", "v_pages", "k_scales", "v_scales"))
def _paged_prefill_chunk(stack, norm_w, head_w, embed_w, rope,
                         k_pages, v_pages, k_scales, v_scales,
                         ids, table, prev_len,
                         page_slot, last_in_chunk, *, eps: float,
                         kvh: int, head_dim: int,
                         transpose_head: bool = False,
                         shardings=None, arch=None):
    """CHUNKED ragged prefill (round 5): process ``ids`` [C] — one
    page-sized chunk of ONE prompt — against the paged cache.  Each
    chunk's K/V fill exactly one page (C == page_size), written with a
    whole-page dynamic_update_slice (the efficient TPU case — no
    per-row scatter), and the chunk's queries attend over ALL of the
    sequence's pages so far via an additive position mask.

    ONE XLA program serves every prompt length and every chunk index
    (prev_len/page_slot/last_in_chunk are traced scalars; the page
    gather spans the static per-sequence page budget), so admission
    stops compiling per length bucket entirely — `prefill_compiles()`
    is 1 for any request mix (VERDICT r4 Missing #5: the
    bucketed-dense prefill's power-of-two compiles).  The attention
    cost per chunk is C × max_len instead of C × len; prefill is
    matmul-dominated so the overhead is the (cheap) attention term
    only.  (The ``table`` must keep its static per-engine width —
    trimming it per prompt would re-introduce per-shape compiles.)

    ``k_scales``/``v_scales`` ([L, KVH, n_pages, P] f32, or None for
    fp pools) switch the cache write to int8: the chunk's K/V rows
    quantize per token before the page dus, and the page gather
    dequantizes for the chunk's (matmul-dominated) attention.

    ids [C] int32 (end-padded on the final chunk); table [maxp] this
    sequence's page table; prev_len tokens already prefilled;
    page_slot the pool index this chunk writes; last_in_chunk =
    clamp(plen-1 - chunk_base, 0, C-1) (the row whose logits matter
    on the final chunk).  Returns (logits [V], k_pages', v_pages',
    k_scales', v_scales') — plus per-layer expert counts [L, E] when
    ``arch`` is an MoE dispatch config (static; None = dense Llama
    FFN, byte-identical to the pre-MoE trace).  MoE routing masks the
    end-padding rows (``> last_in_chunk``) out of the dispatch and
    counts; the chunk is one capacity page-group.
    """
    import jax
    import jax.numpy as jnp

    from ..ops import _nn
    from ..quantization.ops import quantize_rows_raw
    from ..runtime.device import is_compiled_with_tpu

    cos_t, sin_t = rope
    c = ids.shape[0]
    maxp = table.shape[0]
    page = c                                  # C == page_size
    s_kv = maxp * page
    x = jnp.take(embed_w, ids, axis=0)        # [C, H]
    cos = jax.lax.dynamic_slice(cos_t, (prev_len, 0),
                                (c, cos_t.shape[1]))[None, :, None, :]
    sin = jax.lax.dynamic_slice(sin_t, (prev_len, 0),
                                (c, sin_t.shape[1]))[None, :, None, :]

    from ..models.llama import _rotate_half as rotate_half

    # additive visibility mask over the gathered pages: chunk row r
    # (global position prev_len + r) sees kv positions <= prev_len + r
    kvpos = jnp.arange(s_kv)
    allow = kvpos[None, :] <= prev_len + jnp.arange(c)[:, None]
    amask = jnp.where(allow, 0.0, -1e30).astype(jnp.float32)

    def attend(q, k_full, v_full):
        # q [C, NH, D], k/v_full [S_kv, KVH, D]
        if is_compiled_with_tpu():
            from ..ops.pallas import ShapeNotCovered
            from ..ops.pallas.flash_attention import flash_attention_raw
            flash = _per_shard(
                shardings, lambda q_, k_, v_, m_: (flash_attention_raw(
                    q_, k_, v_, causal=False, mask=m_),),
                (2, 2, 2, None), (2,))
            try:
                return flash(q[None], k_full[None], v_full[None],
                             amask[None, None])[0][0]
            except ShapeNotCovered:
                pass
        g = q.shape[1] // kvh
        qg = q.reshape(c, kvh, g, head_dim)
        sc = jnp.einsum("qhgd,khd->hgqk", qg.astype(jnp.float32),
                        k_full.astype(jnp.float32))
        sc = sc / jnp.sqrt(jnp.float32(head_dim)) + amask[None, None]
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("hgqk,khd->qhgd", p,
                       v_full.astype(jnp.float32))
        return o.reshape(c, q.shape[1], head_dim).astype(q.dtype)

    if arch is not None:
        from .moe_dispatch import moe_ffn
        # MoE routing sees only the chunk's REAL rows; the chunk is
        # one capacity page-group starting at row 0
        moe_live = jnp.arange(c) <= last_in_chunk
        moe_group = jnp.zeros(c, jnp.int32)

    def layer(carry, xs):
        hcur = carry
        lp, kp, vp, ksp, vsp = xs             # params + per-layer pools
        if arch is None:
            iln, qw, kw, vw, ow, pln, gw, uw, dw = lp
            qb = kb = vb = None
        else:
            (iln, qw, qb, kw, kb, vw, vb, ow, pln, rw, egw, euw, edw,
             sgw, suw, sdw, seg) = lp
        hn = _nn.rms_norm(hcur, iln, epsilon=eps)
        nh = _wout(qw) // head_dim
        qx, kx, vx = _mm(hn, qw), _mm(hn, kw), _mm(hn, vw)
        if arch is not None and arch.attn_bias:
            qx, kx, vx = qx + qb, kx + kb, vx + vb
        q = _tpc(qx.reshape(c, nh, head_dim), shardings, 1)
        k = _tpc(kx.reshape(c, kvh, head_dim), shardings, 1)
        v = _tpc(vx.reshape(c, kvh, head_dim), shardings, 1)
        qf, kf = q.astype(jnp.float32)[None], k.astype(jnp.float32)[None]
        q = (qf * cos + rotate_half(qf) * sin)[0].astype(q.dtype)
        k = (kf * cos + rotate_half(kf) * sin)[0].astype(k.dtype)
        if ksp is None:
            # whole-page write: [C, KVH, D] -> [KVH, 1, C(=P), D] block
            kblk = jnp.swapaxes(k, 0, 1)[:, None].astype(kp.dtype)
            vblk = jnp.swapaxes(v, 0, 1)[:, None].astype(vp.dtype)
            kp = jax.lax.dynamic_update_slice(kp, kblk,
                                              (0, page_slot, 0, 0))
            vp = jax.lax.dynamic_update_slice(vp, vblk,
                                              (0, page_slot, 0, 0))
            # gather this sequence's pages (chunk included — written)
            k_full = kp[:, table].reshape(kvh, s_kv, head_dim)
            v_full = vp[:, table].reshape(kvh, s_kv, head_dim)
        else:
            # int8 pools: quantize the chunk's rows (per-token absmax)
            # before the page write; the gather dequantizes
            kq8, ksc = quantize_rows_raw(k)   # [C, KVH, D], [C, KVH]
            vq8, vsc = quantize_rows_raw(v)
            kp = jax.lax.dynamic_update_slice(
                kp, jnp.swapaxes(kq8, 0, 1)[:, None],
                (0, page_slot, 0, 0))
            vp = jax.lax.dynamic_update_slice(
                vp, jnp.swapaxes(vq8, 0, 1)[:, None],
                (0, page_slot, 0, 0))
            ksp = jax.lax.dynamic_update_slice(
                ksp, jnp.swapaxes(ksc, 0, 1)[:, None].astype(ksp.dtype),
                (0, page_slot, 0))
            vsp = jax.lax.dynamic_update_slice(
                vsp, jnp.swapaxes(vsc, 0, 1)[:, None].astype(vsp.dtype),
                (0, page_slot, 0))
            k_full = (kp[:, table].astype(jnp.float32)
                      * ksp[:, table][..., None]).reshape(kvh, s_kv,
                                                          head_dim)
            v_full = (vp[:, table].astype(jnp.float32)
                      * vsp[:, table][..., None]).reshape(kvh, s_kv,
                                                          head_dim)
        attn = _tpc(attend(q, jnp.swapaxes(k_full, 0, 1),
                           jnp.swapaxes(v_full, 0, 1)), shardings, 1)
        # gather the head-sharded attention rows BEFORE the o_proj
        # contraction, and the hidden-sharded ff before down_proj —
        # the bit-exactness discipline (see _tpc)
        hcur = _tpc(hcur + _mm(_tpc(attn.reshape(c, nh * head_dim),
                                    shardings), ow), shardings)
        hn = _nn.rms_norm(hcur, pln, epsilon=eps)
        if arch is None:
            ff = _tpc(_nn.silu(_mm(hn, gw)) * _mm(hn, uw), shardings, 1)
            return (_tpc(hcur + _mm(_tpc(ff, shardings), dw),
                         shardings), (kp, vp, ksp, vsp))
        ff, cnt = moe_ffn(hn, (rw, egw, euw, edw, sgw, suw, sdw, seg),
                          arch, moe_live, moe_group, shardings)
        return (_tpc(hcur + ff, shardings), (kp, vp, ksp, vsp, cnt))

    if arch is None:
        x, (k_pages, v_pages, k_scales, v_scales) = jax.lax.scan(
            layer, x,
            (tuple(stack), k_pages, v_pages, k_scales, v_scales))
    else:
        x, (k_pages, v_pages, k_scales, v_scales, counts) = \
            jax.lax.scan(
                layer, x,
                (tuple(stack), k_pages, v_pages, k_scales, v_scales))
    x = _nn.rms_norm(x, norm_w, epsilon=eps)
    xl = jnp.take(x, last_in_chunk, axis=0)   # [H]
    logits = _tpc(jnp.matmul(xl, head_w.T) if transpose_head
                  else _mm(xl, head_w), shardings)
    if arch is None:
        return logits, k_pages, v_pages, k_scales, v_scales
    return logits, k_pages, v_pages, k_scales, v_scales, counts


def _decode_one_token_fn(stack, norm_w, head_w, embed_w, rope, tables,
                         *, eps, kvh, head_dim, transpose_head,
                         strategy, top_k, top_p, temperature,
                         draw_base=None, shardings=None, arch=None,
                         live=None, collect_probs=False):
    """Build the one-token decode body of ``_paged_decode_step`` (the
    fixed-length window recompute resume and capsule replay dispatch)
    and of the speculative draft's k-token program: embed, rope, fused
    append+attend, sample, ``split_step`` key chain.

    ``draw_base`` (traced int32 scalar) offsets the per-row sampling
    fold: row i draws with ``fold_row(sub, draw_base + i)`` — the live
    engine always passes 0 (row i folds i), capsule replay passes the
    CAPTURED row so a request replayed in row 0 re-draws its original
    stream (see inference/sampling.py).  Unused by greedy.
    ``shardings`` threads the tensor-parallel constraints (see _tpc).
    ``collect_probs`` (static) makes the body return ``(carry,
    probs [B, V])`` — the post-filter sampling distribution of this
    step (``filtered_probs``), the draft-side q surface speculative
    decoding's rejection acceptance consumes.

    carry: (tokens [B], positions [B], lens [B], k_pages, v_pages,
    k_scales, v_scales, key) → the same tuple one step later, with the
    sampled token in slot 0.  With an MoE ``arch`` the carry gains a
    trailing ``counts_acc`` [L, E] int32 accumulator and ``live`` [B]
    (from the WINDOW-START lens — pad rows stay masked for the whole
    window) gates which rows route; decode rows are singleton capacity
    groups, so ``group_start=None`` (never drop — top-k experts are
    distinct).
    """
    import jax
    import jax.numpy as jnp

    from ..ops import _nn
    from ..ops.pallas.paged_attention import (
        paged_decode_append_attend_raw,
        paged_decode_append_attend_reference)
    from ..runtime.device import is_compiled_with_tpu
    from ..models.llama import _rotate_half as rotate_half
    from .sampling import sample_logits, split_step

    cos_t, sin_t = rope                       # [maxpos, D]

    # ONE fused kernel appends this step's K/V and attends over them —
    # the separate XLA paged_write rewrote the whole pool per step on
    # TPU (round-3 serving bottleneck; see paged_attention.py).  The
    # _raw form: this body is traced INSIDE an already-jitted program,
    # often inside its scan/while loop.
    on_tpu = is_compiled_with_tpu()
    append_attend = paged_decode_append_attend_raw \
        if on_tpu else paged_decode_append_attend_reference

    def kernel_dims(n_scales):
        # q/k_new/v_new shard on their head dim, pools and scale pools
        # on KVH, tables and lengths replicate
        return ((1, 0, 0, 1, 1, None, None) + (0,) * n_scales,
                (1, 0, 0) + (0,) * n_scales)

    if arch is not None:
        from .moe_dispatch import moe_ffn

    def one_token(carry):
        if arch is None:
            (tokens, positions, lens, k_pages, v_pages, k_scales,
             v_scales, key) = carry
        else:
            (tokens, positions, lens, k_pages, v_pages, k_scales,
             v_scales, key, counts_acc) = carry
        b = tokens.shape[0]
        x = jnp.take(embed_w, tokens, axis=0)  # [B, H]
        cos = jnp.take(cos_t, positions, axis=0)[:, None, :]  # [B,1,D]
        sin = jnp.take(sin_t, positions, axis=0)[:, None, :]

        def layer(carry, xs):
            hcur = carry
            lp, kp, vp, ksp, vsp = xs          # per-layer params + pools
            if arch is None:
                iln, qw, kw, vw, ow, pln, gw, uw, dw = lp
                qb = kb = vb = None
            else:
                (iln, qw, qb, kw, kb, vw, vb, ow, pln, rw, egw, euw,
                 edw, sgw, suw, sdw, seg) = lp
            hn = _nn.rms_norm(hcur, iln, epsilon=eps)
            nh = _wout(qw) // head_dim
            qx, kx, vx = _mm(hn, qw), _mm(hn, kw), _mm(hn, vw)
            if arch is not None and arch.attn_bias:
                qx, kx, vx = qx + qb, kx + kb, vx + vb
            q = _tpc(qx.reshape(b, nh, head_dim), shardings, 1)
            k = _tpc(kx.reshape(b, kvh, head_dim), shardings, 1)
            v = _tpc(vx.reshape(b, kvh, head_dim), shardings, 1)
            qf = q.astype(jnp.float32)
            kf = k.astype(jnp.float32)
            q = (qf * cos + rotate_half(qf) * sin).astype(q.dtype)
            k = (kf * cos + rotate_half(kf) * sin).astype(k.dtype)
            step_fn = append_attend if not on_tpu else _per_shard(
                shardings, append_attend,
                *kernel_dims(0 if ksp is None else 2))
            if ksp is None:
                attn, kp, vp = step_fn(q, kp, vp, k, v, tables, lens)
            else:
                # int8 pools ride the same fused kernel with their
                # per-token scale rows ([KVH, n_pages, 1, P] views)
                attn, kp, vp, ks4, vs4 = step_fn(
                    q, kp, vp, k, v, tables, lens,
                    ksp[:, :, None, :], vsp[:, :, None, :])
                ksp = ks4.reshape(ksp.shape)
                vsp = vs4.reshape(vsp.shape)
            attn = _tpc(attn, shardings, 1)
            hcur = _tpc(hcur + _mm(
                _tpc(attn.reshape(b, nh * head_dim), shardings), ow),
                shardings)
            hn = _nn.rms_norm(hcur, pln, epsilon=eps)
            if arch is None:
                ff = _tpc(_nn.silu(_mm(hn, gw)) * _mm(hn, uw),
                          shardings, 1)
                return (_tpc(hcur + _mm(_tpc(ff, shardings), dw),
                             shardings), (kp, vp, ksp, vsp))
            ff, cnt = moe_ffn(hn, (rw, egw, euw, edw, sgw, suw, sdw,
                                   seg), arch, live,
                              shardings=shardings)
            return (_tpc(hcur + ff, shardings),
                    (kp, vp, ksp, vsp, cnt))

        if arch is None:
            x, (k_pages, v_pages, k_scales, v_scales) = jax.lax.scan(
                layer, x, (tuple(stack), k_pages, v_pages, k_scales,
                           v_scales))
        else:
            x, (k_pages, v_pages, k_scales, v_scales, cnts) = \
                jax.lax.scan(
                    layer, x, (tuple(stack), k_pages, v_pages,
                               k_scales, v_scales))
        x = _nn.rms_norm(x, norm_w, epsilon=eps)
        logits = _tpc(jnp.matmul(x, head_w.T) if transpose_head
                      else _mm(x, head_w), shardings)
        key, sub = split_step(key)
        row_ids = None if strategy == "greedy_search" else \
            draw_base + jnp.arange(b, dtype=jnp.int32)
        nxt, _ = sample_logits(logits, sub, strategy=strategy,
                               top_k=top_k, top_p=top_p,
                               temperature=temperature,
                               row_ids=row_ids)
        if arch is None:
            out = (nxt, positions + 1, lens + 1, k_pages, v_pages,
                   k_scales, v_scales, key)
        else:
            out = (nxt, positions + 1, lens + 1, k_pages, v_pages,
                   k_scales, v_scales, key, counts_acc + cnts)
        if collect_probs:
            from ..nn.generation import filtered_probs
            return out, filtered_probs(
                logits, strategy=strategy, top_k=top_k, top_p=top_p,
                temperature=temperature)
        return out

    return one_token


@functools.partial(
    __import__("jax").jit,
    static_argnames=("eps", "kvh", "head_dim", "transpose_head",
                     "strategy", "top_k", "top_p", "temperature",
                     "n_steps", "shardings", "arch"),
    donate_argnames=("k_pages", "v_pages", "k_scales", "v_scales"))
def _paged_decode_step(stack, norm_w, head_w, embed_w, rope,
                       k_pages, v_pages, k_scales, v_scales,
                       tokens, positions, tables, lens,
                       key, draw_base=0, *, eps: float, kvh: int,
                       head_dim: int,
                       transpose_head: bool = False,
                       strategy: str = "greedy_search", top_k: int = 0,
                       top_p: float = 1.0, temperature: float = 1.0,
                       n_steps: int = 1, shardings=None, arch=None):
    """``n_steps`` decode tokens for every active sequence as ONE XLA
    program (multi-step scheduling: the host syncs — EOS checks,
    admission — every n_steps tokens, so dispatch latency amortizes
    over n_steps; page capacity for all n_steps is pre-allocated by the
    caller).

    stack: 9 arrays [L, ...] (decoder weights, _decoder_layer_raw
    order; weight-only-int8 entries are (values, scale) pairs) — or 17
    with an MoE ``arch`` (see LLMEngine.__init__); k/v_pages
    [L, KVH, n_pages, P, D]; k/v_scales [L, KVH, n_pages, P] f32
    per-token dequant scales for int8 pools (None for fp); tokens [B]
    int32; positions [B] (= current lengths); tables [B, maxp]; lens
    [B].  Returns (tokens [n_steps, B], k_pages', v_pages', k_scales',
    v_scales') — plus a trailing routed-token counts [L, E] int32 when
    ``arch`` is an MoE (pad rows, lens == 0, route nowhere).
    """
    import jax
    import jax.numpy as jnp

    live = None if arch is None else lens > 0
    one_token = _decode_one_token_fn(
        stack, norm_w, head_w, embed_w, rope, tables,
        eps=eps, kvh=kvh, head_dim=head_dim,
        transpose_head=transpose_head, strategy=strategy, top_k=top_k,
        top_p=top_p, temperature=temperature, draw_base=draw_base,
        shardings=shardings, arch=arch, live=live)

    carry0 = (tokens, positions, lens, k_pages, v_pages, k_scales,
              v_scales, key)
    if arch is not None:
        carry0 = carry0 + (jnp.zeros(
            (stack[0].shape[0], arch.num_experts), jnp.int32),)

    if n_steps == 1:
        out = one_token(carry0)
        (nxt, _, _, k_pages, v_pages, k_scales, v_scales, _) = out[:8]
        if arch is None:
            return nxt[None], k_pages, v_pages, k_scales, v_scales
        return (nxt[None], k_pages, v_pages, k_scales, v_scales,
                out[8])

    def body(carry, _):
        carry = one_token(carry)
        return carry, carry[0]

    (final, toks) = jax.lax.scan(body, carry0, None, length=n_steps)
    (_, _, _, k_pages, v_pages, k_scales, v_scales, _) = final[:8]
    if arch is None:
        return toks, k_pages, v_pages, k_scales, v_scales
    return toks, k_pages, v_pages, k_scales, v_scales, final[8]


def _mixed_forward(stack, norm_w, head_w, embed_w, rope,
                   k_pages, v_pages, k_scales, v_scales,
                   ids, positions, row_tables,
                   q_start, q_len, kv_len, desc_tables,
                   desc_of_row, off_of_row, key, draw_base=0,
                   rec_state=None, conv_state=None, desc_slot=None, *,
                   eps: float, kvh: int, head_dim: int,
                   transpose_head: bool = False,
                   strategy: str = "greedy_search", top_k: int = 0,
                   top_p: float = 1.0, temperature: float = 1.0,
                   shardings=None, arch=None, return_probs=False,
                   hybrid=None):
    """Un-jitted body of ``_paged_mixed_step`` — ALSO the per-step body
    of ``_paged_mixed_window``'s on-device loop, which is what makes
    a window's tokens equal the per-token stream's: the two programs
    trace the very same ops in the very same order (see
    ``_paged_mixed_step`` for the argument contract).  With an MoE
    ``arch`` the return gains a trailing routed-token counts [L, E]:
    rows past their descriptor's ``q_len`` (padding) route nowhere,
    and each descriptor is one capacity page-group (``group_start =
    q_start[desc_of_row]``) so ``add_request``'s prefill chunks rank
    identically.

    ONE layer function, ``layer(kind, ...)``: norm -> mixer(kind) ->
    residual -> norm -> FFN/MoE, the kind STATIC — and each HALF there
    only if the layer has it: kind ``"ffn"`` is a layer with no mixer
    (its feed-forward part alone), and a layer whose weights bring
    neither a router nor a dense ``gate`` / ``up`` / ``down`` is its
    mixer alone (one norm and one residual add, then).  ``"full"`` is
    softmax attention over the KV pages — every layer of the Llama and
    Qwen2-MoE backbones, scanned over their stacked weights — with
    what a layer's weights bring along: projection biases, a q/k norm
    over the head (``q_norm`` / ``k_norm``), rotary on the first
    ``rope`` -width dims only, an output gate packed beside q (a ``q``
    projection twice as wide as ``o``'s input).  ``"linear"`` is the
    Gated-DeltaNet mixer and ``"ssm"`` the Mamba-2 (SSD) mixer, whose
    per-slot recurrent state and conv window ride the carry beside the
    pools.  A ``hybrid`` backbone
    (``backbone.HybridArch``) gives the kinds; its ``stack`` is one
    weight dict a layer (``BackboneSpec.layer_weights``: the model's
    own arrays, so the expert matrices exist once on the device)
    walked by a Python loop over the period, its KV pools hold the full
    layers only, and ``rec_state`` / ``conv_state`` (one array a
    recurrent layer) plus ``desc_slot`` [S] (each descriptor's sequence slot)
    come in and go out after the counts."""
    import jax
    import jax.numpy as jnp

    from ..ops import _nn
    from ..ops.pallas.paged_attention import (
        ragged_paged_append_attend_raw,
        ragged_paged_append_attend_reference)
    from ..runtime.device import is_compiled_with_tpu

    cos_t, sin_t = rope
    t = ids.shape[0]

    from ..models.llama import _rotate_half as rotate_half
    from .sampling import sample_logits, split_step

    x = jnp.take(embed_w, ids, axis=0)             # [T, H]
    cos = jnp.take(cos_t, positions, axis=0)[:, None, :]   # [T, 1, D]
    sin = jnp.take(sin_t, positions, axis=0)[:, None, :]
    on_tpu = is_compiled_with_tpu()
    if arch is not None:
        from .moe_dispatch import moe_ffn
        moe_live = off_of_row < jnp.take(q_len, desc_of_row)
        moe_group = jnp.take(q_start, desc_of_row)

    # Every operand of the layer loop is used WHERE IT LIES (PR 26).
    # The KV pools ride the scan's carry whole, [L, KVH, ..]: the
    # ragged kernel takes the stacked pool plus the layer index (a
    # scalar-prefetch operand), picks the layer before its page DMAs
    # and aliases the whole pool input to output.  The expert matrices
    # are not scanned either: their [L, E, ..] stacks are closed over,
    # flattened to [L·E, ..] (a bitcast), and ``moe_ffn`` offsets the
    # grouped matmul's tile→expert map by ``layer · E``.  As scanned
    # ``xs`` (and ``ys`` for the pools) each of them was copied out of
    # its stack for the custom call every layer — a Pallas call is a
    # custom call, nothing fuses into it — the pools were written back
    # into the stacked ``ys``, and those were copied whole to the
    # program's outputs: 60 % of the chip's busy time in both serving
    # cells.  What stays scanned is what XLA's own matmuls consume.
    n_layers = k_pages.shape[0]
    if hybrid is None:
        scanned = tuple(stack)
        if arch is not None:
            def flat(w):
                if isinstance(w, tuple):
                    return tuple(flat(a) for a in w)
                return w.reshape((-1,) + w.shape[2:])
            egw, euw, edw = (flat(w) for w in scanned[10:13])
            scanned = scanned[:10] + scanned[13:]
    else:
        from ..ops.pallas.gated_delta import (
            causal_conv_step, gdn_inputs, gdn_output, ragged_gated_delta)
        from ..ops.pallas.mamba2_ssd import (
            ragged_ssd, ssd_inputs, ssd_output)
        # the rows' view of the descriptors, for the conv window: each
        # row's slot, how many of this step's rows of its sequence
        # precede it (they are contiguous), live rows per slot, and the
        # slots whose sequence starts in this step
        n_slots = rec_state[0].shape[0] - 1
        row_live = off_of_row < jnp.take(q_len, desc_of_row)
        row_slot = jnp.where(row_live, jnp.take(desc_slot, desc_of_row),
                             n_slots)
        far = jnp.iinfo(jnp.int32).max
        seq_start = jnp.full(n_slots + 1, far, jnp.int32).at[
            jnp.where(q_len > 0, desc_slot, n_slots)].min(
            jnp.where(q_len > 0, kv_len, far))
        row_hist = jnp.where(row_live,
                             positions - jnp.take(seq_start, row_slot), 0)
        slot_rows = jnp.zeros(n_slots + 1, jnp.int32).at[row_slot].add(
            row_live.astype(jnp.int32)).at[n_slots].set(0)
        slot_fresh = (seq_start == 0) & (slot_rows > 0)

    def attend(q, k, v, pools, li):
        """Append this step's K/V rows to the pools at layer ``li`` and
        attend every row over its own sequence's pages."""
        if on_tpu:
            # ragged kernel on the STACKED pools at layer ``li``:
            # per-descriptor [P, H, D] output blocks, gathered back to
            # the flat row order
            # (under a tp mesh each shard runs the kernel on its own
            # heads: q/k/v rows shard on the head dim, pools and scale
            # pools on KVH, descriptors and the layer index replicate,
            # output blocks come back sharded on H)
            kps, vps, kss, vss = pools
            n_sc = 0 if kss is None else 2
            ragged = _per_shard(
                shardings,
                lambda *a: ragged_paged_append_attend_raw(
                    *a[:-1], layer=a[-1]),
                (1, 1, 1, 1, 1, None, None, None, None)
                + (1,) * n_sc + (None,),
                (2, 1, 1) + (1,) * n_sc)
            if kss is None:
                blocks, kps, vps = ragged(
                    q, kps, vps, k, v, q_start, q_len, kv_len,
                    desc_tables, li)
            else:
                blocks, kps, vps, ks5, vs5 = ragged(
                    q, kps, vps, k, v, q_start, q_len, kv_len,
                    desc_tables, kss[:, :, :, None, :],
                    vss[:, :, :, None, :], li)
                kss = ks5.reshape(kss.shape)
                vss = vs5.reshape(vss.shape)
            pools = (kps, vps, kss, vss)
            attn = blocks[desc_of_row, off_of_row]          # [T, NH, D]
        else:
            # the per-row jnp mirror, on this layer's slice
            kp, vp, ksp, vsp = (
                None if p is None else
                jax.lax.dynamic_index_in_dim(p, li, keepdims=False)
                for p in pools)
            if ksp is None:
                attn, kp, vp = ragged_paged_append_attend_reference(
                    q, kp, vp, k, v, positions, row_tables)
            else:
                attn, kp, vp, ks4, vs4 = \
                    ragged_paged_append_attend_reference(
                        q, kp, vp, k, v, positions, row_tables,
                        ksp[:, :, None, :], vsp[:, :, None, :])
                ksp = ks4.reshape(ksp.shape)
                vsp = vs4.reshape(vsp.shape)
            pools = tuple(
                None if p is None else
                jax.lax.dynamic_update_index_in_dim(p, new, li, 0)
                for p, new in zip(pools, (kp, vp, ksp, vsp)))
        return attn, pools

    def linear_mixer(hn, lp, rec, conv):
        """Gated DeltaNet over the flat rows: projections, the causal
        conv over each sequence's window, the ragged recurrence over
        the descriptors (state read and written where it lies), the
        gated norm, the output projection."""
        f32 = jnp.float32
        hv, cc = hybrid.linear_num_value_heads, hybrid.conv_channels
        qkvz, ba = _mm(hn, lp["qkvz"]), _mm(hn, lp["ba"])
        mixed, conv = causal_conv_step(
            qkvz[:, :cc].astype(f32), lp["conv"].astype(f32), conv,
            row_slot, row_hist, slot_rows, slot_fresh)
        q, k, v, g, beta = gdn_inputs(mixed, ba[:, :hv], ba[:, hv:],
                                      lp["A_log"], lp["dt_bias"], hybrid)
        o, rec = ragged_gated_delta(
            q, k, v, g, beta, rec, q_start, q_len, kv_len, desc_slot,
            page_size=k_pages.shape[3])
        y = gdn_output(o, qkvz[:, cc:], lp["norm"], eps).astype(hn.dtype)
        return _mm(y, lp["o"]), rec, conv

    def ssm_mixer(hn, lp, rec, conv):
        """Mamba-2 over the flat rows: one projection, the causal conv
        (with its bias) over each sequence's window, the ragged SSD
        recurrence over the descriptors (state read and written where
        it lies), the gated group norm, the output projection."""
        f32 = jnp.float32
        d_in = hybrid.mamba_num_heads * hybrid.mamba_head_dim
        cc = hybrid.conv_channels
        zxd = _mm(hn, lp["in_proj"])
        xbc, conv = causal_conv_step(
            zxd[:, d_in:d_in + cc].astype(f32), lp["conv"].astype(f32),
            conv, row_slot, row_hist, slot_rows, slot_fresh)
        xs, delta, a, b, c = ssd_inputs(
            xbc + lp["conv_bias"].astype(f32)[None, :],
            zxd[:, d_in + cc:], lp["A_log"], lp["dt_bias"], hybrid)
        y, rec = ragged_ssd(
            xs, delta, a, b, c, lp["D"].astype(f32), rec, q_start, q_len,
            kv_len, desc_slot, page_size=k_pages.shape[3])
        y = ssd_output(y, zxd[:, :d_in], lp["norm"], eps,
                       hybrid.n_groups).astype(hn.dtype)
        return _mm(y, lp["o"]), rec, conv

    def norm(x, w):
        if hybrid is not None and hybrid.zero_centred_norm:
            return _nn.rms_norm_zero_centred(x, w, epsilon=eps)
        return _nn.rms_norm(x, w, epsilon=eps)

    def rotary(xf):
        """Half rotation over the first ``rot`` dims of the head (all
        of them for the homogeneous backbones); float32 in and out."""
        rot = cos.shape[-1]
        if rot == 0:                # tables of width 0: no rotary
            return xf
        if rot == head_dim:
            return xf * cos + rotate_half(xf) * sin
        xr = xf[..., :rot]
        return jnp.concatenate(
            [xr * cos + rotate_half(xr) * sin, xf[..., rot:]], -1)

    def attention_mixer(hn, lp, pools, li):
        """Softmax attention over the KV pages; what is optional comes
        with the layer's weights."""
        nh = _win(lp["o"]) // head_dim
        gated = _wout(lp["q"]) == 2 * nh * head_dim
        qx, kx, vx = _mm(hn, lp["q"]), _mm(hn, lp["k"]), _mm(hn, lp["v"])
        if "q_bias" in lp and arch is not None and arch.attn_bias:
            qx, kx, vx = (qx + lp["q_bias"], kx + lp["k_bias"],
                          vx + lp["v_bias"])
        if gated:
            qx = qx.reshape(t, nh, 2 * head_dim)
            qx, gate = qx[..., :head_dim], qx[..., head_dim:]
        q = _tpc(qx.reshape(t, nh, head_dim), shardings, 1)
        k = _tpc(kx.reshape(t, kvh, head_dim), shardings, 1)
        v = _tpc(vx.reshape(t, kvh, head_dim), shardings, 1)
        if "q_norm" in lp:
            q, k = norm(q, lp["q_norm"]), norm(k, lp["k_norm"])
        qf = q.astype(jnp.float32)
        kf = k.astype(jnp.float32)
        q = rotary(qf).astype(q.dtype)
        k = rotary(kf).astype(k.dtype)
        attn, pools = attend(q, k, v, pools, li)
        if gated:
            attn = (attn.astype(jnp.float32) * jax.nn.sigmoid(
                gate.astype(jnp.float32))).astype(hn.dtype)
        attn = _tpc(attn, shardings, 1)
        return _mm(_tpc(attn.reshape(t, nh * head_dim), shardings),
                   lp["o"]), pools

    def layer(kind, hcur, pools, li, lp, lin=None):
        """norm -> mixer(kind) -> residual, then norm -> FFN/MoE ->
        residual, each half if the layer has it.  ``li`` indexes the
        KV pools (and, scanned, the expert stacks); ``lp`` is the
        layer's weights by name (a scanned layer's tuple gets its names
        here); ``lin`` is a recurrent layer's (state, conv window)."""
        if not isinstance(lp, dict):
            lp = dict(zip(_DENSE_LAYER if arch is None else _MOE_LAYER,
                          lp))
        if kind != "ffn":
            hn = norm(hcur, lp["in_norm"])
            if kind == "linear":
                mixed, *lin = linear_mixer(hn, lp, *lin)
            elif kind == "ssm":
                mixed, *lin = ssm_mixer(hn, lp, *lin)
            else:
                mixed, pools = attention_mixer(hn, lp, pools, li)
            hcur = _tpc(hcur + mixed, shardings)
        lin = tuple(lin) if lin else None
        if "router" in lp:
            hn = norm(hcur, lp["post_norm"])
            if "experts_up" in lp:      # the layer's own [E_held, ..]
                mw, base = lp, 0
            else:                       # every layer's, flattened
                mw = (lp["router"], egw, euw, edw, lp["shared_gate"],
                      lp["shared_up"], lp["shared_down"],
                      lp["shared_expert_gate"])
                base = li * arch.num_experts
            ff, cnt = moe_ffn(hn, mw, arch, moe_live, moe_group,
                              shardings, expert_base=base)
            return (_tpc(hcur + ff, shardings), pools, lin), cnt
        if "gate" in lp:
            hn = norm(hcur, lp["post_norm"])
            ff = _tpc(_nn.silu(_mm(hn, lp["gate"])) * _mm(hn, lp["up"]),
                      shardings, 1)
            hcur = _tpc(hcur + _mm(_tpc(ff, shardings), lp["down"]),
                        shardings)
        return (hcur, pools, lin), None

    pools = (k_pages, v_pages, k_scales, v_scales)
    if hybrid is None:
        def scan_body(carry, xs):
            (hcur, pools, _), cnt = layer("full", *carry, *xs)
            return (hcur, pools), cnt
        (x, pools), cnts = jax.lax.scan(
            scan_body, (x, pools),
            (jnp.arange(n_layers, dtype=jnp.int32), scanned))
        x = norm(x, norm_w)
    else:
        # the period's layers one by one: the KV pools hold the full
        # layers only (``fi`` counts them), each linear layer has its
        # own state and conv-window array (``ji``)
        rec_state, conv_state = list(rec_state), list(conv_state)
        cnts, fi, ji = [], 0, 0
        for kind, lp in zip(hybrid.kinds, stack):
            if kind in ("linear", "ssm"):
                (x, pools, lin), cnt = layer(
                    kind, x, pools, None, lp,
                    (rec_state[ji], conv_state[ji]))
                rec_state[ji], conv_state[ji] = lin
                ji += 1
            else:
                (x, pools, _), cnt = layer(kind, x, pools,
                                           jnp.int32(fi), lp)
                fi += kind == "full"
            if cnt is not None:         # one row an expert layer
                cnts.append(cnt)
        cnts = jnp.stack(cnts) if cnts else None
        x = norm(x, norm_w)
    k_pages, v_pages, k_scales, v_scales = pools
    logits = _tpc(jnp.matmul(x, head_w.T) if transpose_head
                  else _mm(x, head_w), shardings)
    key, sub = split_step(key)
    row_ids = None if strategy == "greedy_search" else \
        draw_base + jnp.arange(t, dtype=jnp.int32)
    nxt, _ = sample_logits(logits, sub, strategy=strategy,
                           top_k=top_k, top_p=top_p,
                           temperature=temperature, row_ids=row_ids)
    if arch is None:
        out = (nxt, k_pages, v_pages, k_scales, v_scales, key)
    else:
        out = (nxt, k_pages, v_pages, k_scales, v_scales, key, cnts)
    if hybrid is not None:
        out = out + (tuple(rec_state), tuple(conv_state))
    if return_probs:
        # static flag (speculative verify, sampled mode): append the
        # per-row post-filter target distribution — the p surface the
        # rejection acceptance consumes — WITHOUT touching the default
        # trace (greedy speculative verify reuses the plain program)
        from ..nn.generation import filtered_probs
        return out + (filtered_probs(
            logits, strategy=strategy, top_k=top_k, top_p=top_p,
            temperature=temperature),)
    return out


@functools.partial(
    __import__("jax").jit,
    static_argnames=("eps", "kvh", "head_dim", "transpose_head",
                     "strategy", "top_k", "top_p", "temperature",
                     "shardings", "arch", "return_probs", "hybrid"),
    donate_argnames=("k_pages", "v_pages", "k_scales", "v_scales",
                     "rec_state", "conv_state"))
def _paged_mixed_step(stack, norm_w, head_w, embed_w, rope,
                      k_pages, v_pages, k_scales, v_scales,
                      ids, positions, row_tables,
                      q_start, q_len, kv_len, desc_tables,
                      desc_of_row, off_of_row, key, draw_base=0,
                      rec_state=None, conv_state=None, desc_slot=None, *,
                      eps: float, kvh: int, head_dim: int,
                      transpose_head: bool = False,
                      strategy: str = "greedy_search", top_k: int = 0,
                      top_p: float = 1.0, temperature: float = 1.0,
                      shardings=None, arch=None, return_probs=False,
                      hybrid=None):
    """ONE compiled program for the whole MIXED prefill+decode batch
    (the ragged unified step): a flat token batch of T rows — every
    active decode slot contributes 1 row, each pending prefill chunk
    up to page_size rows — runs the full decoder once, appending every
    row's K/V at its own position and attending each row over its own
    sequence's pages under the causal mask ``kv_pos <= position``.

    All batch-mix information is TRACED data (row ids/positions/tables
    and the per-descriptor (q_start, q_len, kv_len) scalars the TPU
    kernel prefetches), so one XLA program serves every interleaving —
    ``mixed_compiles() == 1`` however prefill chunks and decode slots
    mix.  On TPU the attention+append is the ragged Pallas kernel
    (descriptor outputs gathered back to flat rows via the host-built
    (desc_of_row, off_of_row) map); on CPU it is the per-row jnp
    mirror, bit-compatible with the split prefill/decode programs.

    ids/positions [T] int32 (position = the row's kv length before its
    own append); row_tables [T, maxp]; q_start/q_len/kv_len [S] with
    ``q_len == 0`` marking unused descriptors; desc_tables [S, maxp].
    Dead padding rows carry position 0 and the all-zero table — their
    writes land in the reserved pad page.  Returns (next_token [T],
    k_pages', v_pages', k_scales', v_scales', key') — the key after
    this step's ``split_step``, which the window program's loop carries
    to its next step.  With an MoE ``arch`` the
    return gains a trailing routed-token counts [L, E]; with a
    ``hybrid`` backbone the per-slot recurrent state and conv-window
    arrays (``rec_state`` / ``conv_state``, donated and aliased like
    the pools) follow the counts, and ``desc_slot`` [S] names each
    descriptor's sequence slot.

    The engine's step launches this behind ``_packed_mixed_step``
    (every int32 input above one upload, ``row_tables`` gathered from
    ``desc_tables[desc_of_row]``, what the host reads one array);
    speculative verify, the tests and the compile guards call it as it
    stands."""
    return _mixed_forward(
        stack, norm_w, head_w, embed_w, rope,
        k_pages, v_pages, k_scales, v_scales,
        ids, positions, row_tables, q_start, q_len, kv_len,
        desc_tables, desc_of_row, off_of_row, key, draw_base,
        rec_state, conv_state, desc_slot,
        eps=eps, kvh=kvh, head_dim=head_dim,
        transpose_head=transpose_head, strategy=strategy,
        top_k=top_k, top_p=top_p, temperature=temperature,
        shardings=shardings, arch=arch, return_probs=return_probs,
        hybrid=hybrid)


@functools.partial(
    __import__("jax").jit,
    static_argnames=("eps", "kvh", "head_dim", "transpose_head",
                     "strategy", "top_k", "top_p", "temperature",
                     "n_steps", "shardings", "arch", "hybrid"),
    donate_argnames=("k_pages", "v_pages", "k_scales", "v_scales",
                     "rec_state", "conv_state"))
def _paged_mixed_window(stack, norm_w, head_w, embed_w, rope,
                        k_pages, v_pages, k_scales, v_scales,
                        ids, positions, row_tables,
                        q_start, q_len, kv_len, desc_tables,
                        desc_of_row, off_of_row, key, draw_base,
                        eos_ids, budgets, n_rows,
                        rec_state=None, conv_state=None, desc_slot=None,
                        *, eps: float, kvh: int, head_dim: int,
                        transpose_head: bool = False,
                        strategy: str = "greedy_search", top_k: int = 0,
                        top_p: float = 1.0, temperature: float = 1.0,
                        n_steps: int = 2, shardings=None, arch=None,
                        hybrid=None):
    """The step loop's ON-DEVICE decode window: up to ``n_steps``
    pure-decode steps of ``_mixed_forward`` — attend+append (the
    ragged kernel, aliases intact), sample, feed-back — chained in a
    ``lax.while_loop`` so the whole window is ONE dispatch, with EARLY
    EXIT once every live row has retired (its EOS ``eos_ids[i]``, −1
    for none, or its remaining budget ``budgets[i]``).  The in-graph
    feedback is what per-token stepping does on the host: row < n_rows
    gets its sampled token as the next input with position/kv_len
    bumped — including already-retired rows, whose surplus tokens the
    host merge discards (masking them would cost a select on every
    tensor; their appends land in pages that release at retirement).
    The key chains through ``split_step`` inside the graph — one
    ``jax.random.split`` a step (``sampling.window_keys``).

    Only pure-decode windows dispatch here (the caller forces
    ``nsteps == 1`` whenever prefill chunks are packed), so q_len is
    constant 1 for live rows across the loop.  Returns
    (tokens [n_steps, T] — step rows ≥ steps_done zero-filled —,
    emitted [T] per-row delivered counts, steps_done, k_pages',
    v_pages', k_scales', v_scales', key') — plus a trailing
    routed-token counts [L, E] with an MoE ``arch`` (accumulated over
    the whole window, retired rows included).  A ``hybrid``
    backbone's recurrent state and conv windows ride the loop's carry
    like the pools and come back after the counts.  The engine launches
    it behind ``_packed_mixed_window``: one upload in; tokens,
    ``steps_done``, the window key and the counts out as one array."""
    import jax
    import jax.numpy as jnp

    t = ids.shape[0]
    live = jnp.arange(t) < n_rows
    # decode row i is descriptor i; a backbone may have fewer
    # descriptors than rows
    live_desc = live if kv_len.shape[0] == t else \
        jnp.arange(kv_len.shape[0]) < n_rows
    toks0 = jnp.zeros((n_steps, t), jnp.int32)
    state0 = (ids, positions, kv_len, k_pages, v_pages, k_scales,
              v_scales, key)
    if arch is not None:
        state0 = state0 + (jnp.zeros(
            (sum("router" in lp for lp in stack) if hybrid is not None
             else stack[0].shape[0], arch.num_experts), jnp.int32),)
    if hybrid is not None:
        state0 = state0 + (rec_state, conv_state)
    carry0 = (jnp.zeros((), jnp.int32), state0, toks0,
              jnp.logical_not(live), jnp.zeros(t, jnp.int32))

    def cond(carry):
        si, _, _, done, _ = carry
        return jnp.logical_and(si < n_steps,
                               jnp.logical_not(jnp.all(done)))

    def body(carry):
        si, state, toks, done, emitted = carry
        (ids, positions, kv_len, k_pages, v_pages, k_scales, v_scales,
         key) = state[:8]
        cacc = state[8] if arch is not None else None
        rec, conv = state[9:11] if hybrid is not None else (None, None)
        res = _mixed_forward(
            stack, norm_w, head_w, embed_w, rope,
            k_pages, v_pages, k_scales, v_scales,
            ids, positions, row_tables, q_start, q_len, kv_len,
            desc_tables, desc_of_row, off_of_row, key, draw_base,
            rec, conv, desc_slot,
            eps=eps, kvh=kvh, head_dim=head_dim,
            transpose_head=transpose_head, strategy=strategy,
            top_k=top_k, top_p=top_p, temperature=temperature,
            shardings=shardings, arch=arch, hybrid=hybrid)
        (nxt, k_pages, v_pages, k_scales, v_scales, key) = res[:6]
        nxt = nxt.astype(jnp.int32)
        toks = jax.lax.dynamic_update_slice(toks, nxt[None], (si, 0))
        fresh = jnp.logical_not(done)
        emitted = emitted + fresh.astype(jnp.int32)
        hit_eos = jnp.logical_and(eos_ids >= 0, nxt == eos_ids)
        done = jnp.logical_or(
            done, jnp.logical_and(fresh, jnp.logical_or(
                hit_eos, emitted >= budgets)))
        # the token feed-back, in-graph: live rows advance, pad
        # rows keep position 0 / the pad table
        ids = jnp.where(live, nxt, ids)
        positions = jnp.where(live, positions + 1, positions)
        kv_len = jnp.where(live_desc, kv_len + 1, kv_len)
        state = (ids, positions, kv_len, k_pages, v_pages, k_scales,
                 v_scales, key)
        if arch is not None:
            state = state + (cacc + res[6],)
        if hybrid is not None:
            state = state + res[7:9]
        return (si + 1, state, toks, done, emitted)

    si, state, toks, done, emitted = jax.lax.while_loop(
        cond, body, carry0)
    (_, _, _, k_pages, v_pages, k_scales, v_scales, key) = state[:8]
    if arch is None:
        return (toks, emitted, si, k_pages, v_pages, k_scales,
                v_scales, key)
    return (toks, emitted, si, k_pages, v_pages, k_scales, v_scales,
            key) + state[8:]


# -- one upload in, one read-back out ------------------------------------------
# What ``LLMEngine._step_mixed`` launches: the two programs above behind
# a wrapper that takes every host-made int32 input as ONE buffer and
# gives every host-read result as ONE small int32 array, so a step
# crosses the host-device boundary once each way.

@functools.lru_cache(maxsize=None)
def _step_layout(t_cap: int, s_cap: int, maxp: int, hybrid: bool):
    """Where each int32 input of a unified step lies in the step's one
    upload: ``(((name, offset, shape), ...), size)``.  The geometry is
    one of the two the engine observes at construction (rows,
    descriptors, pages a sequence), all static: the mixed step's —
    slots + prefill budget rows, a descriptor a row or the hybrid
    backbone's cap — or the decode window's — one row and one
    descriptor a slot, and for a hybrid backbone one dead descriptor
    more, which its padding rows name.
    ``row_tables`` is not here: it is ``desc_tables[desc_of_row]``, row
    for row, and the wrappers gather it on the device."""
    shapes = [(name, (t_cap,)) for name in (
        "ids", "positions", "desc_of_row", "off_of_row", "eos_ids",
        "budgets")]
    shapes += [(name, (s_cap,)) for name in ("q_start", "q_len", "kv_len")]
    if hybrid:
        shapes.append(("desc_slot", (s_cap,)))
    shapes.append(("desc_tables", (s_cap, maxp)))
    shapes += [(name, ()) for name in ("draw_base", "n_rows")]
    fields, off = [], 0
    for name, shape in shapes:
        fields.append((name, off, shape))
        off += int(np.prod(shape, dtype=np.int64))
    return tuple(fields), off


def _unpack_step(packed, geom, hybrid: bool):
    """The upload taken apart at its static offsets (views on the host,
    slices XLA folds into their consumers on the device)."""
    fields, size = _step_layout(*geom, hybrid)
    assert packed.shape == (size,), (packed.shape, size)
    return {name: packed[off:off + int(np.prod(shape, dtype=np.int64))]
            .reshape(shape) for name, off, shape in fields}


def _descriptor_args(f):
    """The unpacked upload as the inner programs take it, ``ids`` to
    ``off_of_row``, with ``row_tables`` gathered from the descriptors'
    tables (the CPU mirror reads it; the ragged kernel does not)."""
    import jax.numpy as jnp

    return (f["ids"], f["positions"],
            jnp.take(f["desc_tables"], f["desc_of_row"], axis=0),
            f["q_start"], f["q_len"], f["kv_len"], f["desc_tables"],
            f["desc_of_row"], f["off_of_row"])


def _pack_result(toks, steps_done, sub, counts, shardings):
    """Everything the host reads of a step, as one int32 array: the
    sampled tokens, ``steps_done``, the window key's two words, the
    routed counts [L, E] (MoE)."""
    import jax
    import jax.numpy as jnp

    parts = [toks.astype(jnp.int32).ravel(),
             jnp.full((1,), steps_done, jnp.int32),
             jax.lax.bitcast_convert_type(sub, jnp.int32).ravel()]
    if counts is not None:
        parts.append(counts.astype(jnp.int32).ravel())
    return _tpc(jnp.concatenate(parts), shardings)


def _unpack_result(host, n_toks: int, counts_shape):
    """``_pack_result``'s inverse on the host copy: (tokens [n_toks],
    steps_done, window-key fingerprint, counts or None)."""
    words = host[n_toks + 1:n_toks + 3].view(np.uint32)
    counts = None
    if counts_shape is not None:
        counts = host[n_toks + 3:].reshape(counts_shape)
    return (host[:n_toks], int(host[n_toks]), [int(w) for w in words],
            counts)


@functools.partial(
    __import__("jax").jit,
    static_argnames=("geom", "eps", "kvh", "head_dim", "transpose_head",
                     "strategy", "top_k", "top_p", "temperature",
                     "shardings", "arch", "hybrid"),
    donate_argnames=("k_pages", "v_pages", "k_scales", "v_scales",
                     "rec_state", "conv_state"))
def _packed_mixed_step(stack, norm_w, head_w, embed_w, rope,
                       k_pages, v_pages, k_scales, v_scales,
                       packed, key, rec_state=None, conv_state=None, *,
                       geom, eps: float, kvh: int, head_dim: int,
                       transpose_head: bool = False,
                       strategy: str = "greedy_search", top_k: int = 0,
                       top_p: float = 1.0, temperature: float = 1.0,
                       shardings=None, arch=None, hybrid=None):
    """``_paged_mixed_step`` with one transfer each way.  ``packed`` is
    the step's ONE upload (``_step_layout``), ``key`` the engine's
    sampling key, which never leaves the device: it is split here, the
    same bits as ``jax.random.split`` on the host.  Returns (result —
    ``_pack_result``, the ONE array the host reads —, k_pages',
    v_pages', k_scales', v_scales', engine key') plus a hybrid
    backbone's state arrays; pools and state donated and aliased as in
    the inner program."""
    f = _unpack_step(packed, geom, hybrid is not None)
    next_key, sub = _sampling.split_step(key)
    res = _mixed_forward(
        stack, norm_w, head_w, embed_w, rope,
        k_pages, v_pages, k_scales, v_scales,
        *_descriptor_args(f), sub, f["draw_base"],
        rec_state, conv_state, f.get("desc_slot"),
        eps=eps, kvh=kvh, head_dim=head_dim,
        transpose_head=transpose_head, strategy=strategy,
        top_k=top_k, top_p=top_p, temperature=temperature,
        shardings=shardings, arch=arch, hybrid=hybrid)
    out = _pack_result(res[0], 1, sub,
                       None if arch is None else res[6], shardings)
    return (out,) + res[1:5] + (_tpc(next_key, shardings),) + res[7:9]


@functools.partial(
    __import__("jax").jit,
    static_argnames=("geom", "eps", "kvh", "head_dim", "transpose_head",
                     "strategy", "top_k", "top_p", "temperature",
                     "n_steps", "shardings", "arch", "hybrid"),
    donate_argnames=("k_pages", "v_pages", "k_scales", "v_scales",
                     "rec_state", "conv_state"))
def _packed_mixed_window(stack, norm_w, head_w, embed_w, rope,
                         k_pages, v_pages, k_scales, v_scales,
                         packed, key, rec_state=None, conv_state=None, *,
                         geom, eps: float, kvh: int, head_dim: int,
                         transpose_head: bool = False,
                         strategy: str = "greedy_search", top_k: int = 0,
                         top_p: float = 1.0, temperature: float = 1.0,
                         n_steps: int = 2, shardings=None, arch=None,
                         hybrid=None):
    """``_paged_mixed_window`` with one transfer each way: same upload,
    same returns as ``_packed_mixed_step`` (the result's tokens are
    [n_steps, T] flattened, its ``steps_done`` the loop's count)."""
    f = _unpack_step(packed, geom, hybrid is not None)
    next_key, sub = _sampling.split_step(key)
    res = _paged_mixed_window(
        stack, norm_w, head_w, embed_w, rope,
        k_pages, v_pages, k_scales, v_scales,
        *_descriptor_args(f), sub, f["draw_base"],
        f["eos_ids"], f["budgets"], f["n_rows"],
        rec_state, conv_state, f.get("desc_slot"),
        eps=eps, kvh=kvh, head_dim=head_dim,
        transpose_head=transpose_head, strategy=strategy,
        top_k=top_k, top_p=top_p, temperature=temperature,
        n_steps=n_steps, shardings=shardings, arch=arch, hybrid=hybrid)
    out = _pack_result(res[0], res[2], sub,
                       None if arch is None else res[8], shardings)
    return (out,) + res[3:7] + (_tpc(next_key, shardings),) + res[9:11]


class LLMEngine:
    """Continuous batching for backbone-registered models (Llama and
    Qwen2-MoE/DeepSeekMoE families; see inference/backbone.py)."""

    def __init__(self, model, max_seqs: int = 8, max_len: int = 2048,
                 page_size: int = 128, n_pages: Optional[int] = None,
                 dtype=np.float32, decode_strategy: str = "greedy_search",
                 top_k: int = 0, top_p: float = 1.0,
                 temperature: float = 1.0, seed: int = 0,
                 steps_per_sync: int = 1,
                 kv_dtype: Optional[str] = None,
                 weight_dtype: Optional[str] = None,
                 enable_metrics: bool = True,
                 enable_prefix_caching: Optional[bool] = None,
                 swap_pool_pages: Optional[int] = None,
                 prefill_token_budget: Optional[int] = None,
                 mesh=None, tp_axis: str = "tp",
                 moe_dispatch: str = "grouped",
                 moe_dropless: bool = True,
                 moe_capacity_factor: Optional[float] = None,
                 draft_model=None, spec_k: int = 4):
        import math

        import jax
        import jax.numpy as jnp

        from ..quantization.layers import QuantizedLinear
        from ..quantization.ops import quantize_absmax_raw
        from .backbone import resolve_backbone
        from .moe_dispatch import MoEArch

        enforce(decode_strategy in ("greedy_search", "sampling"),
                f"unsupported decode_strategy {decode_strategy!r}")
        enforce(steps_per_sync >= 1, "steps_per_sync must be >= 1")
        enforce(kv_dtype in (None, "int8", "float32", "bfloat16",
                             "float16"),
                f"unsupported kv_dtype {kv_dtype!r}")
        enforce(weight_dtype in (None, "int8"),
                f"unsupported weight_dtype {weight_dtype!r}")
        enforce(moe_dispatch in ("grouped", "dense"),
                f"unsupported moe_dispatch {moe_dispatch!r}")
        # steps_per_sync > 1: a pure-decode window runs as ONE compiled
        # while_loop program (attend → sample → KV-append chained
        # in-graph, early exit when every row retires) whose step body
        # IS the single-step program's body
        self.steps_per_sync = steps_per_sync
        self.last_window_steps = 0
        self.decode_strategy = decode_strategy
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.temperature = float(temperature)
        self._key = jax.random.PRNGKey(seed)
        self.model = model
        self.max_seqs = max_seqs
        self.max_len = max_len
        self.kv_dtype = kv_dtype
        self.weight_dtype = weight_dtype
        # ONE compiled program serves every mixed prefill+decode
        # batch.  The STATIC prefill-token budget sizes its flat batch
        # (T = max_seqs + budget rows; a pure-decode window's program
        # runs max_seqs rows); the runtime budget
        # (``prefill_token_budget`` attribute) can be lowered per step
        # — e.g. by a scheduler's decode-latency SLO loop — without
        # recompiling, since T never changes.
        self._pf_budget_static = int(prefill_token_budget) \
            if prefill_token_budget is not None else page_size
        enforce(self._pf_budget_static >= 1,
                "prefill_token_budget must be >= 1")
        self.prefill_token_budget = self._pf_budget_static
        self._prefilling: List[GenRequest] = []
        # host-side prefix-cache stats (kept even with metrics off —
        # the benchmark and tests read them directly)
        self.prefix_stats = {"hit_tokens": 0, "miss_tokens": 0,
                             "shared_pages": 0, "hit_requests": 0,
                             "miss_requests": 0}
        # the backbone seam: resolve the model family by duck typing
        # (llama / qwen2_moe; see inference/backbone.py) instead of
        # the old hardwired ``model.llama.*`` reads
        spec = resolve_backbone(model)
        self._backbone = spec
        c = spec.config
        self.eps = c.rms_norm_eps
        self.kvh = c.num_key_value_heads
        self.head_dim = c.hidden_size // c.num_attention_heads
        layers = spec.layers
        # a backbone whose layers are of several kinds (linear-attention
        # layers with a per-slot recurrent state beside full-attention
        # layers over KV pages) is admitted through ``begin_request``
        # only, and what the step does not carry for it yet is refused
        # HERE, one clear error each, never silently
        self._hybrid = hy = spec.hybrid
        if hy is not None:
            def refuse(bad, what, why):
                if bad:
                    raise ValueError(
                        f"LLMEngine({spec.arch}): {what} is not "
                        f"supported for a backbone with recurrent "
                        f"(linear-attention or state-space) layers — "
                        f"{why}")
            refuse(enable_prefix_caching, "enable_prefix_caching=True",
                   "a prefix hit would need the recurrent state at the "
                   "prefix boundary, which the cache does not snapshot "
                   "(it builds with prefix caching off)")
            refuse(mesh is not None, "mesh=",
                   "the recurrent state pools and their mixers have no "
                   "tensor-parallel plan")
            refuse(draft_model is not None, "draft_model=",
                   "speculative verify cannot roll a recurrent state "
                   "back")
            refuse(kv_dtype == "int8", "kv_dtype='int8'",
                   "the full-attention layers' int8 pools are not "
                   "carried through")
            refuse(weight_dtype == "int8", "weight_dtype='int8'",
                   "the per-layer weight dicts carry no int8 scales")
            refuse(not moe_dropless, "moe_dropless=False",
                   "capacity-factor dispatch over a held expert share "
                   "is not carried through")
            self.head_dim = int(c.head_dim)
            enable_prefix_caching = False
            # descriptors a step.  A step's live ones are at most one a
            # slot (a decode row, or the chunk that ends a prompt), the
            # whole pages the budget holds, a chunk that starts
            # mid-page and one the budget cuts: max_seqs + budget/P + 2.
            # One more stays dead, for the padding rows.  The ragged
            # kernel builds a page of row blocks for EVERY descriptor,
            # so at 16k of context and head 256 one a row (the other
            # backbones' T) would move a gigabyte a step for
            # descriptors that are never used.
            self._desc_cap = max_seqs + 3 + \
                -(-self._pf_budget_static // page_size)
        self.enable_prefix_caching = True \
            if enable_prefix_caching is None \
            else bool(enable_prefix_caching)
        # freeze the MoE router geometry into ONE hashable static jit
        # argument — None keeps every Llama program trace byte
        # identical to the pre-seam engine
        self._arch = None
        if spec.moe is not None:
            m = spec.moe
            cf = float(moe_capacity_factor
                       if moe_capacity_factor is not None
                       else m["capacity_factor"])
            # capacity-factor mode: per-page-group per-expert slot cap
            # (a group = one prefill page chunk of page_size rows;
            # decode rows are singleton groups and never drop)
            cap = 0 if moe_dropless else max(
                int(math.ceil(m["top_k"] * page_size * cf
                              / m["num_experts"])), 1)
            self._arch = MoEArch(
                num_experts=int(m["num_experts"]),
                top_k=int(m["top_k"]), norm_topk=bool(m["norm_topk"]),
                capacity=cap, shared=bool(m["shared"]),
                shared_gate=bool(m["shared_gate"]),
                attn_bias=bool(spec.attn_bias),
                dispatch=moe_dispatch,
                expert_lo=int(m.get("expert_lo", 0)),
                experts_held=int(m.get("experts_held", 0)),
                scoring=m.get("scoring", "softmax"),
                route_scale=float(m.get("route_scale", 1.0)),
                expert_act=m.get("expert_act", "silu_glu"))
            if cap:
                # capacity ranks are defined per page-group, so the
                # step's planner packs WHOLE page chunks in this
                # mode — the static budget must fit one
                enforce(self._pf_budget_static >= page_size,
                        "capacity-factor MoE needs "
                        f"prefill_token_budget >= page_size "
                        f"({page_size}) — the planner packs whole "
                        "page chunks so capacity ranks match "
                        "add_request's chunked prefill")
        # tensor-parallel serving (``mesh=``): attention heads and MLP
        # hidden shard over the ``tp_axis`` of the given 1-D mesh
        # (distributed.topology.serving_mesh builds one); the paged KV
        # pools shard on their KV-head axis so each chip holds
        # num_kv_heads/tp heads of EVERY page.  The plan is a hashable
        # static jit arg — one extra trace per mesh shape, zero when
        # mesh is None (the constraints vanish and the programs are
        # the single-chip ones byte for byte).
        self._shardings = None
        self._step_sharding = None      # the step's upload: replicated
        if mesh is not None:
            from ..distributed.sharding import TPShardings
            self._shardings = TPShardings(mesh, tp_axis)
            self._step_sharding = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec())
            tp = self._shardings.tp
            nh = c.num_attention_heads
            enforce(tp >= 1 and mesh.shape.get(tp_axis) is not None,
                    f"mesh has no {tp_axis!r} axis: {mesh!r}")
            enforce(self.kvh % tp == 0,
                    f"tp={tp} must divide num_key_value_heads "
                    f"({self.kvh}) — each shard holds whole KV heads")
            enforce(nh % tp == 0,
                    f"tp={tp} must divide num_attention_heads ({nh})")
        if n_pages is None:
            n_pages = max_seqs * (max_len // page_size) + 1
        if kv_dtype not in (None, "int8"):
            dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
                     "float16": jnp.float16}[kv_dtype]
        # host swap pool for preemption: default as many pages as the
        # device pool (host DRAM is cheap next to HBM; 0 disables swap
        # and makes every resume recompute)
        if swap_pool_pages is None:
            swap_pool_pages = n_pages
        self.cache = PagedKVCache(
            n_pages=n_pages, page_size=page_size, n_kv_heads=self.kvh,
            head_dim=self.head_dim, max_seqs=max_seqs, max_len=max_len,
            dtype=dtype,
            num_layers=len(layers) if hy is None else hy.n_full,
            kv_dtype="int8" if kv_dtype == "int8" else None,
            swap_pool_pages=swap_pool_pages,
            shardings=self._shardings, state_spec=hy)

        def stackp(get):
            return jnp.stack([get(l).value for l in layers])

        def stackw(get):
            """Stack one projection across layers: fp array, or
            (int8 values, f32 scales) when the model's Linears were
            quantize_model'd or weight_dtype='int8' asks for it."""
            mods = [get(l) for l in layers]
            if any(isinstance(m, QuantizedLinear) for m in mods):
                enforce(all(isinstance(m, QuantizedLinear)
                            for m in mods),
                        "mixed fp/int8 Linears across decoder layers")
                return (jnp.stack([m.qweight.value for m in mods]),
                        jnp.stack([m.weight_scale.value
                                   for m in mods]))
            ws = jnp.stack([m.weight.value for m in mods])
            if weight_dtype == "int8":
                # per-(layer, out-channel) absmax over the in axis
                return quantize_absmax_raw(ws, axis=1)
            return ws

        if hy is not None:
            # one weight dict a layer, the model's own arrays: nothing
            # is stacked, so the expert matrices exist ONCE on the
            # device (a second, stacked copy would not fit beside them)
            self._stack = spec.layer_weights
        elif self._arch is None:
            self._stack = (
                stackp(lambda l: l.input_layernorm.weight),
                stackw(lambda l: l.self_attn.q_proj),
                stackw(lambda l: l.self_attn.k_proj),
                stackw(lambda l: l.self_attn.v_proj),
                stackw(lambda l: l.self_attn.o_proj),
                stackp(lambda l: l.post_attention_layernorm.weight),
                stackw(lambda l: l.mlp.gate_proj),
                stackw(lambda l: l.mlp.up_proj),
                stackw(lambda l: l.mlp.down_proj),
            )
        else:
            # MoE stack: 17 per-layer entries.  Attention biases and
            # shared-expert weights that a given config lacks are
            # stacked as [L, 1, 1] zero placeholders — the static arch
            # flags skip their use, and the fixed pytree keeps ONE
            # program signature per geometry.
            zed = jnp.zeros((len(layers), 1, 1), jnp.float32)

            def stackb(get):
                bs = [get(l) for l in layers]
                if bs[0] is None:
                    enforce(all(b is None for b in bs),
                            "mixed biased/bias-free attention across "
                            "decoder layers")
                    return zed
                return jnp.stack([b.value for b in bs])

            def stacke(get, axis):
                """Stack one expert projection [L, E, in, out]; int8
                quantizes per-(layer, expert, out-channel) over the
                contraction ``axis``."""
                ws = jnp.stack([get(l) for l in layers])
                if weight_dtype == "int8":
                    return quantize_absmax_raw(ws, axis=axis)
                return ws

            def stacksh(get):
                mods = [get(l) for l in layers]
                if mods[0] is None:
                    return zed
                return stackw(lambda l: get(l))

            self._stack = (
                stackp(lambda l: l.input_layernorm.weight),
                stackw(lambda l: l.self_attn.q_proj),
                stackb(lambda l: l.self_attn.q_proj.bias),
                stackw(lambda l: l.self_attn.k_proj),
                stackb(lambda l: l.self_attn.k_proj.bias),
                stackw(lambda l: l.self_attn.v_proj),
                stackb(lambda l: l.self_attn.v_proj.bias),
                stackw(lambda l: l.self_attn.o_proj),
                stackp(lambda l: l.post_attention_layernorm.weight),
                # router stays fp — its softmax drives routing and is
                # tiny ([H, E]); expert stacks ride the absmax path
                stackp(lambda l: l.mlp.gate.weight),
                stacke(lambda l: l.mlp.experts.gate_w.value, 2),
                stacke(lambda l: l.mlp.experts.up_w.value, 2),
                stacke(lambda l: l.mlp.experts.down_w.value, 2),
                stacksh(lambda l: l.mlp.shared_gate),
                stacksh(lambda l: getattr(l.mlp, "shared_up", None)),
                stacksh(lambda l: getattr(l.mlp, "shared_down", None)),
                stacksh(lambda l: l.mlp.shared_expert_gate),
            )
        self._norm_w = spec.norm.weight.value
        # tied embeddings: keep the [V, H] weight and transpose in-graph
        # (an eager .T would hold a duplicate of the full vocab matrix)
        self._tied = spec.lm_head is None
        if self._tied:
            self._head_w = spec.embed_tokens.weight.value
        elif isinstance(spec.lm_head, QuantizedLinear):
            self._head_w = (spec.lm_head.qweight.value,
                            spec.lm_head.weight_scale.value)
        elif weight_dtype == "int8":
            self._head_w = quantize_absmax_raw(
                spec.lm_head.weight.value, axis=0)
        else:
            self._head_w = spec.lm_head.weight.value
        self._embed_w = spec.embed_tokens.weight.value
        rope = np.asarray(spec.rope_cos.value), \
            np.asarray(spec.rope_sin.value)
        self._rope = (jnp.asarray(rope[0]), jnp.asarray(rope[1]))
        # the chunked prefill slices a FULL page of rope rows at the
        # last chunk's base; pad the tables to a page multiple so
        # dynamic_slice never clamps the start (clamping would rotate
        # the prompt tail by wrong angles when max_position_embeddings
        # is not a page multiple).  The padded rows back padding ids
        # only — real positions stay < max_position_embeddings by the
        # admission limit check.
        maxpos = rope[0].shape[0]
        pad_to = -(-max(maxpos, page_size) // page_size) * page_size
        if pad_to != maxpos:
            padr = ((0, pad_to - maxpos), (0, 0))
            self._rope_prefill = (
                jnp.asarray(np.pad(rope[0], padr)),
                jnp.asarray(np.pad(rope[1], padr)))
        else:
            self._rope_prefill = self._rope

        if self._shardings is not None:
            # commit every program input up front: projection weights
            # shard on their OUTPUT axis (int8 (values, scales) pairs
            # travel together), everything that feeds a contraction or
            # a norm stays replicated — the device_put placements and
            # the in-graph _tpc constraints are the same plan, so
            # GSPMD never has to guess (a guessed partial-sum would
            # break tp=1 vs tp=N bit-identity).
            sh = self._shardings

            def _put(w, dim):
                if isinstance(w, tuple):
                    return tuple(_put(a, dim) for a in w)
                d = dim if dim is not None and \
                    w.shape[dim] % sh.tp == 0 else None
                return sh.put(w, d)

            if self._arch is None:
                # stack order: iln, qw, kw, vw, ow, pln, gw, uw, dw —
                # layernorm weights (0, 5) replicate, projections
                # shard on the last (output) axis
                rep = (0, 5)
            else:
                # MoE stack: layernorms (0, 8) and the whole FFN tail
                # (router, expert stacks, shared expert; 9..16)
                # replicate — expert parallelism over the mesh is the
                # carried ROADMAP item; attention projections and
                # biases still shard on their output axis (zed
                # placeholders fall back to replicated via the
                # divisibility check in _put)
                rep = (0, 8) + tuple(range(9, 17))
            self._stack = tuple(
                _put(w, None if i in rep else -1)
                for i, w in enumerate(self._stack))
            self._norm_w = _put(self._norm_w, None)
            self._embed_w = _put(self._embed_w, None)
            if self._tied:
                self._head_w = self._embed_w
            else:
                self._head_w = _put(self._head_w, None)
            same_rope = self._rope_prefill is self._rope
            self._rope = _put(self._rope, None)
            self._rope_prefill = self._rope if same_rope \
                else _put(self._rope_prefill, None)

        self.requests: Dict[object, GenRequest] = {}
        self._active: List[GenRequest] = []
        # host-side per-expert load accounting (kept even with metrics
        # off — metrics_snapshot()/statusz and the benchmark read it):
        # routed-slot counts per (layer, expert) plus the running
        # capacity-drop total (always 0 dropless)
        if self._arch is not None:
            # one row an expert layer (every layer of the scanned
            # backbones; those whose weights bring a router otherwise)
            n_moe = len(layers) if hy is None else sum(
                "router" in lp for lp in spec.layer_weights)
            self._moe_counts = np.zeros(
                (n_moe, self._arch.num_experts), np.int64)
            self._moe_dropped = 0
            self._expert_label_values = None     # set by _init_metrics
            # slots routed to experts this engine does not hold (an
            # expert share): counted, computed by no one here
            self._moe_absent = 0
            # rows of the sorted buffer the grouped dispatch handed its
            # kernels (m_pad x MoE layers x forwards: static a
            # dispatch), which ``row_fill`` divides the kept slots by
            self._moe_buffer_rows = 0
        # dispatches' counts put aside (host numbers already) until
        # ``_fold_expert_counts`` folds them behind the next launch;
        # the lock is for a reader on another thread (``/statusz``)
        self._counts_aside: list = []
        self._counts_lock = threading.Lock()
        self.count_folds = {"behind_launch": 0, "at_idle": 0}
        # the unified step's crossings of the host-device boundary:
        # uploads and blocking reads (1.0 each a step, mixed or window)
        self.host_transfers = {"in": 0, "out": 0}
        # ... and its ONE upload: a host buffer the engine owns, the
        # descriptors views into it at static offsets.  A step copies
        # ``blank`` over it and fills in what is live; it is written
        # only after the step that read it has been read back.  There
        # is one for each of the TWO static geometries, observed here:
        # the mixed step's rows are the slots plus the prefill budget
        # (descriptors one a row, or the hybrid backbone's cap); a
        # decode window is pure decode, so its program runs one row and
        # one descriptor a slot (plus the one dead descriptor a hybrid
        # backbone's padding rows name).  Decode row i is descriptor i
        # in both.
        hybrid = self._hybrid is not None
        t_cap = max_seqs + self._pf_budget_static
        maxp = self.cache.page_table.shape[1]
        self._step_geom = (t_cap, self._desc_cap if hybrid else t_cap,
                           maxp)
        self._window_geom = (max_seqs,
                             max_seqs + 1 if hybrid else max_seqs, maxp)
        self._step_bufs = {geom: self._blank_step(geom) for geom in
                           (self._step_geom, self._window_geom)}
        # rows the launched programs ran (rows of the program x its
        # forwards) beside the rows that were live in them, by path:
        # the step's row fill (shapes and host counts, no transfer)
        self.forward_rows = {
            path: {"capacity": 0, "live": 0}
            for path in ("mixed", "window")}
        # what the linear layers' recurrence was given, per step: rows
        # by kind and live descriptors (host counters, like the prefix
        # stats — the registry series mirror them)
        self.linear_stats = {"prefill_rows": 0, "decode_rows": 0,
                             "descriptors": 0, "state_snapshots": 0,
                             "state_bytes_moved": 0}
        # each live descriptor reads and writes one slot's state and
        # conv window in one recurrent layer
        self._state_bytes_a_descriptor = 0 if not (hy and hy.n_linear) \
            else 2 * (self.cache.state_bytes() // (max_seqs + 1)
                      // hy.n_linear)
        self._init_metrics(enable_metrics)
        # compile-watch registration: this engine's three jit entry
        # points and their warmup allowances (the decode program of
        # recompute resume and capsule replay legitimately compiles
        # one power-of-two window bucket per size, bit_length of
        # steps_per_sync of them; prefill and the mixed step are
        # strictly one-program per geometry).
        # A no-op off one global read when the watch is disabled.
        cw = _insp.get_compile_watch()
        cw.register_program("engine.prefill_chunk")
        cw.register_program("engine.decode_step",
                            expected=int(steps_per_sync).bit_length())
        cw.register_program("engine.mixed_step")
        # on-device windows: one program per power-of-two window bucket
        # {2, 4, ..., 2^floor(log2(steps_per_sync))} — the n_steps==1
        # window degenerates to the plain step program above, so the
        # bucket count is bit_length − 1 and ``mixed_compiles()`` stays
        # bounded by DECLARED allowances (a recompile past them is an
        # anomaly the watch flags)
        wb = max(int(steps_per_sync).bit_length() - 1, 0)
        if wb:
            cw.register_program("engine.mixed_window", expected=wb)
        # the paged KV pool (device pages + host swap) as a first-class
        # /memz row; weakly held so a released engine frees its pages
        _insp.register_memory_consumer(
            f"kv_cache:{self.engine_id}", self.cache)
        # request-capsule config fingerprint: everything a replay needs
        # to decide "same engine config" — cheap dict built once, the
        # model hash is a config hash (never a weight sync)
        self._capsule_fp = {
            "engine": self.engine_id,
            "model_hash": _capsule.model_fingerprint(model),
            "kv_dtype": kv_dtype, "weight_dtype": weight_dtype,
            "page_size": page_size, "n_pages": int(n_pages),
            "max_seqs": max_seqs, "max_len": max_len,
            "steps_per_sync": steps_per_sync,
            "decode_strategy": decode_strategy,
            "top_k": self.top_k, "top_p": self.top_p,
            "temperature": self.temperature, "seed": seed,
            "prefix_caching": self.enable_prefix_caching,
            # deliberately NOT token-affecting (capsule._TOKEN_AFFECTING):
            # tp=1 and tp=N streams are bit-identical by construction,
            # so cross-tp replay is allowed — and asserted in tests
            "tp": self._shardings.tp if self._shardings else 1,
            # TOKEN-AFFECTING router geometry (a tampered router config
            # must refuse replay); dispatch mode is deliberately
            # absent — grouped and dense are bit-identical like tp
            "moe": None if self._arch is None else {
                "num_experts": self._arch.num_experts,
                "top_k": self._arch.top_k,
                "norm_topk": self._arch.norm_topk,
                "dropless": self._arch.capacity == 0,
                "capacity": self._arch.capacity,
                "shared": self._arch.shared,
                "shared_gate": self._arch.shared_gate,
            },
            # TOKEN-AFFECTING speculative geometry (filled by
            # _init_spec): a changed draft model / k / acceptance mode
            # must refuse replay via fingerprint_mismatch.  None for
            # plain engines — greedy speculative streams are
            # bit-identical to plain decode, but SAMPLED acceptance
            # draws depend on the draft's q, so the conservative
            # contract covers both modes.
            "spec": None,
        }
        if hy is not None:
            # TOKEN-AFFECTING: the layer pattern and the held share
            self._capsule_fp["hybrid"] = {"kinds": list(hy.kinds)}
            if self._arch is not None:
                ar = self._arch
                self._capsule_fp["hybrid"].update(
                    expert_lo=ar.expert_lo, experts_held=ar.n_held,
                    router=[ar.scoring, ar.route_scale, ar.expert_act])
        self._spec = None
        if draft_model is not None:
            self._init_spec(draft_model, spec_k, dtype, page_size,
                            weight_dtype)

    def config_fingerprint(self) -> dict:
        """This engine's capsule config fingerprint (copy)."""
        return dict(self._capsule_fp)

    # -- speculative decoding --------------------------------------------------
    def _init_spec(self, draft_model, spec_k: int, dtype, page_size: int,
                   weight_dtype):
        """Attach a DRAFT backbone for speculative decoding: its
        weights stack into the same serving pytrees as the target's
        (dense order — MoE drafts are refused; drafts are small), its
        KV rides a second ``PagedKVCache`` with the draft's geometry,
        and per-request draft slots attach LAZILY at the first
        speculative window (one hook covers admission, deferred
        prefill, resume — both restore paths — and import; suspend /
        abort / retire just release).  The draft always runs
        REPLICATED (``shardings=None``): tp shards the target, whose
        verify dispatch dominates — and greedy acceptance never
        depends on draft numerics, only on how often it matches.

        Compile surface, declared: one extra ``engine.prefill_chunk``
        trace (draft geometry), two ``engine.spec_draft`` traces
        (propose ``n_steps=spec_k`` + 1-step catch-up), one
        ``engine.spec_verify`` trace (the ragged mixed program at the
        static ``T_spec = max_seqs * (spec_k + 1)`` bucket — runtime k
        stays traced data, so churning k never recompiles)."""
        import jax.numpy as jnp

        from ..quantization.layers import QuantizedLinear
        from ..quantization.ops import quantize_absmax_raw
        from .backbone import resolve_backbone

        enforce(spec_k >= 1, "spec_k must be >= 1")
        dspec = resolve_backbone(draft_model)
        enforce(dspec.moe is None,
                "speculative draft must be a dense backbone "
                "(MoE drafts defeat the point of a small draft)")
        c, dc = self._backbone.config, dspec.config
        enforce(dc.vocab_size == c.vocab_size,
                f"draft vocab ({dc.vocab_size}) must match target "
                f"vocab ({c.vocab_size})")
        d_maxpos = int(np.asarray(dspec.rope_cos.value).shape[0])
        t_maxpos = int(np.asarray(self._backbone.rope_cos.value).shape[0])
        enforce(d_maxpos >= min(self.max_len, t_maxpos),
                f"draft max_position_embeddings ({d_maxpos}) too short "
                f"for the engine's sequence limit "
                f"({min(self.max_len, t_maxpos)})")
        self.spec_k = int(spec_k)
        self._spec_mode = "greedy" \
            if self.decode_strategy == "greedy_search" else "rejection"
        layers = dspec.layers

        def stackp(get):
            return jnp.stack([get(l).value for l in layers])

        def stackw(get):
            mods = [get(l) for l in layers]
            if any(isinstance(m, QuantizedLinear) for m in mods):
                enforce(all(isinstance(m, QuantizedLinear)
                            for m in mods),
                        "mixed fp/int8 Linears across draft layers")
                return (jnp.stack([m.qweight.value for m in mods]),
                        jnp.stack([m.weight_scale.value
                                   for m in mods]))
            ws = jnp.stack([m.weight.value for m in mods])
            if weight_dtype == "int8":
                return quantize_absmax_raw(ws, axis=1)
            return ws

        d_stack = (
            stackp(lambda l: l.input_layernorm.weight),
            stackw(lambda l: l.self_attn.q_proj),
            stackw(lambda l: l.self_attn.k_proj),
            stackw(lambda l: l.self_attn.v_proj),
            stackw(lambda l: l.self_attn.o_proj),
            stackp(lambda l: l.post_attention_layernorm.weight),
            stackw(lambda l: l.mlp.gate_proj),
            stackw(lambda l: l.mlp.up_proj),
            stackw(lambda l: l.mlp.down_proj),
        )
        d_tied = dspec.lm_head is None
        if d_tied:
            d_head = dspec.embed_tokens.weight.value
        elif isinstance(dspec.lm_head, QuantizedLinear):
            d_head = (dspec.lm_head.qweight.value,
                      dspec.lm_head.weight_scale.value)
        elif weight_dtype == "int8":
            d_head = quantize_absmax_raw(
                dspec.lm_head.weight.value, axis=0)
        else:
            d_head = dspec.lm_head.weight.value
        rope = (np.asarray(dspec.rope_cos.value),
                np.asarray(dspec.rope_sin.value))
        d_rope = (jnp.asarray(rope[0]), jnp.asarray(rope[1]))
        pad_to = -(-max(d_maxpos, page_size) // page_size) * page_size
        if pad_to != d_maxpos:
            padr = ((0, pad_to - d_maxpos), (0, 0))
            d_rope_prefill = (jnp.asarray(np.pad(rope[0], padr)),
                              jnp.asarray(np.pad(rope[1], padr)))
        else:
            d_rope_prefill = d_rope
        # draft KV pool: the draft's geometry, full slot capacity (no
        # prefix sharing thins it like the target's), no swap pool —
        # suspended drafts are cheaper to RECOMPUTE than to swap
        self._spec_cache = PagedKVCache(
            n_pages=self.max_seqs * (self.max_len // page_size) + 1,
            page_size=page_size,
            n_kv_heads=dc.num_key_value_heads,
            head_dim=dc.hidden_size // dc.num_attention_heads,
            max_seqs=self.max_seqs, max_len=self.max_len, dtype=dtype,
            num_layers=len(layers),
            kv_dtype="int8" if self.kv_dtype == "int8" else None,
            swap_pool_pages=0, shardings=None)
        self._spec = {
            "stack": d_stack, "norm_w": dspec.norm.weight.value,
            "head_w": d_head, "embed_w": dspec.embed_tokens.weight.value,
            "rope": d_rope, "rope_prefill": d_rope_prefill,
            "tied": d_tied, "eps": dc.rms_norm_eps,
            "kvh": dc.num_key_value_heads,
            "head_dim": dc.hidden_size // dc.num_attention_heads,
        }
        # host-side acceptance accounting (kept even with metrics off —
        # metrics_snapshot()/statusz/the benchmark read it directly):
        # ``accepted`` counts surviving DRAFT tokens only; the bonus /
        # correction token rides ``delivered``
        self.spec_stats = {"windows": 0, "proposed": 0, "accepted": 0,
                           "delivered": 0}
        cw = _insp.get_compile_watch()
        cw.register_program("engine.prefill_chunk")  # draft geometry
        cw.register_program("engine.spec_draft", expected=2)
        cw.register_program("engine.spec_verify")
        _insp.register_memory_consumer(
            f"kv_cache_draft:{self.engine_id}", self._spec_cache)
        self._capsule_fp["spec"] = {
            "draft_hash": _capsule.model_fingerprint(draft_model),
            "k": self.spec_k, "mode": self._spec_mode}
        if self._metrics is not None:
            reg = get_registry()
            lbl = ("engine",)
            eid = self.engine_id
            self._metrics["spec_proposed"] = reg.counter(
                "llm_engine_spec_proposed_total",
                "Draft tokens proposed to speculative verify "
                "windows.", lbl).labels(eid)
            self._metrics["spec_accepted"] = reg.counter(
                "llm_engine_spec_accepted_total",
                "Draft tokens accepted by the target (bonus/"
                "correction tokens excluded).", lbl).labels(eid)
            self._metrics["spec_rate"] = reg.gauge(
                "llm_engine_spec_acceptance_rate",
                "Cumulative accepted/proposed draft-token ratio.",
                lbl).labels(eid)
            # fixed ladder (NOT spec_k-derived): the registry enforces
            # one bucket set per metric name process-wide, and
            # engines with different k must share it
            self._metrics["spec_len"] = reg.histogram(
                "llm_engine_spec_accepted_len",
                "Accepted draft tokens per sequence per speculative "
                "window.", lbl,
                buckets=_SPEC_LEN_BUCKETS).labels(eid)

    # -- metrics ---------------------------------------------------------------
    def _init_metrics(self, enabled: bool):
        """Per-engine children in the global registry (label
        engine=<id>), so concurrent engines scrape apart.  Recording is
        a handful of host float-adds per step WINDOW (never per token:
        TPOT uses the weighted observe)."""
        self.engine_id = str(next(_ENGINE_IDS))
        self._metrics = None
        if not enabled:
            return
        reg = get_registry()
        lbl = ("engine",)
        eid = self.engine_id
        transfers = reg.counter(
            "llm_engine_host_transfers_total",
            "Crossings of the host-device boundary made by unified "
            "steps: uploads (dir=in) and blocking reads (dir=out); "
            "over llm_engine_steps_total, 1.0 each way.",
            ("engine", "dir"))
        folds = reg.counter(
            "llm_engine_count_folds_total",
            "Folds of the routed-expert counts put aside by earlier "
            "dispatches: behind the next launch (the chip busy under "
            "them), or at_idle (the engine left without work, or a "
            "reader asked).", ("engine", "when"))
        capacity = reg.counter(
            "llm_engine_forward_row_capacity_total",
            "Rows the launched step programs ran: rows of the program "
            "(slots + prefill budget for path=mixed, one a slot for "
            "path=window) x the forwards it ran.  The live rows over "
            "this is the step's row fill (metrics_snapshot()"
            "['forward_rows']).", ("engine", "path"))
        self._metrics = {
            "row_capacity_mixed": capacity.labels(eid, "mixed"),
            "row_capacity_window": capacity.labels(eid, "window"),
            "transfers_in": transfers.labels(eid, "in"),
            "transfers_out": transfers.labels(eid, "out"),
            "folds_behind_launch": folds.labels(eid, "behind_launch"),
            "folds_at_idle": folds.labels(eid, "at_idle"),
            "ttft": reg.histogram(
                "llm_engine_ttft_seconds",
                "Time to first token: add_request() entry to the "
                "prefill-produced token (includes any compile).",
                lbl, buckets=_TTFT_BUCKETS).labels(eid),
            "tpot": reg.histogram(
                "llm_engine_tpot_seconds",
                "Per-token decode latency: step() window wall time / "
                "tokens in the window.", lbl,
                buckets=_TPOT_BUCKETS).labels(eid),
            "prompt_tokens": reg.counter(
                "llm_engine_prompt_tokens_total",
                "Prompt tokens admitted.", lbl).labels(eid),
            "generated_tokens": reg.counter(
                "llm_engine_generated_tokens_total",
                "Tokens returned to requests (prefill token "
                "included).", lbl).labels(eid),
            "requests": reg.counter(
                "llm_engine_requests_total",
                "Requests admitted.", lbl).labels(eid),
            "steps": reg.counter(
                "llm_engine_steps_total",
                "step() calls that dispatched a program (one mixed "
                "step or one decode window); generated tokens / this "
                "= tokens per step.", lbl).labels(eid),
            "step_prefill_tokens": reg.counter(
                "llm_engine_step_prefill_tokens_total",
                "Prompt tokens CONSUMED by unified steps (prefill "
                "chunks packed), where llm_engine_prompt_tokens_total "
                "counts whole prompts at admission.", lbl).labels(eid),
            "aborted": reg.counter(
                "llm_engine_aborted_total",
                "Requests cancelled via abort() before finishing "
                "(suspended requests included — their swap entry is "
                "dropped).", lbl).labels(eid),
            "suspended": reg.counter(
                "llm_engine_suspended_total",
                "Requests preempted out of the decode batch "
                "(suspend()).", lbl).labels(eid),
            "resumed": reg.counter(
                "llm_engine_resumed_total",
                "Suspended requests re-admitted, by restore path "
                "(swap_in: host pages copied back; recompute: prompt "
                "+ generated tokens replayed).", ("engine", "path")),
            "migrated_out": reg.counter(
                "llm_engine_migrated_out_total",
                "Suspended requests exported as migration packages "
                "(export_request) — they now belong to another "
                "engine.", lbl).labels(eid),
            "migrated_in": reg.counter(
                "llm_engine_migrated_in_total",
                "Migration packages adopted (import_request) — they "
                "resume here via resume().", lbl).labels(eid),
            "queue_depth": reg.gauge(
                "llm_engine_queue_depth",
                "Requests active in the decode batch.", lbl).labels(eid),
            "occupancy": reg.gauge(
                "llm_engine_batch_occupancy",
                "Active requests / max_seqs in the last decode "
                "window.", lbl).labels(eid),
            "prefix_hit_tokens": reg.counter(
                "llm_engine_prefix_hit_tokens_total",
                "Prompt tokens served from cached prefix pages (no "
                "prefill compute).", lbl).labels(eid),
            "prefix_miss_tokens": reg.counter(
                "llm_engine_prefix_miss_tokens_total",
                "Prompt tokens that ran chunked prefill.",
                lbl).labels(eid),
            "prefix_shared_pages": reg.counter(
                "llm_engine_prefix_shared_pages_total",
                "Cached pages mapped read-shared into admitted "
                "slots.", lbl).labels(eid),
            "prefix_hit_rate": reg.gauge(
                "llm_engine_prefix_cache_hit_rate",
                "Cumulative cached / total prompt tokens (0 when "
                "prefix caching is off or nothing admitted).",
                lbl).labels(eid),
            "mixed_decode_slots": reg.gauge(
                "llm_engine_mixed_batch_decode_slots",
                "Decode rows packed into the last unified mixed "
                "step.", lbl).labels(eid),
            "mixed_prefill_tokens": reg.gauge(
                "llm_engine_mixed_batch_prefill_tokens",
                "Prefill-chunk tokens packed into the last unified "
                "mixed step (interleave ratio = this / (this + decode "
                "slots)).", lbl).labels(eid),
        }
        if self._arch is not None:
            # MoE serving observability: the per-(layer, expert) load
            # counter family plus the imbalance SLO gauge (max/mean
            # per-expert load over all layers — 1.0 is perfect
            # balance, E means one expert takes everything)
            self._metrics["expert_tokens"] = reg.counter(
                "llm_engine_expert_tokens_total",
                "Routed token-slots kept per (layer, expert) — "
                "capacity-dropped slots are excluded (see "
                "llm_engine_expert_dropped_tokens_total).",
                ("engine", "layer", "expert"))
            self._expert_label_values = [
                [(eid, str(l), str(e))
                 for e in range(self._arch.num_experts)]
                for l in range(self._moe_counts.shape[0])]
            self._metrics["expert_dropped"] = reg.counter(
                "llm_engine_expert_dropped_tokens_total",
                "Routed token-slots dropped by the capacity factor "
                "(always 0 dropless).", lbl).labels(eid)
            self._metrics["expert_absent"] = reg.counter(
                "llm_engine_expert_absent_slots_total",
                "Routed token-slots whose expert lies outside the "
                "share this engine holds (they add +0 here; the "
                "deployment's other chips compute them).",
                lbl).labels(eid)
            self._metrics["expert_buffer_rows"] = reg.counter(
                "llm_engine_expert_buffer_rows_total",
                "Rows of the sorted, tile-padded buffer the grouped "
                "expert dispatch handed its kernels, summed over MoE "
                "layers and forwards (kept slots held here over this "
                "is the snapshot's row_fill).", lbl).labels(eid)
            self._metrics["expert_imbalance"] = reg.gauge(
                "llm_engine_expert_imbalance",
                "Max/mean cumulative per-expert routed load across "
                "layers (the MoE balance SLO; 1.0 = uniform).",
                lbl).labels(eid)
        if self._hybrid is not None:
            rows = reg.counter(
                "llm_engine_linear_rows_total",
                "Token rows handed to the linear-attention recurrence, "
                "summed over linear layers, by kind.",
                ("engine", "kind"))
            self._metrics["linear_rows_prefill"] = rows.labels(
                eid, "prefill")
            self._metrics["linear_rows_decode"] = rows.labels(
                eid, "decode")
            self._metrics["linear_descriptors"] = reg.counter(
                "llm_engine_linear_descriptors_total",
                "Live step descriptors handed to the linear-attention "
                "recurrence, summed over linear layers (each reads and "
                "writes one slot's state).", lbl).labels(eid)
            self._metrics["state_bytes"] = reg.gauge(
                "llm_engine_state_bytes",
                "Device bytes of the per-slot recurrent state and "
                "conv-window pools.", lbl).labels(eid)
            self._metrics["state_bytes"].set(self.cache.state_bytes())
            self._metrics["state_bytes_moved"] = reg.counter(
                "llm_engine_state_bytes_moved_total",
                "Recurrent-state bytes the recurrent layers' live "
                "descriptors read plus wrote (each reads and writes one "
                "slot's state and conv window in one layer; from "
                "shapes, nothing transferred).", lbl).labels(eid)
            self._metrics["state_snapshots"] = reg.counter(
                "llm_engine_state_snapshots_total",
                "Per-slot recurrent-state snapshots moved between the "
                "device and the host (suspend, resume, export).",
                lbl).labels(eid)
        # compile-count gauges are process-global (the jit caches are),
        # unlabeled: any drift past 1 means a recompile regression —
        # alarm on it instead of diagnosing a silent latency cliff
        self._metrics["prefill_compiles"] = reg.gauge(
            "llm_engine_prefill_compiles",
            "Distinct compiled prefill programs (expected: 1).")
        self._metrics["decode_compiles"] = reg.gauge(
            "llm_engine_decode_compiles",
            "Distinct compiled decode programs (expected: ~1, at most "
            "log2(steps_per_sync) window buckets).")
        self._metrics["mixed_compiles"] = reg.gauge(
            "llm_engine_mixed_compiles",
            "Distinct compiled unified mixed-step programs "
            "(expected: 1 per engine geometry, plus one scanned "
            "mixed-window program per power-of-two window bucket).")
        self._metrics["window_compiles"] = reg.gauge(
            "llm_engine_window_compiles",
            "Distinct compiled on-device decode-window programs "
            "(expected: at most log2(steps_per_sync) power-of-two "
            "buckets).")

    def _record_compiles(self):
        m = self._metrics
        m["prefill_compiles"].set(self.prefill_compiles())
        m["decode_compiles"].set(self.decode_compiles())
        m["mixed_compiles"].set(self.mixed_compiles())
        m["window_compiles"].set(self.window_compiles())

    def _blank_step(self, geom):
        """One geometry's upload as the host holds it: (blank, buffer,
        the buffer's fields as views at ``_step_layout``'s offsets).
        In the blank every descriptor is dead (``q_len == 0``: its
        kernel output block is zeroed, its table zeros) and every row a
        padding row that names one — its own, or a hybrid backbone's
        last, whose slot is the pad slot."""
        hybrid = self._hybrid is not None
        blank = np.zeros(_step_layout(*geom, hybrid)[1], np.int32)
        f = _unpack_step(blank, geom, hybrid)
        if hybrid:
            f["desc_of_row"][:] = geom[1] - 1
            f["desc_slot"][:] = self.max_seqs
        else:
            f["desc_of_row"][:] = np.arange(geom[0])
        f["eos_ids"][:] = -1
        f["budgets"][:] = 1
        buf = blank.copy()
        return blank, buf, _unpack_step(buf, geom, hybrid)

    def _note_expert_counts(self, counts, routed_slots: int, rows: int,
                            forwards: int = 1):
        """Put one MoE dispatch's routed-token counts ([L, E]: a slice
        of the unified step's one read-back, or another path's device
        array, read here) aside for ``_fold_expert_counts``.
        ``routed_slots`` is the number of live (row, top-k) slots the
        dispatch routed PER LAYER — kept + capacity-dropped; ``rows``
        the token rows (live or padding) each of its ``forwards`` ran
        the expert layer over, which fixes the sorted buffer's size
        (``expert_buffer_rows``: shapes, nothing read).  What an
        earlier dispatch put aside is folded first, behind this one's
        launch, so the counters are never more than one dispatch
        behind.  One read per dispatch WINDOW, never per token."""
        self._fold_expert_counts("behind_launch")
        cnt = np.asarray(counts, np.int64)
        with self._counts_lock:
            self._counts_aside.append(
                (cnt, int(routed_slots), int(rows), int(forwards)))

    def _crossed(self, way: str):
        """A unified step crossed the host-device boundary: one upload
        (``"in"``) or one blocking read (``"out"``)."""
        self.host_transfers[way] += 1
        if self._metrics is not None:
            self._metrics["transfers_" + way].inc()

    def _fold_expert_counts(self, when: str):
        """Fold the counts put aside into the host accounting and the
        registry: ``_moe_counts``, dropped (``routed_slots·L −
        counts.sum()``, identically 0 dropless) and absent slots, the
        labelled counters, the imbalance gauge.  Host numbers only, so
        the loop runs it with the chip busy (``when="behind_launch"``)
        and a reader on another thread may (``"at_idle"``, like the
        step that leaves the engine without work)."""
        if not self._counts_aside:
            return
        with self._counts_lock:
            aside, self._counts_aside = self._counts_aside, []
            if not aside:
                return
            cnt = sum(a[0] for a in aside)
            dropped = sum(a[1] for a in aside) * cnt.shape[0] \
                - int(cnt.sum())
            buf = sum(expert_buffer_rows(self._arch, rows) * forwards
                      for _, _, rows, forwards in aside) * cnt.shape[0]
            self._moe_counts += cnt
            self._moe_dropped += dropped
            self._moe_buffer_rows += buf
            absent = 0
            if self._arch.experts_held:
                lo = self._arch.expert_lo
                absent = int(cnt.sum()
                             - cnt[:, lo:lo + self._arch.n_held].sum())
                self._moe_absent += absent
            self.count_folds[when] += 1
            if self._metrics is None:
                return
            self._metrics["folds_" + when].inc()
            if absent:
                self._metrics["expert_absent"].inc(absent)
            if buf:
                self._metrics["expert_buffer_rows"].inc(buf)
            # hundreds of labelled counts a dispatch (L x E): one lock,
            # label tuples made once
            names = self._expert_label_values
            ls, es = np.nonzero(cnt)
            self._metrics["expert_tokens"].inc_many(
                (names[l][e], v) for l, e, v in zip(
                    ls.tolist(), es.tolist(), cnt[ls, es].tolist()))
            if dropped:
                self._metrics["expert_dropped"].inc(dropped)
            tot = self._moe_counts.sum(axis=0).astype(np.float64)
            if tot.sum() > 0:
                self._metrics["expert_imbalance"].set(
                    float(tot.max() / tot.mean()))

    # -- prefill / replay internals --------------------------------------------
    def _prefill_seq(self, slot, seq, start_chunk: int):
        """Run the single compiled chunked-prefill program over
        ``seq`` in ``slot``, starting at chunk ``start_chunk`` (earlier
        chunks' pages are already written — the prefix-cache-hit
        path).  Returns the last real token's logits row.  Shared by
        admission and the recompute-resume replay: both go through the
        SAME jit entry, so ``prefill_compiles() == 1`` holds across
        preemption too."""
        import jax.numpy as jnp

        P = self.cache.page_size
        plen = len(seq)
        table = np.asarray(self.cache.page_table[slot])
        logits = None
        for ci in range(start_chunk, -(-plen // P)):
            base = ci * P
            chunk = np.zeros(P, np.int32)
            real = min(P, plen - base)
            chunk[:real] = np.asarray(seq[base:base + real], np.int32)
            # per-chunk span (nests under the active admit/prefill
            # span); one object per PAGE of prompt, never per token —
            # and the shared NULL_SPAN when tracing is off
            chunk_span = _tracing.span("engine.prefill_chunk")
            chunk_span.set_attr("chunk", ci).set_attr("tokens", real)
            out = _insp.watched_call(
                "engine.prefill_chunk", _paged_prefill_chunk,
                self._stack, self._norm_w, self._head_w,
                self._embed_w, self._rope_prefill,
                self.cache.k_pages, self.cache.v_pages,
                self.cache.k_scales, self.cache.v_scales,
                jnp.asarray(chunk),
                jnp.asarray(table), jnp.int32(base),
                jnp.int32(int(table[ci])),
                jnp.int32(min(plen - 1 - base, P - 1)),
                eps=self.eps, kvh=self.kvh,
                head_dim=self.head_dim,
                transpose_head=self._tied,
                shardings=self._shardings, arch=self._arch)
            if self._arch is not None:
                self._note_expert_counts(
                    out[-1], real * self._arch.top_k, rows=P)
                out = out[:-1]
            (logits, self.cache.k_pages, self.cache.v_pages,
             self.cache.k_scales, self.cache.v_scales) = out
            chunk_span.end()
        return logits

    def _replay_decode(self, slot, toks):
        """Recompute-resume tail: re-append the KV of already-generated
        ``toks`` through the SAME compiled decode program the original
        run used, ignoring its sampled outputs and never touching the
        engine's sampling key (an unpreempted run's key stream must
        stay reproducible).  Greedy replay re-derives the recorded
        tokens inside multi-step windows (bit-identical logits ⇒ same
        argmax), so it reuses the power-of-two window programs;
        sampling replay forces 1-token windows so the RECORDED token —
        not a fresh draw — feeds every step."""
        import jax
        import jax.numpy as jnp

        key = jax.random.PRNGKey(0)            # unused by greedy
        pad = self.max_seqs - 1
        padt = np.zeros((pad,) + self.cache.page_table.shape[1:],
                        np.int32)
        i = 0
        while i < len(toks):
            nsteps = min(self.steps_per_sync, len(toks) - i)
            if self.decode_strategy != "greedy_search":
                nsteps = 1
            while nsteps & (nsteps - 1):
                nsteps &= nsteps - 1
            self.cache.extend(slot, nsteps)
            tokens = np.array([toks[i]] + [0] * pad, np.int32)
            lens = np.concatenate([self.cache.seq_lens[[slot]],
                                   np.zeros(pad, np.int32)])
            tables = np.concatenate(
                [self.cache.page_table[[slot]], padt])
            out = _insp.watched_call(
                "engine.decode_step", _paged_decode_step,
                self._stack, self._norm_w, self._head_w,
                self._embed_w, self._rope, self.cache.k_pages,
                self.cache.v_pages, self.cache.k_scales,
                self.cache.v_scales, jnp.asarray(tokens),
                jnp.asarray(lens, np.int32), jnp.asarray(tables),
                jnp.asarray(lens, np.int32), key, jnp.int32(0),
                eps=self.eps, kvh=self.kvh,
                head_dim=self.head_dim,
                transpose_head=self._tied,
                strategy=self.decode_strategy,
                top_k=self.top_k, top_p=self.top_p,
                temperature=self.temperature, n_steps=nsteps,
                shardings=self._shardings, arch=self._arch)
            if self._arch is not None:
                self._note_expert_counts(
                    out[-1], self._arch.top_k * nsteps,
                    rows=self.max_seqs, forwards=nsteps)
                out = out[:-1]
            (_, self.cache.k_pages, self.cache.v_pages,
             self.cache.k_scales, self.cache.v_scales) = out
            self.cache.advance([slot], nsteps)
            i += nsteps

    # -- speculative window internals ------------------------------------------
    def _spec_prefill(self, dslot, seq):
        """Chunked prefill of ``seq`` into DRAFT slot ``dslot`` —
        ``_prefill_seq``'s mirror over the draft weights and cache
        (replicated, dense ``arch=None``).  Rides the same
        ``engine.prefill_chunk`` watch point; its one extra trace
        (draft geometry) is declared at ``_init_spec``."""
        import jax.numpy as jnp

        sp = self._spec
        dcache = self._spec_cache
        P = dcache.page_size
        plen = len(seq)
        table = np.asarray(dcache.page_table[dslot])
        for ci in range(-(-plen // P)):
            base = ci * P
            chunk = np.zeros(P, np.int32)
            real = min(P, plen - base)
            chunk[:real] = np.asarray(seq[base:base + real], np.int32)
            out = _insp.watched_call(
                "engine.prefill_chunk", _paged_prefill_chunk,
                sp["stack"], sp["norm_w"], sp["head_w"],
                sp["embed_w"], sp["rope_prefill"],
                dcache.k_pages, dcache.v_pages,
                dcache.k_scales, dcache.v_scales,
                jnp.asarray(chunk), jnp.asarray(table),
                jnp.int32(base), jnp.int32(int(table[ci])),
                jnp.int32(min(plen - 1 - base, P - 1)),
                eps=sp["eps"], kvh=sp["kvh"],
                head_dim=sp["head_dim"], transpose_head=sp["tied"],
                shardings=None, arch=None)
            (_, dcache.k_pages, dcache.v_pages, dcache.k_scales,
             dcache.v_scales) = out
        dcache.set_len(dslot, plen)

    def _spec_attach(self, req):
        """Lazily attach the request's DRAFT KV slot at its first
        speculative window: allocate the full page reservation on the
        draft cache and chunk-prefill ``prompt + out[:-1]`` — the
        draft mirror of the target's window-start state (KV through
        position ``cur - 1``, next input ``out[-1]``).  ONE hook
        covers every way a request reaches decode — admission,
        deferred prefill, resume via either restore path, import —
        because all of them land in ``_step_spec`` with a bare
        ``draft_slot``; retire / suspend / abort just release."""
        seq = list(req.prompt) + req.out[:-1]
        req.draft_slot = self._spec_cache.allocate(
            len(req.prompt) + req.max_new)
        self._spec_prefill(req.draft_slot, seq)

    def _spec_release(self, req):
        """Drop the request's draft slot (retire / suspend / abort /
        capsule-replay scratch).  Guarded no-op when the request never
        reached a speculative window — the lazy attach means plain
        interludes and first-token retires hold no draft state."""
        if self._spec is not None and req.draft_slot is not None:
            self._spec_cache.release(req.draft_slot)
            req.draft_slot = None

    def _spec_window(self, rows, sub, k_run):
        """One speculative window over ``rows`` (dicts with the
        request's target ``slot``, ``dslot``, ``last`` input token,
        ``cur`` KV length, full token ``seq`` and draw-id ``row``):
        draft catch-up + propose, ONE ragged target verify, accept,
        and the advance/rollback bookkeeping on BOTH caches.  Returns
        ``[(delivered_tokens, n_accepted)]`` aligned with ``rows`` and
        touches no request state — capsule replay re-invokes it with a
        single scratch row, which is why draws key off ``row`` (the
        CAPTURED batch index) and never off packing position.

        ``sub`` is the window's engine-key fork; ``spec_window_keys``
        derives the draft / accept / resample roots from it, so the
        engine key stream is identical to a plain window's and the
        capsule's per-window key fingerprint replays either kind.

        ``k_run`` (<= ``spec_k``) is the runtime draft length — TRACED
        data in both programs: propose always runs the static
        ``spec_k`` steps (overrun rows land in reserved pages or the
        pad page and are never attended), verify always dispatches the
        static ``T_spec = max_seqs * (spec_k + 1)`` bucket with
        ``q_len`` descriptors carving out the live ``k_run + 1`` rows
        — so churning ``k_run`` never recompiles."""
        import jax
        import jax.numpy as jnp

        from . import speculative as _spec_mod

        sp = self._spec
        dcache = self._spec_cache
        sampled = self._spec_mode == "rejection"
        draft_root, accept_root, resample_root = \
            _sampling.spec_window_keys(sub)
        B = self.max_seqs
        maxp_d = dcache.page_table.shape[1]

        # -- draft catch-up: teacher-force the draft level with the
        # target (deficit 1 after a fully-accepted window — the bonus
        # token's KV was never drafted — or more after plain-decode
        # interludes), one 1-step program dispatch per deficit level;
        # rows already level ride along as len-0 pad rows
        while True:
            lag = [r for r in rows
                   if int(dcache.seq_lens[r["dslot"]]) < r["cur"]]
            if not lag:
                break
            ids = np.zeros(B, np.int32)
            pos = np.zeros(B, np.int32)
            tabs = np.zeros((B, maxp_d), np.int32)
            lens = np.zeros(B, np.int32)
            dslots = []
            for j, r in enumerate(lag):
                dl = int(dcache.seq_lens[r["dslot"]])
                dcache.extend(r["dslot"], 1)
                ids[j] = r["seq"][dl]
                pos[j] = dl
                tabs[j] = dcache.page_table[r["dslot"]]
                lens[j] = dl
                dslots.append(r["dslot"])
            res = _insp.watched_call(
                "engine.spec_draft", _spec_mod._paged_draft_propose,
                sp["stack"], sp["norm_w"], sp["head_w"],
                sp["embed_w"], sp["rope"],
                dcache.k_pages, dcache.v_pages,
                dcache.k_scales, dcache.v_scales,
                jnp.asarray(ids), jnp.asarray(pos),
                jnp.asarray(tabs), jnp.asarray(lens),
                jax.random.PRNGKey(0), jnp.int32(0),
                eps=sp["eps"], kvh=sp["kvh"],
                head_dim=sp["head_dim"], transpose_head=sp["tied"],
                n_steps=1, collect_probs=False, shardings=None)
            (_, dcache.k_pages, dcache.v_pages, dcache.k_scales,
             dcache.v_scales) = res
            dcache.advance(dslots, 1)

        # -- propose: spec_k free-running draft tokens per row as ONE
        # program; the host advances only k_run (overrun rows are
        # garbage-by-construction: within the slot's reservation they
        # sit above the length watermark, past it the zero table
        # entries land them in pad page 0)
        ids = np.zeros(B, np.int32)
        pos = np.zeros(B, np.int32)
        tabs = np.zeros((B, maxp_d), np.int32)
        lens = np.zeros(B, np.int32)
        dslots = [r["dslot"] for r in rows]
        for j, r in enumerate(rows):
            dcache.extend(r["dslot"], k_run)
            ids[j] = r["last"]
            pos[j] = r["cur"]
            tabs[j] = dcache.page_table[r["dslot"]]
            lens[j] = r["cur"]
        db = rows[0]["row"] if len(rows) == 1 else 0
        res = _insp.watched_call(
            "engine.spec_draft", _spec_mod._paged_draft_propose,
            sp["stack"], sp["norm_w"], sp["head_w"], sp["embed_w"],
            sp["rope"], dcache.k_pages, dcache.v_pages,
            dcache.k_scales, dcache.v_scales,
            jnp.asarray(ids), jnp.asarray(pos), jnp.asarray(tabs),
            jnp.asarray(lens), draft_root, jnp.int32(db),
            eps=sp["eps"], kvh=sp["kvh"], head_dim=sp["head_dim"],
            transpose_head=sp["tied"], strategy=self.decode_strategy,
            top_k=self.top_k, top_p=self.top_p,
            temperature=self.temperature, n_steps=self.spec_k,
            collect_probs=sampled, shardings=None)
        if sampled:
            (toks_d, dcache.k_pages, dcache.v_pages, dcache.k_scales,
             dcache.v_scales, q_all) = res
            q_all = np.asarray(jax.device_get(q_all), np.float64)
        else:
            (toks_d, dcache.k_pages, dcache.v_pages, dcache.k_scales,
             dcache.v_scales) = res
        toks_d = np.asarray(jax.device_get(toks_d))  # [spec_k, B]
        dcache.advance(dslots, k_run)

        # -- verify: ONE ragged mixed dispatch scores every row's
        # whole draft window — k_run + 1 rows [last, d_1..d_k] per
        # sequence, descriptors split at page boundaries for the TPU
        # kernel's ``kv_len % P + q_len <= P`` contract (descriptor
        # index = the segment's first flat row, so live descriptors
        # never collide with pad rows' self-descriptors)
        P = self.cache.page_size
        maxp = self.cache.page_table.shape[1]
        T = self.max_seqs * (self.spec_k + 1)
        kw = k_run + 1
        v_ids = np.zeros(T, np.int32)
        positions = np.zeros(T, np.int32)
        row_tables = np.zeros((T, maxp), np.int32)
        q_start = np.zeros(T, np.int32)
        q_len = np.zeros(T, np.int32)
        kv_len = np.zeros(T, np.int32)
        desc_tables = np.zeros((T, maxp), np.int32)
        desc_of_row = np.arange(T, dtype=np.int32)
        off_of_row = np.zeros(T, np.int32)
        slots = [r["slot"] for r in rows]
        for i, r in enumerate(rows):
            self.cache.extend(r["slot"], kw)
            tbl = self.cache.page_table[r["slot"]]
            r0 = i * kw
            v_ids[r0] = r["last"]
            v_ids[r0 + 1:r0 + kw] = toks_d[:k_run, i]
            positions[r0:r0 + kw] = np.arange(r["cur"],
                                              r["cur"] + kw)
            row_tables[r0:r0 + kw] = tbl
            s = 0
            while s < kw:
                pos0 = r["cur"] + s
                seg = min(kw - s, P - pos0 % P)
                d = r0 + s
                q_start[d] = r0 + s
                q_len[d] = seg
                kv_len[d] = pos0
                desc_tables[d] = tbl
                desc_of_row[r0 + s:r0 + s + seg] = d
                off_of_row[r0 + s:r0 + s + seg] = np.arange(seg)
                s += seg
        res = _insp.watched_call(
            "engine.spec_verify", _paged_mixed_step,
            self._stack, self._norm_w, self._head_w, self._embed_w,
            self._rope, self.cache.k_pages, self.cache.v_pages,
            self.cache.k_scales, self.cache.v_scales,
            jnp.asarray(v_ids), jnp.asarray(positions),
            jnp.asarray(row_tables), jnp.asarray(q_start),
            jnp.asarray(q_len), jnp.asarray(kv_len),
            jnp.asarray(desc_tables), jnp.asarray(desc_of_row),
            jnp.asarray(off_of_row), sub, jnp.int32(0),
            eps=self.eps, kvh=self.kvh, head_dim=self.head_dim,
            transpose_head=self._tied, strategy=self.decode_strategy,
            top_k=self.top_k, top_p=self.top_p,
            temperature=self.temperature, shardings=self._shardings,
            arch=self._arch, return_probs=sampled)
        (nxt, self.cache.k_pages, self.cache.v_pages,
         self.cache.k_scales, self.cache.v_scales, _) = res[:6]
        if self._arch is not None:
            self._note_expert_counts(
                res[6], len(rows) * kw * self._arch.top_k, rows=T)
        if sampled:
            p_all = np.asarray(jax.device_get(res[-1]), np.float64)
        nxt = np.asarray(jax.device_get(nxt))
        self.cache.advance(slots, kw)

        # -- accept + rejected-suffix rollback on both caches: the
        # target keeps rows for [last, d_1..d_a] (the delivered
        # correction/bonus token's KV appends next window); the draft
        # keeps [last, d_1..d_{a-1}] when a < k_run (mirror level
        # cur + a + 1) and stays one short after full acceptance —
        # next window's catch-up teacher-forces d_k
        out = []
        for i, r in enumerate(rows):
            r0 = i * kw
            if sampled:
                toks, a = _spec_mod.rejection_accept(
                    toks_d[:k_run, i], q_all[:k_run, i],
                    p_all[r0:r0 + kw], accept_root, resample_root,
                    r["row"])
            else:
                toks, a = _spec_mod.greedy_accept(
                    toks_d[:k_run, i], nxt[r0:r0 + kw])
            self.cache.rollback(r["slot"], k_run - a)
            if a < k_run:
                dcache.rollback(r["dslot"], k_run - a - 1)
            out.append((toks, a))
        return out

    def _step_spec(self, sp) -> Dict[object, List[int]]:
        """The speculative decode window: draft-propose ``k_run``
        tokens per active request, verify them all in ONE ragged
        target dispatch, deliver the accepted prefix plus the
        correction/bonus token.  Greedy acceptance is BIT-IDENTICAL to
        plain decode (the verify rows' argmaxes ARE the plain stream);
        rejection acceptance preserves the target's post-filter
        sampling distribution for any draft.  Windows with pending
        prefill fall back to the plain unified step — chunked prefill
        interleaving is that path's job, and plain greedy windows are
        the same token stream anyway; drafts catch back up at the next
        speculative window."""
        import jax

        if self._prefilling:
            return self._step_mixed(sp)
        if not self._active:
            return {}
        with _phase("engine.step.plan"):
            batch = list(self._active)
            for req in batch:
                if req.draft_slot is None:
                    self._spec_attach(req)
            # runtime draft length: never draft past the tightest
            # budget (the window delivers at most k_run + 1 <=
            # remaining + 1 tokens; the merge loop truncates the last
            # one exactly like a plain multi-step window)
            k_run = min([self.spec_k] +
                        [r.max_new - len(r.out) for r in batch])
            k_run = max(k_run, 1)
        sp.set_metadata(decode_slots=len(batch), prefill_tokens=0,
                        nsteps=k_run, path="spec")
        with _phase("engine.step.pack"):
            rows = [{"slot": r.slot, "dslot": r.draft_slot,
                     "last": r.out[-1],
                     "cur": len(r.prompt) + len(r.out) - 1,
                     "seq": list(r.prompt) + r.out, "row": i}
                    for i, r in enumerate(batch)]
        # the draft-propose / verify / accept chain packs, dispatches
        # and reads back several times inside _spec_window; it is not
        # divided further (no cell runs it)
        with _phase("engine.step.launch"):
            self._key, sub = jax.random.split(self._key)
            t_win = time.perf_counter()
            results = self._spec_window(rows, sub, k_run)
            dt_win = time.perf_counter() - t_win

        with _phase("engine.step.merge"):
            out = {}
            accepted = {}
            for i, req in enumerate(batch):
                toks, _a = results[i]
                accepted[req.rid] = int(_a)
                new_toks = []
                for tok in toks:
                    if req.done:
                        break
                    req.out.append(tok)
                    new_toks.append(tok)
                    if (req.eos is not None and tok == req.eos) or \
                            len(req.out) >= req.max_new:
                        req.done = True
                        self.cache.release(req.slot)
                        self._spec_release(req)
                        self._active.remove(req)
                if new_toks:
                    out[req.rid] = new_toks
            delivered = max((len(v) for v in out.values()), default=0)
            self.last_window_steps = delivered

        with _phase("engine.step.account"):
            n_prop = len(batch) * k_run
            n_acc = sum(a for (_, a) in results)
            st = self.spec_stats
            st["windows"] += 1
            st["proposed"] += n_prop
            st["accepted"] += n_acc
            st["delivered"] += sum(len(v) for v in out.values())

            cs = _capsule.get_capsule_store()
            if cs.enabled and out:
                cs.on_window(out, _sampling.key_fingerprint(sub),
                             k_run + 1, delivered, "spec_window",
                             rows={r.rid: i
                                   for i, r in enumerate(batch)},
                             accepted=accepted)
            # TPOT counts only DELIVERED tokens: dt_win amortizes over
            # the window's real payoff, so a low-acceptance draft shows
            # up as WORSE per-token latency, not phantom throughput
            # (proposed-but-rejected tokens never touch the histogram
            # or the AIMD SLO window)
            if delivered:
                _health.get_health().observe_tpot(dt_win / delivered,
                                                  n=delivered)
            if self._metrics is not None:
                m = self._metrics
                if delivered:
                    m["tpot"].observe(dt_win / delivered, n=delivered)
                m["steps"].inc()
                m["generated_tokens"].inc(
                    sum(len(v) for v in out.values()))
                m["queue_depth"].set(len(self._active))
                m["occupancy"].set(len(batch) / self.max_seqs)
                m["spec_proposed"].inc(n_prop)
                m["spec_accepted"].inc(n_acc)
                if st["proposed"]:
                    m["spec_rate"].set(st["accepted"] / st["proposed"])
                for _, a in results:
                    m["spec_len"].observe(float(a))
                self._record_compiles()
        return out

    # -- admission -------------------------------------------------------------
    def add_request(self, rid, prompt_ids, max_new_tokens: int = 64,
                    eos_token_id: Optional[int] = None):
        """Prefill the prompt into pages; the request joins the decode
        batch at the next step().

        The prompt runs through page-size CHUNKS of one compiled
        program (each chunk fills exactly one page in-graph), so a
        mixed-length request stream costs ONE prefill compile total
        (assert with ``prefill_compiles()``) — round 2 recompiled per
        prompt, round 4 per power-of-two bucket.

        Automatic prefix caching (on by default): the longest cached
        page-aligned prefix of the prompt is mapped into the slot's
        page table WITHOUT touching the device, and the chunk loop
        runs only over the uncached tail — same compiled program, it
        just starts at a later chunk, so ``prefill_compiles() == 1``
        survives.  The cacheable prefix is capped strictly below the
        prompt length: the chunk holding the last prompt token always
        recomputes (into a private page), which is what produces the
        first-token logits even when the whole prompt is cached."""
        import jax
        import jax.numpy as jnp

        enforce(self._hybrid is None,
                "add_request prefills through the split prefill "
                "program, which a backbone with linear-attention "
                "layers does not have: admit with begin_request "
                "(Scheduler(chunked_prefill=True) does)")
        t_admit = time.perf_counter()
        enforce(rid not in self.requests, f"duplicate request id {rid!r}")
        enforce(max_new_tokens >= 1, "max_new_tokens must be >= 1")
        req = GenRequest(rid, prompt_ids, max_new_tokens, eos_token_id)
        plen = len(req.prompt)
        enforce(plen >= 1, "empty prompt")
        total = plen + max_new_tokens
        limit = min(self.max_len,
                    self.model.config.max_position_embeddings)
        enforce(total <= limit,
                f"prompt ({plen}) + max_new_tokens "
                f"({max_new_tokens}) exceeds the engine/model limit "
                f"{limit}")
        P = self.cache.page_size
        cached, shared_pages = 0, []
        if self.enable_prefix_caching:
            # cap at the last page boundary STRICTLY below plen so the
            # final chunk (the one whose logits seed decoding) always
            # runs — shared pages stay immutable, logits stay real
            cacheable = ((plen - 1) // P) * P
            cached, shared_pages = self.cache.lookup_prefix(
                req.prompt[:cacheable])
        req.slot = self.cache.allocate(total, shared_pages=shared_pages)

        # CHUNKED ragged prefill (round 5): page-size chunks, each one
        # filling exactly one page in-graph — ONE compiled program for
        # any prompt-length mix (prefill_compiles() == 1), vs the r4
        # power-of-two buckets (one compile per bucket).  Cached-prefix
        # chunks are skipped: their pages are already written.
        try:
            with RecordEvent("llm_engine.prefill"):
                logits = self._prefill_seq(req.slot, req.prompt,
                                           cached // P)
                self.cache.set_len(req.slot, plen)
                if self.enable_prefix_caching:
                    # publish this prompt's full pages (the just-
                    # prefilled ones included) for future requests
                    self.cache.register_prefix(
                        req.slot, req.prompt, upto=(plen // P) * P)

                self._key, sub = jax.random.split(self._key)
                from ..nn.generation import sample_logits
                # row_ids=[0]: the synchronous first token draws as
                # row 0 — exactly what anchored capsule replay re-folds
                first_tok, _ = sample_logits(
                    logits[None], sub, strategy=self.decode_strategy,
                    top_k=self.top_k, top_p=self.top_p,
                    temperature=self.temperature,
                    row_ids=np.zeros(1, np.int32))
                first = int(np.asarray(first_tok)[0])
        except BaseException:
            # chunked prefill / sampling failed: the slot (and its
            # page references) must not leak — release, then re-raise
            self.cache.release(req.slot)
            raise
        req.out.append(first)
        self.requests[rid] = req
        st = self.prefix_stats
        st["hit_tokens"] += cached
        st["miss_tokens"] += plen - cached
        st["shared_pages"] += len(shared_pages)
        st["hit_requests" if cached else "miss_requests"] += 1
        # capsule capture (one global read; no-op on the NULL store):
        # the admission subkey IS the key anchor — replay re-samples
        # the first token with exactly these words
        cs = _capsule.get_capsule_store()
        if cs.enabled:
            cs.begin(rid, prompt=list(req.prompt),
                     max_new=req.max_new, eos=req.eos,
                     fingerprint=self._capsule_fp,
                     key_anchor=_sampling.key_fingerprint(sub),
                     prefix={"hit_tokens": int(cached),
                             "shared_pages": len(shared_pages)},
                     tokens=[first])
        # the int() above synced the device: TTFT is honest
        ttft = time.perf_counter() - t_admit
        _health.get_health().observe_ttft(ttft)
        if self._metrics is not None:
            m = self._metrics
            m["ttft"].observe(ttft)
            m["prompt_tokens"].inc(plen)
            m["generated_tokens"].inc(1)
            m["requests"].inc()
            m["prefix_hit_tokens"].inc(cached)
            m["prefix_miss_tokens"].inc(plen - cached)
            m["prefix_shared_pages"].inc(len(shared_pages))
            seen = st["hit_tokens"] + st["miss_tokens"]
            m["prefix_hit_rate"].set(st["hit_tokens"] / seen
                                     if seen else 0.0)
            self._record_compiles()
        # the prefill-produced token counts toward the limits too
        if (req.eos is not None and first == req.eos) or \
                req.max_new <= 1:
            req.done = True
            self.cache.release(req.slot)
        else:
            self._active.append(req)
        if self._metrics is not None:
            self._metrics["queue_depth"].set(len(self._active))
        return rid

    def begin_request(self, rid, prompt_ids, max_new_tokens: int = 64,
                      eos_token_id: Optional[int] = None):
        """DEFERRED admission for the ragged unified step: reserve the
        slot and page budget now, but run the prompt's prefill inside
        subsequent ``step()`` calls — page-sized chunks ride the same
        mixed-batch dispatch as every ongoing decode, up to the
        per-step ``prefill_token_budget``, so a long prompt never
        stalls in-flight decodes (the chunk-level-admission half of
        the head-of-line fix; ``add_request`` remains the synchronous
        prefill-then-join path).  The first token arrives in a later
        ``step()`` return value, exactly like every other token.
        Prefix caching applies as in ``add_request``: cached pages map
        in host-side and the chunk stream starts at the first uncached
        position."""
        enforce(rid not in self.requests, f"duplicate request id {rid!r}")
        enforce(max_new_tokens >= 1, "max_new_tokens must be >= 1")
        req = GenRequest(rid, prompt_ids, max_new_tokens, eos_token_id)
        plen = len(req.prompt)
        enforce(plen >= 1, "empty prompt")
        total = plen + max_new_tokens
        limit = min(self.max_len,
                    self.model.config.max_position_embeddings)
        enforce(total <= limit,
                f"prompt ({plen}) + max_new_tokens "
                f"({max_new_tokens}) exceeds the engine/model limit "
                f"{limit}")
        P = self.cache.page_size
        cached, shared_pages = 0, []
        if self.enable_prefix_caching:
            cacheable = ((plen - 1) // P) * P
            cached, shared_pages = self.cache.lookup_prefix(
                req.prompt[:cacheable])
        req.slot = self.cache.allocate(total, shared_pages=shared_pages)
        req.pf_pos = cached
        req.t_submit = time.perf_counter()
        self.requests[rid] = req
        self._prefilling.append(req)
        # capsule capture: no key anchor on the deferred path — the
        # first token arrives inside a later mixed window, whose key
        # the window record carries like any other step's
        cs = _capsule.get_capsule_store()
        if cs.enabled:
            cs.begin(rid, prompt=list(req.prompt),
                     max_new=req.max_new, eos=req.eos,
                     fingerprint=self._capsule_fp, key_anchor=None,
                     prefix={"hit_tokens": int(cached),
                             "shared_pages": len(shared_pages)},
                     tokens=[])
        st = self.prefix_stats
        st["hit_tokens"] += cached
        st["miss_tokens"] += plen - cached
        st["shared_pages"] += len(shared_pages)
        st["hit_requests" if cached else "miss_requests"] += 1
        if self._metrics is not None:
            m = self._metrics
            m["prompt_tokens"].inc(plen)
            m["requests"].inc()
            m["prefix_hit_tokens"].inc(cached)
            m["prefix_miss_tokens"].inc(plen - cached)
            m["prefix_shared_pages"].inc(len(shared_pages))
            seen = st["hit_tokens"] + st["miss_tokens"]
            m["prefix_hit_rate"].set(st["hit_tokens"] / seen
                                     if seen else 0.0)
        return rid

    # -- decode loop -----------------------------------------------------------
    def step(self) -> Dict[object, List[int]]:
        """One serving step: returns {request_id: [new tokens]} and
        retires finished requests (streaming callers see every
        intermediate token).

        This is the RAGGED MIXED step (``_step_mixed``): one compiled
        program packs every active decode slot plus up to
        ``prefill_token_budget`` tokens of pending ``begin_request``
        prefill chunks — prefill rides alongside decode instead of
        stalling it — and, with no prefill pending, a
        ``steps_per_sync`` window of decode steps runs as one
        on-device loop.

        A ``draft_model`` engine routes pure-decode windows through
        the speculative path (``_step_spec``): greedy streams stay
        bit-identical to plain decode, sampled streams stay
        distributionally exact — only the tokens-per-dispatch ratio
        changes.

        Whichever path runs, the call is ONE ``engine.step`` phase on
        the profiler's clock (``observability.tracing.phase``) whose
        leaves — ``engine.step.plan`` / ``.pack`` / ``.launch`` /
        ``.wait`` / ``.moe_counts`` / ``.merge`` / ``.account`` —
        cover every line of the path, so a capture shows which host
        stretch the chip idled under.  ``.moe_counts`` is the fold of
        routed-expert counts a dispatch BEFORE put aside: it runs
        behind a launch, or here, at once, when the step leaves the
        engine without work."""
        with _phase("engine.step") as sp:
            out = self._step_spec(sp) if self._spec is not None \
                else self._step_mixed(sp)
            if self._counts_aside and not self.has_work():
                # no launch will follow to fold the last counts behind
                with _phase("engine.step.moe_counts"):
                    self._fold_expert_counts("at_idle")
            return out

    def _step_mixed(self, sp) -> Dict[object, List[int]]:
        """The ragged unified step: ONE ``_paged_mixed_step`` dispatch
        carries every active decode slot (1 row each — the slot→row
        map is compacted host-side, no padded dead slots) plus pending
        prefill chunks packed FIFO up to the runtime
        ``prefill_token_budget`` (chunks never cross page boundaries,
        so one request may contribute several descriptors).  When no
        prefill is pending, the ``steps_per_sync`` window dispatches
        ONCE as the on-device ``_paged_mixed_window`` program
        (power-of-two buckets, early exit), whose tokens are the
        per-token stream's by construction.  Each program runs at its
        own static geometry (``_step_geom`` / ``_window_geom``): the
        mixed step over slots + prefill budget rows, the window — pure
        decode — over ONE row and one descriptor a slot, so nothing a
        decode forward does (embedding, projections, routing, the
        expert buffer, the ragged kernel's grid, the argmax) is sized
        by a prefill budget it cannot use.  Decode row i is descriptor
        i in both; the tokens, the expert buffer's rows and the
        row-capacity counter are taken by the rows of the program that
        ran.

        Either way the call is ONE launch, which crosses the
        host-device boundary ONCE EACH WAY.  In: every host-made
        descriptor is written into the engine's one step buffer
        (``_step_layout``) and uploaded by one ``device_put`` —
        ``row_tables`` is not, the program gathers it from
        ``desc_tables[desc_of_row]``; the sampling key stays on
        the device and is split inside the program.  Out: the program's
        one small result (tokens, ``steps_done``, the window key's
        words, the routed counts) starts its copy to the host at launch
        and ``engine.step.wait`` is one blocking read.  The counts are
        put aside and folded behind the NEXT launch
        (``engine.step.moe_counts``: the chip busy under it), or at
        once by ``step()`` when the engine is left without work."""
        import jax

        if not self._active and not self._prefilling:
            return {}
        with _phase("engine.step.plan"):
            P = self.cache.page_size
            hy = self._hybrid
            # the MIXED step's descriptors, which the prefill plan is
            # held to: one a row, or the hybrid backbone's cap (its last
            # descriptor stays dead: the padding rows' own)
            s_cap = self._step_geom[1]
            batch = list(self._active)
            n = len(batch)

            # prefill plan: (req, pos, chunk_len, first_row,
            # descriptor).  The runtime budget is clamped to the static
            # one (T is fixed) and floored at 1 when only prefill is
            # pending — a zero budget must not livelock has_work().
            budget = max(0, min(int(self.prefill_token_budget),
                                self._pf_budget_static))
            if not batch and budget == 0:
                budget = min(P, self._pf_budget_static)
            # capacity-factor MoE defines its drop ranks per page-group
            # = page chunk, so the planner must pack WHOLE chunks (a
            # split chunk would rank differently than the split prefill
            # path); floor the runtime budget to one chunk when only
            # prefill is pending so a low budget can't livelock
            # has_work()
            whole_chunks = self._arch is not None and \
                self._arch.capacity > 0
            if whole_chunks and not batch:
                budget = max(budget, min(P, self._pf_budget_static))
            plan = []
            finishing = []                        # (req, last_row)
            cursor, desc_i, used = n, n, 0
            stop = False
            for req in self._prefilling:
                plen = len(req.pf_seq)
                pos = req.pf_pos
                while pos < plen and used < budget:
                    chunk = min(P - pos % P, plen - pos)
                    if (whole_chunks and used + chunk > budget) or \
                            (hy is not None and desc_i >= s_cap - 1):
                        stop = True
                        break
                    cl = min(chunk, budget - used)
                    plan.append((req, pos, cl, cursor, desc_i))
                    pos += cl
                    cursor += cl
                    used += cl
                    desc_i += 1
                if pos >= plen:
                    finishing.append((req, cursor - 1))
                if stop or used >= budget:
                    break
            if not batch and not plan:
                return {}

            if plan or n == 0:
                nsteps = 1
            else:
                nsteps = min([self.steps_per_sync] +
                             [r.max_new - len(r.out) for r in batch])
                nsteps = max(nsteps, 1)
                while nsteps & (nsteps - 1):
                    nsteps &= nsteps - 1
            slots = np.array([r.slot for r in batch], np.int64)
            for r in batch:
                self.cache.extend(r.slot, nsteps)
        # ON-DEVICE window (pure decode by construction — prefill plans
        # force nsteps == 1): the whole attend → sample → append chain
        # runs as one while_loop program that exits as soon as every
        # row has retired, syncing the host once
        window = nsteps > 1
        # ... at its own geometry: one row and one descriptor a slot
        geom = self._window_geom if window else self._step_geom
        t_rows = geom[0]
        path = "window" if window else "mixed"
        sp.set_metadata(decode_slots=n, prefill_tokens=used,
                        nsteps=nsteps, path=path)

        with _phase("engine.step.pack"):
            # the step's ONE upload, written in place: the blank (dead
            # descriptors, padding rows that name them) copied over
            # what the last step left, then the live rows
            blank, step_buf, f = self._step_bufs[geom]
            np.copyto(step_buf, blank)
            ids, positions = f["ids"], f["positions"]
            q_start, q_len, kv_len = f["q_start"], f["q_len"], f["kv_len"]
            desc_tables = f["desc_tables"]
            desc_of_row, off_of_row = f["desc_of_row"], f["off_of_row"]
            f["n_rows"][...] = n
            if n:
                # decode row i is descriptor i, with its slot's table
                ids[:n] = [r.out[-1] for r in batch]
                lens = self.cache.seq_lens[slots]
                positions[:n] = lens
                desc_of_row[:n] = q_start[:n] = np.arange(n)
                q_len[:n] = 1
                kv_len[:n] = lens
                desc_tables[:n] = self.cache.page_table[slots]
                if hy is not None:
                    # each descriptor's sequence slot, for the
                    # recurrent state
                    f["desc_slot"][:n] = slots
            for req, pos, cl, row0, d in plan:
                ids[row0:row0 + cl] = req.pf_seq[pos:pos + cl]
                positions[row0:row0 + cl] = np.arange(pos, pos + cl)
                q_start[d] = row0
                q_len[d] = cl
                kv_len[d] = pos
                desc_tables[d] = self.cache.page_table[req.slot]
                desc_of_row[row0:row0 + cl] = d
                off_of_row[row0:row0 + cl] = np.arange(cl)
                if hy is not None:
                    f["desc_slot"][d] = req.slot
            if window:
                eos_ids, budgets = f["eos_ids"], f["budgets"]
                for i, r in enumerate(batch):
                    if r.eos is not None:
                        eos_ids[i] = r.eos
                    budgets[i] = r.max_new - len(r.out)

        kw = dict(geom=geom, eps=self.eps, kvh=self.kvh,
                  head_dim=self.head_dim, transpose_head=self._tied,
                  strategy=self.decode_strategy, top_k=self.top_k,
                  top_p=self.top_p, temperature=self.temperature,
                  shardings=self._shardings, arch=self._arch, hybrid=hy)
        name, program = "engine.mixed_step", _packed_mixed_step
        if window:
            name, program = "engine.mixed_window", _packed_mixed_window
            kw["n_steps"] = nsteps
        counts_shape = None if self._arch is None else \
            self._moe_counts.shape
        t_win = time.perf_counter()
        with _phase("engine.step.launch"):
            # a hybrid backbone's second kind of state (None
            # otherwise) is handed over whole, donated like the pools
            res = _insp.watched_call(
                name, program,
                self._stack, self._norm_w, self._head_w,
                self._embed_w, self._rope,
                self.cache.k_pages, self.cache.v_pages,
                self.cache.k_scales, self.cache.v_scales,
                jax.device_put(step_buf, self._step_sharding),
                self._key, self.cache.rec_state, self.cache.conv_state,
                **kw)
            (out_d, self.cache.k_pages, self.cache.v_pages,
             self.cache.k_scales, self.cache.v_scales,
             self._key) = res[:6]
            if hy is not None:
                self.cache.rec_state, self.cache.conv_state = res[6:8]
            # the copy follows the program on the device's own queue
            out_d.copy_to_host_async()
            self._crossed("in")
        if self._counts_aside:
            # the dispatch before this one's counts, the chip busy
            with _phase("engine.step.moe_counts"):
                self._fold_expert_counts("behind_launch")
        with _phase("engine.step.wait"):
            toks, steps_done, sub_words, counts = _unpack_result(
                jax.device_get(out_d), nsteps * t_rows, counts_shape)
            self._crossed("out")
            toks_all = toks.reshape(nsteps, t_rows)
            if counts is not None:
                # live rows this dispatch: n decode slots, every
                # step of a window, + the packed prefill tokens
                # (multi-step windows are pure decode)
                self._note_expert_counts(
                    counts, (n * steps_done + used) * self._arch.top_k,
                    rows=t_rows, forwards=steps_done)
        dt_win = time.perf_counter() - t_win

        with _phase("engine.step.merge"):
            if n:
                self.cache.advance(slots, steps_done)
            self.last_window_steps = steps_done
            out = {}
            for i, req in enumerate(batch):
                new_toks = []
                for j in range(steps_done):
                    if req.done:
                        break
                    tok = int(toks_all[j][i])
                    req.out.append(tok)
                    new_toks.append(tok)
                    if (req.eos is not None and tok == req.eos) or \
                            len(req.out) >= req.max_new:
                        req.done = True
                        self.cache.release(req.slot)
                        self._spec_release(req)
                        self._active.remove(req)
                if new_toks:
                    out[req.rid] = new_toks
            # decode tokens DELIVERED this window (prefill-completing
            # first tokens are TTFT, appended to `out` below, never
            # TPOT)
            delivered = max((len(v) for v in out.values()), default=0)

            # prefill bookkeeping AFTER the dispatch succeeded — a raise
            # above leaves every pf_pos where it was (no token lost)
            for req, pos, cl, row0, d in plan:
                req.pf_pos = pos + cl
            for req, last_row in finishing:
                first = int(toks_all[0][last_row])
                plen = len(req.pf_seq)
                self.cache.set_len(req.slot, plen)
                if req.replay is not None:
                    # a recompute-resume: state and pages are rebuilt,
                    # the sampled token is the one it already holds
                    req.replay = None
                    self._prefilling.remove(req)
                    self._active.append(req)
                    continue
                if self.enable_prefix_caching:
                    self.cache.register_prefix(req.slot, req.prompt,
                                               upto=(plen // P) * P)
                req.out.append(first)
                self._prefilling.remove(req)
                out[req.rid] = [first]
                if req.t_submit is not None:
                    ttft = time.perf_counter() - req.t_submit
                    _health.get_health().observe_ttft(ttft)
                    if self._metrics is not None:
                        self._metrics["ttft"].observe(ttft)
                if (req.eos is not None and first == req.eos) or \
                        req.max_new <= 1:
                    req.done = True
                    self.cache.release(req.slot)
                    self._spec_release(req)
                else:
                    self._active.append(req)
        with _phase("engine.step.account"):
            # capsule capture after the finishing loop, so prefill-
            # completing first tokens ride the same window record as
            # the decode tokens (the forked key `sub` anchors the whole
            # window's split_step chain)
            cs = _capsule.get_capsule_store()
            if cs.enabled and out:
                # per-rid draw rows: decode slots are rows 0..n-1 in
                # batch order; a prefill-finishing first token drew at
                # its chunk's last flat row — recorded so stochastic
                # replay can re-fold the exact draw id whatever slot
                # the request decoded in
                rows = {r.rid: i for i, r in enumerate(batch)}
                for req, last_row in finishing:
                    rows[req.rid] = int(last_row)
                cs.on_window(out, sub_words, nsteps, steps_done,
                             "mixed_window" if window else "mixed_step",
                             rows=rows)
            # TPOT over-count fix: only DELIVERED decode positions
            # advance the histogram / SLO window — a window whose
            # requests all finished early contributes its real token
            # count, not nsteps; pure-prefill steps contribute nothing
            # (their latency is TTFT)
            if delivered:
                _health.get_health().observe_tpot(dt_win / steps_done,
                                                  n=delivered)
            # the rows the program ran against the rows live in it
            ran = self.forward_rows[path]
            ran["capacity"] += t_rows * steps_done
            ran["live"] += n * steps_done + used
            if self._metrics is not None:
                m = self._metrics
                m["row_capacity_" + path].inc(t_rows * steps_done)
                if delivered:
                    m["tpot"].observe(dt_win / steps_done, n=delivered)
                m["steps"].inc()
                m["step_prefill_tokens"].inc(used)
                m["generated_tokens"].inc(
                    sum(len(v) for v in out.values()))
                m["queue_depth"].set(len(self._active))
                m["occupancy"].set(n / self.max_seqs)
                m["mixed_decode_slots"].set(n)
                m["mixed_prefill_tokens"].set(used)
                self._record_compiles()
            if hy is not None and hy.n_linear:
                # what the recurrence was given: the decode rows of
                # every step of the window, the packed prefill rows,
                # one live descriptor each decode row and chunk
                st, nl = self.linear_stats, hy.n_linear
                dec, pre = nl * n * steps_done, nl * used
                st["decode_rows"] += dec
                st["prefill_rows"] += pre
                desc = nl * (n * steps_done + len(plan))
                st["descriptors"] += desc
                moved = desc * self._state_bytes_a_descriptor
                st["state_bytes_moved"] += moved
                if self._metrics is not None:
                    m["linear_rows_decode"].inc(dec)
                    m["linear_rows_prefill"].inc(pre)
                    m["linear_descriptors"].inc(desc)
                    m["state_bytes_moved"].inc(moved)
        return out

    def has_work(self) -> bool:
        return bool(self._active or self._prefilling)

    # -- admission-control introspection ---------------------------------------
    def free_slots(self) -> int:
        """Sequence slots available for admission right now.  Paired
        with ``cache.free_pages()`` this lets a scheduler decide
        admission WITHOUT try/except on the OOM raise: a request fits
        iff ``free_slots() >= 1`` and ``cache.free_pages() >=
        ceil((len(prompt) + max_new_tokens) / page_size)`` (the engine
        reserves the full page budget at admission, so a request that
        admits can always decode to its budget)."""
        return self.cache.free_slot_count()

    def capacity(self) -> tuple:
        """ATOMIC admission snapshot: ``(free_slots, free_pages)`` in
        one call.  Invariant (the scheduler relies on it): every
        capacity-mutating engine operation — ``add_request``,
        ``step``, ``abort``, ``suspend``, ``resume`` — runs under the
        scheduler's lock on the stepping thread, so a snapshot taken
        inside that lock stays exact until the admission decision acts
        on it.  Reading ``free_slots()`` and ``cache.free_pages()``
        as two separate calls invites drift the moment anything (a
        preemption, a retirement) frees capacity between them —
        admission must use this helper."""
        return self.cache.free_slot_count(), self.cache.free_pages()

    def suspended_count(self) -> int:
        """Live requests currently preempted out of the decode batch
        (they hold no slot or device pages)."""
        return sum(1 for r in self.requests.values()
                   if r.suspended and not r.done)

    # -- preemption ------------------------------------------------------------
    def suspend(self, rid) -> bool:
        """Preempt an ACTIVE request: capture its generated-so-far
        tokens (they stay on the request record), swap its KV pages
        into the cache's host pool (or just release them when the pool
        is full — resume then recomputes), and free its slot.  The
        freed slot + pages are the point: a higher-priority request
        can admit into them NOW.  Returns True when the swap path is
        armed, False when resume will recompute.  Suspended requests
        still ``result()``-raise like active ones and can be
        ``abort()``-ed (their swap entry is dropped)."""
        enforce(rid in self.requests,
                f"unknown request id {rid!r} (never admitted to this "
                f"engine)")
        req = self.requests[rid]
        enforce(not req.done, f"request {rid!r} already retired")
        enforce(not req.suspended, f"request {rid!r} already suspended")
        if req in self._prefilling:
            # mid-prefill preemptee (begin_request, prefill not done):
            # its partial KV is cheaper to recompute than to swap —
            # release the pages outright; resume restarts the chunk
            # stream (prefix-cache hits still skip cached pages)
            self._prefilling.remove(req)
            with _tracing.span("engine.swap_out") as sp:
                self.cache.release(req.slot)
                req.swap_handle = None
                sp.set_attr("rid", str(rid))
                sp.set_attr("armed", False)
            req.slot = None
            req.suspended = True
            req.pf_pos = 0
            req.replay = None
            if self._metrics is not None:
                self._metrics["suspended"].inc()
                self._metrics["queue_depth"].set(len(self._active))
            return False
        self._active.remove(req)
        # the draft slot never swaps — a suspended draft is cheaper to
        # re-prefill at the next speculative window (lazy re-attach)
        # than to hold pages or pool space for
        self._spec_release(req)
        with _tracing.span("engine.swap_out") as sp, \
                _phase("engine.state.snapshot"):
            # (the phase: with a recurrent state the swap also copies
            # the slot's state and conv windows to the host)
            req.swap_handle = self.cache.swap_out(req.slot)
            sp.set_attr("rid", str(rid))
            sp.set_attr("armed", req.swap_handle is not None)
        self._note_state_snapshot(req.swap_handle is not None)
        req.slot = None
        req.suspended = True
        _capsule.get_capsule_store().event(
            rid, "suspend:swap" if req.swap_handle is not None
            else "suspend:drop")
        if self._metrics is not None:
            self._metrics["suspended"].inc()
            self._metrics["queue_depth"].set(len(self._active))
        return req.swap_handle is not None

    def resume(self, rid) -> str:
        """Re-admit a suspended request; it rejoins the decode batch
        at the next ``step()`` with tokens bit-identical to a run that
        was never preempted (greedy decoding — see the class
        docstring).  Returns the restore path taken: ``"swap_in"``
        (host pages copied back, no recompute) or ``"recompute"``
        (prompt replayed through the chunked-prefill program, the
        generated tokens through the compiled decode program — no new
        prefill compiles either way).  The caller must ensure capacity
        first (``capacity()``): the full page budget is re-reserved,
        exactly like admission."""
        enforce(rid in self.requests,
                f"unknown request id {rid!r} (never admitted to this "
                f"engine)")
        req = self.requests[rid]
        enforce(req.suspended and not req.done,
                f"request {rid!r} is not suspended")
        plen = len(req.prompt)
        total = plen + req.max_new
        if not req.out:
            # mid-prefill preemptee: re-reserve its budget and rejoin
            # the unified step's chunk stream — the prefill that ran
            # before the preemption recomputes (bit-identical rows)
            P = self.cache.page_size
            cached, shared_pages = 0, []
            if self.enable_prefix_caching:
                cacheable = ((plen - 1) // P) * P
                cached, shared_pages = self.cache.lookup_prefix(
                    req.prompt[:cacheable])
            req.slot = self.cache.allocate(total,
                                           shared_pages=shared_pages)
            req.pf_pos = cached
            req.suspended = False
            self._prefilling.append(req)
            if self._metrics is not None:
                self._metrics["resumed"].labels(
                    self.engine_id, "recompute").inc()
            return "recompute"
        path = None
        if req.swap_handle is not None:
            with _tracing.span("engine.swap_in") as sp, \
                    _phase("engine.state.restore"):
                sp.set_attr("rid", str(rid))
                slot = self.cache.swap_in(req.swap_handle, total)
            self._note_state_snapshot(slot is not None)
            req.swap_handle = None             # consumed either way
            if slot is not None:
                # KV restored byte-exact; length = prompt + generated
                # so far MINUS the last token (it is the next decode
                # input — its KV is appended by the next step)
                self.cache.set_len(slot, plen + len(req.out) - 1)
                path = "swap_in"
        if path is None and self._hybrid is not None:
            # no split prefill program for this backbone: the history
            # re-prefills through the step's chunk stream
            # (state from zero, pages rewritten), then decode goes on
            req.slot = self.cache.allocate(total)
            req.replay = list(req.prompt) + list(req.out[:-1])
            req.pf_pos = 0
            req.suspended = False
            self._prefilling.append(req)
            _capsule.get_capsule_store().event(rid, "resume:recompute")
            if self._metrics is not None:
                self._metrics["resumed"].labels(
                    self.engine_id, "recompute").inc()
            return "recompute"
        if path is None:
            with RecordEvent("llm_engine.resume_recompute"):
                slot = self._recompute_resume(req)
            path = "recompute"
        req.slot = slot
        req.suspended = False
        self._active.append(req)
        _capsule.get_capsule_store().event(rid, f"resume:{path}")
        if self._metrics is not None:
            self._metrics["resumed"].labels(self.engine_id, path).inc()
            self._metrics["queue_depth"].set(len(self._active))
        return path

    def _note_state_snapshot(self, moved: bool):
        """One recurrent-state snapshot went to the host or came back
        (backbones with linear layers only)."""
        if moved and self._hybrid is not None:
            self.linear_stats["state_snapshots"] += 1
            if self._metrics is not None:
                self._metrics["state_snapshots"].inc()

    def _recompute_resume(self, req):
        """Swapless resume: re-derive the suspended request's KV from
        its token history — the prompt through the SAME chunked
        prefill (prefix-cache hits still apply: the prompt's pages
        often still sit in the LRU pool), the generated tokens through
        the SAME decode program (``_replay_decode``).  Bit-identical
        state by construction: same programs, same inputs."""
        plen = len(req.prompt)
        P = self.cache.page_size
        cached, shared_pages = 0, []
        if self.enable_prefix_caching:
            cacheable = ((plen - 1) // P) * P
            cached, shared_pages = self.cache.lookup_prefix(
                req.prompt[:cacheable])
        slot = self.cache.allocate(plen + req.max_new,
                                   shared_pages=shared_pages)
        try:
            self._prefill_seq(slot, req.prompt, cached // P)
            self.cache.set_len(slot, plen)
            if self.enable_prefix_caching:
                self.cache.register_prefix(slot, req.prompt,
                                           upto=(plen // P) * P)
            self._replay_decode(slot, req.out[:-1])
        except BaseException:
            self.cache.release(slot)
            raise
        return slot

    # -- migration (multi-host drain/rebalance) --------------------------------
    def export_request(self, rid) -> dict:
        """Package a SUSPENDED request for migration to another engine:
        token history (prompt + generated so far) plus its swap entry
        serialized portably (``PagedKVCache.export_swap``), or
        ``swap=None`` when the entry was never armed / already dropped
        — the destination then resumes via recompute, bit-identical
        either way (same programs, same token history).  The request
        leaves THIS engine's map: after export it belongs to whoever
        imports the package.  Suspend first (``suspend(rid)``) —
        active requests hold device pages that must swap or release
        before their state can travel."""
        enforce(rid in self.requests,
                f"unknown request id {rid!r} (never admitted to this "
                f"engine)")
        req = self.requests[rid]
        enforce(req.suspended and not req.done,
                f"request {rid!r} is not suspended — suspend() before "
                f"export_request()")
        blob = self.cache.export_swap(req.swap_handle)
        req.swap_handle = None
        del self.requests[rid]
        if self._metrics is not None:
            self._metrics["migrated_out"].inc()
        # the request's capsule travels INSIDE the package (plain
        # JSON; transports ship it untouched) so a drained request's
        # capture history stays whole on the destination replica
        cs = _capsule.get_capsule_store()
        return {"rid": rid, "prompt": list(req.prompt),
                "out": list(req.out), "max_new": req.max_new,
                "eos": req.eos, "swap": blob,
                "capsule": cs.export(rid) if cs.enabled else None}

    def import_request(self, pkg: dict):
        """Adopt a migration package: the request registers here in
        the SUSPENDED state (no slot, no device pages) with its swap
        blob imported into this cache's host pool when it fits —
        ``resume(rid)`` then restores it exactly like a locally
        preempted request (swap-in, or recompute from the token
        history).  Raises when the request cannot fit this engine's
        limits or the blob's geometry mismatches the cache; the caller
        (a draining router) tries another destination.  Returns the
        rid."""
        rid = pkg["rid"]
        enforce(rid not in self.requests,
                f"duplicate request id {rid!r}")
        plen = len(pkg["prompt"])
        enforce(plen >= 1, "empty prompt in migration package")
        total = plen + pkg["max_new"]
        limit = min(self.max_len,
                    self.model.config.max_position_embeddings)
        enforce(total <= limit,
                f"migrated request {rid!r}: prompt ({plen}) + "
                f"max_new_tokens ({pkg['max_new']}) exceeds this "
                f"engine's limit {limit}")
        P = self.cache.page_size
        need = -(-total // P)
        enforce(need <= self.cache.n_pages - 1,
                f"migrated request {rid!r} needs {need} KV pages but "
                f"this cache holds {self.cache.n_pages - 1} usable")
        req = GenRequest(rid, pkg["prompt"], pkg["max_new"], pkg["eos"])
        req.out = list(pkg["out"])
        enforce(len(req.out) >= 1,
                f"migrated request {rid!r} carries no generated "
                f"tokens — it was never admitted; resubmit it instead")
        req.suspended = True
        req.swap_handle = self.cache.import_swap(pkg.get("swap"))
        self.requests[rid] = req
        cs = _capsule.get_capsule_store()
        if cs.enabled and pkg.get("capsule"):
            cs.adopt(pkg["capsule"])
        if self._metrics is not None:
            self._metrics["migrated_in"].inc()
        return rid

    def abort(self, rid) -> bool:
        """Cancel a request: release its KV pages and retire it with
        ``cancelled=True`` so ``result()`` has a defined answer (the
        tokens produced before the abort).  SUSPENDED requests cancel
        too — their host swap-pool entry is dropped (they hold no
        device pages), so an aborted preemptee cannot pin swap space.
        Returns True if the request was live and is now cancelled,
        False if it had already retired (idempotent — a race between
        natural completion and a client disconnect is not an error).
        Unknown rids raise."""
        enforce(rid in self.requests,
                f"unknown request id {rid!r} (never admitted to this "
                f"engine)")
        req = self.requests[rid]
        if req.done:
            return False
        req.done = True
        req.cancelled = True
        if req.suspended:
            self.cache.drop_swap(req.swap_handle)
            req.swap_handle = None
            req.suspended = False
        elif req in self._active:
            self._active.remove(req)
            self.cache.release(req.slot)
            self._spec_release(req)
        elif req in self._prefilling:
            self._prefilling.remove(req)
            self.cache.release(req.slot)
        if self._metrics is not None:
            self._metrics["aborted"].inc()
            self._metrics["queue_depth"].set(len(self._active))
        return True

    def result(self, rid) -> List[int]:
        """Final token list of a RETIRED request.

        Retirement contract: a request retires when it hits EOS, its
        max_new_tokens budget (its pages are released then), or is
        ``abort()``-ed (check ``requests[rid].cancelled`` to tell a
        partial stream from a completed one); until that point its
        tokens stream out of ``step()``'s return value and ``result``
        raises.  Unknown rids raise too — both are clear errors
        instead of a bare KeyError or a silently partial read.

        Retention: results stay readable after retirement for the
        engine's lifetime — the entry is only dropped by
        ``pop_result()``.  Long-running servers MUST use
        ``pop_result`` (the serving scheduler does), or the
        ``requests`` map grows by one retired entry per request
        forever."""
        enforce(rid in self.requests,
                f"unknown request id {rid!r} (never admitted to this "
                f"engine)")
        req = self.requests[rid]
        enforce(req.done,
                f"request {rid!r} is still generating ({len(req.out)} "
                f"tokens so far) — consume step() output to stream, "
                f"or call result() after it retires")
        return list(req.out)

    def pop_result(self, rid) -> List[int]:
        """``result(rid)``, then forget the request — the
        memory-retention primitive for long-running serving (a
        week-long server that never pops grows ``requests`` without
        bound).  Same contract as ``result``: only retired rids
        pop."""
        out = self.result(rid)
        del self.requests[rid]
        return out

    # -- observability ---------------------------------------------------------
    @staticmethod
    def prefill_compiles() -> int:
        """Number of distinct prefill XLA programs compiled — 1 for
        any request mix (the chunked program's shape is fixed by the
        engine geometry, not the prompt lengths; the int8 KV / int8
        weight variants are distinct engine CONFIGS, not request
        shapes, so each engine still sees exactly one)."""
        return _paged_prefill_chunk._cache_size()

    @staticmethod
    def decode_compiles() -> int:
        """Distinct compiled decode-side programs: the multi-step
        decode program recompute resume and capsule replay dispatch
        (its window buckets) PLUS the mixed-step program ``step()``
        launches PLUS the on-device window programs — a window-bucket
        recompile must trip the same unchanged-across-runs assertions
        the step program lives under."""
        return _paged_decode_step._cache_size() + \
            _paged_mixed_step._cache_size() + \
            _packed_mixed_step._cache_size() + \
            LLMEngine.window_compiles()

    @staticmethod
    def mixed_compiles() -> int:
        """Distinct compiled step-loop programs: the mixed-step
        program (1 per engine geometry for ANY interleaving of prefill
        chunks and decode slots — every batch-mix input is traced
        data) plus one mixed-window program per power-of-two window
        bucket — bounded by the CompileWatch
        allowances declared at engine construction
        (bit_length(steps_per_sync) − 1 buckets).  What is counted is
        what is launched: the one-transfer wrappers
        (``_packed_mixed_step`` / ``_packed_mixed_window``) and the
        inner step program where it is dispatched bare (speculative
        verify).  Like the other counters this reads a process-global
        jit cache: assert deltas, not absolutes, when several
        geometries share the process."""
        return _packed_mixed_step._cache_size() + \
            _paged_mixed_step._cache_size() + \
            _packed_mixed_window._cache_size()

    @staticmethod
    def window_compiles() -> int:
        """Distinct compiled ON-DEVICE decode-window programs.
        Expected: one per power-of-two window bucket actually
        dispatched — {2, 4, ..., 2^floor(log2(steps_per_sync))} at
        most; 0 when steps_per_sync == 1 (the degenerate window IS the
        plain step program)."""
        return _packed_mixed_window._cache_size()

    def metrics_snapshot(self) -> dict:
        """One JSON-able dict with everything an operator tunes
        against: TTFT/TPOT histogram snapshots, token counters,
        queue/occupancy, KV-page pressure, and the compile-count
        invariants.  Works with ``enable_metrics=False`` too (the
        registry-backed series are then absent; compile counts and
        page stats are always available)."""
        # the counts a running engine has put aside are host numbers
        # already: a reader folds them first and touches no device
        self._fold_expert_counts("at_idle")
        seen = self.prefix_stats["hit_tokens"] + \
            self.prefix_stats["miss_tokens"]
        snap = {
            "engine": self.engine_id,
            "tp": self._capsule_fp["tp"],
            "prefill_compiles": self.prefill_compiles(),
            "decode_compiles": self.decode_compiles(),
            "mixed_compiles": self.mixed_compiles(),
            "window_compiles": self.window_compiles(),
            "last_window_steps": int(self.last_window_steps),
            "prefill_token_budget": int(self.prefill_token_budget),
            "kv_cache": self.cache.metrics_snapshot(),
            "kv_page_utilization": self.cache.page_utilization(),
            "active_requests": len(self._active),
            "prefilling_requests": len(self._prefilling),
            "suspended_requests": self.suspended_count(),
            "free_slots": self.free_slots(),
            "host_transfers": dict(self.host_transfers),
            "forward_rows": {path: dict(ran) for path, ran
                             in self.forward_rows.items()},
            "count_folds": dict(self.count_folds),
            "prefix_caching": dict(
                self.prefix_stats,
                enabled=self.enable_prefix_caching,
                hit_rate=(self.prefix_stats["hit_tokens"] / seen
                          if seen else 0.0)),
        }
        if self._arch is not None:
            # per-expert load plane (host counters — present with
            # metrics off too, like the prefix stats): cumulative
            # routed slots summed over layers, the capacity-drop
            # total, and the max/mean imbalance SLO
            tot = self._moe_counts.sum(axis=0)
            snap["moe"] = {
                "num_experts": self._arch.num_experts,
                "top_k": self._arch.top_k,
                "dropless": self._arch.capacity == 0,
                "capacity": self._arch.capacity,
                "dispatch": self._arch.dispatch,
                "shared_experts": self._arch.shared,
                "expert_tokens": [int(v) for v in tot],
                "expert_lo": self._arch.expert_lo,
                "experts_held": self._arch.n_held,
                "absent_slots": int(self._moe_absent),
                "dropped_tokens": int(self._moe_dropped),
                # how full the grouped dispatch's sorted buffer ran:
                # kept slots of experts held here / rows handed to the
                # kernels (0.0 before any, and for the dense reference)
                "buffer_rows": int(self._moe_buffer_rows),
                "row_fill": ((int(tot.sum()) - int(self._moe_absent))
                             / self._moe_buffer_rows
                             if self._moe_buffer_rows else 0.0),
                "imbalance": (float(tot.max() / tot.mean())
                              if tot.sum() else 0.0),
            }
        if self._hybrid is not None:
            hy = self._hybrid
            snap["linear"] = dict(
                self.linear_stats, layers=hy.n_linear,
                full_layers=hy.n_full,
                state_bytes=self.cache.state_bytes(),
                state_bytes_per_slot=(self.cache.state_bytes()
                                      // (self.max_seqs + 1)))
        if self._spec is not None:
            # speculative acceptance plane (host counters — present
            # with metrics off too): proposed counts DRAFT tokens
            # offered to verify, accepted the survivors, delivered
            # every token returned to requests (bonus / correction
            # included)
            st = self.spec_stats
            snap["spec"] = {
                "enabled": True,
                "k": self.spec_k,
                "mode": self._spec_mode,
                "draft_hash": self._capsule_fp["spec"]["draft_hash"],
                "windows": int(st["windows"]),
                "proposed": int(st["proposed"]),
                "accepted": int(st["accepted"]),
                "delivered": int(st["delivered"]),
                "acceptance_rate": (st["accepted"] / st["proposed"]
                                    if st["proposed"] else 0.0),
                "kv_cache_draft": self._spec_cache.metrics_snapshot(),
            }
        if self._metrics is not None:
            m = self._metrics
            snap.update({
                "ttft_seconds": m["ttft"]._snapshot_value(),
                "tpot_seconds": m["tpot"]._snapshot_value(),
                "prompt_tokens": int(m["prompt_tokens"].value),
                "generated_tokens": int(m["generated_tokens"].value),
                "requests": int(m["requests"].value),
                "steps": int(m["steps"].value),
                "step_prefill_tokens":
                    int(m["step_prefill_tokens"].value),
                "queue_depth": m["queue_depth"].value,
                "batch_occupancy": m["occupancy"].value,
                "mixed_batch_decode_slots":
                    m["mixed_decode_slots"].value,
                "mixed_batch_prefill_tokens":
                    m["mixed_prefill_tokens"].value,
            })
        return snap
