"""Ragged paged attention for TPU decode serving (Pallas/Mosaic).

Reference parity: the reference's inference engine attention path
(paddle/fluid/inference + phi fused attention kernels, SURVEY.md §1 L8);
kernel blueprint: "Ragged Paged Attention: A High-Performance and
Flexible LLM Inference Kernel for TPU" (PAPERS.md).

TPU-native design: the KV cache lives in fixed-size PAGES
([KVH, n_pages, page_size, D]) so ragged per-sequence lengths share one
physical pool with no padding waste; a per-sequence page table maps
logical page slots to physical pages.

The decode kernel runs one grid step per (sequence, kv-head) — NOT per
page: the page pool stays in HBM (``memory_space=ANY``) and the body
streams that sequence's pages itself with MANUALLY-issued async copies
(``pltpu.make_async_copy``) into a double-buffered VMEM scratch, so
page i+1's DMA overlaps page i's online-softmax accumulation and the
grid-step count is B·KVH instead of B·KVH·max_pages.  The round-3
per-page-grid variant spent ~3.5 µs of Mosaic grid/DMA-setup overhead
per TINY page step (1024 steps ≈ 3.6 ms at batch 8 × 2k context);
this design is what the ragged-paged-attention paper's kernel does and
measures ~30× faster (see BASELINE.md serving rows).  The query-head
group of each KV head (GQA) rides the same page DMA; pages past a
sequence's length are never copied.

INT8 KV mode (the quantization subsystem's serving path): pages are
stored int8 with ONE f32 absmax scale per token row, kept in a sibling
scale pool laid out [KVH, n_pages, 1, page_size] — the page's scale
vector lives on the LANE dimension, so in-kernel dequantization never
needs a sublane broadcast: the K scale multiplies the logits row
s[g, t] (shape [G, P] × [1, P]) and the V scale folds into the softmax
probabilities before the PV matmul.  The int8 page + its scale row
stream through the same _NBUF-deep DMA pipeline; HBM traffic per page
drops ~2× vs fp16 (page bytes P·D → P·D + 4·P for the scales).

RAGGED MIXED MODE (``ragged_paged_append_attend``): one dispatch serves
a whole mixed prefill+decode batch.  The flat token batch carries
per-sequence descriptors ``(q_start, q_len, kv_len)`` — a decode slot
contributes one query row (q_len == 1), a prefill chunk up to
``page_size`` rows, all landing inside ONE page (the engine chunks
prompts at page boundaries, so ``kv_len % P + q_len <= P`` holds per
descriptor).  Each descriptor's P query rows and (page-aligned) new K/V
rows are gathered by XLA into per-descriptor blocks that reach the
kernel through plain BlockSpecs; the only manual DMAs are whole pages.
The grid is (descriptor, kv-head); each step streams that
sequence's pages through the same double-buffered pipeline, substitutes
the chunk's freshly-projected K/V rows in registers (quantizing them
per row first in int8 mode), applies the causal-within-chunk mask
(``kv_pos <= kv_len + row``), and writes the ONE modified page (plus
its scale row) back — the fused-append contract of the decode kernel,
generalized to ragged row counts.  Grid steps run sequentially on TPU,
so a long prompt split across several descriptors in one dispatch sees
its earlier chunks' pages already written.  The jnp mirror
(``ragged_paged_append_attend_reference``) is the CPU/oracle path the
engine's mixed-step program uses off-TPU.

TENSOR-PARALLEL SERVING (engine ``mesh=``/``tp_axis=``): the engine
shards the page pools on the KVH axis (dim 0 of one layer's pool, dim
1 of the layer-stacked pools the ragged kernel takes with ``layer=``)
and the query/new-KV projections on the head axis,
so under GSPMD each shard's kernel dispatch sees a self-contained
problem — KVH/tp heads of EVERY page, with the (sequence, kv-head)
grid partitioning trivially along its second axis and zero cross-chip
traffic inside the kernel (page tables and seq_lens are replicated
scalars/int32 vectors).  Nothing in this file needs a mesh, but GSPMD
cannot partition a Mosaic call ("wrap the call in a shard_map"): on
the TPU the engine wraps each kernel call in a ``shard_map`` over the
tp axis (``TPShardings.per_shard``: pools sharded on KVH, q/k_new/v_new
on the head dim, descriptors replicated), so every shard runs the
kernel body on its own heads.  The
per-token scale pools ride the same KVH sharding, so the int8 path's
~2× HBM saving multiplies the tp capacity win instead of fighting it.
The jnp reference paths below are likewise head-parallel by
construction (every einsum/gather is elementwise or contracted over
D/S only, never over KVH), which is what makes the CPU mesh tests
bit-exact vs tp=1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...quantization.ops import EPS, QMAX, quantize_rows_raw
from .vma import out_sds

__all__ = ["paged_attention_raw", "paged_attention_reference",
           "paged_write", "paged_write_quant",
           "paged_decode_append_attend",
           "paged_decode_append_attend_raw",
           "paged_decode_append_attend_reference",
           "ragged_paged_append_attend",
           "ragged_paged_append_attend_raw",
           "ragged_paged_append_attend_reference",
           "paged_write_rows", "paged_write_rows_quant"]

_NEG_INF = float(-1e30)
_LANES = 128


_NBUF = 8          # DMA pipeline depth: outstanding page copies per stream


def _stream_pages(pt_ref, b, h, q, k_hbm, v_hbm, k_scr, v_scr, sem,
                  length, npages, page_size, inject=None, quant=None):
    """Online-softmax attention over a sequence's pages, streamed from
    HBM with an _NBUF-deep manual DMA pipeline.

    ``inject``: optional append substitution performed in registers —
    fp mode (append_page, append_slot, k_row [D], v_row [D]); int8 mode
    additionally carries the pre-quantized row and its scales
    (append_page, append_slot, k_row_q [D] i8, v_row_q [D] i8,
    k_scale, v_scale).  The modified page (and, in int8 mode, its
    modified scale row) is handed back for write-back.

    ``quant``: (ks_hbm, vs_hbm, ks_scr, vs_scr) — int8 pages with
    per-token scale rows [1, P] streamed alongside each page;
    ``sem`` then has 4 columns (k, v, k-scale, v-scale).

    Returns (l, acc, writeback) where writeback is None, (kmod, vmod),
    or (kmod, vmod, ksmod, vsmod)."""
    if quant is not None:
        ks_hbm, vs_hbm, ks_scr, vs_scr = quant

    def k_copy(i, slot):
        return pltpu.make_async_copy(
            k_hbm.at[h, pt_ref[b, i]], k_scr.at[slot], sem.at[slot, 0])

    def v_copy(i, slot):
        return pltpu.make_async_copy(
            v_hbm.at[h, pt_ref[b, i]], v_scr.at[slot], sem.at[slot, 1])

    def ks_copy(i, slot):
        return pltpu.make_async_copy(
            ks_hbm.at[h, pt_ref[b, i]], ks_scr.at[slot], sem.at[slot, 2])

    def vs_copy(i, slot):
        return pltpu.make_async_copy(
            vs_hbm.at[h, pt_ref[b, i]], vs_scr.at[slot], sem.at[slot, 3])

    def start(i, slot):
        k_copy(i, slot).start()
        v_copy(i, slot).start()
        if quant is not None:
            ks_copy(i, slot).start()
            vs_copy(i, slot).start()

    def wait(i, slot):
        k_copy(i, slot).wait()
        v_copy(i, slot).wait()
        if quant is not None:
            ks_copy(i, slot).wait()
            vs_copy(i, slot).wait()

    for j in range(_NBUF):
        @pl.when(j < npages)
        def _(j=j):
            start(j, j)

    g = q.shape[0]
    d = q.shape[1]
    m0 = jnp.full((g, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((g, 1), jnp.float32)
    acc0 = jnp.zeros((g, d), jnp.float32)

    def body(i, carry):
        if inject is not None and quant is not None:
            m, l, acc, kmod, vmod, ksmod, vsmod = carry
        elif inject is not None:
            m, l, acc, kmod, vmod = carry
        else:
            m, l, acc = carry
        slot = jax.lax.rem(i, _NBUF)

        wait(i, slot)
        kpg = k_scr[slot]                                  # [P, D]
        vpg = v_scr[slot]
        if quant is not None:
            ks = ks_scr[slot]                              # [1, P] f32
            vs = vs_scr[slot]
        if inject is not None:
            if quant is not None:
                ap, aslot, krow, vrow, ksrow, vsrow = inject
            else:
                ap, aslot, krow, vrow = inject
            hit = i == ap
            rowsel = jax.lax.broadcasted_iota(
                jnp.int32, (page_size, 1), 0) == aslot
            sel = jnp.logical_and(hit, rowsel)
            kpg = jnp.where(sel, krow[None, :], kpg)
            vpg = jnp.where(sel, vrow[None, :], vpg)
            kmod = jnp.where(hit, kpg, kmod)
            vmod = jnp.where(hit, vpg, vmod)
            if quant is not None:
                lanesel = jax.lax.broadcasted_iota(
                    jnp.int32, (1, page_size), 1) == aslot
                lsel = jnp.logical_and(hit, lanesel)
                ks = jnp.where(lsel, ksrow, ks)
                vs = jnp.where(lsel, vsrow, vs)
                ksmod = jnp.where(hit, ks, ksmod)
                vsmod = jnp.where(hit, vs, vsmod)
        k = kpg.astype(jnp.float32)
        v = vpg.astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if quant is not None:
            # per-token K scale lands on the logit LANES: [G,P] * [1,P]
            s = s * ks
        pos = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                             # [G, P]
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        if quant is not None:
            # fold V's per-token scale into the probabilities (lanes
            # again), so the PV matmul consumes the raw int8 page
            p = p * vs
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

        # refill this slot only after the dots consumed its data
        @pl.when(i + _NBUF < npages)
        def _():
            start(i + _NBUF, slot)
        if inject is not None and quant is not None:
            return (m_new, l_new, acc * alpha + pv, kmod, vmod,
                    ksmod, vsmod)
        if inject is not None:
            return m_new, l_new, acc * alpha + pv, kmod, vmod
        return m_new, l_new, acc * alpha + pv

    if inject is not None:
        kz = jnp.zeros((page_size, d),
                       jnp.int8 if quant is not None else jnp.float32)
        if quant is not None:
            sz = jnp.zeros((1, page_size), jnp.float32)
            _, l, acc, kmod, vmod, ksmod, vsmod = jax.lax.fori_loop(
                0, npages, body, (m0, l0, acc0, kz, kz, sz, sz))
            return l, acc, (kmod, vmod, ksmod, vsmod)
        _, l, acc, kmod, vmod = jax.lax.fori_loop(
            0, npages, body, (m0, l0, acc0, kz, kz))
        return l, acc, (kmod, vmod)
    _, l, acc = jax.lax.fori_loop(0, npages, body, (m0, l0, acc0))
    return l, acc, None


def _decode_kernel(pt_ref, len_ref, q_ref, k_hbm, v_hbm, *rest,
                   scale, page_size, maxp, quantized):
    if quantized:
        (ks_hbm, vs_hbm, o_ref,
         k_scr, v_scr, sem, ks_scr, vs_scr) = rest
        quant = (ks_hbm, vs_hbm, ks_scr, vs_scr)
    else:
        o_ref, k_scr, v_scr, sem = rest
        quant = None
    b, h = pl.program_id(0), pl.program_id(1)
    length = len_ref[b]
    npages = jnp.minimum((length + page_size - 1) // page_size, maxp)

    @pl.when(npages == 0)
    def _():
        o_ref[0, 0] = jnp.zeros(o_ref.shape[2:], o_ref.dtype)

    @pl.when(npages > 0)
    def _():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # [G, D]
        l, acc, _ = _stream_pages(
            pt_ref, b, h, q, k_hbm, v_hbm, k_scr, v_scr, sem, length,
            npages, page_size, quant=quant)
        o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale",))
def paged_attention_raw(q, k_pages, v_pages, page_table, seq_lens,
                        k_scales=None, v_scales=None, *, scale=None):
    """Single-token (decode) ragged paged attention.

    q:          [B, H, D] — one query token per sequence.
    k_pages:    [KVH, n_pages, page_size, D] physical page pool
                (fp, or int8 when k_scales/v_scales are given).
    v_pages:    like k_pages.
    page_table: [B, max_pages] int32 — physical page per logical slot
                (entries past a sequence's page count must still be
                valid indices; their keys are masked by seq_lens).
    seq_lens:   [B] int32 — valid tokens per sequence.
    k_scales/v_scales: optional [KVH, n_pages, 1, page_size] f32
                per-token dequantization scales for int8 pools; the
                kernel dequantizes in VMEM (pages never round-trip
                through a dense fp copy).

    Returns [B, H, D].
    """
    b, h, d = q.shape
    kvh, n_pages, page_size, _ = k_pages.shape
    maxp = page_table.shape[1]
    g = h // kvh
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    qg = q.reshape(b, kvh, g, d)
    quantized = k_scales is not None

    grid = (b, kvh)
    kernel = functools.partial(_decode_kernel, scale=scale,
                               page_size=page_size, maxp=maxp,
                               quantized=quantized)
    in_specs = [
        pl.BlockSpec((1, 1, g, d),
                     lambda b_, h_, pt, ln: (b_, h_, 0, 0)),
        # page pools stay in HBM; the kernel streams pages with
        # manual double-buffered async copies
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    scratch = [
        pltpu.VMEM((_NBUF, page_size, d), k_pages.dtype),
        pltpu.VMEM((_NBUF, page_size, d), v_pages.dtype),
        pltpu.SemaphoreType.DMA((_NBUF, 4 if quantized else 2)),
    ]
    operands = [qg, k_pages, v_pages]
    if quantized:
        in_specs += [pl.BlockSpec(memory_space=pl.ANY),
                     pl.BlockSpec(memory_space=pl.ANY)]
        scratch += [pltpu.VMEM((_NBUF, 1, page_size), jnp.float32),
                    pltpu.VMEM((_NBUF, 1, page_size), jnp.float32)]
        operands += [k_scales, v_scales]
    out = pl.pallas_call(
        kernel,
        name="paged_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, g, d),
                                   lambda b_, h_, pt, ln: (b_, h_,
                                                           0, 0)),
            scratch_shapes=scratch,
        ),
        out_shape=out_sds((b, kvh, g, d), q.dtype, page_table,
                          seq_lens, *operands),
    )(page_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
      *operands)
    return out.reshape(b, h, d)


def _decode_append_kernel(pt_ref, len_ref, q_ref, knew_ref, vnew_ref,
                          k_in, v_in, *rest,
                          scale, page_size, maxp, quantized):
    if quantized:
        (ks_in, vs_in, o_ref, k_out, v_out, ks_out, vs_out,
         k_scr, v_scr, w_scr, sem, wsem, ks_scr, vs_scr,
         ws_scr) = rest
        quant = (ks_in, vs_in, ks_scr, vs_scr)
    else:
        (o_ref, k_out, v_out, k_scr, v_scr, w_scr, sem, wsem) = rest
        quant = None
    b, h = pl.program_id(0), pl.program_id(1)
    pos = len_ref[b]                        # append position
    length = pos + 1                        # attend incl. the new token
    npages = jnp.minimum((length + page_size - 1) // page_size, maxp)
    ap = pos // page_size
    aslot = pos % page_size

    # this kv-head's new K/V rows: select row h from the [KVH, D] block
    kvh = knew_ref.shape[1]
    hsel = jax.lax.broadcasted_iota(jnp.int32, (kvh, 1), 0) == h
    krow = jnp.sum(jnp.where(hsel, knew_ref[0].astype(jnp.float32), 0.0),
                   axis=0)                                  # [D]
    vrow = jnp.sum(jnp.where(hsel, vnew_ref[0].astype(jnp.float32), 0.0),
                   axis=0)
    if quantized:
        # quantize the appended rows in registers: one absmax scale
        # per row (the pool's per-token granularity)
        kamax = jnp.maximum(jnp.max(jnp.abs(krow)), EPS)
        vamax = jnp.maximum(jnp.max(jnp.abs(vrow)), EPS)
        ksrow = kamax / QMAX
        vsrow = vamax / QMAX
        krow = jnp.clip(jnp.round(krow / ksrow), -QMAX,
                        QMAX).astype(jnp.int8)
        vrow = jnp.clip(jnp.round(vrow / vsrow), -QMAX,
                        QMAX).astype(jnp.int8)
        inject = (ap, aslot, krow, vrow, ksrow, vsrow)
    else:
        inject = (ap, aslot, krow, vrow)

    q = q_ref[0, 0].astype(jnp.float32) * scale             # [G, D]
    l, acc, wb = _stream_pages(
        pt_ref, b, h, q, k_in, v_in, k_scr, v_scr, sem, length, npages,
        page_size, inject=inject, quant=quant)
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)

    # write the modified append page back with ONE full-page DMA (the
    # row-granular write is a register select above — no sublane-
    # alignment constraints, unlike a direct scatter/partial DMA)
    if quantized:
        kmod, vmod, ksmod, vsmod = wb
    else:
        kmod, vmod = wb
    w_scr[0] = kmod.astype(w_scr.dtype)
    w_scr[1] = vmod.astype(w_scr.dtype)
    copies = [
        pltpu.make_async_copy(w_scr.at[0], k_out.at[h, pt_ref[b, ap]],
                              wsem.at[0]),
        pltpu.make_async_copy(w_scr.at[1], v_out.at[h, pt_ref[b, ap]],
                              wsem.at[1]),
    ]
    if quantized:
        ws_scr[0] = ksmod
        ws_scr[1] = vsmod
        copies += [
            pltpu.make_async_copy(ws_scr.at[0],
                                  ks_out.at[h, pt_ref[b, ap]],
                                  wsem.at[2]),
            pltpu.make_async_copy(ws_scr.at[1],
                                  vs_out.at[h, pt_ref[b, ap]],
                                  wsem.at[3]),
        ]
    for c in copies:
        c.start()
    for c in copies:
        c.wait()


def paged_decode_append_attend_raw(q, k_pages, v_pages, k_new, v_new,
                                   page_table, seq_lens,
                                   k_scales=None, v_scales=None, *,
                                   scale=None):
    """Fused decode step: append ``k_new``/``v_new`` [B, KVH, D] at
    position ``seq_lens[b]`` AND attend ``q`` [B, H, D] over the
    ``seq_lens[b] + 1`` tokens, in ONE kernel.

    The page pools alias input→output (donated), so the only KV-cache
    writes are one modified page per (sequence, kv-head) — the XLA
    ``paged_write`` scatter/dus path rewrites the whole pool per step
    on TPU (dynamic sublane offsets defeat in-place updates) and was
    the round-3 serving bottleneck.

    With ``k_scales``/``v_scales`` ([KVH, n_pages, 1, P] f32) the pools
    are int8: the kernel quantizes the appended rows in registers,
    streams + dequantizes pages in VMEM, and writes back the modified
    int8 page together with its scale row.  Returns
    (out [B, H, D], k_pages', v_pages') — plus (k_scales', v_scales')
    in int8 mode; caller bumps seq_lens.
    """
    b, h, d = q.shape
    kvh, n_pages, page_size, _ = k_pages.shape
    maxp = page_table.shape[1]
    g = h // kvh
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    qg = q.reshape(b, kvh, g, d)
    quantized = k_scales is not None

    kernel = functools.partial(_decode_append_kernel, scale=scale,
                               page_size=page_size, maxp=maxp,
                               quantized=quantized)
    in_specs = [
        pl.BlockSpec((1, 1, g, d),
                     lambda b_, h_, pt, ln: (b_, h_, 0, 0)),
        pl.BlockSpec((1, kvh, d),
                     lambda b_, h_, pt, ln: (b_, 0, 0)),
        pl.BlockSpec((1, kvh, d),
                     lambda b_, h_, pt, ln: (b_, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    out_specs = [
        pl.BlockSpec((1, 1, g, d),
                     lambda b_, h_, pt, ln: (b_, h_, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    scratch = [
        pltpu.VMEM((_NBUF, page_size, d), k_pages.dtype),
        pltpu.VMEM((_NBUF, page_size, d), v_pages.dtype),
        pltpu.VMEM((2, page_size, d), k_pages.dtype),
        pltpu.SemaphoreType.DMA((_NBUF, 4 if quantized else 2)),
        pltpu.SemaphoreType.DMA((4 if quantized else 2,)),
    ]
    # new K/V rows are passed fp even in int8 mode (the kernel
    # quantizes them in registers)
    operands = [qg, k_new.astype(jnp.float32 if quantized
                                 else k_pages.dtype),
                v_new.astype(jnp.float32 if quantized
                             else v_pages.dtype),
                k_pages, v_pages]
    out_shape = [
        out_sds((b, kvh, g, d), q.dtype, qg, k_pages, v_pages),
        out_sds(k_pages.shape, k_pages.dtype, qg, k_pages, v_pages),
        out_sds(v_pages.shape, v_pages.dtype, qg, k_pages, v_pages),
    ]
    aliases = {5: 1, 6: 2}
    if quantized:
        in_specs += [pl.BlockSpec(memory_space=pl.ANY),
                     pl.BlockSpec(memory_space=pl.ANY)]
        out_specs += [pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)]
        scratch += [pltpu.VMEM((_NBUF, 1, page_size), jnp.float32),
                    pltpu.VMEM((_NBUF, 1, page_size), jnp.float32),
                    pltpu.VMEM((2, 1, page_size), jnp.float32)]
        operands += [k_scales, v_scales]
        out_shape += [
            out_sds(k_scales.shape, k_scales.dtype, qg, k_scales),
            out_sds(v_scales.shape, v_scales.dtype, qg, v_scales),
        ]
        aliases = {5: 1, 6: 2, 7: 3, 8: 4}
    outs = pl.pallas_call(
        kernel,
        name="paged_decode_append",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, kvh),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        input_output_aliases=aliases,
    )(page_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
      *operands)
    if quantized:
        out, kp, vp, ks, vs = outs
        return out.reshape(b, h, d), kp, vp, ks, vs
    out, kp, vp = outs
    return out.reshape(b, h, d), kp, vp


# standalone dispatch entry; the ``_raw`` body above stays callable
# from INSIDE an enclosing jit (the engine's on-device decode-window
# programs trace it per scan step — the pallas_call's
# input_output_aliases keep the pools in-place across the carry either
# way, while a nested jit here would only add a dispatch-cache entry
# per enclosing program)
paged_decode_append_attend = functools.partial(
    jax.jit, static_argnames=("scale",),
    donate_argnames=("k_pages", "v_pages", "k_scales", "v_scales"),
)(paged_decode_append_attend_raw)


def paged_decode_append_attend_reference(q, k_pages, v_pages, k_new,
                                         v_new, page_table, seq_lens,
                                         k_scales=None, v_scales=None):
    """jnp oracle / CPU path for the fused decode step (fp and int8)."""
    if k_scales is not None:
        k_pages, v_pages, k_scales, v_scales = paged_write_quant(
            k_pages, v_pages, k_scales, v_scales, k_new, v_new,
            page_table, seq_lens)
        out = paged_attention_reference(q, k_pages, v_pages, page_table,
                                        seq_lens + 1, k_scales, v_scales)
        return out, k_pages, v_pages, k_scales, v_scales
    k_pages, v_pages = paged_write(k_pages, v_pages, k_new, v_new,
                                   page_table, seq_lens)
    out = paged_attention_reference(q, k_pages, v_pages, page_table,
                                    seq_lens + 1)
    return out, k_pages, v_pages


def paged_attention_reference(q, k_pages, v_pages, page_table, seq_lens,
                              k_scales=None, v_scales=None):
    """jnp oracle (and CPU fallback): gather pages into dense [B, S, ...]
    then masked attention.  With ``k_scales``/``v_scales`` the pools are
    int8 and the gather dequantizes (token t of page p uses scale
    [..., p, 0, t])."""
    b, h, d = q.shape
    kvh, _, page_size, _ = k_pages.shape
    maxp = page_table.shape[1]
    g = h // kvh
    # [B, KVH, maxp, P, D] -> [B, KVH, S, D]
    kg = jnp.swapaxes(k_pages[:, page_table], 0, 1)
    vg = jnp.swapaxes(v_pages[:, page_table], 0, 1)
    if k_scales is not None:
        # [B, KVH, maxp, 1, P] -> per-token column [B, KVH, maxp, P, 1]
        ksg = jnp.swapaxes(jnp.swapaxes(k_scales[:, page_table], 0, 1),
                           -1, -2)
        vsg = jnp.swapaxes(jnp.swapaxes(v_scales[:, page_table], 0, 1),
                           -1, -2)
        kg = kg.astype(jnp.float32) * ksg
        vg = vg.astype(jnp.float32) * vsg
    s_tot = maxp * page_size
    kg = kg.reshape(b, kvh, s_tot, d)
    vg = vg.reshape(b, kvh, s_tot, d)
    qg = q.reshape(b, kvh, g, d).astype(jnp.float32)
    s = jnp.einsum("bkgd,bksd->bkgs", qg,
                   kg.astype(jnp.float32)) / (d ** 0.5)
    mask = jnp.arange(s_tot)[None, :] < seq_lens[:, None]   # [B, S]
    s = jnp.where(mask[:, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bksd->bkgd", p, vg.astype(jnp.float32))
    return o.reshape(b, h, d).astype(q.dtype)


def paged_write(k_pages, v_pages, k_new, v_new, page_table, seq_lens):
    """Append one token per sequence into the page pool.

    k_new/v_new: [B, KVH, D]; the token lands at logical position
    seq_lens[b] (page page_table[b, pos // P], slot pos % P).
    Returns (k_pages, v_pages) updated; caller bumps seq_lens.

    Implemented as B chained ``dynamic_update_slice``s (statically
    unrolled) rather than one gather-indexed scatter: XLA:TPU keeps a
    dus chain fully in place, while the scatter lowering was the
    round-3 serving bottleneck (sorting/serializing per element).
    """
    page_size = k_pages.shape[2]
    b = k_new.shape[0]
    kt = jnp.swapaxes(k_new, 0, 1).astype(k_pages.dtype)    # [KVH, B, D]
    vt = jnp.swapaxes(v_new, 0, 1).astype(v_pages.dtype)
    zero = jnp.zeros((), jnp.int32)
    for i in range(b):
        page = page_table[i, seq_lens[i] // page_size]
        slot = seq_lens[i] % page_size
        idx = (zero, page, slot, zero)
        k_pages = jax.lax.dynamic_update_slice(
            k_pages, kt[:, i][:, None, None, :], idx)
        v_pages = jax.lax.dynamic_update_slice(
            v_pages, vt[:, i][:, None, None, :], idx)
    return k_pages, v_pages


def paged_write_quant(k_pages, v_pages, k_scales, v_scales,
                      k_new, v_new, page_table, seq_lens):
    """INT8 ``paged_write``: quantize each new row (per-token absmax)
    on the way in, updating both the int8 pools and the scale pools
    ([KVH, n_pages, 1, P]).  Same dus-chain shape as paged_write."""
    page_size = k_pages.shape[2]
    b = k_new.shape[0]
    kq, ks = quantize_rows_raw(k_new)        # [B, KVH, D] i8, [B, KVH]
    vq, vs = quantize_rows_raw(v_new)
    kt = jnp.swapaxes(kq, 0, 1)                             # [KVH, B, D]
    vt = jnp.swapaxes(vq, 0, 1)
    kst = jnp.swapaxes(ks, 0, 1).astype(k_scales.dtype)     # [KVH, B]
    vst = jnp.swapaxes(vs, 0, 1).astype(v_scales.dtype)
    zero = jnp.zeros((), jnp.int32)
    for i in range(b):
        page = page_table[i, seq_lens[i] // page_size]
        slot = seq_lens[i] % page_size
        idx = (zero, page, slot, zero)
        k_pages = jax.lax.dynamic_update_slice(
            k_pages, kt[:, i][:, None, None, :], idx)
        v_pages = jax.lax.dynamic_update_slice(
            v_pages, vt[:, i][:, None, None, :], idx)
        sidx = (zero, page, zero, slot)
        k_scales = jax.lax.dynamic_update_slice(
            k_scales, kst[:, i][:, None, None, None], sidx)
        v_scales = jax.lax.dynamic_update_slice(
            v_scales, vst[:, i][:, None, None, None], sidx)
    return k_pages, v_pages, k_scales, v_scales


# -- ragged mixed prefill+decode (one kernel for the whole batch) -------------

def _stream_pages_ragged(pt_ref, s_i, h, q2, k_hbm, v_hbm, k_scr, v_scr,
                         sem, kv_len, q_len, npages, page_size, g,
                         inject, quant=None):
    """Online-softmax attention for ONE ragged descriptor's query rows
    ([page_size·G, D] — rows past ``q_len`` are dead lanes) over its
    pages, streamed with the same _NBUF pipeline as ``_stream_pages``.

    Differences from the single-row streamer: the causal mask is
    per-ROW (chunk row r sees kv positions <= kv_len + r), and
    ``inject`` substitutes a BLOCK of rows ([base, base + q_len) of the
    append page) instead of one — fp mode (append_page, rowsel [P,1],
    k_rows [P,D], v_rows [P,D]); int8 mode additionally carries the
    pre-quantized rows' lane-oriented scales and their lane selector
    (…, k_scale_lane [1,P], v_scale_lane [1,P], lanesel [1,P]).

    Returns (l, acc, writeback) like ``_stream_pages``."""
    if quant is not None:
        ks_hbm, vs_hbm, ks_scr, vs_scr = quant
        ap, rowsel, krows, vrows, ksl, vsl, lanesel = inject
    else:
        ap, rowsel, krows, vrows = inject

    def k_copy(i, slot):
        return pltpu.make_async_copy(
            k_hbm.at[h, pt_ref[s_i, i]], k_scr.at[slot], sem.at[slot, 0])

    def v_copy(i, slot):
        return pltpu.make_async_copy(
            v_hbm.at[h, pt_ref[s_i, i]], v_scr.at[slot], sem.at[slot, 1])

    def ks_copy(i, slot):
        return pltpu.make_async_copy(
            ks_hbm.at[h, pt_ref[s_i, i]], ks_scr.at[slot],
            sem.at[slot, 2])

    def vs_copy(i, slot):
        return pltpu.make_async_copy(
            vs_hbm.at[h, pt_ref[s_i, i]], vs_scr.at[slot],
            sem.at[slot, 3])

    def start(i, slot):
        k_copy(i, slot).start()
        v_copy(i, slot).start()
        if quant is not None:
            ks_copy(i, slot).start()
            vs_copy(i, slot).start()

    def wait(i, slot):
        k_copy(i, slot).wait()
        v_copy(i, slot).wait()
        if quant is not None:
            ks_copy(i, slot).wait()
            vs_copy(i, slot).wait()

    for j in range(_NBUF):
        @pl.when(j < npages)
        def _(j=j):
            start(j, j)

    rows = q2.shape[0]                                 # page_size · G
    d = q2.shape[1]
    m0 = jnp.full((rows, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((rows, 1), jnp.float32)
    acc0 = jnp.zeros((rows, d), jnp.float32)

    def body(i, carry):
        if quant is not None:
            m, l, acc, kmod, vmod, ksmod, vsmod = carry
        else:
            m, l, acc, kmod, vmod = carry
        slot = jax.lax.rem(i, _NBUF)

        wait(i, slot)
        kpg = k_scr[slot]                              # [P, D]
        vpg = v_scr[slot]
        if quant is not None:
            ks = ks_scr[slot]                          # [1, P] f32
            vs = vs_scr[slot]
        hit = i == ap
        sel = jnp.logical_and(hit, rowsel)
        kpg = jnp.where(sel, krows, kpg)
        vpg = jnp.where(sel, vrows, vpg)
        kmod = jnp.where(hit, kpg, kmod)
        vmod = jnp.where(hit, vpg, vmod)
        if quant is not None:
            lsel = jnp.logical_and(hit, lanesel)
            ks = jnp.where(lsel, ksl, ks)
            vs = jnp.where(lsel, vsl, vs)
            ksmod = jnp.where(hit, ks, ksmod)
            vsmod = jnp.where(hit, vs, vsmod)
        k = kpg.astype(jnp.float32)
        v = vpg.astype(jnp.float32)
        s = jax.lax.dot_general(q2, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if quant is not None:
            s = s * ks
        # causal-within-chunk: query row r (global position
        # kv_len + r) sees kv positions <= kv_len + r
        pos = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // g
        s = jnp.where(pos <= kv_len + row, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                         # [rows, P]
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        if quant is not None:
            p = p * vs
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

        @pl.when(i + _NBUF < npages)
        def _():
            start(i + _NBUF, slot)
        if quant is not None:
            return (m_new, l_new, acc * alpha + pv, kmod, vmod,
                    ksmod, vsmod)
        return m_new, l_new, acc * alpha + pv, kmod, vmod

    kz = jnp.zeros((page_size, d),
                   jnp.int8 if quant is not None else k_scr.dtype)
    if quant is not None:
        sz = jnp.zeros((1, page_size), jnp.float32)
        _, l, acc, kmod, vmod, ksmod, vsmod = jax.lax.fori_loop(
            0, npages, body, (m0, l0, acc0, kz, kz, sz, sz))
        return l, acc, (kmod, vmod, ksmod, vsmod)
    _, l, acc, kmod, vmod = jax.lax.fori_loop(
        0, npages, body, (m0, l0, acc0, kz, kz))
    return l, acc, (kmod, vmod)


def _ragged_kernel(ql_ref, kl_ref, pt_ref, ly_ref, q_ref, kn_ref, vn_ref,
                   k_in, v_in, *rest,
                   scale, page_size, maxp, quantized):
    # the pools arrive STACKED over layers and whole ([L, KVH, ...], in
    # HBM): this call's layer is picked here, before any page DMA, so
    # the caller never slices a layer out or writes one back
    ly = ly_ref[0]
    k_in, v_in = k_in.at[ly], v_in.at[ly]
    if quantized:
        (ks_in, vs_in, o_ref, k_out, v_out, ks_out, vs_out,
         k_scr, v_scr, w_scr, sem, wsem,
         ks_scr, vs_scr, ws_scr) = rest
        quant = (ks_in.at[ly], vs_in.at[ly], ks_scr, vs_scr)
        ks_out, vs_out = ks_out.at[ly], vs_out.at[ly]
    else:
        (o_ref, k_out, v_out, k_scr, v_scr, w_scr, sem, wsem) = rest
        quant = None
    k_out, v_out = k_out.at[ly], v_out.at[ly]
    s_i, h = pl.program_id(0), pl.program_id(1)
    q_len = ql_ref[s_i]
    kv_len = kl_ref[s_i]
    P = page_size
    d = q_ref.shape[3]
    g = q_ref.shape[2] // P

    @pl.when(q_len == 0)
    def _():
        # unused descriptor: zero its output block so the flat-row
        # gather never reads uninitialized memory
        o_ref[0, 0] = jnp.zeros(o_ref.shape[2:], o_ref.dtype)

    @pl.when(q_len > 0)
    def _():
        length = kv_len + q_len
        npages = jnp.minimum((length + P - 1) // P, maxp)
        ap = kv_len // P                    # the ONE page this chunk
        base = kv_len - ap * P              # fills, from row ``base``

        # q_ref holds this descriptor's P query rows x G heads; kn_ref /
        # vn_ref its new K/V rows already shifted so block row r is
        # append-page row r (rows outside [base, base + q_len) are dead
        # and deselected below)
        riota = jax.lax.broadcasted_iota(jnp.int32, (P, 1), 0)
        rowsel = jnp.logical_and(riota >= base, riota < base + q_len)
        knf = kn_ref[0, 0]
        vnf = vn_ref[0, 0]
        if quantized:
            # per-row absmax quantize of the appended rows in registers
            # (the quantize_rows_raw contract, like the decode kernel)
            kamax = jnp.maximum(
                jnp.max(jnp.abs(knf), axis=1, keepdims=True), EPS)
            vamax = jnp.maximum(
                jnp.max(jnp.abs(vnf), axis=1, keepdims=True), EPS)
            ksr = kamax / QMAX                            # [P, 1]
            vsr = vamax / QMAX
            krows = jnp.clip(jnp.round(knf / ksr), -QMAX,
                             QMAX).astype(jnp.int8)
            vrows = jnp.clip(jnp.round(vnf / vsr), -QMAX,
                             QMAX).astype(jnp.int8)
            # rotate the sublane scale column into a LANE row without a
            # transpose: ones[1,P] @ diag(scales) — the diagonal is a
            # where() on a 2-D iota, all Mosaic-friendly shapes
            eye = jax.lax.broadcasted_iota(jnp.int32, (P, P), 0) == \
                jax.lax.broadcasted_iota(jnp.int32, (P, P), 1)
            ones = jnp.ones((1, P), jnp.float32)
            ksl = jax.lax.dot_general(
                ones, jnp.where(eye, ksr, 0.0),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)       # [1, P]
            vsl = jax.lax.dot_general(
                ones, jnp.where(eye, vsr, 0.0),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            liota = jax.lax.broadcasted_iota(jnp.int32, (1, P), 1)
            lanesel = jnp.logical_and(liota >= base,
                                      liota < base + q_len)
            inject = (ap, rowsel, krows, vrows, ksl, vsl, lanesel)
        else:
            inject = (ap, rowsel, knf, vnf)

        q2 = q_ref[0, 0].astype(jnp.float32) * scale
        l, acc, wb = _stream_pages_ragged(
            pt_ref, s_i, h, q2, k_in, v_in, k_scr, v_scr, sem, kv_len,
            q_len, npages, P, g, inject, quant=quant)
        o = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        o_ref[0, 0] = o

        # write the modified append page (and its scale row) back with
        # full-page DMAs — same contract as the decode append kernel
        if quantized:
            kmod, vmod, ksmod, vsmod = wb
        else:
            kmod, vmod = wb
        w_scr[0] = kmod.astype(w_scr.dtype)
        w_scr[1] = vmod.astype(w_scr.dtype)
        copies = [
            pltpu.make_async_copy(w_scr.at[0],
                                  k_out.at[h, pt_ref[s_i, ap]],
                                  wsem.at[0]),
            pltpu.make_async_copy(w_scr.at[1],
                                  v_out.at[h, pt_ref[s_i, ap]],
                                  wsem.at[1]),
        ]
        if quantized:
            ws_scr[0] = ksmod
            ws_scr[1] = vsmod
            copies += [
                pltpu.make_async_copy(ws_scr.at[0],
                                      ks_out.at[h, pt_ref[s_i, ap]],
                                      wsem.at[2]),
                pltpu.make_async_copy(ws_scr.at[1],
                                      vs_out.at[h, pt_ref[s_i, ap]],
                                      wsem.at[3]),
            ]
        for c in copies:
            c.start()
        for c in copies:
            c.wait()


def ragged_paged_append_attend_raw(q, k_pages, v_pages, k_new, v_new,
                                   q_start, q_len, kv_len, page_tables,
                                   k_scales=None, v_scales=None, *,
                                   scale=None, layer=None):
    """Ragged mixed prefill+decode step: ONE kernel appends and attends
    every descriptor of a flat token batch.

    q:            [T, H, D] flat query rows (decode slots and prefill
                  chunks packed back to back; T is the static row count
                  of the program that calls: the engine's mixed step
                  runs slots + prefill budget rows, its decode window
                  one a slot — T may be smaller than a page, the row
                  blocks below clamp to T - 1).
    k_new/v_new:  [T, KVH, D] the rows to append, same flat layout.
    q_start/q_len/kv_len: [S] int32 descriptors — descriptor s covers
                  flat rows [q_start, q_start + q_len) at context
                  length kv_len (its rows land at positions
                  kv_len … kv_len + q_len - 1, all inside page
                  kv_len // P: callers chunk at page boundaries so
                  ``kv_len % P + q_len <= P``).  ``q_len == 0`` marks
                  an unused descriptor slot.
    page_tables:  [S, maxp] int32 per-descriptor page tables.
    k_scales/v_scales: optional [KVH, n_pages, 1, P] f32 — int8 pools.
    layer:        ``None`` for one layer's pools [KVH, n_pages, P, D];
                  an int or traced int32 scalar for pools (and scale
                  pools) STACKED over layers, [L, KVH, n_pages, P, D]
                  and [L, KVH, n_pages, 1, P].  The index reaches the
                  kernel as a scalar-prefetch operand and selects the
                  layer before the page DMAs and the write-back, so the
                  whole stacked pool is aliased input to output and no
                  layer is ever sliced out of it or copied back.

    Returns (out [S, P, H, D], k_pages', v_pages'[, k_scales',
    v_scales']): descriptor s's row j lives at out[s, j] — the caller
    gathers flat rows with its (descriptor, offset) map.  Pools come
    back in the shape they came in and are donated/aliased; the only
    KV writes are one modified page per (descriptor, kv-head)."""
    if layer is None:
        # one layer's pools are a stack of one (a bitcast, no copy)
        outs = ragged_paged_append_attend_raw(
            q, k_pages[None], v_pages[None], k_new, v_new, q_start,
            q_len, kv_len, page_tables,
            None if k_scales is None else k_scales[None],
            None if v_scales is None else v_scales[None],
            scale=scale, layer=0)
        return outs[:1] + tuple(o[0] for o in outs[1:])
    t, h, d = q.shape
    _, kvh, n_pages, page_size, _ = k_pages.shape
    s_max = q_start.shape[0]
    maxp = page_tables.shape[1]
    g = h // kvh
    P = page_size
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    quantized = k_scales is not None

    # per-descriptor row blocks, gathered by XLA and handed to the
    # kernel through plain BlockSpecs: q block row j is flat row
    # q_start + j; the k/v block is aligned to the append page (page
    # row r <- flat row q_start - base + r).  Rows outside the
    # descriptor are clamped reads, dead, and deselected in the kernel.
    # (Round 10 fetched these rows with manual DMAs of one kv-head's
    # slice at a dynamic row offset.  Mosaic refuses that slice for
    # 16-bit operands, and for 32-bit ones it compiles but the copy
    # never completes on a v5e when G is not a power of two — the
    # kernel hung at 12/4 heads.  The only DMAs left are whole pages.)
    ar = jnp.arange(P, dtype=jnp.int32)[None, :]
    qs = q_start.astype(jnp.int32)[:, None]
    base = (kv_len.astype(jnp.int32) % P)[:, None]
    qrows = jnp.clip(qs + ar, 0, t - 1)
    krows = jnp.clip(qs - base + ar, 0, t - 1)
    qb = jnp.transpose(q.reshape(t, kvh, g, d)[qrows],
                       (0, 2, 1, 3, 4)).reshape(s_max, kvh, P * g, d)
    ndt = jnp.float32 if quantized else k_pages.dtype
    knb = jnp.transpose(k_new.astype(ndt)[krows], (0, 2, 1, 3))
    vnb = jnp.transpose(v_new.astype(ndt)[krows], (0, 2, 1, 3))

    kernel = functools.partial(_ragged_kernel, scale=scale,
                               page_size=P, maxp=maxp,
                               quantized=quantized)

    def blk(rows):
        return pl.BlockSpec((1, 1, rows, d),
                            lambda s_, h_, ql, kl, pt, ly: (s_, h_, 0, 0))
    in_specs = [
        blk(P * g), blk(P), blk(P),
        pl.BlockSpec(memory_space=pl.ANY),   # k_pages
        pl.BlockSpec(memory_space=pl.ANY),   # v_pages
    ]
    out_specs = [
        blk(P * g),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    scratch = [
        pltpu.VMEM((_NBUF, P, d), k_pages.dtype),
        pltpu.VMEM((_NBUF, P, d), v_pages.dtype),
        pltpu.VMEM((2, P, d), k_pages.dtype),
        pltpu.SemaphoreType.DMA((_NBUF, 4 if quantized else 2)),
        pltpu.SemaphoreType.DMA((4 if quantized else 2,)),
    ]
    operands = [qb, knb, vnb, k_pages, v_pages]
    out_shape = [
        out_sds((s_max, kvh, P * g, d), q.dtype, qb, k_pages, v_pages),
        out_sds(k_pages.shape, k_pages.dtype, qb, k_pages, v_pages),
        out_sds(v_pages.shape, v_pages.dtype, qb, k_pages, v_pages),
    ]
    # alias indices count the 4 scalar-prefetch operands first
    aliases = {7: 1, 8: 2}
    if quantized:
        in_specs += [pl.BlockSpec(memory_space=pl.ANY),
                     pl.BlockSpec(memory_space=pl.ANY)]
        out_specs += [pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)]
        scratch += [pltpu.VMEM((_NBUF, 1, P), jnp.float32),
                    pltpu.VMEM((_NBUF, 1, P), jnp.float32),
                    pltpu.VMEM((2, 1, P), jnp.float32)]
        operands += [k_scales, v_scales]
        out_shape += [
            out_sds(k_scales.shape, k_scales.dtype, qb, k_scales),
            out_sds(v_scales.shape, v_scales.dtype, qb, v_scales),
        ]
        aliases = {7: 1, 8: 2, 9: 3, 10: 4}
    outs = pl.pallas_call(
        kernel,
        name="ragged_paged_append_attend",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(s_max, kvh),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        input_output_aliases=aliases,
    )(q_len.astype(jnp.int32), kv_len.astype(jnp.int32),
      page_tables.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), *operands)
    out = jnp.transpose(outs[0].reshape(s_max, kvh, P, g, d),
                        (0, 2, 1, 3, 4)).reshape(s_max, P, h, d)
    return (out,) + tuple(outs[1:])


# standalone dispatch entry / in-graph body split, same contract as
# ``paged_decode_append_attend``: the engine's scanned mixed-window
# program calls the ``_raw`` form once per on-device step
ragged_paged_append_attend = functools.partial(
    jax.jit, static_argnames=("scale",),
    donate_argnames=("k_pages", "v_pages", "k_scales", "v_scales"),
)(ragged_paged_append_attend_raw)


def paged_write_rows(k_pages, v_pages, k_new, v_new, positions,
                     row_tables):
    """Per-ROW pool append: flat row i lands at logical position
    ``positions[i]`` of its own sequence (page
    ``row_tables[i, pos // P]``, slot ``pos % P``).  The ragged
    generalization of ``paged_write`` — T chained dus (statically
    unrolled), decode rows and prefill-chunk rows alike.  Padding rows
    point at all-zero tables and position 0, landing in the reserved
    pad page."""
    page_size = k_pages.shape[2]
    t = k_new.shape[0]
    kt = jnp.swapaxes(k_new, 0, 1).astype(k_pages.dtype)    # [KVH, T, D]
    vt = jnp.swapaxes(v_new, 0, 1).astype(v_pages.dtype)
    zero = jnp.zeros((), jnp.int32)
    for i in range(t):
        page = row_tables[i, positions[i] // page_size]
        slot = positions[i] % page_size
        idx = (zero, page, slot, zero)
        k_pages = jax.lax.dynamic_update_slice(
            k_pages, kt[:, i][:, None, None, :], idx)
        v_pages = jax.lax.dynamic_update_slice(
            v_pages, vt[:, i][:, None, None, :], idx)
    return k_pages, v_pages


def paged_write_rows_quant(k_pages, v_pages, k_scales, v_scales,
                           k_new, v_new, positions, row_tables):
    """INT8 ``paged_write_rows``: per-token absmax quantize on the way
    in, scale pools [KVH, n_pages, 1, P] updated alongside."""
    page_size = k_pages.shape[2]
    t = k_new.shape[0]
    kq, ks = quantize_rows_raw(k_new)        # [T, KVH, D] i8, [T, KVH]
    vq, vs = quantize_rows_raw(v_new)
    kt = jnp.swapaxes(kq, 0, 1)                             # [KVH, T, D]
    vt = jnp.swapaxes(vq, 0, 1)
    kst = jnp.swapaxes(ks, 0, 1).astype(k_scales.dtype)     # [KVH, T]
    vst = jnp.swapaxes(vs, 0, 1).astype(v_scales.dtype)
    zero = jnp.zeros((), jnp.int32)
    for i in range(t):
        page = row_tables[i, positions[i] // page_size]
        slot = positions[i] % page_size
        idx = (zero, page, slot, zero)
        k_pages = jax.lax.dynamic_update_slice(
            k_pages, kt[:, i][:, None, None, :], idx)
        v_pages = jax.lax.dynamic_update_slice(
            v_pages, vt[:, i][:, None, None, :], idx)
        sidx = (zero, page, zero, slot)
        k_scales = jax.lax.dynamic_update_slice(
            k_scales, kst[:, i][:, None, None, None], sidx)
        v_scales = jax.lax.dynamic_update_slice(
            v_scales, vst[:, i][:, None, None, None], sidx)
    return k_pages, v_pages, k_scales, v_scales


def ragged_paged_append_attend_reference(q, k_pages, v_pages, k_new,
                                         v_new, positions, row_tables,
                                         k_scales=None, v_scales=None):
    """jnp oracle / CPU path for the ragged mixed step, PER-ROW form:
    append every flat row at its own position (``paged_write_rows``),
    then attend each row over its sequence's pages under the mask
    ``kv_pos <= positions[i]`` — which IS the causal-within-chunk mask
    (a chunk's rows carry consecutive positions) and degenerates to the
    decode mask for q_len == 1 rows.  Bit-compatible with both split
    programs: the decode reference's ``kv_pos < len + 1`` and the
    chunked prefill's additive ``-1e30`` mask select the same exact
    logit values, and every other op is row-independent.

    Returns (out [T, H, D], k_pages', v_pages'[, k_scales',
    v_scales'])."""
    t, h, d = q.shape
    kvh, _, page_size, _ = k_pages.shape
    maxp = row_tables.shape[1]
    g = h // kvh
    if k_scales is not None:
        k_pages, v_pages, k_scales, v_scales = paged_write_rows_quant(
            k_pages, v_pages, k_scales, v_scales, k_new, v_new,
            positions, row_tables)
    else:
        k_pages, v_pages = paged_write_rows(k_pages, v_pages, k_new,
                                            v_new, positions,
                                            row_tables)
    # [T, KVH, maxp, P, D] -> [T, KVH, S, D]
    kg = jnp.swapaxes(k_pages[:, row_tables], 0, 1)
    vg = jnp.swapaxes(v_pages[:, row_tables], 0, 1)
    if k_scales is not None:
        ksg = jnp.swapaxes(jnp.swapaxes(k_scales[:, row_tables], 0, 1),
                           -1, -2)
        vsg = jnp.swapaxes(jnp.swapaxes(v_scales[:, row_tables], 0, 1),
                           -1, -2)
        kg = kg.astype(jnp.float32) * ksg
        vg = vg.astype(jnp.float32) * vsg
    s_tot = maxp * page_size
    kg = kg.reshape(t, kvh, s_tot, d)
    vg = vg.reshape(t, kvh, s_tot, d)
    qg = q.reshape(t, kvh, g, d).astype(jnp.float32)
    s = jnp.einsum("tkgd,tksd->tkgs", qg,
                   kg.astype(jnp.float32)) / (d ** 0.5)
    mask = jnp.arange(s_tot)[None, :] <= positions[:, None]  # [T, S]
    s = jnp.where(mask[:, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("tkgs,tksd->tkgd", p, vg.astype(jnp.float32))
    o = o.reshape(t, h, d).astype(q.dtype)
    if k_scales is not None:
        return o, k_pages, v_pages, k_scales, v_scales
    return o, k_pages, v_pages
