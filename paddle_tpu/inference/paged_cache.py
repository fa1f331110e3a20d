"""Paged KV cache manager for the serving path.

Reference parity: the inference engine's KV memory management (the
reference grows per-request dense caches inside AnalysisPredictor's
memory optim; modern serving uses paged pools — the PAPERS.md ragged
paged attention blueprint).  Host-side page accounting (free list, per-
sequence page lists) stays in python; the page pools are device memory
consumed by ops.pallas.paged_attention.

One object manages ALL decoder layers (``num_layers`` pools sharing one
page table): a token occupies the same (page, slot) in every layer, the
length advances once per token — per-layer bookkeeping cannot drift.

``kv_dtype="int8"`` stores the pools quantized (per-token absmax, one
f32 scale per row kept in sibling scale pools [L, KVH, n_pages, P]):
write_prefill/append quantize on the way in, attend dequantizes inside
the kernel — KV HBM bytes drop ~2× vs fp16 / ~4× vs fp32, which is the
whole game for bandwidth-bound TPU decode and for page capacity at a
fixed HBM budget.

Automatic prefix caching (vLLM-style, host-side only): pages are
REF-COUNTED, and full, immutable prefill pages can be registered in a
hash index keyed by the CHAIN of token-block hashes — ``[sys][A]`` and
``[sys][B]`` share exactly the ``[sys]`` pages, because block k's key
digests block k-1's key.  ``lookup_prefix`` walks the chain,
``allocate(shared_pages=...)`` maps the hits into a new slot's page
table without touching the device, and ``release`` keeps unreferenced
registered pages CACHED (an LRU pool) instead of freeing them: a later
``allocate``/``extend`` evicts LRU-oldest only when the free list runs
dry.  Writes into a shared page copy-on-write (``extend`` grabs a
fresh page and device-copies the row — scales included — before any
mutation), so shared content is immutable by construction.  The int8
scale pools are indexed by the same physical page ids, so quantized
serving shares scales with their pages for free.

KV swap (preemptive scheduling, vLLM-style): ``swap_out(slot)`` copies
the slot's PRIVATE written pages (and int8 scale rows) into a bounded
host-side swap pool and releases every device page — prefix-cache
pages the slot maps read-shared are NOT copied, only unpinned, and
recorded by their chain key so ``swap_in`` can re-pin them (registered
pages are immutable, so the key still names the same bytes).
``swap_in(handle, n_tokens)`` restores the sequence into a fresh slot
with its full ``n_tokens`` page budget re-reserved.  Both degrade
gracefully: a full pool makes ``swap_out`` release-only (returns
``None``), and an evicted shared page makes ``swap_in`` fail cleanly
(returns ``None``) — in either case the caller recomputes the KV from
the token history instead.  The pool is host DRAM, deliberately
outside the device HBM budget: preemption trades host memory + PCIe
copies for freed device pages.
"""
from __future__ import annotations

import hashlib
import io
import itertools
import json
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..common.errors import enforce
from ..observability import get_registry

__all__ = ["PagedKVCache"]

_CACHE_IDS = itertools.count()


def _chain_hash(prev: bytes, tokens) -> bytes:
    """Key for one full token block given the previous block's key —
    chaining makes the key identify the whole prefix, not the block in
    isolation (so equal blocks under different prefixes never alias)."""
    h = hashlib.blake2b(prev, digest_size=16)
    h.update(np.asarray(tokens, np.int64).tobytes())
    return h.digest()


def _put_slot(pool, slot, value):
    """``pool[slot] = value`` in place (the pool is donated)."""
    global _PUT_SLOT
    if _PUT_SLOT is None:
        import jax
        _PUT_SLOT = jax.jit(
            lambda p, s, v: p.at[s].set(v.astype(p.dtype)),
            donate_argnums=(0,))
    return _PUT_SLOT(pool, np.int32(slot), value)


_PUT_SLOT = None


class _SwapEntry:
    """Host-side record of one swapped-out sequence: per written page
    either ("data", j) — row j of the host arrays holds a private
    page's bytes — or ("key", chain_key) — a shared prefix page to
    re-pin through the index at swap-in time."""

    __slots__ = ("plan", "k_host", "v_host", "k_scale_host",
                 "v_scale_host", "n_host_pages", "state")

    def __init__(self, plan, k_host, v_host, k_scale_host,
                 v_scale_host, state=None):
        self.plan = plan
        # (rec [L_lin, Hv, dk, dv], conv [L_lin, K-1, C]) host copies of
        # the slot's recurrent state, where the cache holds one
        self.state = state
        self.k_host = k_host
        self.v_host = v_host
        self.k_scale_host = k_scale_host
        self.v_scale_host = v_scale_host
        self.n_host_pages = 0 if k_host is None else k_host.shape[2]


class PagedKVCache:
    def __init__(self, n_pages: int, page_size: int, n_kv_heads: int,
                 head_dim: int, max_seqs: int, max_len: int,
                 dtype=np.float32, num_layers: int = 1,
                 kv_dtype: Optional[str] = None,
                 swap_pool_pages: int = 0, shardings=None,
                 state_spec=None):
        import jax.numpy as jnp
        enforce(kv_dtype in (None, "int8"),
                f"unsupported kv_dtype {kv_dtype!r} (None or 'int8')")
        self.n_pages = n_pages
        self.page_size = page_size
        self.num_layers = num_layers
        self.kv_dtype = kv_dtype
        self.max_pages_per_seq = (max_len + page_size - 1) // page_size
        pool_dtype = jnp.int8 if kv_dtype == "int8" else dtype
        # tensor-parallel pools (``shardings``: a distributed.sharding
        # TPShardings plan): the pools commit sharded on the KV-HEAD
        # axis — each shard holds n_kv_heads/tp heads of EVERY page, so
        # the page tables, free lists, prefix index and swap plans stay
        # global (host bookkeeping is tp-agnostic).  jax.device_get on
        # a sharded pool gathers the full logical array, which is what
        # keeps swap blobs portable across mesh shapes by construction.
        self._shardings = shardings
        if shardings is not None:
            enforce(n_kv_heads % shardings.tp == 0,
                    f"tp={shardings.tp} must divide n_kv_heads "
                    f"({n_kv_heads})")
        # [L, KVH, n_pages, P, D]
        self.k_pages = jnp.zeros((num_layers, n_kv_heads, n_pages,
                                  page_size, head_dim), pool_dtype)
        self.v_pages = jnp.zeros_like(self.k_pages)
        if kv_dtype == "int8":
            # per-token dequant scales; the kernels consume per-layer
            # [KVH, n_pages, 1, P] views (scale vector on the lanes)
            self.k_scales = jnp.zeros((num_layers, n_kv_heads, n_pages,
                                       page_size), jnp.float32)
            self.v_scales = jnp.zeros_like(self.k_scales)
        else:
            self.k_scales = None
            self.v_scales = None
        if shardings is not None:
            # commit on the mesh, KV-head axis sharded; the serving
            # programs donate the pools so the placement survives every
            # step, and eager .at[].set updates (swap-in, import)
            # re-scatter through it
            self.k_pages = shardings.put(self.k_pages, 1)
            self.v_pages = shardings.put(self.v_pages, 1)
            if self.k_scales is not None:
                self.k_scales = shardings.put(self.k_scales, 1)
                self.v_scales = shardings.put(self.v_scales, 1)
        # A SECOND kind of per-request state, for backbones with
        # recurrent mixers (``state_spec``: backbone.HybridArch): per
        # such layer one float32 recurrent state per slot [max_seqs +
        # 1, *state_spec.state_shape] and the conv window of the last
        # K - 1 inputs [max_seqs + 1, K - 1, C].  They are indexed by
        # the SLOT (not by pages), live and die with it, ride the step
        # programs' carry whole and are aliased in and out like the
        # pools.  Nothing here zeroes them: a sequence whose first
        # descriptor has ``kv_len == 0`` reads its state as zeros in
        # the program (traced data), and the last slot is the pad slot
        # that takes dead rows' writes.  One array a layer, so a layer
        # loop uses each where it lies.
        self.state_spec = state_spec
        self.rec_state = self.conv_state = None
        if state_spec is not None:
            enforce(shardings is None,
                    "recurrent state pools are not sharded over a mesh")
            sp = state_spec
            self.rec_state = tuple(
                jnp.zeros((max_seqs + 1,) + sp.state_shape, jnp.float32)
                for _ in range(sp.n_linear))
            self.conv_state = tuple(
                jnp.zeros((max_seqs + 1, sp.linear_conv_kernel_dim - 1,
                           sp.conv_channels), dtype)
                for _ in range(sp.n_linear))
        self._free = list(range(n_pages - 1, 0, -1))   # page 0 = pad
        self._pages: Dict[int, List[int]] = {}
        self._lens = np.zeros(max_seqs, np.int32)
        self._table = np.zeros((max_seqs, self.max_pages_per_seq),
                               np.int32)
        self._used = [False] * max_seqs
        # prefix caching state: per-page reference counts (how many
        # slots map the page), the chain-hash index over registered
        # full prefill pages, and the LRU pool of registered pages with
        # ref 0 — cached content kept warm until page pressure evicts
        self._ref = np.zeros(n_pages, np.int64)
        self._index: Dict[bytes, int] = {}       # chain key -> page
        self._page_key: Dict[int, bytes] = {}    # page -> chain key
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        # swap state: bounded host pool of page copies for preempted
        # sequences (0 pages = swap disabled, recompute-only fallback)
        self.swap_pool_pages = int(swap_pool_pages)
        self._swap: Dict[int, _SwapEntry] = {}
        self._swap_used = 0
        self._swap_ids = itertools.count()
        # page-pressure telemetry (host-side counters — negligible next
        # to the device work these methods bracket); one label set per
        # cache instance so concurrent engines don't blur each other
        reg = get_registry()
        self.cache_id = str(next(_CACHE_IDS))
        lbl = ("cache",)
        self._m_alloc = reg.counter(
            "kv_cache_pages_allocated_total",
            "KV pages taken from the free list.", lbl).labels(
                self.cache_id)
        self._m_release = reg.counter(
            "kv_cache_pages_released_total",
            "KV pages returned to the free list.", lbl).labels(
                self.cache_id)
        self._m_oom = reg.counter(
            "kv_cache_oom_total",
            "Allocation/extension failures: not enough free pages.",
            lbl).labels(self.cache_id)
        self._m_util = reg.gauge(
            "kv_cache_page_utilization",
            "Fraction of usable pages referenced by live slots (page 0 "
            "is the reserved pad page; prefix-cached LRU pages count "
            "as reclaimable, not in use).", lbl).labels(self.cache_id)
        self._m_evict = reg.counter(
            "kv_cache_prefix_evicted_pages_total",
            "Prefix-cached pages evicted from the LRU pool under page "
            "pressure.", lbl).labels(self.cache_id)
        self._m_cow = reg.counter(
            "kv_cache_cow_pages_total",
            "Copy-on-write page copies (a write targeted a shared "
            "page).", lbl).labels(self.cache_id)
        self._m_cached = reg.gauge(
            "kv_cache_prefix_cached_pages",
            "Registered prefix pages currently unreferenced (the LRU "
            "pool).", lbl).labels(self.cache_id)
        self._m_swap_out = reg.counter(
            "kv_cache_swap_out_pages_total",
            "Device pages copied to the host swap pool by swap_out "
            "(shared prefix pages are unpinned, not copied).",
            lbl).labels(self.cache_id)
        self._m_swap_in = reg.counter(
            "kv_cache_swap_in_pages_total",
            "Host pages copied back to device pages by swap_in.",
            lbl).labels(self.cache_id)
        self._m_swap_fallback = reg.counter(
            "kv_cache_swap_fallback_total",
            "swap_out/swap_in attempts that degraded to the recompute "
            "path (pool full or disabled, entry dropped, or a shared "
            "prefix page evicted while suspended).", lbl).labels(
                self.cache_id)
        self._m_swap_pool = reg.gauge(
            "kv_cache_swap_pool_pages",
            "Host swap-pool pages currently holding preempted KV.",
            lbl).labels(self.cache_id)
        self._m_swap_export = reg.counter(
            "kv_cache_swap_exported_pages_total",
            "Swap-pool pages serialized into portable migration blobs "
            "(export_swap).", lbl).labels(self.cache_id)
        self._m_swap_import = reg.counter(
            "kv_cache_swap_imported_pages_total",
            "Swap-pool pages restored from portable migration blobs "
            "(import_swap).", lbl).labels(self.cache_id)

    def page_utilization(self) -> float:
        """Referenced fraction of the usable pool (excludes pad page 0
        and counts prefix-cached LRU pages as reclaimable — they are
        handed back by eviction before any allocation can fail)."""
        usable = self.n_pages - 1
        if not usable:
            return 0.0
        return 1.0 - (len(self._free) + len(self._lru)) / usable

    def _track_pages(self):
        self._m_util.set(self.page_utilization())
        self._m_cached.set(len(self._lru))

    # -- prefix-caching internals ----------------------------------------------
    def _unregister(self, pg: int):
        key = self._page_key.pop(pg)
        del self._index[key]

    def _grab_page(self, what: str) -> int:
        """One page off the free list, evicting the LRU-oldest cached
        prefix page when the list is dry; counts the OOM (and leaves
        the gauges honest) before raising when neither pool has one."""
        if self._free:
            pg = self._free.pop()
        elif self._lru:
            pg, _ = self._lru.popitem(last=False)      # oldest first
            self._unregister(pg)
            self._m_evict.inc()
        else:
            self._m_oom.inc()
            self._track_pages()
            enforce(False, f"paged cache OOM on {what}: no free or "
                           f"evictable pages")
        self._m_alloc.inc()
        self._ref[pg] = 1
        return pg

    def _unref(self, pg: int) -> bool:
        """Drop one reference; True if the page went back to the free
        list (registered pages park in the LRU pool instead)."""
        self._ref[pg] -= 1
        if self._ref[pg] > 0:
            return False
        if pg in self._page_key:
            self._lru[pg] = None                       # newest at end
            return False
        self._free.append(pg)
        return True

    def _copy_page(self, src: int, dst: int):
        """Device-copy one physical page (both pools, and the scale
        rows when quantized — scales travel with their pages)."""
        self.k_pages = self.k_pages.at[:, :, dst].set(
            self.k_pages[:, :, src])
        self.v_pages = self.v_pages.at[:, :, dst].set(
            self.v_pages[:, :, src])
        if self.kv_dtype == "int8":
            self.k_scales = self.k_scales.at[:, :, dst].set(
                self.k_scales[:, :, src])
            self.v_scales = self.v_scales.at[:, :, dst].set(
                self.v_scales[:, :, src])

    def _make_private(self, slot: int, idx: int):
        """Copy-on-write guard before writing into the slot's idx-th
        page: a shared page (ref > 1) is copied to a fresh page first;
        a solely-owned but registered page just unregisters (its cached
        content is about to diverge from the indexed prefix)."""
        pg = self._pages[slot][idx]
        if self._ref[pg] > 1:
            npg = self._grab_page("copy-on-write")
            self._copy_page(pg, npg)
            self._unref(pg)
            self._m_release.inc()
            self._pages[slot][idx] = npg
            self._table[slot, idx] = npg
            self._m_cow.inc()
        elif pg in self._page_key:
            self._unregister(pg)

    # -- host-side accounting --------------------------------------------------
    def allocate(self, n_tokens: int, shared_pages=()) -> int:
        """Reserve a sequence slot with capacity for n_tokens; returns
        the slot id (batch row for the kernel).  ``shared_pages``
        (from ``lookup_prefix``) are mapped read-shared into the front
        of the slot's page table — a reference each, no device work —
        and only the remainder comes off the free list."""
        free_slots = [i for i, u in enumerate(self._used) if not u]
        enforce(free_slots, "paged cache: all sequence slots in use")
        slot = free_slots[0]
        need = (n_tokens + self.page_size - 1) // self.page_size
        shared = list(shared_pages)
        enforce(len(shared) <= need,
                f"paged cache: {len(shared)} shared pages exceed the "
                f"{need}-page capacity request")
        # pin the shared pages FIRST so grabbing the remainder can
        # never evict them out from under this allocation
        for pg in shared:
            self._ref[pg] += 1
            if pg in self._lru:
                del self._lru[pg]
        avail = len(self._free) + len(self._lru)
        if avail < need - len(shared):
            self._m_oom.inc()
            for pg in reversed(shared):
                self._unref(pg)
            self._track_pages()
            enforce(False,
                    f"paged cache OOM: need {need - len(shared)} "
                    f"pages, {avail} free/evictable")
        self._m_alloc.inc(len(shared))      # the shared references
        pages = shared + [self._grab_page("allocate")
                          for _ in range(need - len(shared))]
        self._used[slot] = True
        self._pages[slot] = pages
        self._lens[slot] = 0
        self._table[slot, :] = 0
        self._table[slot, :need] = pages
        self._track_pages()
        return slot

    def extend(self, slot: int, n_tokens: int = 1):
        """Ensure capacity for n_tokens more; grabs pages as needed.
        Already-attached pages the new tokens will land in are made
        private first (copy-on-write), so appends after a shared
        prefix can never mutate another sequence's view."""
        pages = self._pages[slot]
        cur = int(self._lens[slot])
        need_total = cur + n_tokens
        if n_tokens > 0 and pages:
            first = cur // self.page_size
            last = (need_total - 1) // self.page_size
            for idx in range(first, min(last, len(pages) - 1) + 1):
                self._make_private(slot, idx)
        have = len(pages) * self.page_size
        while have < need_total:
            pg = self._grab_page("extend")
            idx = len(pages)
            pages.append(pg)
            self._table[slot, idx] = pg
            have += self.page_size
        self._track_pages()

    def release(self, slot: int):
        """Drop the slot's page references.  Unregistered pages return
        to the free list; registered prefix pages with no remaining
        reference stay cached in the LRU pool (still allocatable —
        eviction reclaims them oldest-first under pressure)."""
        pages = self._pages.pop(slot)
        for pg in reversed(pages):
            self._unref(pg)
        self._m_release.inc(len(pages))
        self._used[slot] = False
        self._lens[slot] = 0
        self._table[slot, :] = 0
        self._track_pages()

    # -- KV swap (preemption) --------------------------------------------------
    def swap_out(self, slot: int) -> Optional[int]:
        """Preempt ``slot``: copy its private WRITTEN pages (and int8
        scale rows) into the host swap pool, then release every device
        page the slot holds — the freed pages are what preemption buys.
        Shared prefix pages are not copied, only unpinned; their chain
        keys are recorded so ``swap_in`` can re-pin them (registered
        pages are immutable, so a key that still resolves names the
        same bytes).

        Returns a swap handle for ``swap_in``, or ``None`` when the
        bounded pool cannot hold the private pages (or swap is
        disabled) — the slot is released either way, and the caller
        falls back to recomputing the KV from the token history."""
        import jax

        P = self.page_size
        written = -(-int(self._lens[slot]) // P)
        pages = self._pages[slot]
        plan: List[tuple] = []
        data_pages: List[int] = []
        for i in range(written):
            pg = pages[i]
            if pg in self._page_key:
                plan.append(("key", self._page_key[pg]))
            else:
                plan.append(("data", len(data_pages)))
                data_pages.append(pg)
        handle = None
        if self.swap_pool_pages and \
                self._swap_used + len(data_pages) <= self.swap_pool_pages:
            k_host = v_host = ks_host = vs_host = None
            if data_pages:
                sel = np.asarray(data_pages)
                # device_get materializes host copies BEFORE the pages
                # return to the free list and get overwritten
                k_host = np.asarray(jax.device_get(
                    self.k_pages[:, :, sel]))
                v_host = np.asarray(jax.device_get(
                    self.v_pages[:, :, sel]))
                if self.kv_dtype == "int8":
                    ks_host = np.asarray(jax.device_get(
                        self.k_scales[:, :, sel]))
                    vs_host = np.asarray(jax.device_get(
                        self.v_scales[:, :, sel]))
            handle = next(self._swap_ids)
            self._swap[handle] = _SwapEntry(
                plan, k_host, v_host, ks_host, vs_host,
                state=self.snapshot_state(slot))
            self._swap_used += len(data_pages)
            self._m_swap_out.inc(len(data_pages))
            self._m_swap_pool.set(self._swap_used)
        else:
            self._m_swap_fallback.inc()
        self.release(slot)
        return handle

    def swap_in(self, handle: int, n_tokens: int) -> Optional[int]:
        """Restore a swapped-out sequence into a fresh slot with its
        full ``n_tokens`` page budget re-reserved (shared prefix pages
        re-pinned through the index, private pages device-written from
        the host pool, the unwritten remainder freshly grabbed).

        Returns the new slot id, or ``None`` when the entry cannot be
        restored (dropped, a shared prefix page was evicted while
        suspended, or the free/evictable pools cannot cover the
        budget).  The handle is CONSUMED either way — on ``None`` the
        caller must recompute, not retry."""
        import jax.numpy as jnp

        entry = self._swap.pop(handle, None)
        if entry is None:
            self._m_swap_fallback.inc()
            return None

        def _drop(n_shared_pinned=0, shared=()):
            for pg in list(shared)[:n_shared_pinned][::-1]:
                self._unref(pg)
            self._swap_used -= entry.n_host_pages
            self._m_swap_pool.set(self._swap_used)
            self._m_swap_fallback.inc()
            self._track_pages()
            return None

        # resolve the shared chain keys first (pure reads): any miss
        # means the prefix page was evicted while we were suspended
        shared: List[int] = []
        for kind, val in entry.plan:
            if kind == "key":
                pg = self._index.get(val)
                if pg is None:
                    return _drop()
                shared.append(pg)
        free_slots = [i for i, u in enumerate(self._used) if not u]
        if not free_slots:
            return _drop()
        slot = free_slots[0]
        need = -(-n_tokens // self.page_size)
        enforce(need >= len(entry.plan),
                f"swap_in budget {need} pages < {len(entry.plan)} "
                f"written pages")
        # pin shared pages FIRST (mirrors allocate: grabbing the
        # remainder can then never evict them out from under us)
        for pg in shared:
            self._ref[pg] += 1
            if pg in self._lru:
                del self._lru[pg]
        if len(self._free) + len(self._lru) < need - len(shared):
            return _drop(len(shared), shared)
        self._m_alloc.inc(len(shared))
        sit = iter(shared)
        pages: List[int] = []
        restore: List[tuple] = []              # (device page, host row)
        for kind, val in entry.plan:
            if kind == "key":
                pages.append(next(sit))
            else:
                pg = self._grab_page("swap-in")
                pages.append(pg)
                restore.append((pg, val))
        pages += [self._grab_page("swap-in")
                  for _ in range(need - len(entry.plan))]
        if restore:
            sel = np.asarray([pg for pg, _ in restore])
            src = np.asarray([j for _, j in restore])
            self.k_pages = self.k_pages.at[:, :, sel].set(
                jnp.asarray(entry.k_host[:, :, src]))
            self.v_pages = self.v_pages.at[:, :, sel].set(
                jnp.asarray(entry.v_host[:, :, src]))
            if self.kv_dtype == "int8":
                self.k_scales = self.k_scales.at[:, :, sel].set(
                    jnp.asarray(entry.k_scale_host[:, :, src]))
                self.v_scales = self.v_scales.at[:, :, sel].set(
                    jnp.asarray(entry.v_scale_host[:, :, src]))
        self.restore_state(slot, entry.state)
        self._used[slot] = True
        self._pages[slot] = pages
        self._lens[slot] = 0                   # caller set_len()s
        self._table[slot, :] = 0
        self._table[slot, :need] = pages
        self._swap_used -= entry.n_host_pages
        self._m_swap_in.inc(len(restore))
        self._m_swap_pool.set(self._swap_used)
        self._track_pages()
        return slot

    # -- the recurrent state's snapshots ----------------------------------------
    def state_bytes(self) -> int:
        """Device bytes of the recurrent-state and conv-window pools."""
        if self.rec_state is None:
            return 0
        return sum(int(a.nbytes) for a in self.rec_state
                   + self.conv_state)

    def snapshot_state(self, slot: int):
        """Host copy of one slot's recurrent state over all linear
        layers, ``None`` where the cache holds none."""
        import jax
        if self.rec_state is None:
            return None
        rec = np.stack([np.asarray(jax.device_get(a[slot]))
                        for a in self.rec_state])
        conv = np.stack([np.asarray(jax.device_get(a[slot]))
                         for a in self.conv_state])
        return rec, conv

    def restore_state(self, slot: int, snap):
        """Write a snapshot into ``slot`` of the pools, in place."""
        if snap is None:
            return
        enforce(self.rec_state is not None,
                "a swap entry carries a recurrent state; this cache "
                "holds none")
        rec, conv = snap
        self.rec_state = tuple(
            _put_slot(a, slot, rec[i])
            for i, a in enumerate(self.rec_state))
        self.conv_state = tuple(
            _put_slot(a, slot, conv[i])
            for i, a in enumerate(self.conv_state))

    def drop_swap(self, handle: Optional[int]) -> bool:
        """Free a swap entry without restoring it (the abort path for
        suspended requests).  ``None`` and already-consumed handles
        are no-ops — abort stays idempotent."""
        entry = self._swap.pop(handle, None) if handle is not None \
            else None
        if entry is None:
            return False
        self._swap_used -= entry.n_host_pages
        self._m_swap_pool.set(self._swap_used)
        return True

    def swap_pool_used(self) -> int:
        """Host swap-pool pages currently holding preempted KV."""
        return self._swap_used

    # -- KV migration (multi-host drain/rebalance) -----------------------------
    def _swap_geometry(self) -> dict:
        """The shape contract a migration blob must match: mismatched
        geometry would reinterpret page bytes, so import refuses it."""
        sp = self.state_spec
        return {"page_size": self.page_size,
                "state": None if sp is None else [
                    sp.n_linear, *sp.state_shape,
                    sp.linear_conv_kernel_dim - 1, sp.conv_channels,
                    str(np.dtype(self.conv_state[0].dtype))],
                "num_layers": self.num_layers,
                "n_kv_heads": int(self.k_pages.shape[1]),
                "head_dim": int(self.k_pages.shape[-1]),
                "kv_dtype": self.kv_dtype or "",
                "pool_dtype": str(np.dtype(self.k_pages.dtype))}

    def export_swap(self, handle: Optional[int]) -> Optional[bytes]:
        """Serialize one swap entry into a PORTABLE blob (self-described
        npz: a json meta record plus the host page arrays) for shipping
        to another host's cache.  The entry is CONSUMED — its pool pages
        free immediately, mirroring ``swap_in``'s handle semantics.
        Shared-prefix plan entries travel as their chain keys (hex), so
        the destination re-pins them through ITS index — a miss there
        degrades to the recompute path at resume, never to wrong bytes.
        ``None`` / already-consumed handles return ``None`` (the caller
        ships a recompute-only package)."""
        import jax

        entry = self._swap.pop(handle, None) if handle is not None \
            else None
        if entry is None:
            return None
        self._swap_used -= entry.n_host_pages
        self._m_swap_pool.set(self._swap_used)
        # MATERIALIZE shared-prefix plan entries whose chain key still
        # resolves locally: the destination's index almost never holds
        # this host's prefixes, so a key-only blob would degrade every
        # cross-host migration to recompute.  Registered pages are
        # immutable, so their bytes can be read out here; keys that no
        # longer resolve (evicted while suspended) stay keys — the
        # destination gets one last chance to re-pin, else recompute.
        n_data = entry.n_host_pages
        extra_sel: List[int] = []
        plan: List[tuple] = []
        for kind, val in entry.plan:
            if kind == "key":
                pg = self._index.get(val)
                if pg is not None:
                    plan.append(("data", n_data + len(extra_sel)))
                    extra_sel.append(pg)
                    continue
            plan.append((kind, val))
        k_host, v_host = entry.k_host, entry.v_host
        ks_host, vs_host = entry.k_scale_host, entry.v_scale_host
        if extra_sel:
            sel = np.asarray(extra_sel)
            ek = np.asarray(jax.device_get(self.k_pages[:, :, sel]))
            ev = np.asarray(jax.device_get(self.v_pages[:, :, sel]))
            k_host = ek if k_host is None else \
                np.concatenate([k_host, ek], axis=2)
            v_host = ev if v_host is None else \
                np.concatenate([v_host, ev], axis=2)
            if self.kv_dtype == "int8":
                eks = np.asarray(jax.device_get(
                    self.k_scales[:, :, sel]))
                evs = np.asarray(jax.device_get(
                    self.v_scales[:, :, sel]))
                ks_host = eks if ks_host is None else \
                    np.concatenate([ks_host, eks], axis=2)
                vs_host = evs if vs_host is None else \
                    np.concatenate([vs_host, evs], axis=2)
        meta = dict(self._swap_geometry())
        meta["plan"] = [["key", val.hex()] if kind == "key"
                        else ["data", int(val)]
                        for kind, val in plan]
        meta["n_host_pages"] = n_data + len(extra_sel)
        arrays = {"meta": np.frombuffer(
            json.dumps(meta).encode("utf-8"), np.uint8)}
        if k_host is not None:
            arrays["k_host"] = k_host
            arrays["v_host"] = v_host
            if ks_host is not None:
                arrays["k_scale_host"] = ks_host
                arrays["v_scale_host"] = vs_host
        if entry.state is not None:
            # the conv window travels as float32 (npz knows no bf16;
            # the upcast is exact)
            arrays["rec_state"] = entry.state[0]
            arrays["conv_state"] = entry.state[1].astype(np.float32)
        buf = io.BytesIO()
        np.savez(buf, **arrays)
        self._m_swap_export.inc(meta["n_host_pages"])
        return buf.getvalue()

    def import_swap(self, blob: Optional[bytes]) -> Optional[int]:
        """Adopt a migrated swap blob into THIS cache's host pool and
        return a local handle ``swap_in`` understands.  Geometry
        mismatches raise (an operator wiring error, not a degradable
        fault); a pool that cannot hold the blob's pages returns
        ``None`` — the caller resumes via recompute instead, so a small
        destination never blocks a drain."""
        if blob is None:
            return None
        with np.load(io.BytesIO(blob)) as z:
            meta = json.loads(bytes(z["meta"]).decode("utf-8"))
            geo = self._swap_geometry()
            for k, v in geo.items():
                enforce(meta.get(k) == v,
                        f"migration blob geometry mismatch: {k} is "
                        f"{meta.get(k)!r}, this cache has {v!r}")
            k_host = z["k_host"] if "k_host" in z else None
            v_host = z["v_host"] if "v_host" in z else None
            ks_host = z["k_scale_host"] if "k_scale_host" in z else None
            vs_host = z["v_scale_host"] if "v_scale_host" in z else None
            state = (z["rec_state"], z["conv_state"]) \
                if "rec_state" in z else None
        n_host = int(meta["n_host_pages"])
        if not self.swap_pool_pages or \
                self._swap_used + n_host > self.swap_pool_pages:
            self._m_swap_fallback.inc()
            return None
        plan = [("key", bytes.fromhex(val)) if kind == "key"
                else ("data", int(val)) for kind, val in meta["plan"]]
        handle = next(self._swap_ids)
        self._swap[handle] = _SwapEntry(plan, k_host, v_host,
                                        ks_host, vs_host, state=state)
        self._swap_used += n_host
        self._m_swap_import.inc(n_host)
        self._m_swap_pool.set(self._swap_used)
        return handle

    # -- prefix caching (public) -----------------------------------------------
    def lookup_prefix(self, token_ids) -> Tuple[int, List[int]]:
        """Longest page-aligned cached prefix of ``token_ids``: walks
        the chain of full-page block hashes through the index and
        returns (n_cached_tokens, pages).  Pure host work — pass the
        pages to ``allocate(shared_pages=...)`` to map them."""
        token_ids = list(token_ids)
        P = self.page_size
        key = b""
        pages: List[int] = []
        for i in range(len(token_ids) // P):
            key = _chain_hash(key, token_ids[i * P:(i + 1) * P])
            pg = self._index.get(key)
            if pg is None:
                break
            pages.append(pg)
        return len(pages) * P, pages

    def register_prefix(self, slot: int, token_ids, upto: Optional[int]
                        = None) -> int:
        """Publish the slot's full, already-written prefill pages into
        the prefix index (first ``upto`` tokens of ``token_ids``,
        rounded DOWN to whole pages and clamped to the written length).
        Pages whose chain key is already indexed are skipped — first
        writer wins, duplicates stay private.  Returns the number of
        pages newly registered."""
        P = self.page_size
        n = len(token_ids) if upto is None else min(upto, len(token_ids))
        n = min(n, int(self._lens[slot]))
        key = b""
        added = 0
        for i in range(n // P):
            key = _chain_hash(key, token_ids[i * P:(i + 1) * P])
            pg = self._pages[slot][i]
            if key not in self._index and pg not in self._page_key:
                self._index[key] = pg
                self._page_key[pg] = key
                added += 1
        self._track_pages()
        return added

    def cached_page_count(self) -> int:
        """Registered prefix pages currently unreferenced (evictable)."""
        return len(self._lru)

    def shared_page_count(self) -> int:
        """Physical pages mapped by more than one slot right now."""
        return int((self._ref > 1).sum())

    def page_ref_count(self, page: int) -> int:
        return int(self._ref[page])

    def set_len(self, slot: int, n: int):
        """Host-side length after an in-graph prefill wrote the pages
        directly (chunked prefill)."""
        self._lens[slot] = n

    def advance(self, slots, n: int = 1):
        for s in np.atleast_1d(slots):
            self._lens[s] += n

    def rollback(self, slot: int, n: int):
        """Un-append the last ``n`` tokens of ``slot`` (speculative
        decoding's rejected-suffix rollback): a host-side ``_lens``
        decrement and NOTHING else — the mirror of ``advance``'s
        under-advance contract.  The rejected rows' K/V (and, for int8
        pools, their scale rows) stay physically in the pages but are
        never attended (every attention path masks at ``kv_pos <
        len``) and the next append overwrites them in place, scale
        rows traveling alongside.  Pages stay attached to the slot —
        release-safe: ``release`` still walks the full table, and
        re-appending never re-grabs pages the slot already holds."""
        n = int(n)
        enforce(n >= 0, f"rollback of {n} tokens")
        enforce(self._used[slot], f"rollback on free slot {slot}")
        enforce(self._lens[slot] >= n,
                f"rollback of {n} tokens but slot {slot} holds "
                f"{int(self._lens[slot])}")
        self._lens[slot] -= n

    @property
    def seq_lens(self) -> np.ndarray:
        return self._lens

    @property
    def page_table(self) -> np.ndarray:
        return self._table

    def free_page_count(self) -> int:
        """Allocatable pages: truly free plus the prefix-cached LRU
        pool (reclaimed transparently by eviction)."""
        return len(self._free) + len(self._lru)

    def free_pages(self) -> int:
        """Admission-control view of capacity: pages an ``allocate``
        can obtain RIGHT NOW — the free list plus the evictable
        prefix-cached LRU pool.  A scheduler that checks
        ``free_pages() >= ceil(total_tokens / page_size)`` before
        admitting can never see the OOM raise (the engine reserves a
        request's full page budget at admission, so decode never grabs
        more)."""
        return len(self._free) + len(self._lru)

    def free_slot_count(self) -> int:
        """Sequence slots not currently bound to a live request."""
        return sum(1 for u in self._used if not u)

    def kv_bytes_per_token(self) -> int:
        """HBM bytes one cached token costs across all layers and both
        pools — int8 counts its f32 scale rows, so capacity claims stay
        honest."""
        head_dim = self.k_pages.shape[-1]
        kvh = self.k_pages.shape[1]
        if self.kv_dtype == "int8":
            per_row = head_dim * 1 + 4          # int8 values + f32 scale
        else:
            per_row = head_dim * self.k_pages.dtype.itemsize
        return 2 * self.num_layers * kvh * per_row

    def metrics_snapshot(self) -> dict:
        """This cache's page-pressure counters (host view; the same
        series are in the global registry under label cache=<id>)."""
        return {"pages_allocated": int(self._m_alloc.value),
                "pages_released": int(self._m_release.value),
                "oom_events": int(self._m_oom.value),
                "free_pages": self.free_page_count(),
                "page_utilization": self.page_utilization(),
                "prefix_cached_pages": self.cached_page_count(),
                "prefix_shared_pages": self.shared_page_count(),
                "prefix_evicted_pages": int(self._m_evict.value),
                "cow_pages": int(self._m_cow.value),
                "swap_pool_pages": self.swap_pool_pages,
                "swap_pool_used": self._swap_used,
                "swap_out_pages": int(self._m_swap_out.value),
                "swap_in_pages": int(self._m_swap_in.value),
                "swap_exported_pages": int(self._m_swap_export.value),
                "swap_imported_pages": int(self._m_swap_import.value),
                "swap_fallbacks": int(self._m_swap_fallback.value),
                "state_bytes": self.state_bytes()}

    def memory_rows(self) -> dict:
        """Memory-plane accounting row (observability.introspection):
        actual bytes held by the device page pools (values + int8 scale
        planes) and by the host swap pool's staged page copies.

        Under tensor parallelism ``device_bytes`` stays the GLOBAL
        logical pool size (``jax.Array.nbytes`` is logical bytes, and
        fleet aggregation sums these rows — a tp=4 replica must not
        look 4× cheaper than it is); ``device_bytes_per_shard`` is
        what one chip's HBM actually holds (the /memz capacity-planning
        number), with ``tp`` alongside so the division is auditable."""
        dev = int(self.k_pages.nbytes) + int(self.v_pages.nbytes) \
            + self.state_bytes()
        if self.k_scales is not None:
            dev += int(self.k_scales.nbytes) + int(self.v_scales.nbytes)
        tp = self._shardings.tp if self._shardings is not None else 1
        host = 0
        for entry in self._swap.values():
            for arr in (entry.k_host, entry.v_host,
                        entry.k_scale_host, entry.v_scale_host):
                if arr is not None:
                    host += int(arr.nbytes)
        return {"device_bytes": dev,
                "device_bytes_per_shard": dev // tp,
                "tp": tp,
                "host_bytes": host,
                "pages": int(self.n_pages),
                "free_pages": self.free_page_count(),
                "bytes_per_token": self.kv_bytes_per_token(),
                "swap_pool_pages": int(self.swap_pool_pages),
                "swap_pool_used": int(self._swap_used)}

    # -- device-side ops -------------------------------------------------------
    def _norm_layers(self, k, v, tokens_axis: int):
        """Accept [S?, KVH, D]-style per-layer input when num_layers==1,
        else require a leading layer dim."""
        import jax.numpy as jnp
        k, v = jnp.asarray(k), jnp.asarray(v)
        if k.ndim == 3:
            enforce(self.num_layers == 1,
                    f"cache holds {self.num_layers} layers; pass "
                    f"[L, ...] keys/values")
            k, v = k[None], v[None]
        return k, v

    def write_prefill(self, slot: int, k, v):
        """Bulk-write a prefill's keys/values into the sequence's pages
        with ONE vectorized scatter per pool (int8 mode quantizes the
        rows on the way in and scatters the scales alongside).

        k/v: [S, KVH, D] (num_layers==1) or [L, S, KVH, D]."""
        import jax.numpy as jnp
        k, v = self._norm_layers(k, v, 1)
        s = k.shape[1]
        self.extend(slot, s)
        start = int(self._lens[slot])
        pos = np.arange(start, start + s)
        pages = jnp.asarray(self._table[slot, pos // self.page_size])
        slots_ = jnp.asarray(pos % self.page_size)
        # [L, S, KVH, D] -> [L, KVH, S, D] scatter at (pages, slots)
        kt = jnp.swapaxes(k, 1, 2)
        vt = jnp.swapaxes(v, 1, 2)
        if self.kv_dtype == "int8":
            from ..quantization.ops import quantize_rows_raw
            kt, ksc = quantize_rows_raw(kt)       # + [L, KVH, S] scales
            vt, vsc = quantize_rows_raw(vt)
            self.k_scales = self.k_scales.at[:, :, pages, slots_].set(ksc)
            self.v_scales = self.v_scales.at[:, :, pages, slots_].set(vsc)
        else:
            kt = kt.astype(self.k_pages.dtype)
            vt = vt.astype(self.v_pages.dtype)
        self.k_pages = self.k_pages.at[:, :, pages, slots_, :].set(kt)
        self.v_pages = self.v_pages.at[:, :, pages, slots_, :].set(vt)
        self._lens[slot] = start + s

    def append(self, slots, k_new, v_new):
        """Decode step: one new token for each sequence in ``slots``.

        k_new/v_new: [B, KVH, D] (num_layers==1) or [L, B, KVH, D];
        lengths advance by 1 (once, across all layers)."""
        import jax.numpy as jnp
        k_new, v_new = self._norm_layers(k_new, v_new, 1)
        slots = np.atleast_1d(slots)
        for s in slots:
            self.extend(int(s), 1)
        pos = self._lens[slots]
        pages = jnp.asarray(self._table[slots, pos // self.page_size])
        slot_in_page = jnp.asarray(pos % self.page_size)
        # ONE all-layer scatter: this method is EAGER (each op call
        # copies its output), so a per-layer dus chain would copy the
        # pool 2·L·B times per token; the jit-compiled serving path
        # (engine's fused append+attend kernel) never comes through here
        kt = jnp.swapaxes(k_new, 1, 2)
        vt = jnp.swapaxes(v_new, 1, 2)
        if self.kv_dtype == "int8":
            from ..quantization.ops import quantize_rows_raw
            kt, ksc = quantize_rows_raw(kt)       # + [L, KVH, B] scales
            vt, vsc = quantize_rows_raw(vt)
            self.k_scales = self.k_scales.at[
                :, :, pages, slot_in_page].set(ksc)
            self.v_scales = self.v_scales.at[
                :, :, pages, slot_in_page].set(vsc)
        else:
            kt = kt.astype(self.k_pages.dtype)
            vt = vt.astype(self.v_pages.dtype)
        self.k_pages = self.k_pages.at[:, :, pages, slot_in_page, :].set(kt)
        self.v_pages = self.v_pages.at[:, :, pages, slot_in_page, :].set(vt)
        self.advance(slots, 1)

    def attend(self, slots, q, layer: int = 0,
               use_kernel: Optional[bool] = None):
        """Decode attention for ``q`` [B, H, D] over the cached pages of
        ``slots`` in ``layer``.  Kernel on TPU, jnp reference elsewhere;
        int8 pools hand the kernel their per-token scales and dequantize
        in VMEM."""
        import jax.numpy as jnp
        from ..runtime.device import is_compiled_with_tpu
        from ..ops.pallas.paged_attention import (paged_attention_raw,
                                                  paged_attention_reference)
        slots = np.atleast_1d(slots)
        table = jnp.asarray(self._table[slots])
        lens = jnp.asarray(self._lens[slots])
        if use_kernel is None:
            use_kernel = is_compiled_with_tpu()
        fn = paged_attention_raw if use_kernel else \
            paged_attention_reference
        args = ()
        if self.kv_dtype == "int8":
            args = (self.k_scales[layer][:, :, None, :],
                    self.v_scales[layer][:, :, None, :])
        return fn(jnp.asarray(q), self.k_pages[layer],
                  self.v_pages[layer], table, lens, *args)
