"""Paged KV cache: pure-XLA page ops + the host-side page allocator.

The dense decode cache (models/backbone.py ``_cached_attention``) pins a
full ``[B, H, max_len, Dh]`` buffer per layer for the whole batch — a slot
serving a 20-token reply holds the same HBM as one at 4k context, and the
worst-case batch must fit even when nothing runs that long. The serving
answer (vLLM's PagedAttention; PAPERS: "Fine-Tuning and Serving Gemma 4 31B
on Google Cloud TPU") is to store K/V in a shared pool of fixed-size PAGES
indirected through a per-slot block table: slots consume pages as they
grow, short requests free their pages on completion, and total residency is
the pool size, not ``slots x max_len``.

Device side (this module, pure jax — it is a leaf: no framework imports, so
models/backbone.py can call into it without a cycle):

* pages tensor per layer: ``[num_pages, page_size, H * Dh]`` for K and V —
  a token's heads lie side by side in ONE lane-dense row. The TPU tiles an
  array's two minor dimensions to (8, 128) ((16, 128) for bf16): with
  ``Dh`` = 64 alone in the lanes the compiler stored a ``[P, ps, H, Dh]``
  pool page-index-minor and relaid every pool to row-major and back in
  every program that scatters or gathers by page (three pool-sized copies
  a pool a decode step on the v5e, PERF.md PR 28). ``H * Dh`` is the model
  width: whole lane tiles, no padding, and the layout the page-indexed
  scatter and gather want. The XLA decode arm splits the heads of its
  GATHERED view, never of the pool; the flash-decode kernel
  (ops/flash_decode.py), which 'auto' takes on the TPU for rows of whole
  lane tiles, reads the rows as they lie and splits nothing;
* :func:`write_prompt_kv` — scatter a prefill's [B, H, L, Dh] K/V as
  ``[B * L, H * Dh]`` rows into the slots' pages (invalid/padded rows ->
  the trash page);
* :func:`write_token_kv`  — scatter one decode step's [B, H, Dh] as
  ``[B, H * Dh]`` rows at each slot's own position;
* :func:`gather_kv`       — gather a slot-major dense ``[B, H, Lmax, Dh]``
  view for attention: the pure-XLA arm of the decode seam (CPU, shapes the
  kernel's rule refuses, the tests' twin); its cost follows slots x
  reservation, the flash-decode kernel's follows live tokens.

Everything is gather/scatter/``where`` — no host control flow — so the ops
trace into the AOT-compiled prefill/decode executables and run on CPU for
tier-1 tests. Page 0 is reserved as the TRASH page: every write that must
not land anywhere (padded prompt tail, inactive slot, out-of-range
position) is redirected there, and no read ever sees it (reads are masked
to each slot's live prefix, which only spans pages the allocator assigned).
The XLA arm's gather still MOVES it, for every table entry that names it,
under that mask; the flash-decode kernel does not: it copies the pages
that hold a live position of a slot and starts no copy for any other entry
(ops/flash_decode.py; before PR 38 its schedule named the trash page for
the dead entries of a slot's last block and copied it at every such step).

Host side: :class:`PageManager` owns the free list and the block tables as
plain numpy — allocation policy is host code (the scheduler reserves a
request's worst-case pages at admission, so a mid-flight request can never
strand), while the device only ever sees table CONTENTS as data.
"""

from __future__ import annotations

import collections
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

__all__ = ["TRASH_PAGE", "gather_kv", "write_prompt_kv", "write_token_kv",
           "write_span_kv", "write_prompt_kv_q8", "write_token_kv_q8",
           "write_span_kv_q8", "dequant_gathered", "PageManager",
           "PrefixCache"]

TRASH_PAGE = 0  # reserved: masked/invalid writes land here, reads never do

Q8_MAX = 127.0  # symmetric int8: value = q * scale, q in [-127, 127]


def _rows(kv: jnp.ndarray) -> jnp.ndarray:
    """[B, H, L, Dh] -> the pool's row form [B * L, H * Dh]."""
    b, h, l, dh = kv.shape
    return kv.transpose(0, 2, 1, 3).reshape(b * l, h * dh)


def gather_kv(pages: jnp.ndarray, block_table: jnp.ndarray,
              num_heads: int) -> jnp.ndarray:
    """Dense per-slot view of the paged pool.

    ``pages`` [P, page_size, H * Dh], ``block_table`` [B, n_pages] ->
    [B, H, n_pages * page_size, Dh] (the XLA arm's view: the heads are
    split here, from the gathered copy; the pool itself is never
    reshaped). Entries beyond a
    slot's live length are trash-page garbage; the caller masks them
    (backbone ``_paged_attention``), and masked entries contribute exact
    zeros to the softmax — at equal padded length the result is
    bit-identical to the dense cache."""
    g = pages[block_table]                        # [B, n, page_size, H*Dh]
    b, n, ps, hd = g.shape
    return g.reshape(b, n * ps, num_heads,
                     hd // num_heads).transpose(0, 2, 1, 3)


def write_prompt_kv(pages: jnp.ndarray, block_table: jnp.ndarray,
                    kv: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Scatter a prefill's K (or V) rows into the slots' pages.

    ``kv`` [B, H, L, Dh] holds positions 0..L-1 of each slot's prompt;
    ``valid`` [B, L] (1 = real prompt token) routes padded tail positions
    to the trash page instead. Returns the updated pages tensor
    ([P, page_size, H * Dh], as it came)."""
    b, _, l, _ = kv.shape
    ps = pages.shape[1]
    pos = jnp.arange(l, dtype=jnp.int32)
    page_idx = jnp.minimum(pos // ps, block_table.shape[1] - 1)
    phys = block_table[:, page_idx]               # [B, L]
    phys = jnp.where(valid > 0, phys, TRASH_PAGE)
    off = jnp.broadcast_to(pos % ps, (b, l)).reshape(-1)
    return pages.at[phys.reshape(-1), off].set(_rows(kv))


def write_token_kv(pages: jnp.ndarray, block_table: jnp.ndarray,
                   kv: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
    """Scatter one decode step's K (or V) row at each slot's own position.

    ``kv`` [B, H, Dh] (written as [B, H * Dh] rows); ``positions`` [B] is
    the index being written. Slots whose block-table row is all trash
    (inactive/freed) write to the trash page; positions past the table
    width clamp into the row, whose value is then trash for exactly those
    slots."""
    ps = pages.shape[1]
    page_idx = jnp.minimum(positions // ps, block_table.shape[1] - 1)
    phys = jnp.take_along_axis(block_table, page_idx[:, None], axis=1)[:, 0]
    return pages.at[phys, positions % ps].set(kv.reshape(kv.shape[0], -1))


def write_span_kv(pages: jnp.ndarray, block_table: jnp.ndarray,
                  kv: jnp.ndarray, start: jnp.ndarray) -> jnp.ndarray:
    """Scatter a speculative-verify span's K (or V) rows.

    ``kv`` [B, H, L, Dh] holds each slot's chain links at positions
    ``start[b]..start[b]+L-1``; positions past the block table's reach
    clamp to the LAST addressable cell instead of wrapping through the
    OOB-clamped page lookup into a live lower cell (``pos // page_size``
    clamps to the last table column while ``pos % page_size`` re-enters
    at offset 0). Clamped links are always past a slot's budget-final
    position: their picks are discarded by the host acceptance walk and
    the cell they land in is either never queried again or overwritten
    by the next legitimate feed before any query reads it, so a clamp
    collision's last-write-wins nondeterminism can never reach an
    accepted token."""
    l = kv.shape[2]
    ps = pages.shape[1]
    pos = start[:, None] + jnp.arange(l, dtype=jnp.int32)[None, :]  # [B, L]
    pos = jnp.minimum(pos, block_table.shape[1] * ps - 1)
    phys = jnp.take_along_axis(block_table, pos // ps, axis=1)      # [B, L]
    return pages.at[phys.reshape(-1), (pos % ps).reshape(-1)].set(_rows(kv))


def _q8(rows: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """Quantize fp rows to int8 under a per-row ``scale`` (broadcastable).
    ``scale == 0`` (all-zero content) maps everything to 0."""
    s = jnp.where(scale > 0, scale, 1.0)
    q = jnp.round(rows.astype(jnp.float32) / s)
    return jnp.clip(q, -Q8_MAX, Q8_MAX).astype(jnp.int8)


def write_prompt_kv_q8(pages: jnp.ndarray, scales: jnp.ndarray,
                       block_table: jnp.ndarray, kv: jnp.ndarray,
                       valid: jnp.ndarray
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """int8 twin of :func:`write_prompt_kv`: quantize a prefill's K (or V)
    rows at page granularity and SET each touched page's scale.

    ``pages`` is the int8 pool ([P, page_size, H * Dh]), ``scales`` the
    [P] fp32 sidecar. A touched page's scale becomes
    ``absmax(its prompt rows) / 127`` — SET, not max-accumulated against
    the leftover scale of whatever request used the page before, so
    quantization is a pure function of prompt content and a shared-prefix
    page is rewritten identically by every sharing prefill
    (the PrefixCache soundness argument survives quantization: same tokens
    -> same rows -> same scale -> same int8 bits). Untouched pages (and the
    trash page, which every prefill scribbles on) keep their scales: the
    trash scale is garbage, but no read ever maps it."""
    b, _, l, _ = kv.shape
    ps = pages.shape[1]
    pos = jnp.arange(l, dtype=jnp.int32)
    page_idx = jnp.minimum(pos // ps, block_table.shape[1] - 1)
    phys = block_table[:, page_idx]               # [B, L]
    phys = jnp.where(valid > 0, phys, TRASH_PAGE).reshape(-1)
    rows = _rows(kv)
    row_amax = jnp.max(jnp.abs(rows.astype(jnp.float32)), axis=1)
    fresh = jnp.zeros_like(scales).at[phys].max(row_amax / Q8_MAX)
    touched = jnp.zeros_like(scales, dtype=jnp.int32).at[phys].max(1)
    # trash writes must not perturb the (meaningless but live-indexed)
    # trash scale between dispatches of differently-padded batches
    touched = touched.at[TRASH_PAGE].set(0)
    new_scales = jnp.where(touched > 0, fresh, scales)
    off = jnp.broadcast_to(pos % ps, (b, l)).reshape(-1)
    q = _q8(rows, new_scales[phys][:, None])
    return pages.at[phys, off].set(q), new_scales


def write_token_kv_q8(pages: jnp.ndarray, scales: jnp.ndarray,
                      block_table: jnp.ndarray, kv: jnp.ndarray,
                      positions: jnp.ndarray
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """int8 twin of :func:`write_token_kv` with rescale-on-grow.

    A decode write may exceed its page's current scale; clipping there
    would be an unbounded relative error, so instead the page's scale grows
    to ``max(old, absmax(row)/127)`` and the page's EXISTING int8 content
    is re-expressed under the new scale (``q * old/new``, rounded — a
    bounded re-rounding of already-quantized values). This is a gather/
    rewrite of B pages per step, but those are exactly the pages the
    attention read is about to DMA anyway, so the traffic stays O(live
    pages), matching the ``decode_hbm_bytes`` census."""
    ps = pages.shape[1]
    page_idx = jnp.minimum(positions // ps, block_table.shape[1] - 1)
    phys = jnp.take_along_axis(block_table, page_idx[:, None], axis=1)[:, 0]
    rows = kv.reshape(kv.shape[0], -1)            # [B, H*Dh]
    row_amax = jnp.max(jnp.abs(rows.astype(jnp.float32)), axis=1)  # [B]
    old = scales[phys]
    new = jnp.maximum(old, row_amax / Q8_MAX)
    ratio = jnp.where(new > 0, old / jnp.where(new > 0, new, 1.0), 0.0)
    page = pages[phys].astype(jnp.float32)        # [B, ps, H*Dh]
    page = jnp.clip(jnp.round(page * ratio[:, None, None]),
                    -Q8_MAX, Q8_MAX).astype(jnp.int8)
    page = page.at[jnp.arange(phys.shape[0]), positions % ps].set(
        _q8(rows, new[:, None]))
    # duplicate phys ids only ever happen on the trash page (inactive
    # slots) — last-write-wins there is fine, nothing reads it
    return pages.at[phys].set(page), scales.at[phys].set(new)


def write_span_kv_q8(pages: jnp.ndarray, scales: jnp.ndarray,
                     block_table: jnp.ndarray, kv: jnp.ndarray,
                     start: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """int8 twin of :func:`write_span_kv` with rescale-on-grow.

    Span rows may straddle a page boundary, so several rows can land in
    one page; scales grow by deterministic scatter-max (``max(old,
    absmax(row)/127)`` over every row landing in the page) and existing
    pool content is re-expressed under the grown scales with a full-pool
    elementwise pass — pages whose scale did not grow see ratio 1.0 and
    ``round(q * 1.0)`` leaves their bits untouched, so this is
    mathematically the same per-page rewrite as write_token_kv_q8, just
    O(pool) compute instead of O(touched pages). Verify dispatches are
    span-granular (one per K-token round), so the extra traffic
    amortizes; swap to a page-set scatter if TPU profiles object."""
    l = kv.shape[2]
    ps = pages.shape[1]
    pos = start[:, None] + jnp.arange(l, dtype=jnp.int32)[None, :]  # [B, L]
    pos = jnp.minimum(pos, block_table.shape[1] * ps - 1)
    phys = jnp.take_along_axis(block_table, pos // ps, axis=1).reshape(-1)
    rows = _rows(kv)
    row_amax = jnp.max(jnp.abs(rows.astype(jnp.float32)), axis=1)
    new_scales = scales.at[phys].max(row_amax / Q8_MAX)
    ratio = jnp.where(new_scales > 0,
                      scales / jnp.where(new_scales > 0, new_scales, 1.0),
                      0.0)
    pages = jnp.clip(jnp.round(pages.astype(jnp.float32)
                               * ratio[:, None, None]),
                     -Q8_MAX, Q8_MAX).astype(jnp.int8)
    q = _q8(rows, new_scales[phys][:, None])
    return pages.at[phys, (pos % ps).reshape(-1)].set(q), new_scales


def dequant_gathered(dense: jnp.ndarray, scales: jnp.ndarray,
                     block_table: jnp.ndarray, page_size: int,
                     dtype: jnp.dtype) -> jnp.ndarray:
    """Dequantize a :func:`gather_kv` result: ``dense`` [B, H, n*ps, Dh]
    int8 -> ``dtype``, scaling each position by its source page's scale
    (``scales[block_table]`` broadcast across the page's rows)."""
    per_page = scales[block_table]                # [B, n]
    per_pos = jnp.repeat(per_page, page_size, axis=1)  # [B, n*ps]
    return (dense.astype(jnp.float32)
            * per_pos[:, None, :, None]).astype(dtype)


class PageManager:
    """Host-side page allocator: free list + per-slot block tables.

    Page ids are ints into the device pool; page 0 (TRASH_PAGE) is never
    handed out. ``alloc`` is all-or-nothing (returns None when the pool
    can't cover the request) so the scheduler's reserve-at-admission policy
    stays atomic; ``free`` returns a slot's pages to the pool — the device
    arrays involved are functional values, so freeing is pure bookkeeping
    (an in-flight step that still reads those pages reads the array version
    it was dispatched with)."""

    def __init__(self, num_pages: int, page_size: int) -> None:
        if num_pages < 2:
            raise ValueError(f"need >= 2 pages (1 is the reserved trash "
                             f"page), got {num_pages}")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.num_pages = num_pages
        self.page_size = page_size
        # LIFO free list: recently-freed (still-warm) pages are reused first
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._allocated: set = set()

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def capacity(self) -> int:
        """Max pages a single allocation can ever get (pool minus trash)."""
        return self.num_pages - 1

    def pages_for(self, length: int) -> int:
        """Pages needed to hold ``length`` tokens (>= 1)."""
        return max(1, -(-int(length) // self.page_size))

    def alloc(self, n: int) -> Optional[np.ndarray]:
        """``n`` page ids as int32, or None if the pool can't cover them."""
        if n > len(self._free):
            return None
        ids = [self._free.pop() for _ in range(n)]
        self._allocated.update(ids)
        return np.asarray(ids, np.int32)

    def free(self, ids: np.ndarray) -> None:
        for i in map(int, np.asarray(ids).ravel()):
            if i not in self._allocated:
                raise ValueError(f"double free / foreign page id {i}")
            self._allocated.discard(i)
            self._free.append(i)


class PrefixCache:
    """Shared read-only block-table entries: requests whose prompts open
    with the same token run reuse the pages holding that prefix's K/V.

    WHY THIS IS SOUND: a GPT-2 K/V row at position ``p`` is a pure
    function of tokens ``0..p`` — identical prefix tokens produce
    bit-identical K/V. Sharing is restricted to FULL pages strictly
    inside the prompt (``prompt_len // page_size`` pages), so a sharer's
    own writes — the rest of its prompt and every generated token — land
    at positions past the shared region, in its private pages. A sharing
    prefill does re-write the shared pages, with bit-identical values
    (same tokens, same positions), so concurrent readers are unaffected
    and output equality vs a cold prefill is exact (tested).

    LIFETIME is refcounted, because replay/eviction must never free a
    page a live slot still reads:

    * ``slot refs`` — how many in-flight requests hold the page in their
      block table. Incremented by :meth:`acquire`, decremented by
      :meth:`release`.
    * ``entry refs`` — how many cache entries contain the page. A page is
      returned to the :class:`PageManager` only when BOTH hit zero
      (release frees private pages immediately; shared pages persist in
      the cache — that is the feature — until eviction drops their
      entries under pool pressure, LRU-first). Eviction never frees a
      page a live slot still reads: dropping the entry merely orphans
      it, and :meth:`release` frees it with the last slot ref. Entries
      are always droppable — eviction that waited for slot refs to
      clear would deadlock admission on shared-prefix workloads, where
      every entry's head pages are pinned by the very request being
      admitted.

    Entries are keyed by the raw bytes of the page-aligned token prefix,
    one entry per full-page depth, so nested prefixes share page ids and
    a lookup takes the LONGEST cached match.
    """

    def __init__(self, mgr: PageManager, max_entries: int = 512) -> None:
        self.mgr = mgr
        self.page_size = mgr.page_size
        self.max_entries = max_entries
        self._entries: "collections.OrderedDict[bytes, List[int]]" = \
            collections.OrderedDict()
        self._slot_refs: Dict[int, int] = collections.defaultdict(int)
        self._entry_refs: Dict[int, int] = collections.defaultdict(int)
        self.hits = 0
        self.misses = 0
        self.pages_reused = 0
        self.evicted_entries = 0

    # ------------------------------------------------------------- internal

    def _key(self, prompt: np.ndarray, n_pages: int) -> bytes:
        return np.ascontiguousarray(
            prompt[:n_pages * self.page_size], np.int32).tobytes()

    def _full_pages(self, prompt_len: int) -> int:
        return int(prompt_len) // self.page_size

    @property
    def resident_pages(self) -> int:
        """Pages held alive by cache entries (shared capital; an upper
        bound on what :meth:`evict` could hand back under pressure)."""
        return len(self._entry_refs)

    # -------------------------------------------------------------- acquire

    def acquire(self, prompt: np.ndarray) -> Tuple[List[int], int]:
        """Longest cached full-page prefix of ``prompt``: slot-refs its
        pages for the caller and returns ``(page_ids, covered_tokens)``.
        ``([], 0)`` on a miss — the caller allocates everything fresh."""
        for j in range(self._full_pages(len(prompt)), 0, -1):
            pages = self._entries.get(self._key(prompt, j))
            if pages is not None:
                self._entries.move_to_end(self._key(prompt, j))
                for p in pages:
                    self._slot_refs[p] += 1
                self.hits += 1
                self.pages_reused += len(pages)
                return list(pages), j * self.page_size
        self.misses += 1
        return [], 0

    def publish(self, prompt: np.ndarray, pages: np.ndarray,
                n_acquired: int = 0) -> None:
        """Register every full-page prefix of an admitted prompt, making
        its pages shared-capable. ``pages`` is the slot's full reserved
        page list (shared head + fresh); only the prompt-covering full
        pages are published — the tail (partial prompt page + generation
        budget) stays private to the slot.

        INVARIANT: after admission, the slot holds ONE slot-ref on every
        page of its full-page head — :meth:`acquire` ref'd the first
        ``n_acquired`` (the cached share), and publish refs the freshly
        allocated remainder here. Without the publisher's own refs, a
        sharer could still be reading the pages when the publisher
        completes, drops the count to zero, and pool-pressure eviction
        hands them to a new request mid-read (caught by test)."""
        ids = [int(p) for p in np.asarray(pages).ravel()]
        k = self._full_pages(len(prompt))
        for p in ids[n_acquired:k]:
            self._slot_refs[p] += 1
        for j in range(1, k + 1):
            key = self._key(prompt, j)
            if key in self._entries:
                self._entries.move_to_end(key)
                continue
            entry = ids[:j]
            self._entries[key] = entry
            for p in entry:
                self._entry_refs[p] += 1
        while len(self._entries) > self.max_entries:
            if not self._evict_one():
                break

    # -------------------------------------------------------------- release

    def release(self, prompt: np.ndarray, pages: np.ndarray) -> np.ndarray:
        """A slot finished (completion OR replay-abandonment): drop its
        slot refs on the prefix pages and return the pages now safe to
        free — the PRIVATE tail, plus any prefix page ORPHANED by an
        eviction that ran while this slot still read it (entry refs
        already zero; this was its last slot ref). Shared pages still in
        the cache stay resident for the next sharer."""
        ids = [int(p) for p in np.asarray(pages).ravel()]
        k = min(self._full_pages(len(prompt)), len(ids))
        freeable = ids[k:]
        for p in ids[:k]:
            if self._slot_refs[p] > 0:
                self._slot_refs[p] -= 1
            if self._slot_refs[p] == 0:
                del self._slot_refs[p]
                if self._entry_refs.get(p, 0) == 0:
                    freeable.append(p)
        return np.asarray(freeable, np.int32)

    # --------------------------------------------------------------- evict

    def _evict_one(self) -> bool:
        """Drop one cache entry, LRU-first, and free every page that
        leaves BOTH its last entry and its last slot ref. Pages a live
        slot still reads are never freed here — dropping the entry only
        orphans them, and :meth:`release` frees them when the last slot
        ref goes. Prefers the oldest entry whose eviction frees a page
        RIGHT NOW; with nothing immediately freeable it still drops the
        LRU head (progress under pool pressure must not depend on the
        eviction freeing synchronously — a shared-prefix workload keeps
        slot refs on every entry's head pages, and skipping all of them
        deadlocked admission: nothing evictable, pool exhausted, the
        scheduler's head-of-line wait spinning forever). Returns whether
        an entry was dropped."""
        def drop(key: bytes) -> None:
            entry = self._entries.pop(key)
            self.evicted_entries += 1
            freed = []
            for p in entry:
                self._entry_refs[p] -= 1
                if self._entry_refs[p] == 0:
                    del self._entry_refs[p]
                    if self._slot_refs.get(p, 0) == 0:
                        freed.append(p)
            if freed:
                self.mgr.free(np.asarray(freed, np.int32))

        if not self._entries:
            return False
        for key in list(self._entries):
            entry = self._entries[key]
            if any(self._entry_refs[p] == 1
                   and self._slot_refs.get(p, 0) == 0 for p in entry):
                drop(key)
                return True
        drop(next(iter(self._entries)))
        return True

    def evict_for(self, n_pages: int) -> int:
        """Free cache-resident pages until the pool can cover ``n_pages``
        (or nothing evictable remains). Returns pages freed."""
        freed0 = self.mgr.free_pages
        while self.mgr.free_pages < n_pages and self._evict_one():
            pass
        return self.mgr.free_pages - freed0

    def stats(self) -> Dict[str, int]:
        return {"prefix_hits": self.hits, "prefix_misses": self.misses,
                "prefix_pages_reused": self.pages_reused,
                "prefix_entries": len(self._entries),
                "prefix_resident_pages": self.resident_pages,
                "prefix_evicted_entries": self.evicted_entries}
