"""Host-side paged KV pool: fixed pages, per-request tables, freelist.

Device layout (one pool per attention slot, its periods' pages laid end
to end by :func:`repro.models.transformer.init_paged_pools`; owned by
:mod:`repro.kernels.paged_attention`, see ``pool_shape``):

    (n_periods * n_pages, kv_heads, page_size, 2 * head_dim)

K and V live *fused* in one page — K of each kv head on the first
``head_dim`` lanes of its rows, V on the next — so the decode kernel
streams a whole page (both halves) with a single block DMA per grid step
instead of two, and the pool's minor dim fills whole 128-lane vregs. Period
``l`` reads and writes page ``l * n_pages + id`` for a table id. Page 0 is the
**reserved null page**: padded table entries and inactive-row scatter
writes are routed there, and it is never read because those rows report
length 0 (the kernel's ragged mask skips them), so it can hold arbitrary
garbage forever.

This module owns only the *accounting*: which physical pages belong to
which request, what is free, and high-water/churn counters the scheduler
exports as serving metrics. All device mutation happens in the jitted
decode/prefill steps through the table this class maintains.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

NULL_PAGE = 0
# Sentinel owner for pages held out of circulation by reserve() — never a
# real request id (engine rids count up from 0).
RESERVED_RID = -1


class PoolExhausted(RuntimeError):
    """Raised by :meth:`KVPool.alloc` when the freelist cannot satisfy a
    request — the scheduler catches this and preempts instead."""


class KVPool:
    """Freelist allocator over ``n_pages`` physical pages of ``page_size``
    token positions each. Page 0 is reserved (null page) and never leaves
    the allocator."""

    def __init__(self, n_pages: int, page_size: int):
        if n_pages < 2:
            raise ValueError(f"pool needs >= 2 pages (null + 1), got {n_pages}")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.n_pages = n_pages
        self.page_size = page_size
        # pop() from the tail -> pages hand out in ascending id order, which
        # keeps small repro cases readable in dumps
        self._free: List[int] = list(range(n_pages - 1, 0, -1))
        self._owner: Dict[int, int] = {}     # page id -> request id
        self.alloc_count = 0
        self.free_count = 0
        self.high_water = 0

    # ------------------------------------------------------------------
    # capacity queries
    # ------------------------------------------------------------------

    @property
    def capacity(self) -> int:
        """Allocatable pages (the null page is not one)."""
        return self.n_pages - 1

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return len(self._owner)

    def pages_for(self, n_tokens: int) -> int:
        """Pages needed to store ``n_tokens`` positions (0 tokens -> 0)."""
        return -(-n_tokens // self.page_size)

    # ------------------------------------------------------------------
    # alloc / release
    # ------------------------------------------------------------------

    def alloc(self, n: int, rid: int) -> List[int]:
        """Take ``n`` pages for request ``rid``; raises :class:`PoolExhausted`
        (allocating nothing) when fewer than ``n`` are free."""
        if n > len(self._free):
            raise PoolExhausted(
                f"request {rid} needs {n} pages, only {len(self._free)} of "
                f"{self.capacity} free")
        pages = [self._free.pop() for _ in range(n)]
        for pg in pages:
            self._owner[pg] = rid
        self.alloc_count += n
        self.high_water = max(self.high_water, self.used_pages)
        return pages

    def release(self, pages: Sequence[int], rid: int) -> None:
        """Return a request's pages to the freelist. Double-free and
        foreign-page release raise — a leak here silently serves one
        request's KV to another, so fail loudly."""
        for pg in pages:
            owner = self._owner.get(pg)
            if owner is None:
                raise ValueError(f"release of unowned page {pg} (rid {rid})")
            if owner != rid:
                raise ValueError(
                    f"request {rid} releasing page {pg} owned by {owner}")
            del self._owner[pg]
            self._free.append(pg)
        self.free_count += len(pages)

    def owner(self, page: int):
        return self._owner.get(page)

    # ------------------------------------------------------------------
    # external pressure (chaos drills, future maintenance windows)
    # ------------------------------------------------------------------

    def reserve(self, n: int) -> List[int]:
        """Take up to ``n`` pages out of circulation under the sentinel
        owner ``RESERVED_RID`` — external pool pressure (a chaos-drill
        squeeze, a future defrag/maintenance window) that the scheduler
        experiences exactly like real demand. Never raises: reserves what
        is free and returns the page list for :meth:`unreserve`."""
        n = min(n, len(self._free))
        return self.alloc(n, RESERVED_RID) if n > 0 else []

    def unreserve(self, pages: Sequence[int]) -> None:
        """Return pages taken by :meth:`reserve` to the freelist."""
        if pages:
            self.release(pages, RESERVED_RID)
