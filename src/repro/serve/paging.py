"""Host-side page allocator for the paged KV cache (DESIGN §14).

The device side is dumb on purpose: per attention layer a
(n_pages, KV, page_size, hd) pool plus a (n_slots, max_pages) int32 page
table passed into every jitted decode step.  All ownership bookkeeping —
which physical pages a slot holds, which are free — lives here on the host,
where it costs a few list ops per admitted/evicted request instead of a
retrace.

Page 0 is reserved as the SCRATCH page: a free (or page-stalled) slot's
table entries stay 0, so its masked write in the fused step lands there and
is never read back (the per-slot length masks exclude it).  The allocator
therefore only ever hands out pages 1..n_pages-1.
"""
from __future__ import annotations


class OutOfPages(RuntimeError):
    """No free page in the pool (the caller should stall, not crash)."""


class PageAllocator:
    def __init__(self, n_pages: int):
        assert n_pages >= 2, "need at least one real page beyond scratch"
        self.n_pages = n_pages
        # LIFO free list: recently-freed (cache-hot) pages are reused first
        self._free = list(range(n_pages - 1, 0, -1))

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def alloc(self) -> int:
        if not self._free:
            raise OutOfPages(f"pool of {self.n_pages - 1} pages exhausted")
        return self._free.pop()

    def free(self, pages) -> None:
        for p in pages:
            assert 0 < p < self.n_pages, p
            self._free.append(int(p))
