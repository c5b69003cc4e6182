"""Block-pool allocator for the paged KV cache (tentpole of the paged
continuous-batching DecodeEngine; reference shape: vLLM's BlockAllocator
behind "Ragged Paged Attention", arxiv 2604.15464).

The device side is a ``[L, n_blocks, kvh, block_size, hd]`` pool plus a
per-row int32 block table; this module owns the HOST side: a free-list
of page ids. Page 0 is the reserved NULL page (kernels/paged_attention
NULL_PAGE): padded table entries and inactive rows read/write it, so
the fixed-shape programs need no validity masks — the allocator simply
never hands it out.

Policy: LIFO free list (hot pages stay hot in HBM), O(1) allocate and
free, loud double-free / unknown-page errors — an aliased page would
silently corrupt another row's KV history, the one failure mode a paged
cache must never have.

Pages are REFCOUNTED (prefix-sharing layer, ISSUE 2): ``allocate``
hands out pages at refcount 1; the radix prefix cache and every row
that maps a shared page take additional references with :meth:`incref`
and drop them with :meth:`decref`. A page returns to the free list only
when its last reader lets go. ``free`` keeps its r6 loud-error
semantics and additionally refuses to free a page something else still
references — sharing makes a unilateral free exactly the aliasing bug
the allocator exists to prevent.

STRIPING (2-D mesh, ISSUE 16): under a ``seq``-sharded pool, seq shard
``s`` physically holds pages ``[s·N/seq, (s+1)·N/seq)``. The allocator
partitions its free list into ``stripes`` such ranges and ``allocate``
draws page ``i`` from stripe ``(start_col + i) % stripes``, where
``start_col`` is the block-table column the first new page will occupy.
That maintains the invariant *the page at table column j always lives
in stripe j % stripes*, so each seq shard's attention gathers exactly
the strided columns ``shard, shard+seq, ...`` of every table — a dense
1/seq slice, no masking of foreign pages. COW inherits the invariant
for free: the copy replaces a page at the SAME column, so src and dst
share a stripe and the on-device copy never crosses shards.
"""

from __future__ import annotations

from ..kernels.paged_attention import NULL_PAGE

__all__ = ["BlockAllocator"]


class BlockAllocator:
    """Refcounted free-list over page ids ``1..n_blocks-1`` (page 0 =
    NULL)."""

    def __init__(self, n_blocks: int, stripes: int = 1):
        if n_blocks < 2:
            raise ValueError(
                f"n_blocks={n_blocks}: need at least one allocatable "
                f"page beyond the reserved NULL page")
        if stripes < 1:
            raise ValueError(f"stripes={stripes}")
        if n_blocks % stripes:
            raise ValueError(
                f"n_blocks={n_blocks} not divisible by stripes="
                f"{stripes} (each seq shard holds n_blocks/stripes "
                f"pages)")
        if stripes > 1 and n_blocks // stripes < 2:
            raise ValueError(
                f"n_blocks={n_blocks} with stripes={stripes}: stripe 0 "
                f"loses a page to NULL, leaving it empty")
        self.n_blocks = int(n_blocks)
        self.stripes = int(stripes)
        self._stripe_size = self.n_blocks // self.stripes
        # Per-stripe LIFO free lists: freed pages are reused first.
        # stripes=1 degenerates to the single r6 free list; NULL_PAGE
        # (page 0, stripe 0) is never listed.
        self._frees = [
            list(range((s + 1) * self._stripe_size - 1,
                       max(s * self._stripe_size - 1, NULL_PAGE), -1))
            for s in range(self.stripes)]
        self._rc: dict[int, int] = {}   # page -> live reference count
        self.track_allocations = False  # int8 engines flip this on
        self._handed_out: list[int] = []  # since last drain_allocated()
        self.high_watermark = 0         # max pages ever in use at once
        self.total_allocated = 0        # cumulative allocate() pages —
        #                                 prefix hits show up as a FLAT
        #                                 counter across re-submissions
        self.total_freed = 0            # cumulative pages returned to
        #                                 the free list; the conservation
        #                                 invariant total_allocated -
        #                                 total_freed == in_use holds at
        #                                 every step (ISSUE 3 satellite)

    @property
    def capacity(self) -> int:
        """Total allocatable pages (excludes the NULL page)."""
        return self.n_blocks - 1

    @property
    def num_free(self) -> int:
        return sum(len(f) for f in self._frees)

    def stripe_of(self, page: int) -> int:
        """The stripe (= seq shard) that physically holds ``page``."""
        return page // self._stripe_size

    @property
    def num_used(self) -> int:
        return len(self._rc)

    @property
    def in_use(self) -> int:
        """The ONE source of truth for occupancy (alias of num_used):
        the refcount map's size. ``allocator_in_use`` gauges read this
        at collection time instead of mirroring a hand-maintained
        counter that could drift from the free list."""
        return len(self._rc)

    def refcount(self, page: int) -> int:
        """Live references on ``page`` (0 = not allocated)."""
        return self._rc.get(page, 0)

    @property
    def conservation_ok(self) -> bool:
        """The ISSUE 3 invariant as a predicate: every page ever
        allocated is either still referenced or has been freed —
        ``total_allocated - total_freed == in_use``. Cross-pool
        transplants (r19) assert this on BOTH endpoints: a migration
        uses only allocate/incref/decref, so a violation here means a
        transplant leaked or double-freed a page."""
        return self.total_allocated - self.total_freed == self.in_use

    def shortfall(self, n: int, start_col: int = 0) -> int:
        """Pages missing for ``allocate(n, start_col)`` to succeed
        (0 = it will). Striped allocators count per STRIPE — free
        pages in another stripe can't satisfy a starved one, so the
        reclamation path must not stop at the global free count."""
        if self.stripes == 1:
            return max(0, n - len(self._frees[0]))
        need = [0] * self.stripes
        for i in range(n):
            need[(start_col + i) % self.stripes] += 1
        return sum(max(0, need[s] - len(self._frees[s]))
                   for s in range(self.stripes))

    def allocate(self, n: int, start_col: int = 0) -> list[int] | None:
        """n pages at refcount 1, all-or-nothing. None when the pool
        can't cover it (caller decides: defer admission, evict cached
        pages, preempt a row, or fail the one row that needed growth).

        ``start_col`` is the block-table column page 0 of this request
        will occupy (striped allocators only): page ``i`` comes from
        stripe ``(start_col + i) % stripes``, preserving the
        column-residency invariant. All-or-nothing is per STRIPE — a
        request can fail with free pages elsewhere, same as a sharded
        pool would physically."""
        if n < 0:
            raise ValueError(f"allocate({n})")
        if self.stripes == 1:
            free = self._frees[0]
            if n > len(free):
                return None
            pages = [free.pop() for _ in range(n)]
        else:
            need = [0] * self.stripes
            for i in range(n):
                need[(start_col + i) % self.stripes] += 1
            if any(need[s] > len(self._frees[s])
                   for s in range(self.stripes)):
                return None
            pages = [self._frees[(start_col + i) % self.stripes].pop()
                     for i in range(n)]
        for p in pages:
            self._rc[p] = 1
        if self.track_allocations:
            self._handed_out.extend(pages)
        self.total_allocated += n
        self.high_watermark = max(self.high_watermark, len(self._rc))
        return pages

    def drain_allocated(self) -> list[int]:
        """Pages handed out since the last drain (int8 paged KV, ISSUE
        8): a recycled page carries the PREVIOUS tenant's running-max
        scale, which would never shrink and slowly coarsen every new
        row quantized into it. An engine with int8 pools sets
        ``track_allocations`` and drains this list before each device
        step that writes KV, resetting the drained pages' scales to the
        eps floor. fp engines leave tracking off so the list stays
        empty."""
        out = self._handed_out
        self._handed_out = []
        return out

    def incref(self, page: int) -> None:
        """A new reader maps an already-allocated page (prefix hit)."""
        if page not in self._rc:
            raise ValueError(
                f"incref of page {page} which is not allocated")
        self._rc[page] += 1

    def decref(self, page: int) -> None:
        """Drop one reference; the last reference frees the page."""
        rc = self._rc.get(page)
        if rc is None:
            raise ValueError(
                f"decref of page {page} which is not allocated "
                f"(double-free or foreign id)")
        if rc > 1:
            self._rc[page] = rc - 1
        else:
            del self._rc[page]
            self._frees[self.stripe_of(page)].append(page)
            self.total_freed += 1

    def free(self, pages) -> None:
        """Return a row's EXCLUSIVELY-owned pages. Double-free, foreign
        ids, and shared pages raise — all three would alias live KV
        history. (Shared pages must go through decref.)"""
        for p in pages:
            rc = self._rc.get(p)
            if rc is None:
                raise ValueError(
                    f"free of page {p} which is not allocated "
                    f"(double-free or foreign id)")
            if rc != 1:
                raise ValueError(
                    f"free of page {p} with {rc} live references — "
                    f"shared pages release via decref")
            del self._rc[p]
            self._frees[self.stripe_of(p)].append(p)
            self.total_freed += 1

    def stats(self) -> dict:
        """Occupancy snapshot (bench/engine observability). The
        ``stripes`` key appears only on striped allocators so the r6
        snapshot shape is byte-stable for 1-D engines."""
        out = {"capacity": self.capacity, "used": self.num_used,
               "free": self.num_free,
               "high_watermark": self.high_watermark,
               "total_allocated": self.total_allocated,
               "total_freed": self.total_freed}
        if self.stripes > 1:
            out["stripes"] = self.stripes
        return out
