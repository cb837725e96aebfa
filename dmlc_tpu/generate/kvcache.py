"""Paged KV cache: fixed-size pages, a free-list allocator, per-slot tables.

The contiguous-cache alternative reserves max_len KV rows per slot up
front, so HBM cost is max_slots * max_len regardless of what is actually
cached — short requests strand most of it, and a long request cannot
borrow a short one's slack. Paging (vLLM's insight, specialized for TPU by
"Ragged Paged Attention", PAPERS.md) carves the pool into fixed-size pages
and binds them to slots on demand through an int32 page table, so capacity
is a FLEET of pages shared by whatever mix of requests is resident.

Layout (docs/GENERATE.md):

- What a cached position's row holds is the model family's to say. A family
  that caches K and V per KV head (``models/lm``, ``nemotron_h``,
  ``olmo_hybrid``, ``lfm2_moe``) gets two pools, ``k_pages`` / ``v_pages``:
  [num_layers * num_pages, page_size, KV * Dh]; a page is ``page_size`` rows
  of every KV head side by side. A family that caches ONE latent row a
  position, shared by all its heads (``models/deepseek_v3``: the compressed
  K/V and the turned rotary key, ``latent_row`` lanes), gets one pool,
  ``k_pages``: [num_layers * num_pages, page_size, latent_row], and
  ``v_pages`` is ``None``: no second pool is allocated, carried, donated or
  released.
- Either way layer ``l``'s page ``p`` is row ``l * num_pages + p``. That is
  the one layout both programs write and the attention reads, so the
  donated pools are updated in place: heads folded into the last axis fill
  the chip's tiles (a trailing [H, Dh] such as 20 x 64 pads to 32 x 128, and
  the compiler then re-lays the WHOLE pool around every write), and layers
  folded into the page axis mean no layer is ever sliced out. One page id
  spans EVERY layer — allocating a page grants page_size token positions
  in all layers at once, so there is one allocator and one table, not
  num_layers of each.
- **page 0 is the reserved scratch page**: never allocated, the write/read
  target for inactive batch rows (the decode step runs at a fixed batch
  shape; rows with no request must still index something). Garbage lands
  there and is never attended to.
- ``page_table``: int32 [max_slots, max_pages_per_slot], host-owned
  (NumPy) and shipped to the device per step — it is tiny, and host
  ownership keeps allocation pure Python with no device round trip.
  Released rows are reset to scratch so a stale table can never reach a
  recycled page.

Beside the pages a model may carry a second kind of per-slot state
(``SlotState``): arrays of FIXED size per slot that every step rewrites, such
as a state-space layer's conv window and SSM state. It takes no pages and
needs no allocator: slot ``s`` owns row ``s`` of every array.

The allocator is a plain LIFO free list under a lock: page exhaustion
raises the typed ``PagePoolExhausted``, which the slot scheduler converts
into a typed ``Overloaded`` shed at admission (docs/OVERLOAD.md) — the
pool being full is an overload condition, not an error.
"""

from __future__ import annotations

import threading
from typing import Any

import numpy as np

#: Page id 0 — the scratch page inactive rows point at; never allocated.
SCRATCH_PAGE = 0


class PagePoolExhausted(Exception):
    """No free pages: the caller must shed, evict, or retry later."""


class PageAllocator:
    """Free-list allocator over the page pool. Thread-safe; LIFO reuse so
    a just-released page is the next one handed out — which is exactly
    what the cross-slot-contamination tests want to stress."""

    def __init__(self, num_pages: int, page_size: int) -> None:
        if num_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is reserved scratch)")
        if page_size <= 0:
            raise ValueError("page_size must be positive")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self._lock = threading.Lock()
        # Ascending pop order (list.pop() takes the tail) keeps allocation
        # deterministic for the seeded tests.
        self._free = list(range(self.num_pages - 1, SCRATCH_PAGE, -1))
        self._held: set[int] = set()
        self.allocs = 0
        self.frees = 0
        self.exhaustions = 0

    @property
    def pages_free(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def pages_total(self) -> int:
        return self.num_pages - 1  # scratch excluded

    def pages_for(self, n_tokens: int) -> int:
        """Pages needed to hold ``n_tokens`` cache positions."""
        return -(-max(0, int(n_tokens)) // self.page_size)

    def alloc(self, n: int) -> list[int]:
        """Take ``n`` pages or none: a partial grant would leave the caller
        holding pages it must immediately free under the same contention."""
        n = int(n)
        if n <= 0:
            return []
        with self._lock:
            if n > len(self._free):
                self.exhaustions += 1
                raise PagePoolExhausted(
                    f"need {n} page(s), {len(self._free)} free "
                    f"of {self.pages_total}"
                )
            pages = [self._free.pop() for _ in range(n)]
            self._held.update(pages)
            self.allocs += n
            return pages

    def free(self, pages: list[int]) -> None:
        with self._lock:
            for p in pages:
                p = int(p)
                if p == SCRATCH_PAGE:
                    raise ValueError("page 0 is the reserved scratch page")
                if p not in self._held:
                    raise ValueError(f"double free (or foreign page): {p}")
                self._held.discard(p)
                self._free.append(p)
                self.frees += 1

    def summary(self) -> dict[str, int]:
        with self._lock:
            return {
                "pages_total": self.pages_total,
                "pages_free": len(self._free),
                "pages_held": len(self._held),
                "allocs": self.allocs,
                "frees": self.frees,
                "exhaustions": self.exhaustions,
            }


class PagedKVCache:
    """Device page pools + the host-side slot table over one allocator:
    a K and a V pool of ``num_heads * head_dim`` lanes a row, or, given
    ``latent_row``, the one pool of a family that caches a latent row.

    Construction is the expensive part (it allocates the whole pool in
    device memory) and happens ONCE per engine — never per request or per
    step; lint rule H1 flags per-hot-path construction of this class the
    same way it flags per-call thread pools.
    """

    def __init__(
        self,
        *,
        num_layers: int,
        num_pages: int,
        page_size: int,
        num_heads: int,
        head_dim: int,
        max_slots: int,
        max_pages_per_slot: int,
        dtype: Any = None,
        latent_row: int | None = None,
    ) -> None:
        import jax.numpy as jnp

        self.num_layers = int(num_layers)
        self.page_size = int(page_size)
        self.max_slots = int(max_slots)
        self.max_pages_per_slot = int(max_pages_per_slot)
        self.dtype = dtype if dtype is not None else jnp.float32
        self.allocator = PageAllocator(num_pages, page_size)
        row = int(num_heads) * int(head_dim) if latent_row is None else int(latent_row)
        shape = (self.num_layers * int(num_pages), self.page_size, row)
        # The pools live on the engine's device; both jitted programs donate
        # them and write the new rows into the same buffers, so exactly one
        # generation of the pool exists at a time.
        self.k_pages = jnp.zeros(shape, self.dtype)
        self.v_pages = jnp.zeros(shape, self.dtype) if latent_row is None else None
        # Host-owned table/lengths; rows default to the scratch page.
        self.page_table = np.full(
            (self.max_slots, self.max_pages_per_slot), SCRATCH_PAGE, np.int32
        )
        self.lengths = np.zeros(self.max_slots, np.int32)
        self._slot_pages: dict[int, list[int]] = {}

    # ---- slot binding ---------------------------------------------------

    @property
    def max_tokens_per_slot(self) -> int:
        return self.max_pages_per_slot * self.page_size

    def bind(self, slot: int, pages: list[int]) -> None:
        """Install an allocated page run as ``slot``'s table row (pages come
        from ``allocator.alloc``, usually via a submit-time reservation)."""
        if slot in self._slot_pages:
            raise ValueError(f"slot {slot} already bound")
        if len(pages) > self.max_pages_per_slot:
            raise ValueError(
                f"{len(pages)} pages exceed max_pages_per_slot="
                f"{self.max_pages_per_slot}"
            )
        self._slot_pages[slot] = list(pages)
        self.page_table[slot, :] = SCRATCH_PAGE
        self.page_table[slot, : len(pages)] = pages
        self.lengths[slot] = 0

    def grow(self, slot: int) -> None:
        """Add one page to ``slot`` (decode crossed a page boundary).
        Raises PagePoolExhausted without disturbing the slot's state."""
        pages = self._slot_pages[slot]
        if len(pages) >= self.max_pages_per_slot:
            raise PagePoolExhausted(
                f"slot {slot} at max_pages_per_slot={self.max_pages_per_slot}"
            )
        (page,) = self.allocator.alloc(1)
        pages.append(page)
        self.page_table[slot, len(pages) - 1] = page
        self.pages_needed_hw = max(getattr(self, "pages_needed_hw", 0), len(pages))

    def capacity_ok(self, slot: int, next_len: int) -> bool:
        """True when the slot's bound pages already cover ``next_len``
        cache positions (no grow needed before the next step)."""
        return len(self._slot_pages[slot]) * self.page_size >= next_len

    def release(self, slot: int) -> list[int]:
        """Recycle the slot's pages into the free list and reset its table
        row to scratch. Returns the freed page ids (tests assert reuse)."""
        pages = self._slot_pages.pop(slot, [])
        if pages:
            self.allocator.free(pages)
        self.page_table[slot, :] = SCRATCH_PAGE
        self.lengths[slot] = 0
        return pages

    def slot_pages(self, slot: int) -> list[int]:
        return list(self._slot_pages.get(slot, []))

    @property
    def pages_free(self) -> int:
        return self.allocator.pages_free


class SlotState:
    """Slot-indexed recurrent state beside the pages: a pytree of device
    arrays whose leading axis is the slot (one array per layer that has
    such state, so a step updates each in place). ``join`` overwrites a
    slot's rows whole, so ``release`` does no device work and a reused slot
    cannot see its predecessor. Built once per engine, like the pools."""

    def __init__(self, shapes: Any, max_slots: int) -> None:
        import jax
        import jax.numpy as jnp

        is_leaf = lambda x: isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)
        self.max_slots = int(max_slots)
        self.arrays = jax.tree_util.tree_map(
            lambda sd: jnp.zeros(sd[0], sd[1]), shapes, is_leaf=is_leaf)
        leaves = jax.tree_util.tree_leaves(self.arrays)
        self.nbytes = sum(int(a.size) * a.dtype.itemsize for a in leaves)
        self.bytes_per_slot = self.nbytes // self.max_slots if leaves else 0
