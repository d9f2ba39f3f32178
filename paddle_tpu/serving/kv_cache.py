"""Block-paged KV-cache manager (host side).

vLLM-style paging re-cut for the TPU execution model: the *device* side
is a pair of global page pools per layer ([num_pages, page_size, H*D]
jax arrays — heads and head_dim fused in one row, the layout the ragged
kernel's page block reads, so a pool's stored bytes ARE its logical
bytes — owned by the engine and threaded functionally through the
jitted decode step); this module owns the *host* bookkeeping — which
physical page belongs to which sequence — as plain python/numpy so
allocation never touches the device or triggers a retrace.

Page id 0 is RESERVED as the trash page: it is never allocated, padding
entries of every page-table row point at it, and masked/inactive batch
lanes scatter into it.  Every page-table entry is therefore always a
valid index — the kernel (ops/pallas_ops/paged_attention.py) needs no
bounds checks, and the decode step needs no per-lane branching.

Mesh-sharded pools (ISSUE 19) generalize this: with the page dimension
split over ``sp`` shards, each shard needs its OWN local trash row, so
the engine passes ``reserved_pages=(0, N/sp, 2N/sp, ...)`` (global page
``s*(N/sp)`` is shard ``s``'s local row 0 — see
``text.generation.ServingMeshLayout.reserved_pages``).  Reserved ids
are simply never placed on the free list; page 0 stays the table-row
padding value either way.

Allocation is a LIFO free list (O(1) alloc/free, recently-freed pages
are reused first which keeps the working set dense).  ``stats()``
reports alloc/free counters, high-water mark, and internal
fragmentation (allocated-but-unused tail slots), the only fragmentation
kind paging admits — there is no external fragmentation to defrag, which
is the point of fixed-size pages.

Refcounted sharing + copy-on-write (the prefix cache, ISSUE 10)
---------------------------------------------------------------
Pages carry a REFERENCE COUNT — the number of sequence page tables that
contain them.  ``share()`` maps already-resident pages (located by the
``serving.prefix_cache`` radix index) into a new sequence's table head
and increfs them; ``free()`` DECREFS instead of unconditionally
releasing, so a page shared by several sequences returns to the free
list only when the last reference drops.  Pages the prefix index holds
(``pin_cached``) additionally stay RESIDENT at refcount 0 — evictable,
not free: ``allocate`` reclaims them through the registered
``reclaimer`` (the index's LRU eviction) only when the free list runs
short, so cached prefixes survive exactly as long as memory allows.
``cow_page`` is the copy-on-write step: when a sequence must write into
a shared page (its first decode position falls inside the matched
prefix), the HOST side swaps in a freshly allocated page here and the
ENGINE device-copies the payload (``serving.page_cow``) — the shared
original is never mutated.  Accounting counts a shared page EXACTLY
ONCE: ``pages_in_use`` is the number of distinct referenced pages (not
the sum of table lengths), ``pages_cached`` the refcount-0 resident
set, and ``pages_in_use + pages_cached + free_pages == num_pages - 1``
always holds (the leak invariant tests pin).

Quantized page layout (the int8 serving path)
---------------------------------------------
With ``kv_cache_dtype="int8"`` the device pools store each page
([P, H*D] on device; [P, H, D] to the reference fns below, a free host
reshape) as int8 plus ONE fp32 dequant scale per (page, head) — a [N, H]
scale array rides next to each [N, P, H*D] pool, so a page costs
``P*H*D + 4*H`` bytes instead of ``2*P*H*D`` (bf16): a ~2x cut in the
bytes the bytes-bound decode loop streams, and 2x the sequences per HBM
byte.  ``quantize_kv_page`` / ``dequantize_kv_page`` below are the
numpy REFERENCE for that layout (symmetric, zero-point-free, qmax 127);
the jitted write path lives in ``text/generation.py`` and the
in-register dequant in ``ops/pallas_ops/paged_attention.py`` — tests
pin all three to each other.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..framework.errors import InvalidArgumentError

from ..testing.chaos import chaos_site

__all__ = ["PagedKVCache", "KV_SCALE_EPS", "kv_page_bytes",
           "quantize_kv_page", "dequantize_kv_page"]

# floor for per-page scales: keeps ratio math finite on never-written
# pages (dynamic mode initializes scales to this)
KV_SCALE_EPS = 1e-8

_KV_ITEMSIZE = {"int8": 1, "bfloat16": 2, "bf16": 2, "float16": 2,
                "fp16": 2, "float32": 4, "fp32": 4}


def kv_page_bytes(page_size: int, num_heads: int, head_dim: int,
                  dtype: str = "bfloat16") -> int:
    """Bytes one K **or** V page occupies on device, including its
    per-page-per-head fp32 scale row when int8."""
    try:
        itemsize = _KV_ITEMSIZE[str(dtype)]
    except KeyError:
        raise InvalidArgumentError(
            f"unknown KV cache dtype {dtype!r}; one of "
            f"{sorted(_KV_ITEMSIZE)}")
    n = page_size * num_heads * head_dim * itemsize
    if itemsize == 1:
        n += num_heads * 4            # fp32 scale per head
    return n


def quantize_kv_page(page: np.ndarray, scales: Optional[np.ndarray] = None):
    """Numpy reference for the device write path: quantize one [P, H, D]
    float page to (int8 page, [H] fp32 scales).

    ``scales=None`` derives per-head abs-max scales from the page itself
    (what the dynamic write path converges to once every slot is
    written); passing calibrated scales reproduces the static path
    (values CLIP at ±127 instead of rescaling).
    """
    page = np.asarray(page, np.float32)
    if scales is None:
        amax = np.abs(page).max(axis=(0, 2))          # [H]
        scales = np.maximum(amax / 127.0, KV_SCALE_EPS)
    scales = np.asarray(scales, np.float32)
    q = np.clip(np.round(page / scales[None, :, None]), -127, 127)
    return q.astype(np.int8), scales


def dequantize_kv_page(qpage: np.ndarray, scales: np.ndarray) -> np.ndarray:
    """Inverse of ``quantize_kv_page``: [P, H, D] int8 + [H] scales →
    f32 (round-trip error ≤ scale/2 per element, tests pin it)."""
    return qpage.astype(np.float32) * np.asarray(
        scales, np.float32)[None, :, None]


class PagedKVCache:
    """Free-list page allocator + per-sequence page tables."""

    def __init__(self, num_pages: int, page_size: int, pages_per_seq: int,
                 reserved_pages: Tuple[int, ...] = (0,)):
        if num_pages < 2:
            raise InvalidArgumentError(
                "num_pages must be >= 2 (page 0 is the "
                "reserved trash page)")
        if page_size < 1 or pages_per_seq < 1:
            raise InvalidArgumentError(
                "page_size and pages_per_seq must be >= 1")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.pages_per_seq = int(pages_per_seq)
        # page 0 is ALWAYS reserved (table-row padding); a mesh-sharded
        # pool reserves one trash row per sp shard on top of it
        reserved = {0} | {int(p) for p in reserved_pages}
        for p in sorted(reserved):
            if not (0 <= p < self.num_pages):
                raise InvalidArgumentError(
                    f"reserved page id {p} out of range "
                    f"(0..{self.num_pages - 1})")
        if len(reserved) >= self.num_pages:
            raise InvalidArgumentError(
                "reserved_pages leaves no allocatable pages")
        self.reserved_pages: Tuple[int, ...] = tuple(sorted(reserved))
        # LIFO free list; reserved pages excluded (trash rows)
        self._free: List[int] = [p for p in
                                 range(self.num_pages - 1, 0, -1)
                                 if p not in reserved]
        self._tables: Dict[str, List[int]] = {}
        # page id -> number of sequence tables containing it (absent =
        # not referenced); a page appears in pages_in_use ONCE however
        # many sequences share it
        self._ref: Dict[int, int] = {}
        # page ids the prefix index holds resident: at refcount 0 they
        # are EVICTABLE (reclaimed via the reclaimer hook), never free
        self._cached: set = set()
        # opt-in hook (the prefix cache's LRU eviction): called with the
        # page deficit when the free list cannot cover an allocation;
        # returns how many pages it released back to the free list
        self._reclaimer: Optional[Callable[[int], int]] = None
        self.total_allocs = 0
        self.total_frees = 0
        self.total_shared_maps = 0
        self.total_cow = 0
        self.peak_pages_in_use = 0

    # --- capacity ---------------------------------------------------------
    def pages_needed(self, num_tokens: int) -> int:
        """Pages covering ``num_tokens`` KV positions."""
        return max(0, -(-int(num_tokens) // self.page_size))

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def allocatable_pages(self) -> int:
        """Pages the allocator can ever hand out: ``num_pages`` minus
        the reserved trash rows (one classically, sp under a mesh).  The
        leak invariant closes over THIS — ``pages_in_use + pages_cached
        + free_pages == allocatable_pages`` always."""
        return self.num_pages - len(self.reserved_pages)

    @property
    def pages_in_use(self) -> int:
        """Distinct pages referenced by >= 1 sequence — a page shared by
        N sequences counts ONCE (the leak-accounting contract)."""
        return len(self._ref)

    @property
    def pages_cached(self) -> int:
        """Resident refcount-0 pages held only by the prefix index
        (evictable on demand — neither leaked nor free)."""
        return sum(1 for p in self._cached if p not in self._ref)

    def ref_count(self, page_id: int) -> int:
        return self._ref.get(int(page_id), 0)

    def is_free(self, page_id: int) -> bool:
        """True when the page is genuinely on the free list —
        unreferenced by any sequence AND not held resident by a prefix
        index.  The quarantine scrub (ISSUE 13) keys on this: a page a
        quarantined sequence SHARED must never be zeroed out from under
        its other readers."""
        p = int(page_id)
        return self._ref.get(p, 0) == 0 and p not in self._cached

    def num_seqs(self) -> int:
        return len(self._tables)

    def seq_pages(self, seq_id: str) -> int:
        return len(self._tables.get(seq_id, ()))

    def allocated_tokens(self, seq_id: str) -> int:
        """KV positions ``seq_id``'s current page table can hold —
        writes at positions >= this land in the trash page (the
        spec-decode junk-containment boundary)."""
        return self.seq_pages(seq_id) * self.page_size

    # --- allocation -------------------------------------------------------
    def allocate(self, seq_id: str, num_tokens: int) -> bool:
        """Grow ``seq_id``'s page table to cover ``num_tokens`` positions.

        All-or-nothing: returns False (no state change) when the free
        list cannot supply the growth or the sequence would exceed
        pages_per_seq — the scheduler then preempts or queues.

        Chaos site ``kv.allocate`` (action ``deny``): simulates transient
        page exhaustion — the call fails exactly as if the free list were
        empty, so tests drive the preemption / deferred-admission paths
        deterministically (paddle_tpu.testing.chaos).
        """
        fault = chaos_site("kv.allocate", key=seq_id)
        if fault is not None and fault.action == "deny":
            return False
        table = self._tables.get(seq_id)
        have = len(table) if table is not None else 0
        need = self.pages_needed(num_tokens) - have
        if need <= 0:
            return True
        if have + need > self.pages_per_seq:
            return False
        if need > len(self._free):
            self._reclaim(need - len(self._free))
        if need > len(self._free):
            # no phantom registration on failure: a rejected first
            # allocation must leave no trace in num_seqs()/stats()
            return False
        if table is None:
            table = self._tables[seq_id] = []
        for _ in range(need):
            page = self._free.pop()
            table.append(page)
            self._ref[page] = 1
        self.total_allocs += need
        self.peak_pages_in_use = max(self.peak_pages_in_use,
                                     self.pages_in_use)
        return True

    def _reclaim(self, deficit: int):
        """Ask the prefix index (if attached) to evict refcount-0 cached
        pages back to the free list — cached prefixes yield to live
        sequences before allocation fails or preemption strikes."""
        if self._reclaimer is not None and deficit > 0:
            self._reclaimer(deficit)

    def set_reclaimer(self, fn: Optional[Callable[[int], int]]):
        """Register the cached-page eviction hook (one owner at a time —
        the prefix cache attaches itself here)."""
        self._reclaimer = fn

    def _release_ref(self, page: int):
        """Drop one reference; a page reaching refcount 0 returns to the
        free list UNLESS the prefix index holds it resident."""
        n = self._ref.get(page, 0) - 1
        if n > 0:
            self._ref[page] = n
            return
        self._ref.pop(page, None)
        if page not in self._cached:
            self._free.append(page)

    def free(self, seq_id: str) -> int:
        """Drop all of ``seq_id``'s page references; returns the table
        length.  Shared pages only DECREF (another reader, or the prefix
        index, may keep them resident) — premature free of a shared page
        is structurally impossible here."""
        table = self._tables.pop(seq_id, None)
        if not table:
            return 0
        for page in reversed(table):
            self._release_ref(page)
        self.total_frees += len(table)
        return len(table)

    # --- prefix sharing / copy-on-write ------------------------------------
    def share(self, seq_id: str, page_ids: List[int]) -> bool:
        """Map already-resident ``page_ids`` (a radix-index prefix match)
        as the HEAD of a new sequence's page table, increffing each.
        Must run before the sequence's first ``allocate`` (prefix pages
        cover positions [0, len*page_size)).  Returns False untouched
        when the sequence already has a table or the prefix alone would
        exceed ``pages_per_seq``."""
        if not page_ids:
            return True
        if seq_id in self._tables or len(page_ids) > self.pages_per_seq:
            return False
        for page in page_ids:
            if not (0 < page < self.num_pages) \
                    or page in self.reserved_pages:
                raise InvalidArgumentError(
                    f"shared page id {page} out of range (1.."
                    f"{self.num_pages - 1}) or reserved")
        self._tables[seq_id] = list(int(p) for p in page_ids)
        for page in self._tables[seq_id]:
            self._ref[page] = self._ref.get(page, 0) + 1
        self.total_shared_maps += len(page_ids)
        self.peak_pages_in_use = max(self.peak_pages_in_use,
                                     self.pages_in_use)
        return True

    def cow_page(self, seq_id: str,
                 table_index: int) -> Optional[Tuple[int, int]]:
        """Copy-on-write (host half): replace the SHARED page at
        ``table_index`` of ``seq_id``'s table with a freshly allocated
        private page, decreffing the original.  Returns ``(src, dst)``
        page ids for the engine's ``serving.page_cow`` device copy, or
        None (state untouched — the caller DEFERS the admission) when
        the pool cannot supply a page.

        Chaos: routes through the ``kv.allocate`` site like every other
        page allocation — a ``deny`` fault defers the COW exactly like
        transient exhaustion and can never corrupt the shared page."""
        fault = chaos_site("kv.allocate", key=seq_id)
        if fault is not None and fault.action == "deny":
            return None
        table = self._tables.get(seq_id)
        if table is None or not (0 <= table_index < len(table)):
            raise InvalidArgumentError(
                f"cow_page: sequence {seq_id!r} has no page at table "
                f"index {table_index}")
        if not self._free:
            self._reclaim(1)
        if not self._free:
            return None
        src = table[table_index]
        dst = self._free.pop()
        table[table_index] = dst
        self._ref[dst] = 1
        self._release_ref(src)
        self.total_allocs += 1
        self.total_cow += 1
        self.peak_pages_in_use = max(self.peak_pages_in_use,
                                     self.pages_in_use)
        return src, dst

    # --- prefix-index residency (called by serving.prefix_cache) ----------
    def pin_cached(self, page_id: int):
        """The prefix index took custody of ``page_id``: keep it
        resident (evictable, not free) when its refcount drops to 0."""
        self._cached.add(int(page_id))

    def release_cached(self, page_id: int):
        """The prefix index evicted ``page_id``: a refcount-0 page
        returns to the free list; a still-referenced one just loses its
        index residency (it frees normally when the readers finish)."""
        page_id = int(page_id)
        self._cached.discard(page_id)
        if page_id not in self._ref:
            self._free.append(page_id)

    def take_cached_page(self) -> Optional[int]:
        """Pop one FREE page and hand it straight to the prefix index as
        cached residency (tier promotion, ISSUE 16): free → cached in
        one move, so the leak invariant never sees an intermediate
        state.  Returns None when the free list is empty — promotion
        deliberately does NOT reclaim: evicting a resident prefix to
        promote a demoted one would just churn the index, so under
        pressure the demoted chain stays in its tier (a miss)."""
        if not self._free:
            return None
        page = self._free.pop()
        self._cached.add(page)
        return page

    # --- page-table export ------------------------------------------------
    def seq_page_ids(self, seq_id: str) -> List[int]:
        """The physical page ids ``seq_id`` currently owns, in order."""
        return list(self._tables.get(seq_id, ()))

    def page_table_row(self, seq_id: str) -> np.ndarray:
        """[pages_per_seq] int32 row, padded with the trash page (0)."""
        row = np.zeros((self.pages_per_seq,), np.int32)
        table = self._tables.get(seq_id, ())
        row[: len(table)] = table
        return row

    # --- observability ----------------------------------------------------
    def stats(self, seq_lens: Optional[Dict[str, int]] = None) -> dict:
        """Allocator stats; pass live ``{seq_id: valid_len}`` to also get
        internal fragmentation (allocated slots minus used slots)."""
        out = {
            "num_pages": self.allocatable_pages,  # sans reserved trash rows
            "page_size": self.page_size,
            "pages_in_use": self.pages_in_use,
            "pages_cached": self.pages_cached,
            "pages_free": self.free_pages,
            "num_seqs": self.num_seqs(),
            "total_allocs": self.total_allocs,
            "total_frees": self.total_frees,
            "total_shared_maps": self.total_shared_maps,
            "total_cow": self.total_cow,
            "peak_pages_in_use": self.peak_pages_in_use,
            "utilization": self.pages_in_use / max(self.allocatable_pages,
                                                   1),
        }
        if seq_lens is not None:
            frag = 0
            for sid, table in self._tables.items():
                used = int(seq_lens.get(sid, 0))
                frag += len(table) * self.page_size - used
            out["internal_fragmentation_slots"] = frag
        return out
