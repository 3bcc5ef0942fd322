"""Node-wide paged KV arena: block-pool allocator + per-row block tables.

DESIGN.md §2.3.  The continuous path historically gave every cohort a
contiguous (B, s_max + n_max) slab, so KV memory freed by one model's
finished rows was invisible to every other cohort and node-wide occupancy
sat at 0.12–0.19.  The arena virtualizes that memory vLLM-style:

* ONE device-resident pool of fixed ``block_tokens``-slot pages per KV
  precision, shaped ``(L, n_pages, block_tokens, *tail)`` per cache leaf
  (layers stacked so one page id covers all L layers of a row's block);
* a free-list allocator — ``alloc`` leases pages to a cohort row,
  ``free`` returns them the moment the row completes, so any hosted
  cohort can reuse them at the very next admission boundary;
* a :class:`BlockTable` per cohort mapping (row, logical block) to its
  physical page; the paged flash-decode kernel and the gather fallback
  both read K/V through this indirection.

Two pages are RESERVED and never allocated:

* ``ZERO_PAGE`` — all-zero, NEVER written.  Rows refilled mid-cohort at
  step t have a junk gap ``[s_max, s_max + t)`` the slab path fills with
  zero K/V (the paper's s' padding class); their fully-dead gap blocks
  map here so the gap costs no physical pages.  A live row's first write
  block ``(s_max + t) // block_tokens`` is always a real page, so the
  zero page stays zero.
* ``TRASH_PAGE`` — scratch for rows with no lease (empty slots, and
  completed rows after release) AND for every block beyond a row's
  cap-aware lease span.  Dead rows keep stepping through the model
  (exactly like the slab path), so their writes need somewhere to land,
  and a live row that exhausts its cap mid-segment overflows here too;
  duplicate-index scatters into this page are don't-care garbage that
  no live row ever reads — blocks a row will actually need are leased
  (segment-boundary top-up, ``BlockTable.extend_row``) BEFORE the write
  cursor enters them.

Sizing: ``for_engines`` provisions ``shrink`` × the summed slab page
count of the attached engines (+ the reserved pair).  ``shrink < 1`` is
the whole point — block-level reuse serves the same traffic from less
physical memory (benchmarks/paged_vs_slab.py measures exactly this).
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

ZERO_PAGE = 0
TRASH_PAGE = 1
N_RESERVED = 2
LANES = 128         # TPU vector lanes: the minor dim of a VMEM/HBM tile


class ArenaError(RuntimeError):
    """Allocator misuse: double-free, freeing a reserved page, or a page
    id outside the pool.  A REAL exception (not an assert) so the guards
    survive ``python -O`` — CI smokes the arena suite under ``-O`` to
    keep it that way."""


class ArenaExhausted(ArenaError):
    """alloc() asked for more pages than the free list holds — admission
    control must gate on ``free_pages`` so this never fires in the
    runtime (it firing in a test means the gate is broken)."""


class BlockTable:
    """Logical-block → physical-page map for one cohort (B rows × n_b
    logical blocks).  Host array is authoritative; ``device`` is the
    int32 mirror the jitted decode segment reads (re-shipped only when
    rows change — admission/release/top-up boundaries, never
    mid-segment).  ``n_pages`` (when given) bounds every page id written
    through ``set_row``/``extend_row`` — an id the device buffers don't
    have must fail loudly at the table, not as silent garbage gathers."""

    def __init__(self, batch: int, n_blocks: int,
                 n_pages: Optional[int] = None):
        self.host = np.full((batch, n_blocks), TRASH_PAGE, np.int32)
        self.n_pages = n_pages
        self._device: Optional[jax.Array] = None

    @property
    def device(self) -> jax.Array:
        if self._device is None:
            # ship a copy: on the CPU backend device_put may alias an
            # aligned host array, and the row edits below rewrite
            # ``host`` in place while a dispatched segment still reads it
            self._device = jax.device_put(self.host.copy())
        return self._device

    def _check(self, pages: np.ndarray) -> None:
        if pages.size and (pages.min() < 0 or (self.n_pages is not None
                                               and pages.max()
                                               >= self.n_pages)):
            raise ArenaError(
                f"page id out of range [0, {self.n_pages}): "
                f"{sorted(set(pages.tolist()))}")

    def set_row(self, slot: int, pages: Sequence[int]) -> None:
        pages = np.asarray(pages, np.int32)
        self._check(pages)
        self.host[slot] = pages
        self._device = None

    def extend_row(self, slot: int, start: int,
                   pages: Sequence[int]) -> None:
        """Map blocks ``[start, start + len(pages))`` of a LIVE row to
        freshly leased pages — the incremental lease top-up (DESIGN.md
        §2.3).  Host-side remap only; the device mirror re-ships lazily,
        so any number of same-boundary extends cost ONE transfer."""
        pages = np.asarray(pages, np.int32)
        self._check(pages)
        self.host[slot, start:start + len(pages)] = pages
        self._device = None

    def clear_row(self, slot: int) -> None:
        """Remap a row entirely to the trash page (dead rows keep
        stepping; their writes become don't-care scatters)."""
        self.host[slot] = TRASH_PAGE
        self._device = None

    def row_leases(self, slot: int) -> List[int]:
        """Real (allocated) pages currently mapped by a row."""
        return [int(p) for p in self.host[slot] if p >= N_RESERVED]


class KVArena:
    """Fixed-size block pool shared by every paged engine on the node."""

    def __init__(self, leaf_specs: Dict[str, Any], n_pages: int,
                 block_tokens: int):
        assert n_pages > N_RESERVED, n_pages
        self.block_tokens = int(block_tokens)
        self.n_pages = int(n_pages)
        self.leaf_specs = dict(leaf_specs)
        # ZERO_PAGE relies on zero-init: zero K/V (and zero scales for
        # the int8 leaves — dequant 0 * 0 == the slab path's zero gap)
        self._buffers = {
            name: jnp.zeros((spec.shape[0], n_pages, block_tokens)
                            + tuple(spec.shape[3:]), spec.dtype)
            for name, spec in leaf_specs.items()}
        # LIFO list (pop order: hot pages stay hot) + membership set, so
        # the double-free guard is O(1) and a REAL check — not an O(n)
        # scan hidden inside an assert that ``python -O`` strips
        self._free: List[int] = list(range(n_pages - 1, N_RESERVED - 1, -1))
        self._free_set = set(self._free)
        self.alloc_peak = 0

    # -- construction --------------------------------------------------------

    @classmethod
    def for_engines(cls, engines, block_tokens: int = 16,
                    shrink: float = 1.0, extra_pages: int = 0) -> "KVArena":
        """Size an arena for the paged-capable engines of a node.

        Page-leaf shapes are derived structurally from each engine's
        ``init_cache`` (batch 1).  Engines must share leaf names, layer
        count, and dtype, and have a ``cache_len`` divisible by
        ``block_tokens`` — the divisibility is what makes the gathered
        paged cache bitwise equal to the slab cache, the invariant the
        equivalence tests pin.  Trailing dims (n_kv heads, d_head,
        scale widths) may DIFFER across cohorts: the pool provisions the
        elementwise max and each engine reads/writes only the leading
        slice of a page's tail, so one free list still serves every
        hosted model (the cross-cohort reuse the arena exists for)."""
        paged = [e for e in _as_list(engines) if e.paged_capable]
        if not paged:
            raise ValueError("no paged-capable engine to size the arena for")
        specs: Optional[Dict[str, Any]] = None
        slab_pages = 0
        for e in paged:
            if e.cache_len % block_tokens:
                raise ValueError(
                    f"cache_len {e.cache_len} not divisible by "
                    f"block_tokens {block_tokens}")
            s = jax.eval_shape(lambda e=e: e.model.init_cache(1, e.cache_len))
            s = {k: v for k, v in s.items()}
            if specs is None:
                specs = s
            else:
                if set(specs) != set(s):
                    raise ValueError("paged engines must share KV leaf names")
                for name, spec in s.items():
                    have = specs[name]
                    if (have.dtype != spec.dtype
                            or len(have.shape) != len(spec.shape)
                            or have.shape[0] != spec.shape[0]):
                        raise ValueError(
                            "paged engines must share KV layer count and "
                            f"dtype (leaf {name!r}: {have.shape} "
                            f"{have.dtype} vs {spec.shape} {spec.dtype})")
                    tail = tuple(max(a, b) for a, b in
                                 zip(have.shape[3:], spec.shape[3:]))
                    specs[name] = jax.ShapeDtypeStruct(
                        have.shape[:3] + tail, have.dtype)
            slab_pages += e.batch_capacity * (e.cache_len // block_tokens)
        n_pages = N_RESERVED + extra_pages \
            + max(1, math.ceil(slab_pages * shrink))
        return cls({name: _lane_padded(spec) for name, spec in specs.items()},
                   n_pages, block_tokens)

    # -- allocator -----------------------------------------------------------

    @property
    def total_pages(self) -> int:
        """Allocatable pages (reserved pair excluded)."""
        return self.n_pages - N_RESERVED

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return self.total_pages - len(self._free)

    def alloc(self, n: int) -> List[int]:
        """Lease ``n`` pages (LIFO — hot pages stay hot).  Raises
        :class:`ArenaExhausted` if the free list is short."""
        if n > len(self._free):
            raise ArenaExhausted(
                f"need {n} pages, {len(self._free)} free of "
                f"{self.total_pages}")
        pages = [self._free.pop() for _ in range(n)]
        self._free_set.difference_update(pages)
        self.alloc_peak = max(self.alloc_peak, self.pages_in_use)
        return pages

    def free(self, pages: Sequence[int]) -> None:
        """Return leased pages.  Raises :class:`ArenaError` on a
        double-free, a reserved page, or an id outside the pool —
        real exceptions, because an allocator whose guards vanish under
        ``python -O`` silently grows the free list and later leases
        pages the device buffers don't have."""
        for p in pages:
            p = int(p)
            if p < N_RESERVED:
                raise ArenaError(f"freeing reserved page {p}")
            if p >= self.n_pages:
                raise ArenaError(
                    f"freeing out-of-range page {p} (pool has "
                    f"{self.n_pages} pages)")
            if p in self._free_set:
                raise ArenaError(f"double free of page {p}")
            self._free.append(p)
            self._free_set.add(p)

    # -- device buffers ------------------------------------------------------

    def buffers(self) -> Dict[str, jax.Array]:
        """Current page-buffer handles.  A jitted segment CONSUMES these
        (donation on supporting backends) — always hand the returned
        tree back via ``set_buffers``."""
        return self._buffers

    def set_buffers(self, bufs: Dict[str, jax.Array]) -> None:
        self._buffers = bufs


def _lane_padded(spec):
    """Round the head dim of a K/V value leaf (L, P, bt, nkv, dh) up to
    the TPU's 128 lanes.  With d_head 80 XLA otherwise lays the arena out
    page-minor and copies it in and out of every decode segment: a v5e
    compile of bloom-3b's segment needs 7.7 GiB of temporaries that way
    and 3.7 GiB lane-padded.  Scale leaves (rank 4) keep their shape."""
    if len(spec.shape) != 5:
        return spec
    dh = -(-spec.shape[4] // LANES) * LANES
    return jax.ShapeDtypeStruct(spec.shape[:4] + (dh,), spec.dtype)


def _as_list(engines):
    if isinstance(engines, dict):
        return list(engines.values())
    if isinstance(engines, (list, tuple)):
        return list(engines)
    return [engines]
