"""Paged KV cache for the serving engine.

Two halves, split by where they run:

* :class:`PageAllocator` — pure-Python freelist bookkeeping.  Physical
  page 0 is reserved as a **scratch page**: inactive batch slots carry an
  all-zero page table, so their (masked, never-read) decode writes land on
  the scratch page instead of clobbering a tenant's cache.  The allocator
  is the target of the freelist property tests in
  ``tests/test_serving_scheduler.py`` (never double-allocates, never
  leaks).
* jit-pure pool ops — :func:`gather_pages` materializes each request's
  logical cache ``(L, B, T, Hkv, hd)`` from its page table, and
  :func:`scatter_token` writes the one new KV vector per request back to
  its physical page.  Both are shape-static so they live inside the
  per-bucket decode executable.

>>> al = PageAllocator(6)
>>> al.n_free                      # page 0 is reserved scratch
5
>>> al.alloc("r1", 2)
[1, 2]
>>> al.alloc("r2", 2)
[3, 4]
>>> al.can_alloc(2)
False
>>> al.free("r1")
2
>>> al.alloc("r3", 3)              # freed pages are reused, lowest-first
[1, 2, 5]
>>> al.check()
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.utils import scope

Array = jax.Array

SCRATCH_PAGE = 0


class PageAllocator:
    """Freelist over ``n_pages`` physical KV pages (page 0 reserved).

    Deterministic: pages are handed out lowest-index-first, so a fixed
    request order yields a fixed page-table assignment (the scheduler
    determinism property test relies on this).
    """

    def __init__(self, n_pages: int):
        if n_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is scratch)")
        self.n_pages = n_pages
        self._free = list(range(1, n_pages))
        self._owned: dict[object, list[int]] = {}

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_usable(self) -> int:
        """Max pages a single owner can ever hold."""
        return self.n_pages - 1

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def alloc(self, owner, n: int) -> list[int]:
        if owner in self._owned:
            raise ValueError(f"owner {owner!r} already holds pages")
        if n > len(self._free):
            raise ValueError(
                f"out of KV pages: want {n}, have {len(self._free)} free")
        pages = self._free[:n]
        self._free = self._free[n:]
        self._owned[owner] = pages
        return list(pages)

    def owned(self, owner) -> list[int]:
        return list(self._owned[owner])

    def free(self, owner) -> int:
        pages = self._owned.pop(owner)
        self._free.extend(pages)
        self._free.sort()
        return len(pages)

    def check(self) -> None:
        """Invariants: no page double-owned, none both free and owned,
        every page accounted for.  Raises AssertionError on violation."""
        held: list[int] = []
        for pages in self._owned.values():
            held.extend(pages)
        assert len(held) == len(set(held)), "page double-allocated"
        assert not (set(held) & set(self._free)), "page both free and owned"
        assert SCRATCH_PAGE not in held, "scratch page was allocated"
        assert len(held) + len(self._free) == self.n_pages - 1, "page leaked"


def pages_needed(n_tokens: int, page_size: int) -> int:
    return -(-n_tokens // page_size)


def init_pools(n_layers: int, n_pages: int, page_size: int, n_kv_heads: int,
               head_dim: int, dtype) -> tuple[Array, Array]:
    """Zeroed K/V page pools ``(L, n_pages, P, Hkv, hd)``."""
    shape = (n_layers, n_pages, page_size, n_kv_heads, head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def gather_pages(pool: Array, page_tables: Array) -> Array:
    """Materialize per-request contiguous caches from the page pool.

    pool (L, n_pages, P, Hkv, hd); page_tables (B, maxp) int32 ->
    (L, B, maxp*P, Hkv, hd).  Stale/unwritten positions carry whatever the
    pool holds; the decode mask (``kpos <= idx``) zeroes their softmax
    weight exactly, which is what makes batched rows bit-identical to a
    sequential replay."""
    L = pool.shape[0]
    B, maxp = page_tables.shape
    with scope("kv_cache"):
        g = pool[:, page_tables]                 # (L, B, maxp, P, Hkv, hd)
        return g.reshape(L, B, maxp * pool.shape[2], *pool.shape[3:])


def scatter_token(pool: Array, new: Array, page_tables: Array,
                  lengths: Array) -> Array:
    """Write one new KV vector per request into its physical page.

    pool (L, n_pages, P, Hkv, hd); new (L, B, Hkv, hd); page_tables
    (B, maxp); lengths (B,) = logical position being written.  Inactive
    slots (all-zero page table, length 0) collide on the scratch page by
    construction — harmless, it is never mapped."""
    P = pool.shape[2]
    with scope("kv_cache"):
        logical = lengths // P                    # (B,) page slot in table
        phys = jnp.take_along_axis(page_tables, logical[:, None],
                                   axis=1)[:, 0]
        off = lengths % P
        return pool.at[:, phys, off].set(new)
