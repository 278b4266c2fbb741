"""Paged KV cache: block-table-backed page pools shared across decode slots.

Dense serving allocates ``n_slots * max_len`` KV rows per layer up front, so
memory scales with the *worst case* of every slot simultaneously.  Here the
full-attention KV caches become fixed-size **page pools** shared by all
slots: a request reserves exactly ``ceil((prompt + max_new + 1) / page_size)``
pages at admission and returns them on completion, so hundreds of concurrent
streams fit in the memory a handful of dense slots would take — occupancy is
ragged *and* exact.

Layout
------
Per full-attention layer the pool leaves are ``k``/``v``:
``(n_pages, page_size, H * D)`` (a token's heads are one row, so a page is
one compact block of memory) and ``pos``: ``(n_pages, page_size)`` (−1 =
empty).  A device-resident **block table** ``(n_slots, max_pages)`` maps each
slot's logical pages to physical ones; unallocated entries hold ``n_pages``
(one past the pool), which JAX scatter drops and ``jnp.take(mode="fill")``
masks — no branching anywhere on the device path.

Only full-attention layers page.  SWA rings are O(window), MLA latents are
~7% of expanded KV, cross caches are O(enc_len) and recurrent states are
O(1) per slot; those stay slot-dense ("hybrid paging"), and the cache tree
mixes both kinds transparently.

Correctness invariants (each one guards a real aliasing bug):

* newly allocated pages get their pool ``pos`` reset to −1 *before* use —
  a recycled page's stale positions could otherwise unmask another
  request's keys;
* a freed slot's table row is cleared to ``n_pages`` immediately, so decode
  ticks for dead slots scatter out of bounds instead of into recycled pages;
* the dense per-slot leaves (rings/latents/states) are reset to their
  ``init_cache`` values in the same fused jit at allocation time.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import LanguageModel
from repro.models.model import _is_spec_leaf


def _pages_dim(spec_leaf) -> int | None:
    axes = spec_leaf[1]
    return axes.index("pages") if "pages" in axes else None


def _batch_dim(spec_leaf) -> int:
    return spec_leaf[1].index("batch")


@dataclasses.dataclass
class PageStats:
    n_pages: int
    page_size: int
    pages_in_use: int
    pages_free: int
    tokens_reserved: int

    @property
    def utilization(self) -> float:
        return self.pages_in_use / max(self.n_pages, 1)


class PagedKVCache:
    """Host-side allocator + device-side gather/scatter for the hybrid cache.

    ``max_pages`` bounds one slot's capacity: the dense *view* used during
    chunked prefill is ``max_pages * page_size`` tokens long, and position
    ``p`` of a slot always lives at page ``p // page_size`` of its table row
    — the gathered view is literally a dense cache, so ``prefill_chunk``
    needs no paged-awareness at all.
    """

    def __init__(self, model: LanguageModel, n_slots: int, n_pages: int,
                 page_size: int, max_pages: int, enc_len: int = 0,
                 dtype=jnp.bfloat16):
        self.model = model
        self.n_slots = n_slots
        self.n_pages = n_pages
        self.page_size = page_size
        self.max_pages = max_pages
        self.view_len = max_pages * page_size
        pages = (n_pages, page_size)
        self.specs = model.cache_specs(n_slots, self.view_len, enc_len=enc_len,
                                       dtype=dtype, pages=pages)
        self.view_specs = model.cache_specs(1, self.view_len, enc_len=enc_len,
                                            dtype=dtype, pages=None)
        self.cache = model.init_cache(n_slots, self.view_len, enc_len=enc_len,
                                      dtype=dtype, pages=pages)
        self.table = jnp.full((n_slots, max_pages), n_pages, jnp.int32)
        self._free = list(range(n_pages - 1, -1, -1))  # pop() -> page 0 first
        self._slot_pages: list[list[int]] = [[] for _ in range(n_slots)]

        self._gather = jax.jit(self._gather_impl)
        # scatter/prepare rebuild the whole cache tree from the old one plus
        # a small update; donating the old tree makes them in-place writes
        self._scatter = jax.jit(self._scatter_impl, donate_argnums=(0,))
        self._prepare = jax.jit(self._prepare_impl, donate_argnums=(0,))

    # ------------------------------------------------------------ allocation
    def pages_needed(self, n_tokens: int) -> int:
        return math.ceil(n_tokens / self.page_size)

    def can_alloc(self, n_tokens: int) -> bool:
        need = self.pages_needed(n_tokens)
        return need <= self.max_pages and need <= len(self._free)

    def alloc(self, slot: int, n_tokens: int) -> bool:
        """Reserve capacity for ``n_tokens`` in ``slot`` and reset its state
        (pool positions of the new pages + the dense per-slot leaves)."""
        if self._slot_pages[slot]:
            raise ValueError(f"slot {slot} already allocated")
        need = self.pages_needed(n_tokens)
        if need > self.max_pages or need > len(self._free):
            return False
        pages = [self._free.pop() for _ in range(need)]
        self._slot_pages[slot] = pages
        row = pages + [self.n_pages] * (self.max_pages - need)
        row = jnp.asarray(row, jnp.int32)
        self.table = self.table.at[slot].set(row)
        self.cache = self._prepare(self.cache, row,
                                   jnp.asarray(slot, jnp.int32))
        return True

    def free(self, slot: int) -> None:
        self._free.extend(reversed(self._slot_pages[slot]))
        self._slot_pages[slot] = []
        self.table = self.table.at[slot].set(self.n_pages)

    def stats(self) -> PageStats:
        used = sum(len(p) for p in self._slot_pages)
        return PageStats(
            n_pages=self.n_pages, page_size=self.page_size,
            pages_in_use=used, pages_free=len(self._free),
            tokens_reserved=used * self.page_size)

    # ------------------------------------------------------- device gather/scatter
    def gather_slot(self, slot: int):
        """Dense (B=1, view_len, ...) cache view of one slot — the exact tree
        ``init_cache(1, view_len)`` would produce, for ``prefill_chunk``."""
        return self._gather(self.cache, self.table[slot][None],
                            jnp.asarray([slot], jnp.int32))

    def scatter_slot(self, slot: int, view: Any) -> None:
        self.cache = self._scatter(self.cache, view, self.table[slot][None],
                                   jnp.asarray([slot], jnp.int32))

    def _gather_impl(self, cache, rows, slots):
        """Dense (G, view_len, ...) view of G slots at once (``rows``:
        ``(G, max_pages)``, ``slots``: ``(G,)``).  Padded group members use
        ``slots == n_slots`` / ``rows == n_pages``: their view fills with
        init values and their scatter-back is dropped, so a fixed group size
        costs one jit trace per chunk length."""
        G = slots.shape[0]

        def g(leaf, spec, view):
            fill = -1 if leaf.dtype == jnp.int32 else 0
            pdim = _pages_dim(spec)
            if pdim is None:
                bdim = _batch_dim(spec)
                return jnp.take(leaf, slots, axis=bdim, mode="fill",
                                fill_value=fill)
            v = jnp.take(leaf, rows.reshape(-1), axis=pdim, mode="fill",
                         fill_value=fill)
            # a pool's token row (H * D) opens into the view's (H, D)
            shp = (v.shape[:pdim] + (G, self.view_len)
                   + view[0].shape[pdim + 2:])
            return v.reshape(shp)

        return jax.tree.map(g, cache, self.specs, self.view_specs,
                            is_leaf=_is_spec_leaf)

    def _scatter_impl(self, cache, view, rows, slots):
        G = slots.shape[0]

        def s(leaf, v, spec):
            pdim = _pages_dim(spec)
            if pdim is None:
                bdim = _batch_dim(spec)
                # padded entries == n_slots: out of bounds -> dropped
                idx = (slice(None),) * bdim + (slots,)
                return leaf.at[idx].set(v.astype(leaf.dtype))
            v = v.reshape(v.shape[:pdim]
                          + (G * self.max_pages, self.page_size)
                          + leaf.shape[pdim + 2:])
            idx = (slice(None),) * pdim + (rows.reshape(-1),)
            # unallocated row entries == n_pages: out of bounds -> dropped
            return leaf.at[idx].set(v.astype(leaf.dtype))

        return jax.tree.map(s, cache, view, self.specs, is_leaf=_is_spec_leaf)

    def _prepare_impl(self, cache, row, slot):
        """Fused allocation-time reset: pool ``pos`` of the new pages -> −1
        (kills stale positions on recycled pages) and the slot's dense leaves
        back to their init values."""
        def r(leaf, spec):
            pdim = _pages_dim(spec)
            if pdim is not None:
                if leaf.dtype != jnp.int32:
                    return leaf  # k/v garbage is masked by pos == -1
                idx = (slice(None),) * pdim + (row,)
                return leaf.at[idx].set(-1)
            bdim = _batch_dim(spec)
            idx = (slice(None),) * bdim + (slot,)
            fill = -1 if leaf.dtype == jnp.int32 else 0
            return leaf.at[idx].set(fill)

        return jax.tree.map(r, cache, self.specs, is_leaf=_is_spec_leaf)


@functools.cache
def chunk_ladder(chunk_max: int) -> tuple[int, ...]:
    """Power-of-two chunk sizes {1, 2, 4, ..., chunk_max} — every prompt
    length decomposes exactly (greedy largest-first), so chunked prefill
    needs zero padding and the jit trace count is bounded by the ladder."""
    if chunk_max < 1 or chunk_max & (chunk_max - 1):
        raise ValueError(f"chunk_max must be a power of two, got {chunk_max}")
    out = []
    c = chunk_max
    while c >= 1:
        out.append(c)
        c //= 2
    return tuple(out)


def decompose(n: int, chunk_max: int) -> list[int]:
    """Exact chunk decomposition of ``n`` tokens, largest chunks first."""
    out = []
    for c in chunk_ladder(chunk_max):
        while n >= c:
            out.append(c)
            n -= c
    return out
