"""Cache backends for the slot-pool inference engine.

The CoPRIS inference engine keeps a *fixed pool* of ``N'`` slots — the
TPU-native analogue of vLLM's continuous batching (see DESIGN.md §3). Every
model family's per-request state (KV cache, RWKV wkv state, SSM/conv state,
token-shift carries) lives batched inside one cache pytree:

* ``cache["prefix"][i]`` leaves have the slot/batch axis at **axis 0**
* ``cache["body"]`` leaves are layer-stacked: slot/batch axis at **axis 1**

This module owns the **CacheBackend API**: the engine never touches cache
layout directly, it goes through a backend object. Two implementations:

* :class:`DenseCache` — one dense ``max_len`` KV region per slot (the
  original layout; bit-identical to the historical free functions, which
  survive below as deprecation shims).
* :class:`PagedCache` — vLLM-style paged KV: attention K/V leaves are stored
  as a physical page pool ``(num_pages, page_size, kv, hd)`` shared by all
  slots, with a host-side block table ``(pool, max_pages)`` mapping each
  slot's logical pages to physical pages. Pages carry refcounts, so a GRPO
  group's G samples can *share* their common prompt prefix (one prefill,
  copy-on-write on first divergent write), and admission can be gated on
  free **pages** instead of free slots.

Leaf classification: attention K/V leaves are exactly the dict keys ``"k"``
and ``"v"`` inside block caches (see ``transformer.init_block_cache``); every
other leaf (``mk``/``mv`` media K/V, ``wkv``/``tm_prev``/``cm_prev`` RWKV
state, ``ssm``/``conv``) has no length axis and stays per-slot in both
backends.
"""
from __future__ import annotations

import functools
import warnings
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.tree_util import DictKey, tree_map_with_path


def _is_kv(path) -> bool:
    last = path[-1]
    return isinstance(last, DictKey) and last.key in ("k", "v")


def _map_with_axis(fn, cache, *rest):
    """tree-map over a stack cache with the batch-axis per subtree."""
    prefix = jax.tree.map(functools.partial(fn, 0), cache["prefix"],
                          *[r["prefix"] for r in rest])
    body = jax.tree.map(functools.partial(fn, 1), cache["body"],
                        *[r["body"] for r in rest])
    return {"prefix": prefix, "body": body}


def _map_kv_aware(fn, cache, *rest):
    """Like :func:`_map_with_axis` but ``fn(axis, is_kv, leaf, *rest)`` also
    learns whether the leaf is an attention K/V leaf (paged candidates)."""
    prefix = tree_map_with_path(
        lambda p, x, *r: fn(0, _is_kv(p), x, *r), cache["prefix"],
        *[r["prefix"] for r in rest])
    body = tree_map_with_path(
        lambda p, x, *r: fn(1, _is_kv(p), x, *r), cache["body"],
        *[r["body"] for r in rest])
    return {"prefix": prefix, "body": body}


# ---------------------------------------------------------------------------
# dense implementations (the original jitted free functions)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, donate_argnums=(0,))
def _insert_slots(cache, new_cache, slot_ids):
    """Scatter ``new_cache`` (batch = len(slot_ids)) into ``cache`` at
    ``slot_ids`` along the slot axis. Out-of-bounds ids are DROPPED
    (mode="drop"): padding rows carry slot_id == pool, so one compiled
    scatter serves any number of freed slots without touching live state."""
    def upd(axis, big, small):
        if axis == 0:
            return big.at[slot_ids].set(small.astype(big.dtype), mode="drop")
        return big.at[:, slot_ids].set(small.astype(big.dtype),  # (R, n, ...)
                                       mode="drop")
    return _map_with_axis(upd, cache, new_cache)


def dense_insert_rows(cache, scratch, slot_ids, row_map):
    """Prefill insert, traced inside the engine's jitted prefill: ``scratch``
    holds one row per *unique* prefill (length axes sized to the prompt
    bucket S, not max_len), ``row_map`` maps each output sample/slot to its
    scratch row. Only the first S positions of each length axis are written;
    positions beyond S keep stale data from the slot's previous occupant,
    which is safe because decode writes position c before any step attends
    it (write-before-read along the length axis, masked by cache_len).
    Out-of-bounds slot ids are dropped."""
    def upd(axis, big, small):
        small = jnp.take(small, row_map, axis=axis, mode="clip")
        sl = [slice(None)] * big.ndim
        sl[axis] = slot_ids
        for d in range(big.ndim):
            if d != axis and big.shape[d] != small.shape[d]:
                sl[d] = slice(0, small.shape[d])   # length axis prefix
        return big.at[tuple(sl)].set(small.astype(big.dtype), mode="drop")
    return _map_with_axis(upd, cache, scratch)


@functools.partial(jax.jit, donate_argnums=(0,))
def _insert_slots_prefix(cache, new_cache, slot_ids):
    # identity row_map: one scratch row per slot (the historical contract)
    row_map = jnp.arange(slot_ids.shape[0])
    return dense_insert_rows(cache, new_cache, slot_ids, row_map)


@jax.jit
def _extract_slots(cache, slot_ids):
    """Gather a per-slot snapshot (batch = len(slot_ids))."""
    def take(axis, big):
        return jnp.take(big, slot_ids, axis=axis)
    return _map_with_axis(take, cache)


@functools.partial(jax.jit, donate_argnums=(0,))
def _zero_slots(cache, slot_ids):
    def z(axis, big):
        if axis == 0:
            return big.at[slot_ids].set(0)
        return big.at[:, slot_ids].set(0)
    return _map_with_axis(z, cache)


# ---------------------------------------------------------------------------
# paged implementations (traced inside engine jits or jitted standalone)
# ---------------------------------------------------------------------------


def _flat(big, axis):
    """Collapse (NP, ps) page axes of a K/V pool leaf into one flat position
    axis. axis 0: (NP, ps, kv, hd) -> (NP*ps, kv, hd); axis 1 (layer-stacked):
    (R, NP, ps, kv, hd) -> (R, NP*ps, kv, hd)."""
    if axis == 0:
        return big.reshape(big.shape[0] * big.shape[1], *big.shape[2:])
    return big.reshape(big.shape[0], big.shape[1] * big.shape[2],
                       *big.shape[3:])


def paged_insert_rows(cache, scratch, slot_ids, row_map, flat_pos):
    """Paged prefill insert (traced inside the engine's jitted prefill).

    K/V leaves: ``flat_pos (nrows, S)`` holds, per scratch row, the physical
    flat position (page * page_size + offset) of each prompt token — the
    host computed it from the block table; unmapped/padding positions carry
    an out-of-bounds sentinel and are dropped. Per-slot leaves scatter by
    ``slot_ids`` after gathering ``row_map`` (so prefix-shared samples get
    their own copy of the non-KV state). The scratch is a dense cache:
    its K/V rows (..., S, kv*hd) split into the pool's (kv, hd)."""
    def upd(axis, is_kv, big, small):
        if is_kv:
            small = small.reshape(small.shape[:axis + 2]
                                  + big.shape[axis + 2:])
            f = _flat(big, axis)
            if axis == 0:
                f = f.at[flat_pos].set(small.astype(big.dtype), mode="drop")
            else:
                f = f.at[:, flat_pos].set(small.astype(big.dtype),
                                          mode="drop")
            return f.reshape(big.shape)
        small = jnp.take(small, row_map, axis=axis, mode="clip")
        if axis == 0:
            return big.at[slot_ids].set(small.astype(big.dtype), mode="drop")
        return big.at[:, slot_ids].set(small.astype(big.dtype), mode="drop")
    return _map_kv_aware(upd, cache, scratch)


@functools.partial(jax.jit, donate_argnums=(0,))
def _paged_copy_pages(cache, src_ids, dst_ids):
    """Copy physical pages src -> dst in every K/V pool leaf (COW). Padding
    pairs carry an OOB dst and are dropped."""
    def upd(axis, is_kv, big):
        if not is_kv:
            return big
        src = jnp.take(big, src_ids, axis=axis, mode="clip")
        if axis == 0:
            return big.at[dst_ids].set(src, mode="drop")
        return big.at[:, dst_ids].set(src, mode="drop")
    return _map_kv_aware(upd, cache)


@jax.jit
def _paged_extract(cache, slot_ids, page_ids):
    """Page-list snapshot: K/V leaves gather whole pages (page_ids, padded
    with any valid id), per-slot leaves gather the slot row."""
    def take(axis, is_kv, big):
        ids = page_ids if is_kv else slot_ids
        return jnp.take(big, ids, axis=axis, mode="clip")
    return _map_kv_aware(take, cache)


@functools.partial(jax.jit, donate_argnums=(0,))
def _paged_insert_snapshot(cache, snap, slot_ids, page_ids):
    """Inverse of :func:`_paged_extract`: scatter page contents into freshly
    allocated physical pages (OOB padding page ids dropped) and the per-slot
    state into the slot row."""
    def upd(axis, is_kv, big, small):
        ids = page_ids if is_kv else slot_ids
        if axis == 0:
            return big.at[ids].set(small.astype(big.dtype), mode="drop")
        return big.at[:, ids].set(small.astype(big.dtype), mode="drop")
    return _map_kv_aware(upd, cache, snap)


# ---------------------------------------------------------------------------
# CacheBackend API
# ---------------------------------------------------------------------------


class CacheBackend:
    """Backend-agnostic slot-cache interface used by the rollout engine.

    ``cache`` is the device pytree handed to the model's prefill/decode
    functions; the engine's jitted steps donate it and the engine writes the
    returned buffer back (``backend.cache = new_cache``). Host-side page
    bookkeeping (block tables, refcounts, free lists) lives on the backend.
    """

    is_paged: bool = False
    supports_sharing: bool = False
    cache: object = None

    # --- capacity / admission ---------------------------------------
    def free_page_count(self) -> Optional[int]:
        """Free physical pages (None = not page-limited)."""
        return None

    def admission_pages(self, total_len: int, *, lookahead: int = 0,
                        shared: bool = False) -> int:
        """Worst-case pages a new admission of ``total_len`` prompt+response
        tokens needs through its first ``lookahead`` decode steps."""
        return 0

    def snapshot_pages(self, snap) -> int:
        """Pages needed to restore a kv_snapshot blob."""
        return 0

    # --- slot lifecycle ----------------------------------------------
    def alloc_slot_prefix(self, slot: int, length: int):
        """Map pages covering [0, length) for ``slot``; returns the flat
        physical positions (np.int32 (length,)) for the prefill scatter, or
        None for backends that don't page."""
        return None

    def share_slots(self, src_slot: int, dst_slot: int, length: int):
        raise NotImplementedError

    def grow(self, slot: int, upto: int, write_from: int,
             copies: List[Tuple[int, int]]) -> bool:
        """Ensure positions [0, upto) are mapped and pages in the write range
        [write_from, upto) are exclusively owned (COW). Appends (src, dst)
        page copies to ``copies``; returns False on page exhaustion."""
        return True

    def apply_copies(self, copies: List[Tuple[int, int]]):
        pass

    def free_slot(self, slot: int):
        pass

    # --- snapshots (kv_snapshot resume strategy) ---------------------
    def extract_snapshot(self, slot: int):
        raise NotImplementedError

    def insert_snapshot(self, snap, slot: int):
        raise NotImplementedError

    # --- decode-time view --------------------------------------------
    def block_table_device(self):
        """Device block table for the paged decode path (dummy for dense —
        the engine passes it unconditionally so one jit signature serves
        both backends)."""
        return jnp.zeros((1, 1), jnp.int32)


class DenseCache(CacheBackend):
    """One dense ``max_len`` KV region per slot — the original layout.

    Bit-identical to the historical free-function path (pinned by
    tests/test_kv_snapshot.py and tests/test_rollout_chunked.py)."""

    is_paged = False
    supports_sharing = False

    def __init__(self, model_cfg, pool: int, max_len: int, dtype=None):
        from repro.models import model as M
        self.pool = pool
        self.max_len = max_len
        self.cache = M.init_cache(model_cfg, pool, max_len, dtype)

    # snapshots: the per-slot cache slice, as before
    def extract_snapshot(self, slot: int):
        return _extract_slots(self.cache, jnp.asarray([slot]))

    def insert_snapshot(self, snap, slot: int):
        self.cache = _insert_slots(self.cache, snap, jnp.asarray([slot]))
        return True


class PageExhausted(RuntimeError):
    """Raised when the physical page pool cannot satisfy a request that the
    engine's admission gate should have prevented."""


class PagedCache(CacheBackend):
    """Paged KV cache: physical page pool + per-slot block tables.

    * K/V leaves: ``(num_pages, page_size, kv, hd)`` (layer-stacked body
      leaves carry a leading repeats axis). One *logical* page index maps to
      the same physical page row in every layer's pool, so the allocator is
      layer-agnostic.
    * ``block_table`` (host, np.int32 ``(pool, max_pages)``): physical page
      per logical page; unmapped entries hold the sentinel ``num_pages``,
      which flat-scatters/gathers out of bounds and is dropped/zero-filled.
    * ``refcount`` per physical page enables prefix sharing: a group's G
      slots point at the same prompt pages; the first write into a shared
      page triggers copy-on-write (see :meth:`grow`).
    """

    is_paged = True
    supports_sharing = True

    _SNAP_BUCKET = 4      # snapshot page-id padding bucket (bounds recompiles)

    def __init__(self, model_cfg, pool: int, max_len: int, *,
                 page_size: int, num_pages: int = 0, dtype=None):
        from repro.models import model as M
        if max_len % page_size != 0:
            raise ValueError(
                f"kv_page_size={page_size} must divide the engine max_len="
                f"{max_len} (max_len is rounded to the 64-token prefill "
                "bucket, so any power of two <= 64 works)")
        self.pool = pool
        self.max_len = max_len
        self.page_size = page_size
        self.max_pages = max_len // page_size
        self.num_pages = num_pages or pool * self.max_pages
        if self.num_pages < self.max_pages:
            raise ValueError(
                f"kv_num_pages={self.num_pages} cannot hold even one full-"
                f"length trajectory ({self.max_pages} pages of "
                f"{page_size} tokens)")
        self.cache = M.init_paged_cache(model_cfg, pool, max_len,
                                        page_size=page_size,
                                        num_pages=self.num_pages, dtype=dtype)
        self.block_table = np.full((pool, self.max_pages), self.num_pages,
                                   np.int32)
        self.refcount = np.zeros(self.num_pages, np.int32)
        # LIFO free list, lowest ids first — allocation order is a pure
        # function of the (deterministic) host replay, so paged runs are
        # reproducible
        self._free = list(range(self.num_pages - 1, -1, -1))
        self.pages_allocated = 0
        self.cow_copies = 0

    # --- allocator ----------------------------------------------------
    def free_page_count(self) -> int:
        return len(self._free)

    def _pages_for(self, n: int) -> int:
        return -(-n // self.page_size)

    def admission_pages(self, total_len: int, *, lookahead: int = 0,
                        shared: bool = False) -> int:
        """Conservative page bill for admitting a trajectory whose prompt+
        response is ``total_len`` tokens, through ``lookahead`` decode steps.
        A prefix-shared group member only pays for the pages past the shared
        full prompt pages (its partial-page COW + growth)."""
        end = min(total_len + 1 + lookahead, self.max_len)
        need = self._pages_for(end)
        if shared:
            need -= total_len // self.page_size   # full pages ride for free
        return max(need, 0)

    def snapshot_pages(self, snap) -> int:
        return snap["page_count"]

    def _alloc(self) -> int:
        if not self._free:
            raise PageExhausted("physical KV page pool exhausted")
        p = self._free.pop()
        self.refcount[p] = 1
        self.pages_allocated += 1
        return p

    def _decref(self, p: int):
        self.refcount[p] -= 1
        if self.refcount[p] == 0:
            self._free.append(p)

    # --- slot lifecycle ----------------------------------------------
    def _mapped_pages(self, slot: int) -> int:
        row = self.block_table[slot]
        n = int(np.argmax(row == self.num_pages))
        if n == 0 and row[0] != self.num_pages:
            return self.max_pages
        return n

    def alloc_slot_prefix(self, slot: int, length: int) -> np.ndarray:
        need = self._pages_for(length)
        if len(self._free) < need:
            raise PageExhausted(
                f"prefill of {length} tokens needs {need} pages, "
                f"{len(self._free)} free — the admission gate must prevent "
                "this")
        row = self.block_table[slot]
        assert (row == self.num_pages).all(), \
            "alloc_slot_prefix on a slot with mapped pages (free_slot first)"
        for pg in range(need):
            row[pg] = self._alloc()
        return self.flat_positions(slot, 0, length)

    def flat_positions(self, slot: int, start: int, end: int) -> np.ndarray:
        """Physical flat positions for logical positions [start, end);
        unmapped pages yield the OOB sentinel (num_pages * page_size)."""
        pos = np.arange(start, end)
        phys = self.block_table[slot, pos // self.page_size].astype(np.int64)
        return (phys * self.page_size + pos % self.page_size).astype(np.int32)

    def share_slots(self, src_slot: int, dst_slot: int, length: int):
        """Point ``dst_slot``'s table at ``src_slot``'s pages for the first
        ``length`` tokens (incref). Includes the trailing partial page —
        exclusivity is restored lazily by COW on first write."""
        npg = self._pages_for(length)
        src = self.block_table[src_slot, :npg]
        assert (src < self.num_pages).all(), "sharing unmapped pages"
        dst_row = self.block_table[dst_slot]
        assert (dst_row == self.num_pages).all(), \
            "share_slots target must be empty"
        dst_row[:npg] = src
        for p in src:
            self.refcount[p] += 1

    def grow(self, slot: int, upto: int, write_from: int,
             copies: List[Tuple[int, int]]) -> bool:
        row = self.block_table[slot]
        first_write_pg = write_from // self.page_size
        need_pgs = self._pages_for(upto)
        # fail fast without mutating: count pages this growth will consume
        want = 0
        for pg in range(first_write_pg, need_pgs):
            p = row[pg]
            if p == self.num_pages or self.refcount[p] > 1:
                want += 1
        if want > len(self._free):
            return False
        for pg in range(first_write_pg, need_pgs):
            p = row[pg]
            if p == self.num_pages:
                row[pg] = self._alloc()
            elif self.refcount[p] > 1:                 # copy-on-write
                fresh = self._alloc()
                copies.append((int(p), fresh))
                self._decref(int(p))
                row[pg] = fresh
                self.cow_copies += 1
        return True

    def apply_copies(self, copies: List[Tuple[int, int]]):
        if not copies:
            return
        n = 1 << (len(copies) - 1).bit_length()
        src = np.zeros(n, np.int32)
        dst = np.full(n, self.num_pages, np.int32)     # padding -> dropped
        for i, (s, d) in enumerate(copies):
            src[i], dst[i] = s, d
        self.cache = _paged_copy_pages(self.cache, jnp.asarray(src),
                                       jnp.asarray(dst))

    def free_slot(self, slot: int):
        row = self.block_table[slot]
        for pg in range(self.max_pages):
            if row[pg] == self.num_pages:
                break
            self._decref(int(row[pg]))
            row[pg] = self.num_pages

    # --- snapshots ----------------------------------------------------
    def extract_snapshot(self, slot: int):
        npg = self._mapped_pages(slot)
        pad = -(-max(npg, 1) // self._SNAP_BUCKET) * self._SNAP_BUCKET
        ids = np.zeros(pad, np.int32)
        ids[:npg] = self.block_table[slot, :npg]
        tree = _paged_extract(self.cache, jnp.asarray([slot]),
                              jnp.asarray(ids))
        return {"tree": tree, "page_count": npg, "pad": pad}

    def insert_snapshot(self, snap, slot: int):
        npg = snap["page_count"]
        if len(self._free) < npg:
            raise PageExhausted(
                f"snapshot restore needs {npg} pages, {len(self._free)} free")
        row = self.block_table[slot]
        assert (row == self.num_pages).all(), \
            "insert_snapshot target must be empty"
        ids = np.full(snap["pad"], self.num_pages, np.int32)   # pad dropped
        for pg in range(npg):
            row[pg] = self._alloc()
            ids[pg] = row[pg]
        self.cache = _paged_insert_snapshot(self.cache, snap["tree"],
                                            jnp.asarray([slot]),
                                            jnp.asarray(ids))
        return True

    # --- decode-time view --------------------------------------------
    def block_table_device(self):
        return jnp.asarray(self.block_table)


def make_backend(name: str, model_cfg, pool: int, max_len: int, *,
                 page_size: int = 16, num_pages: int = 0,
                 dtype=None) -> CacheBackend:
    if name == "dense":
        return DenseCache(model_cfg, pool, max_len, dtype)
    if name == "paged":
        return PagedCache(model_cfg, pool, max_len, page_size=page_size,
                          num_pages=num_pages, dtype=dtype)
    raise ValueError(f"unknown kv backend {name!r} (dense|paged)")


# ---------------------------------------------------------------------------
# deprecated free-function API (thin shims over the dense implementations)
# ---------------------------------------------------------------------------


def _deprecated(name: str):
    warnings.warn(
        f"repro.sampling.kv_cache.{name} is deprecated: use the CacheBackend "
        "API (DenseCache / PagedCache methods) instead — the free functions "
        "only understand the dense slot layout",
        DeprecationWarning, stacklevel=3)


def insert_slots(cache, new_cache, slot_ids):
    """DEPRECATED — :class:`DenseCache` method equivalent of the original
    ``insert_slots`` (scatter full-length per-slot state, OOB ids dropped)."""
    _deprecated("insert_slots")
    return _insert_slots(cache, new_cache, slot_ids)


def insert_slots_prefix(cache, new_cache, slot_ids):
    """DEPRECATED — dense prefill insert (length-prefix scatter)."""
    _deprecated("insert_slots_prefix")
    return _insert_slots_prefix(cache, new_cache, slot_ids)


def extract_slots(cache, slot_ids):
    """DEPRECATED — dense per-slot snapshot gather."""
    _deprecated("extract_slots")
    return _extract_slots(cache, slot_ids)


def zero_slots(cache, slot_ids):
    """DEPRECATED — dense slot reset."""
    _deprecated("zero_slots")
    return _zero_slots(cache, slot_ids)
