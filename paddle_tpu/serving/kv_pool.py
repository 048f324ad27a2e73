"""Page-pool KV cache allocator (PagedAttention / vLLM, SOSP '23).

Instead of one dense (B, H, S_max, D) cache per request — which reserves
``max_seq_len`` worth of HBM for every slot whether used or not — the KV
cache is a POOL of fixed-size pages shared by all slots; each sequence
owns just enough pages for its current length, recorded in a per-slot
block table.  Freed pages return to the pool the moment a request
finishes, which is what lets the continuous-batching engine admit a new
request into the slot without draining the batch.

Device layout (one array per side, all layers stacked so the decode jit
threads ONE buffer pair):

  * float pages: ``(L, P, H, page_size, D)`` in the model dtype;
  * int8 pages: the same shape in int8 + an fp32 scale pool
    ``(L, P, H, page_size, 1)`` — one scale per (layer, page-position,
    head), the IDENTICAL per-token quantization layout the dense int8 KV
    cache uses (models/generation.py), so the quantization decisions
    carry over to pages unchanged.

Page 0 is RESERVED as the null page: the allocator never hands it out,
block-table padding points at it, and the engine's page writes route
masked/inactive lanes to it with nothing to put (it stays zero) — so no
gather in the paged-attention kernel can ever index out of the pool, and
no active page can be corrupted by an inactive lane.

Sharing (r09): every page carries a REFCOUNT of live requests holding it.
``alloc`` leases fresh pages at refcount 1; a request matching a cached
prefix ``retain``\\ s the shared pages (+1 each); ``free`` drops one
reference per page and only a page at refcount 0 actually leaves
circulation — back to the free list, unless the pool's
:class:`~paddle_tpu.serving.prefix_cache.PrefixIndex` still names it, in
which case it parks as *reclaimable* (its K/V stay matchable) until LRU
eviction hands it back under pressure.  The free list is mirrored by a
set so alloc/free/double-free checks are all O(1) per page.
"""

from __future__ import annotations

import math
from collections import Counter
from typing import Dict, List, Optional

import numpy as np

import jax.numpy as jnp

from .prefix_cache import PrefixIndex


class KVPool:
    """Fixed-size page pool + refcounted free-list allocator."""

    def __init__(self, num_layers: int, num_heads: int, head_dim: int,
                 num_pages: int, page_size: int, dtype=jnp.float32,
                 int8: bool = False, prefix_cache: bool = False,
                 num_kv_heads: Optional[int] = None,
                 kv_bits: Optional[int] = None,
                 window: Optional[int] = None):
        if num_pages < 2:
            raise ValueError("KVPool needs >= 2 pages (page 0 is the "
                             "reserved null page)")
        if kv_bits is None and int8:
            kv_bits = 8
        if kv_bits not in (None, 4, 8):
            raise ValueError(f"kv_bits must be None, 4 or 8, got {kv_bits}")
        kv_heads = num_kv_heads or num_heads
        if num_heads % kv_heads != 0:
            raise ValueError(f"num_heads={num_heads} not divisible by "
                             f"num_kv_heads={kv_heads}")
        if kv_bits == 4 and head_dim % 2 != 0:
            raise ValueError("kv_bits=4 needs an even head_dim "
                             "(two nibbles per byte)")
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = kv_heads
        self.head_dim = head_dim
        self.num_pages = num_pages
        self.page_size = page_size
        self.kv_bits = kv_bits
        self.int8 = kv_bits is not None
        self.window = window
        # int4 pages pack two nibbles per byte: stored last dim is D//2,
        # with the SAME per-(page-position, head) fp32 scale layout as int8
        store_d = head_dim // 2 if kv_bits == 4 else head_dim
        shape = (num_layers, num_pages, kv_heads, page_size, store_d)
        if kv_bits is not None:
            self.buffers: Dict[str, jnp.ndarray] = {
                "k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "ks": jnp.zeros(shape[:-1] + (1,), jnp.float32),
                "vs": jnp.zeros(shape[:-1] + (1,), jnp.float32),
            }
        else:
            self.buffers = {"k": jnp.zeros(shape, dtype),
                            "v": jnp.zeros(shape, dtype)}
        # LIFO free list over pages 1..P-1 (page 0 stays the null page),
        # mirrored by a set for O(1) membership
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._free_set = set(self._free)
        self.refcount: List[int] = [0] * num_pages
        self.prefix: Optional[PrefixIndex] = (
            PrefixIndex(page_size) if prefix_cache else None)
        # optional fault-injection plan (serving/faults.py): when set, a
        # scripted step makes every alloc fail — the exact observable a
        # real exhausted pool produces, so callers exercise their
        # backoff/preemption paths deterministically
        self.faults = None
        # allocator traffic counters (r11): the engine mirrors these into
        # its metrics registry each step — alloc-failure rate is the
        # earliest pressure signal an operator sees
        self.alloc_calls = 0
        self.alloc_failures = 0

    # -- allocation -------------------------------------------------------

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_cached(self) -> int:
        """Pages parked in the prefix index (reclaimable + shared)."""
        return len(self.prefix) if self.prefix is not None else 0

    @property
    def num_reclaimable(self) -> int:
        """Cached pages with no live reference — evictable on demand."""
        if self.prefix is None:
            return 0
        return sum(1 for p in self.prefix._by_page if self.refcount[p] == 0)

    @property
    def pages_in_use(self) -> int:
        """Pages referenced by at least one live request."""
        return sum(1 for r in self.refcount if r > 0)

    def pages_for(self, n_tokens: int) -> int:
        """Pages needed to hold ``n_tokens`` positions."""
        return max(1, math.ceil(n_tokens / self.page_size))

    def _check_page(self, p: int) -> None:
        if p <= 0 or p >= self.num_pages:
            raise ValueError(f"invalid page id {p}")

    def _push_free(self, p: int) -> None:
        self._free.append(p)
        self._free_set.add(p)

    def alloc(self, n_pages: int) -> Optional[List[int]]:
        """Lease ``n_pages`` fresh pages at refcount 1, or None when even
        LRU-evicting reclaimable cached pages can't satisfy the request
        (caller keeps the request queued — FCFS)."""
        if n_pages == 0:
            return []
        self.alloc_calls += 1
        if self.faults is not None and self.faults.fail_alloc():
            self.alloc_failures += 1
            return None
        if n_pages > len(self._free) and self.prefix is not None:
            for p in self.prefix.evict(n_pages - len(self._free),
                                       self.refcount):
                self._push_free(p)
        if n_pages > len(self._free):
            self.alloc_failures += 1
            return None
        got = []
        for _ in range(n_pages):
            p = self._free.pop()
            self._free_set.discard(p)
            self.refcount[p] = 1
            got.append(p)
        return got

    def retain(self, pages: List[int]) -> None:
        """Add one reference per page — a request adopting cached prefix
        pages (a reclaimable page at refcount 0 becomes live again)."""
        for p in pages:
            self._check_page(p)
            if p in self._free_set:
                raise ValueError(f"retain of free page {p}")
            self.refcount[p] += 1

    def free(self, pages: List[int]) -> None:
        """Drop one reference per page.  A page reaching refcount 0 goes
        back to the free list unless the prefix index still names it (it
        parks as reclaimable instead).  Over-freeing — more drops than
        references, including duplicates within one call — is a
        programming error worth failing loudly on, BEFORE any mutation."""
        for p, n in Counter(pages).items():
            self._check_page(p)
            if self.refcount[p] < n:
                raise ValueError(f"double free of page {p}")
        for p in pages:
            self.refcount[p] -= 1
            if self.refcount[p] == 0 and not (
                    self.prefix is not None and p in self.prefix):
                self._push_free(p)

    # retain/free bracket one REFERENCE; `release` reads better at call
    # sites that drop a whole lease
    release = free

    # -- page payload transfer (r15 disaggregation) -----------------------

    def export_pages(self, pages: List[int]) -> Dict[str, object]:
        """Serialize the K/V bytes of ``pages`` (in the given block-table
        order) as host numpy — the disaggregated prefill→decode handoff
        payload, using the same per-buffer numpy-copy shape as snapshot
        v5's pool section, so quantized pages travel WITH their fp32
        scale planes automatically (``ks``/``vs`` are just more buffers).
        The payload embeds :meth:`layout`; :meth:`ingest_pages` on the
        receiving pool refuses a mismatch."""
        idx = [int(p) for p in pages]
        for p in idx:
            self._check_page(p)
        return {
            "layout": self.layout(),
            "buffers": {k: np.asarray(v[:, idx]).copy()
                        for k, v in self.buffers.items()},
        }

    @staticmethod
    def payload_nbytes(payload: Dict[str, object]) -> int:
        """Wire size of an :meth:`export_pages` payload (page bytes +
        scale planes; the layout dict is negligible)."""
        return sum(int(a.nbytes) for a in payload["buffers"].values())

    def check_layout(self, want: Dict[str, object],
                     what: str = "page payload") -> None:
        """Refuse a foreign KV layout loudly, with the per-key diff —
        the same guard shape snapshot restore uses: mixed layouts would
        reinterpret page bytes silently (wrong dtype, wrong head count,
        wrong nibble packing), which is strictly worse than failing."""
        have = self.layout()
        if have != want:
            diff = {k: (want.get(k), have.get(k))
                    for k in set(want) | set(have)
                    if have.get(k) != want.get(k)}
            raise ValueError(
                f"{what} KV layout does not match this pool — sender vs "
                f"receiver: {diff}; prefill and decode replicas must "
                "share kv heads, page dtype, kv_bits, window and page "
                "geometry for pages to be byte-compatible")

    def ingest_pages(self, payload: Dict[str, object],
                     pages: List[int]) -> None:
        """Adopt an :meth:`export_pages` payload into freshly leased
        ``pages`` (same order).  Layout-guarded; the scatter is a plain
        eager ``.at[].set`` per buffer, so the round-trip
        export→host→ingest is bit-exact for fp, int8 and nibble-packed
        int4 pages and their scales alike."""
        self.check_layout(payload["layout"])
        bufs = payload["buffers"]
        if set(bufs) != set(self.buffers):
            raise ValueError(
                f"payload buffers {sorted(bufs)} != pool buffers "
                f"{sorted(self.buffers)}")
        idx = [int(p) for p in pages]
        for p in idx:
            self._check_page(p)
        n = len(idx)
        rows = jnp.asarray(idx, jnp.int32)
        for name, arr in bufs.items():
            if arr.shape[1] != n:
                raise ValueError(
                    f"payload buffer {name!r} carries {arr.shape[1]} "
                    f"pages for a {n}-page lease")
            self.buffers[name] = self.buffers[name].at[:, rows].set(
                jnp.asarray(arr))

    # -- invariants -------------------------------------------------------

    def check(self) -> None:
        """Refcount / free-list / prefix-index consistency — every page is
        exactly one of: free, live (refcount > 0), or cached-reclaimable.
        The serving tests' leak fixture calls this after every step."""
        if len(self._free) != len(self._free_set) or \
                set(self._free) != self._free_set:
            raise AssertionError("free list and free set diverged")
        if 0 in self._free_set or self.refcount[0] != 0:
            raise AssertionError("null page entered circulation")
        cached = set(self.prefix._by_page) if self.prefix is not None else set()
        for p in range(1, self.num_pages):
            free = p in self._free_set
            rc = self.refcount[p]
            if rc < 0:
                raise AssertionError(f"negative refcount on page {p}")
            if free and (rc != 0 or p in cached):
                raise AssertionError(f"page {p} free while referenced/cached")
            if not free and rc == 0 and p not in cached:
                raise AssertionError(f"leaked page {p}: unreferenced, "
                                     "uncached, not free")

    # -- stats ------------------------------------------------------------

    def utilization(self) -> float:
        """Fraction of usable pages out of the free list (live + cached)."""
        usable = self.num_pages - 1
        return 1.0 - len(self._free) / max(usable, 1)

    def hbm_bytes(self) -> int:
        return sum(b.size * b.dtype.itemsize for b in self.buffers.values())

    def bytes_per_token(self) -> int:
        """HBM bytes one token position costs across all layers and both
        sides — the capacity denominator the KV-capacity bench reports
        (GQA divides it by the group factor, int8 by ~4, int4 by ~8)."""
        per_side = sum(
            b.dtype.itemsize * self.num_kv_heads
            * (b.shape[-1] if name in ("k", "v") else 1)
            for name, b in self.buffers.items())
        return self.num_layers * per_side

    def layout(self) -> Dict[str, object]:
        """The pool's KV storage layout — everything that must MATCH for
        another pool's pages to be byte-compatible with this one (what
        snapshot v5 records and restore() refuses to mix)."""
        return {
            "kv_heads": self.num_kv_heads,
            "page_dtype": str(self.buffers["k"].dtype),
            "kv_bits": self.kv_bits,
            "window": self.window,
            "page_size": self.page_size,
            "head_dim": self.head_dim,
        }


class WindowRing:
    """The page group of sliding-window layers: a fixed ring of pages per
    slot, no allocator.

    A query at position ``q`` of a layer with window ``W`` sees keys
    ``q - W + 1 .. q``, and one dispatch writes at most ``chunk`` positions
    of a slot, so between the oldest key its first query can see and the
    last position it writes lie at most ``R = pages_for(W + chunk) + 1``
    pages.  Logical page ``j`` of slot ``i`` lives at pool page
    ``1 + i * R + j % R`` (page 0 is the null page, as in :class:`KVPool`);
    the slot's table row names it while it is live and the null page
    otherwise, so the kernels read it exactly as they read the full group.
    :meth:`advance` turns the ring before every dispatch that writes, in
    chunked prefill and in decode alike: pages no later query can see go
    dead, and the pages the dispatch writes go live on the ring positions
    the dead ones left.  The group is sized from ``max_slots`` alone and
    can never be why an allocation fails.

    Buffers are ``(L_w, P_w, Hkv, page_size, D)`` like the full group's,
    so the programs treat both groups alike (viewed ``(L_w * P_w, ...)``,
    written in whole pages, read through ``table + j * P_w``)."""

    def __init__(self, num_layers: int, kv_heads: int, head_dim: int,
                 max_slots: int, max_pages: int, page_size: int,
                 window: int, chunk_tokens: int, dtype=jnp.float32):
        self.window, self.page_size = int(window), page_size
        self.ring = math.ceil((self.window + chunk_tokens) / page_size) + 1
        self.chunk_tokens = chunk_tokens
        self.num_layers = num_layers
        self.num_pages = 1 + max_slots * self.ring
        shape = (num_layers, self.num_pages, kv_heads, page_size, head_dim)
        self.buffers: Dict[str, jnp.ndarray] = {
            "k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        self.table = np.zeros((max_slots, max_pages), np.int32)
        # live logical pages of each slot: [lo, hi)
        self.lo = np.zeros((max_slots,), np.int64)
        self.hi = np.zeros((max_slots,), np.int64)
        self.recycled = 0     # ring pages given to a later logical page

    def first_visible_page(self, pos: int) -> int:
        """The logical page of the oldest key a query at ``pos`` sees."""
        return max(0, pos - self.window + 1) // self.page_size

    def advance(self, slot: int, start: int, end: int) -> None:
        """Before a dispatch that writes positions ``[start, end)`` of
        ``slot`` (its earliest query is at ``start``)."""
        if end - start > self.chunk_tokens:
            raise ValueError(f"a dispatch of {end - start} positions turns "
                             f"a ring sized for {self.chunk_tokens}")
        lo = self.first_visible_page(start)
        hi = (end - 1) // self.page_size + 1
        row, base = self.table[slot], 1 + slot * self.ring
        row[self.lo[slot]:lo] = 0
        for j in range(max(int(self.hi[slot]), lo), hi):
            row[j] = base + j % self.ring
            self.recycled += j >= self.ring
        self.lo[slot], self.hi[slot] = lo, max(hi, int(self.hi[slot]))

    def release(self, slot: int) -> None:
        self.table[slot] = 0
        self.lo[slot] = self.hi[slot] = 0

    @property
    def pages_in_use(self) -> int:
        return int((self.hi - self.lo).sum())

    def hbm_bytes(self) -> int:
        return sum(b.size * b.dtype.itemsize for b in self.buffers.values())

    def check(self, next_pos: Dict[int, int]) -> None:
        """``next_pos``: occupied slot -> the next position it writes (and
        its next query's position).  Every other slot's ring is empty; an
        occupied slot holds at most the ring, every live page lies in its
        own ring at its own place, nothing else is named, and every page a
        later query can still see is live (so no ring page was given away
        from under it)."""
        ps = self.page_size
        for slot in range(self.table.shape[0]):
            row, lo, hi = self.table[slot], int(self.lo[slot]), \
                int(self.hi[slot])
            if slot not in next_pos:
                if row.any() or lo or hi:
                    raise AssertionError(
                        f"window ring of empty slot {slot} is not empty")
                continue
            if hi - lo > self.ring:
                raise AssertionError(
                    f"slot {slot} holds {hi - lo} window pages; the ring "
                    f"is {self.ring}")
            want = np.zeros_like(row)
            js = np.arange(lo, hi)
            want[lo:hi] = 1 + slot * self.ring + js % self.ring
            if not np.array_equal(row, want):
                raise AssertionError(
                    f"slot {slot}: window table row names pages outside "
                    f"its ring or live range [{lo}, {hi})")
            pos = next_pos[slot]
            if pos > 0 and (lo > self.first_visible_page(pos)
                            or hi < -(-pos // ps)):
                raise AssertionError(
                    f"slot {slot}: a query at {pos} still sees pages "
                    f"outside the live range [{lo}, {hi})")


class StateSlab:
    """The state group of a model with recurrent layers: a fixed-size state
    a slot a layer, no pages and no allocator.

    Where the page groups keep ``page_size`` positions a page, a
    state-space mixer (``models/ssm.py``) keeps, whatever the context's
    length, the state ``(n_heads, head_dim, d_state)`` in ``state_dtype``
    and the convolution's tail, the last ``d_conv - 1`` rows of its input.
    Buffers: ``ssm`` ``(L, slots, H, P, N)`` and ``conv`` ``(L, slots,
    (d_conv - 1) * conv_dim)``, a slot's tail rows one after another (a
    trailing dim of 3 would be tiled out to 128 lanes, and a fourth dim
    lets the compiler pick a layout a program and re-lay the slab out).
    Like :class:`WindowRing` the group
    is sized from ``max_slots`` alone and can never be why an admission
    fails.

    State cannot be sliced by position, so what the engine does to it is
    coarser than what it does to pages: it is ZEROED when a request takes
    the slot (a first admission or a recompute after preemption alike:
    nothing is saved), advanced in place by the chunk and decode programs
    over valid rows and live lanes only, and left as it lies when the slot
    is released: the next tenant's reset is what keeps it from leaking.
    ``advanced`` mirrors, on the host, how many positions each slot's
    state has folded in since its reset; :meth:`check` holds it to the
    slots' own positions."""

    def __init__(self, num_layers: int, max_slots: int, spec,
                 conv_dtype=jnp.float32):
        self.num_layers, self.max_slots, self.spec = (num_layers, max_slots,
                                                      spec)
        self.buffers: Dict[str, jnp.ndarray] = {
            "ssm": jnp.zeros((num_layers, max_slots, spec.n_heads,
                              spec.head_dim, spec.d_state),
                             jnp.dtype(spec.state_dtype)),
            "conv": jnp.zeros((num_layers, max_slots,
                               (spec.d_conv - 1) * spec.conv_dim),
                              conv_dtype)}
        self.advanced = np.zeros((max_slots,), np.int64)

    def release(self, slot: int) -> None:
        """The slot's state counts for nothing from here on: its tenant
        left, or the engine has just zeroed the device rows for the next."""
        self.advanced[slot] = 0

    def hbm_bytes(self) -> int:
        return sum(b.size * b.dtype.itemsize for b in self.buffers.values())

    def check(self, next_pos: Dict[int, int]) -> None:
        """``next_pos``: occupied slot -> the next position it writes.  An
        occupied slot's state has folded in exactly the positions before
        it; an empty slot's mirror is clear."""
        for slot in range(self.max_slots):
            have, want = int(self.advanced[slot]), next_pos.get(slot, 0)
            if have != want:
                raise AssertionError(
                    f"slot {slot}: recurrent state advanced over {have} "
                    f"positions since its reset, the slot stands at {want}")
