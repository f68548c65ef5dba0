"""Paged KV cache: device page pool + host page allocator.

The serving-side memory manager the reference implements inside
block_multi_head_attention (paddle/phi/kernels/fusion/gpu/
block_multi_head_attention_kernel.cu — block tables, per-sequence page
lists) and AnalysisPredictor's buffer management
(paddle/fluid/inference/api/analysis_predictor.h:105).

TPU-first split of responsibilities:
- **Device**: ONE pool array, laid out page-major ``[layers, num_pages, 2,
  kv_heads, page_size, head_dim]``: every head's K, then every head's V, of
  one page of one layer is one contiguous run in HBM (32 KB at 4 KV heads
  of 128 in bf16, 64 KB at 8), which the Pallas kernel fetches with ONE
  copy, and each head's ``[page_size, head_dim]`` of it is whole tiles of
  the block it lands in.  Static shapes, donated through the jitted decode
  step so XLA updates pages in place.  The decode step must treat the pool
  as read-only until one batched end-of-step commit (see generation.py) —
  a scan that carries the cache copies all of it every step.
- **Host**: a free-list page allocator (pure Python — page bookkeeping is
  control flow, not math) producing the int32 block tables / context-lens /
  slot-mapping operands the Pallas kernel consumes via scalar prefetch.

Pages are REF-COUNTED (ISSUE 4): the same physical page may appear in
several sequences' block tables (a shared prompt prefix — the prefix
cache in ``inference/prefix_cache.py`` — or the cache's own retained
reference after the producing sequence retired).  A page returns to the
free list only when its last reference drops, which makes a page-level
double free structurally impossible: the refcount transition guards the
free-list append, and releasing a page that is already free raises.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _serving_bump(key: str, n: int = 1) -> None:
    """Mirror a prefix-cache counter into the process-wide serving
    telemetry — an ``observability`` registry counter (``serving.<key>``),
    which both ``jit.cache_stats()["serving"]`` and
    ``observability.snapshot()`` read.  The allocator is the ONE place
    every counter increments, so the per-engine and process-wide books
    cannot diverge."""
    from ..observability import metrics as _metrics
    _metrics.counter("serving." + key).inc(n)


class PageAllocator:
    """Free-list allocator mapping sequence ids to ref-counted page lists.

    The pool may be sized BELOW the dense ``max_batch * pages_per_seq``
    worst case: freed pages recycle through the free list, admission
    backpressure handles exhaustion at admission time, a sequence whose
    mid-decode growth finds the pool dry is finalized early by the engine
    (``_grow`` itself raises MemoryError only on the raw allocator API),
    and ``stats()`` reports the high-water mark so operators can size the
    pool to observed traffic instead of the worst case.

    With a prefix cache attached (``set_reclaimer``) the allocator asks
    the cache to evict idle cached pages back into the free list before
    declaring the pool exhausted, so cached history is reclaimed exactly
    when admission or decode growth needs the memory and never sooner.
    """

    def __init__(self, num_pages: int, page_size: int):
        self.num_pages = num_pages
        self.page_size = page_size
        self._free: List[int] = list(range(num_pages - 1, -1, -1))
        self._ref: List[int] = [0] * num_pages     # per-page reference count
        self._pages: Dict[int, List[int]] = {}     # seq id -> page ids
        self._lens: Dict[int, int] = {}            # seq id -> token count
        self.peak_in_use = 0
        # prefix-cache reclaim hooks (inference/prefix_cache.py): evict
        # idle cached pages on demand / count how many could be evicted
        self._reclaim: Optional[Callable[[int], int]] = None
        self._evictable: Optional[Callable[[], int]] = None
        # prefix-cache telemetry (all stay 0 with the cache off)
        self.prefix_hits = 0          # admissions that reused cached pages
        self.prefix_tokens_saved = 0  # prompt tokens whose prefill was skipped
        self.cow_copies = 0           # shared pages privatized copy-on-write
        self.evicted_pages = 0        # cached pages reclaimed under pressure

    # ---- reclaim seam (the prefix cache's LRU free-pool) ----
    def set_reclaimer(self, reclaim: Callable[[int], int],
                      evictable: Callable[[], int]) -> None:
        """Attach an eviction source: ``reclaim(n)`` moves up to ``n`` idle
        cached pages back to the free list (returns how many it moved);
        ``evictable()`` counts pages reclaim could free right now."""
        self._reclaim = reclaim
        self._evictable = evictable

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def available_pages(self) -> int:
        """Pages obtainable right now: free list + evictable cached pages."""
        extra = self._evictable() if self._evictable is not None else 0
        return len(self._free) + extra

    @property
    def pages_in_use(self) -> int:
        return self.num_pages - len(self._free)

    def stats(self) -> Dict[str, int]:
        """Pool telemetry: live/peak page usage, active sequences, and the
        prefix-cache counters (all zero when the cache is off)."""
        return {"num_pages": self.num_pages,
                "pages_in_use": self.pages_in_use,
                "peak_in_use": self.peak_in_use,
                "active_seqs": len(self._pages),
                "prefix_hits": self.prefix_hits,
                "prefix_tokens_saved": self.prefix_tokens_saved,
                "cow_copies": self.cow_copies,
                "evicted_pages": self.evicted_pages}

    def context_len(self, seq_id: int) -> int:
        return self._lens[seq_id]

    def page_list(self, seq_id: int) -> List[int]:
        """The sequence's page ids, in token order (a copy)."""
        return list(self._pages[seq_id])

    def ref_count(self, page: int) -> int:
        return self._ref[page]

    # ---- page-level refcounting ----
    def retain(self, page: int) -> None:
        """Add a reference to a live page (prefix sharing / cache pin)."""
        if self._ref[page] <= 0:
            raise ValueError(f"page {page} is free; cannot retain it")
        self._ref[page] += 1

    def release_page(self, page: int) -> None:
        """Drop one reference; the last drop returns the page to the free
        list.  Releasing an already-free page raises (the structural
        double-free guard)."""
        if self._ref[page] <= 0:
            raise ValueError(f"page {page} is already free (double free)")
        self._ref[page] -= 1
        if self._ref[page] == 0:
            self._free.append(page)

    def _alloc_page(self) -> int:
        if not self._free and self._reclaim is not None:
            self._reclaim(1)
        if not self._free:
            raise MemoryError(
                f"KV cache exhausted: {self.num_pages} pages in use")
        p = self._free.pop()
        if self._ref[p] != 0:
            raise RuntimeError(f"free-list page {p} has live references")
        self._ref[p] = 1
        return p

    def acquire_page(self) -> int:
        """Allocate one standalone page carrying a single reference (the
        spill tier's swap-in target; the holder releases it via
        :meth:`release_page`).  Reclaims from the prefix cache under
        pressure like any other allocation; raises MemoryError dry."""
        p = self._alloc_page()
        self.peak_in_use = max(self.peak_in_use, self.pages_in_use)
        return p

    def _grow(self, seq_id: int, new_len: int) -> None:
        pages = self._pages[seq_id]
        need = -(-new_len // self.page_size)       # ceil
        while len(pages) < need:
            pages.append(self._alloc_page())
        self.peak_in_use = max(self.peak_in_use, self.pages_in_use)
        self._lens[seq_id] = new_len

    def allocate(self, seq_id: int, num_tokens: int,
                 shared_pages: Sequence[int] = ()) -> np.ndarray:
        """Register a new sequence with ``num_tokens`` prompt tokens.

        ``shared_pages`` (prefix-cache hit) are attached FIRST, in token
        order, with a refcount bump each — their KV is reused, not
        rewritten; fresh pages are then allocated for the remaining
        tokens.  Returns the flat slot ids [num_tokens] the sequence's
        KV rows map to (callers with a prefix hit only write the
        uncached tail).  On pool exhaustion the registration is rolled
        back completely before MemoryError propagates."""
        if seq_id in self._pages:
            raise ValueError(f"sequence {seq_id} already allocated")
        pages: List[int] = []
        self._pages[seq_id] = pages
        self._lens[seq_id] = 0
        try:
            for p in shared_pages:
                self.retain(p)
                pages.append(p)
            self._grow(seq_id, num_tokens)
        except BaseException:
            # full rollback on ANY failure (pool exhaustion, a bad
            # shared_pages entry, ...): `pages` holds exactly the
            # references taken so far, so releasing them restores every
            # refcount and the seq id stays allocatable
            for p in pages:
                self.release_page(p)
            del self._pages[seq_id]
            del self._lens[seq_id]
            raise
        return self.slots(seq_id, 0, num_tokens)

    def extend(self, seq_id: int, num_tokens: int = 1) -> np.ndarray:
        """Append token slots to an existing sequence (decode step)."""
        start = self._lens[seq_id]
        self._grow(seq_id, start + num_tokens)
        return self.slots(seq_id, start, num_tokens)

    def truncate(self, seq_id: int, num_tokens: int) -> int:
        """Shrink a sequence's page list to cover exactly ``num_tokens``
        (speculative-decoding tail rollback, ISSUE 9: pages grown for
        draft tokens that were then rejected).  Each dropped tail page
        loses ONE reference — this sequence's — so a page shared with the
        prefix cache or a sibling sequence survives with its other
        references intact (the same structural double-free guard as
        :meth:`free`).  Returns the number of references dropped."""
        pages = self._pages[seq_id]
        keep = max(0, -(-int(num_tokens) // self.page_size))
        dropped = pages[keep:]
        del pages[keep:]
        for p in dropped:
            self.release_page(p)
        self._lens[seq_id] = min(self._lens[seq_id], int(num_tokens))
        return len(dropped)

    def cow(self, seq_id: int,
            page_index: int) -> Optional[Tuple[int, int]]:
        """Copy-on-write: make entry ``page_index`` of the sequence's page
        list private before it is written.  A shared page (refcount > 1)
        is swapped for a fresh one and ``(src, dst)`` is returned — the
        caller owns the device-side page copy; an exclusive page returns
        None (already writable)."""
        pages = self._pages[seq_id]
        src = pages[page_index]
        if self._ref[src] <= 1:
            return None
        dst = self._alloc_page()
        pages[page_index] = dst
        self.release_page(src)       # cannot hit zero: it was > 1
        self.peak_in_use = max(self.peak_in_use, self.pages_in_use)
        self.cow_copies += 1
        _serving_bump("cow_copies")
        return src, dst

    def record_prefix_hit(self, tokens_saved: int) -> None:
        """Count one prefix-cache hit admission (both telemetry books)."""
        self.prefix_hits += 1
        self.prefix_tokens_saved += tokens_saved
        _serving_bump("prefix_hits")
        _serving_bump("prefix_tokens_saved", tokens_saved)

    def record_evictions(self, n: int = 1) -> None:
        """Count cached pages reclaimed under pressure (both books)."""
        self.evicted_pages += n
        _serving_bump("evicted_pages", n)

    def slots(self, seq_id: int, start: int, count: int) -> np.ndarray:
        pages = self._pages[seq_id]
        pos = np.arange(start, start + count)
        page_ids = np.asarray(pages, np.int32)[pos // self.page_size]
        return (page_ids * self.page_size + pos % self.page_size).astype(np.int32)

    def free(self, seq_id: int) -> None:
        """Release the sequence's reference on every page it holds.

        NOT idempotent: freeing an unknown or already-freed ``seq_id``
        raises ``KeyError("seq id ... not allocated")`` on every path —
        callers own exactly one free per allocate.  Pages shared with the
        prefix cache or other sequences survive (their refcount stays
        positive); only last references land back in the free list, so a
        page-level double free cannot occur even if two owners retire in
        either order."""
        if seq_id not in self._pages:
            raise KeyError(
                f"seq id {seq_id} not allocated (double free or never "
                "allocated)")
        for p in self._pages.pop(seq_id):
            self.release_page(p)
        del self._lens[seq_id]

    def release(self, seq_id: int) -> None:
        """Alias of :meth:`free` (same contract, same KeyError)."""
        self.free(seq_id)

    def block_table(self, seq_ids: Sequence[int],
                    max_pages: Optional[int] = None) -> np.ndarray:
        """[batch, max_pages] int32 table (padded with 0 — kernel masks by
        context_lens so pad entries only need to be *valid* page ids)."""
        rows = [self._pages[s] for s in seq_ids]
        width = max_pages if max_pages is not None else max(
            (len(r) for r in rows), default=1)
        width = max(width, 1)
        out = np.zeros((len(rows), width), np.int32)
        for i, r in enumerate(rows):
            if len(r) > width:
                raise ValueError(
                    f"sequence needs {len(r)} pages > table width {width}")
            out[i, :len(r)] = r
        return out

    def context_lens(self, seq_ids: Sequence[int]) -> np.ndarray:
        return np.asarray([self._lens[s] for s in seq_ids], np.int32)


@dataclass(frozen=True)
class LayerPlanes:
    """Which plane of the pool, and of the recurrent state, each layer of a
    stack owns: two kinds of cache, each over its own layers.  ``pages[l]``
    is layer ``l``'s index on the pool's first axis, None where the layer
    keeps no pages (a linear-attention place); ``state[l]`` its index on the
    recurrent state's first axis, None where it keeps none.  Made once from
    the spec; where every layer keeps pages ``pages[l] == l``."""
    pages: Tuple[Optional[int], ...]
    state: Tuple[Optional[int], ...]

    @classmethod
    def of(cls, spec) -> "LayerPlanes":
        lead = len(spec.leading)
        page_of = {p: i for i, p in enumerate(spec.page_places)}
        state_of = {p: i for i, p in enumerate(spec.state_places)}
        pages, state = list(range(lead)), [None] * lead
        for r in range(spec.periods):
            for p in range(len(spec.pattern)):
                pages.append(lead + r * len(page_of) + page_of[p]
                             if p in page_of else None)
                state.append(r * len(state_of) + state_of[p]
                             if p in state_of else None)
        return cls(tuple(pages), tuple(state))


class RecurrentState:
    """What a slot holds besides pages where layers of the stack keep a
    recurrent state (``models.decoder_spec.SsmMixer`` beside attention, or a
    ``DeltaMixer`` in its stead): fixed in size, indexed by SLOT and never
    by page, so no allocator addresses it.  Two arrays over the
    ``num_layers`` layers that HAVE a state (``LayerPlanes.state``), shaped
    by the mixer: the recurrence's state ``ssm [layers, slots,
    *mixer.state_shape]`` in float32 (in bf16 a term under 2^-8 of the
    state's size would be lost at every token) and the convolution's carried
    rows ``conv [layers, slots, conv - 1, conv_width]`` in the model's type.
    A slot's state is zeroed
    on the device by the step that runs its first chunk; nothing here is
    copied, spilled or snapshotted (the prefix cache, the spill tier and
    migration refuse a stack that has one)."""

    def __init__(self, mixer, num_layers: int, slots: int, dtype):
        self.mixer = mixer
        self.ssm = jnp.zeros((num_layers, slots) + tuple(mixer.state_shape),
                             jnp.float32)
        self.conv = jnp.zeros((num_layers, slots, mixer.conv - 1,
                               mixer.conv_width), jnp.dtype(dtype))

    @property
    def arrays(self):
        return self.ssm, self.conv

    def update(self, ssm, conv) -> None:
        self.ssm, self.conv = ssm, conv

    @staticmethod
    def bytes_per_slot(mixer, num_layers: int, dtype) -> int:
        """HBM bytes one slot's recurrent state costs over all layers (the
        fixed counterpart of ``PagedKVCache.bytes_per_page``)."""
        return num_layers * mixer.state_bytes(dtype)


class PagedKVCache:
    """Device KV pool for the layers that keep pages + the allocator that
    addresses it.

    The pool is ``kv [layers, num_pages, 2, kv_heads, page_size,
    head_dim]`` (a page's K and V of every head together: the unit the
    kernel copies; ``layers`` counts the layers that HAVE pages, every layer
    but a linear-attention place's: ``LayerPlanes.pages``); ``.arrays`` is ``(kv,)``, and ``page_axes`` /
    ``head_axes`` say, array by array, where pages and KV heads are
    counted, so that whoever copies, spills or snapshots a page
    (``page_planes``) never restates the layout.

    ``dtype="int8"`` stores the pool quantized (the ISSUE 13 memory
    plane): int8 pages with one fp32 absmax scale per (layer, kv-head,
    page) riding in ``k_scale``/``v_scale`` (``[layers, kv_heads,
    num_pages]``: one layer's plane is the kernel's scalar-prefetched
    ``[kv_heads, num_pages]``).  The ragged paged-attention
    kernel dequantizes on its VMEM slot right after the DMA wait and the
    engine's batched commit requantizes per page on the way in, so
    nothing above the cache changes shape — the pool just holds ~4x more
    tokens per HBM byte.

    Under tensor-parallel serving (``mesh=`` + ``axis=``) page *storage*
    is shard-local: the pool (and int8 scale rows) holds
    ``num_kv_heads/mp`` heads per device via a NamedSharding on the
    kv-head axis, while page ids, block tables, the allocator, the
    prefix cache and the spill ring stay host-global.  ``np.asarray`` on
    a page slice gathers the full global plane, so migration snapshots
    and spill bytes are identical at any shard count.

    ``latent=(rank, rope)`` is a LATENT pool (``models.decoder_spec.
    LatentAttn``): one row ``[c | k_r]`` a token a layer and no head axis,
    held as two arrays because ``rank + rope`` is not a multiple of the
    128 lanes: ``k`` is the compressed part ``[layers, num_pages, page_size,
    rank]`` and ``v`` the rotary keys TWO TOKENS A ROW, ``[layers,
    num_pages, page_size / 2, 2 * rope]`` (token ``t`` of a page lies in
    row ``t % (page_size / 2)``, lanes ``[(t // (page_size / 2)) * rope,
    + rope)``): 64 numbers would be padded to 128 lanes in HBM, two tokens
    fill them, and a page stays one whole tile of each array.  Pages are on
    axis 1 (``page_axis``), as the per-head pool's are; ``.arrays`` is
    ``(k, v)``.  No int8 plane and no tensor-parallel layout: both are
    refused here.  ``index=dim`` (a stack with a learned index,
    ``LatentIndex``): a third array ``index [layers, num_pages, page_size,
    dim]``, one index key a token a layer, in the pool's dtype and with
    pages on axis 1 like the other two; ``.arrays`` is ``(k, v, index)``,
    so whoever moves a page (COW, the prefix cache, a speculative lane's
    rollback) moves its index keys with it.

    ``recurrent`` (a ``RecurrentState``): the slots' fixed state rides
    with the pool as the last two of ``.arrays`` (``(kv, ssm, conv)``),
    donated and updated with it; a float per-head pool on one device
    only."""

    def __init__(self, num_layers: int, num_pages: int, page_size: int,
                 num_kv_heads: int, head_dim: int, dtype="bfloat16",
                 mesh=None, axis: str = "mp", latent=None, recurrent=None,
                 index=None):
        self.num_layers = num_layers
        self.page_size = page_size
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.quantized = str(dtype) == "int8"
        self.mesh = mesh
        self.axis = axis
        self.latent = None if latent is None else tuple(latent)
        self.recurrent = recurrent
        self.index = None
        if index is not None and latent is None:
            raise ValueError("inference/kv_cache.py: index keys are a plane "
                             "of a latent pool")
        if recurrent is not None and (self.quantized or mesh is not None
                                      or latent is not None):
            raise ValueError(
                "inference/kv_cache.py: a recurrent state rides with a "
                "float per-head pool on one device: no int8 plane, no "
                "tensor-parallel layout, no latent pool")
        # the axis of the pool that counts pages (``page_axes``: of every
        # array of ``.arrays``)
        self.page_axis = 1
        self.kv = self.k = self.v = None    # the per-head pool | a latent's
        if latent is not None:
            if self.quantized:
                raise ValueError(
                    "inference/kv_cache.py: a latent pool has no int8 "
                    "plane (the per-(kv-head, page) scales assume per-head "
                    "pages); use kv_cache_dtype auto, bf16 or fp32")
            if mesh is not None:
                raise ValueError(
                    "inference/kv_cache.py: a latent pool has no head axis "
                    "to shard; tensor_parallel must be 1")
            if page_size % 2:
                raise ValueError(f"a latent pool keeps two tokens' rotary "
                                 f"keys a row: page_size ({page_size}) "
                                 "must be even")
            rank, rope = self.latent
            dt = jnp.dtype(dtype)
            self.k = jnp.zeros((num_layers, num_pages, page_size, rank), dt)
            self.v = jnp.zeros((num_layers, num_pages, page_size // 2,
                                2 * rope), dt)
            if index is not None:
                self.index = jnp.zeros((num_layers, num_pages, page_size,
                                        int(index)), dt)
            self.k_scale = self.v_scale = None
            self.allocator = PageAllocator(num_pages, page_size)
            return
        if mesh is not None and num_kv_heads % mesh.shape[axis] != 0:
            raise ValueError(
                f"num_kv_heads={num_kv_heads} not divisible by "
                f"tensor-parallel degree {mesh.shape[axis]}")
        shape = (num_layers, num_pages, 2, num_kv_heads, page_size, head_dim)
        if self.quantized:
            self.kv = self._pool(shape, jnp.int8, jnp.zeros, head_axis=3)
            # all-zero pages dequantize to exactly 0 under any scale;
            # 1.0 keeps untouched pages' dequant well-defined
            planes = (num_layers, num_kv_heads, num_pages)
            self.k_scale = self._pool(planes, jnp.float32, jnp.ones, 1)
            self.v_scale = self._pool(planes, jnp.float32, jnp.ones, 1)
            # pool bytes saved vs an equal-page fp32 pool (K and V, minus
            # the scale planes) — the capacity headroom the quantized
            # plane buys at fixed HBM budget
            per = num_layers * num_kv_heads * num_pages
            saved = 2 * (per * page_size * head_dim * 3 - per * 4)
            _serving_bump("kv.quant_bytes_saved", max(saved, 0))
        else:
            self.kv = self._pool(shape, jnp.dtype(dtype), jnp.zeros, 3)
            self.k_scale = None
            self.v_scale = None
        self.allocator = PageAllocator(num_pages, page_size)

    def _head_spec(self, head_axis):
        from jax.sharding import PartitionSpec
        return PartitionSpec(*(None,) * head_axis, self.axis)

    def _pool(self, shape, dt, fill, head_axis):
        """One array of the pool: host-global shape, shard-local storage
        on its kv-head axis when a mesh is configured."""
        arr = fill(shape, dt)
        if self.mesh is None:
            return arr
        from jax.sharding import NamedSharding
        return jax.device_put(
            arr, NamedSharding(self.mesh, self._head_spec(head_axis)))

    @property
    def arrays(self):
        """The donated device state of one engine step: ``(kv,)`` for a
        float per-head pool, ``(kv, k_scale, v_scale)`` when quantized,
        ``(kv, ssm, conv)`` with a recurrent state; ``(k, v)`` for a latent
        pool (its compressed rows and rotary keys), ``(k, v, index)`` where
        it keeps index keys."""
        if self.latent is not None:
            return (self.k, self.v) if self.index is None \
                else (self.k, self.v, self.index)
        if self.quantized:
            return self.kv, self.k_scale, self.v_scale
        if self.recurrent is not None:
            return (self.kv,) + self.recurrent.arrays
        return (self.kv,)

    @property
    def dtype(self):
        """The type the pool's pages are stored in."""
        return (self.k if self.latent is not None else self.kv).dtype

    @property
    def page_axes(self):
        """The axis that counts pages, for each array of ``.arrays`` that
        holds pages (a recurrent state's two hold none and get none): 1 for
        the pool (and a latent pool's two or three), 2 for an int8 pool's
        scale planes."""
        if self.latent is not None:
            return (1,) * len(self.arrays)
        return (1, 2, 2) if self.quantized else (1,)

    @property
    def head_axes(self):
        """The axis that counts KV heads in ONE PAGE's plane of each array
        (``page_planes``: the page axis taken out), which is where a
        tensor-parallel shard finds its heads in a host-global plane."""
        return (2, 1, 1) if self.quantized else (2,)

    @property
    def pspecs(self):
        """shard_map partition specs matching ``.arrays`` order: every
        array (the pool AND the scale rows) is sharded on its kv-head
        axis."""
        if self.quantized:
            return self._head_spec(3), self._head_spec(1), self._head_spec(1)
        return (self._head_spec(3),)

    def page_planes(self, page_id: int):
        """One page as the host sees it: for each array of ``.arrays`` its
        slice at ``page_id`` (all layers; the pool's is one contiguous
        ``[2, kv_heads, page_size, head_dim]`` a layer), host-global under
        a mesh.  What the spill ring keeps and a snapshot is made from:
        a marked, intentional host<->device sync (it blocks until every
        already-dispatched write to the page has executed)."""
        from .. import observability as _obs
        _obs.count_sync()
        return tuple(np.asarray(arr[(slice(None),) * ax + (page_id,)])
                     for arr, ax in zip(self.arrays, self.page_axes))

    def page_plane_zeros(self):
        """Device zeros shaped like ``page_planes``' (to warm an upload
        program with)."""
        return tuple(jnp.zeros(arr.shape[:ax] + arr.shape[ax + 1:], arr.dtype)
                     for arr, ax in zip(self.arrays, self.page_axes))

    def update(self, *arrays) -> None:
        """Store the cache arrays returned by a jitted (donating) step, in
        ``.arrays``' order."""
        if self.latent is not None:
            self.k, self.v, *rest = arrays
            if rest:
                self.index, = rest
            return
        self.kv, *rest = arrays
        if self.quantized:
            self.k_scale, self.v_scale = rest
        elif self.recurrent is not None:
            self.recurrent.update(*rest)

    @staticmethod
    def pages_for(max_batch: int, max_seq_len: int, page_size: int) -> int:
        return max_batch * (-(-max_seq_len // page_size))

    @staticmethod
    def bytes_per_page(num_layers: int, num_kv_heads: int, page_size: int,
                       head_dim: int, dtype="bfloat16", latent=None,
                       index=None) -> int:
        """HBM bytes one pool page costs (K + V + scales, all layers) —
        the unit the kv_quant bench equalizes across dtype arms.  A latent
        pool (``latent=(rank, rope)``): ``rank + rope`` numbers a token a
        layer, whatever the heads, and ``index`` more where it keeps index
        keys."""
        if latent is not None:
            return num_layers * page_size * (sum(latent) + (index or 0)) \
                * jnp.dtype(dtype).itemsize
        per = num_layers * num_kv_heads
        if str(dtype) == "int8":
            return 2 * per * (page_size * head_dim + 4)
        return 2 * per * page_size * head_dim * jnp.dtype(dtype).itemsize
