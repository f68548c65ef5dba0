"""Pinned-host-RAM spill tier for the paged KV pool (ISSUE 13).

HBM pages are the binding resource of the serving stack: resident
sessions, prefix-cache capacity and migration payloads all compete for
the same pool.  Before this tier, memory pressure made the prefix
cache's LRU eviction DESTRUCTIVE — an evicted page's KV was gone, and
the next request sharing that prefix paid a full re-prefill.  The spill
tier turns that eviction into a memory-hierarchy demotion:

- **Spill (evict)**: when the allocator's reclaim pass evicts an idle
  cached page, its bytes (every layer's K + V rows — and, on the int8
  plane, their fp32 scales) are copied device->host into a fixed ring of
  ``FLAGS_kv_spill_pages`` page slots, the device page returns to the
  free list, and the radix node stays indexed, marked *spilled*.  One
  marked host<->device sync per spilled page, on the admission/growth
  control path — never on the dispatch hot path.
- **Swap-in (admission)**: a prompt that matches a spilled node gets a
  fresh device page and the host bytes are uploaded by a pre-warmed
  donating jit program — dispatch-only, strictly ordered before the
  consumer's first prefill chunk by device dispatch order.  Eviction
  becomes a DMA instead of a re-prefill.
- **Ring pressure**: a full ring drops its coldest spilled node (always
  strictly colder than the page being demoted) to make room; a node
  dropped from the ring is unindexed exactly like a pre-spill eviction.

int8 pages (``FLAGS_kv_cache_dtype=int8``) make the spill ~4x cheaper
both directions — the host ring and both copies move quantized bytes.

The host arrays are plain page-locked process memory (``np.ndarray``);
on TPU runtimes the transfer path is the same pinned-staging DMA the
runtime uses for any host buffer.
"""

from __future__ import annotations

import functools
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import observability as _obs

_SWAPIN_BOUNDS = [0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0]


def _upload_page(cache, page, host, page_axes):
    """Scatter one spilled page's host bytes back into the pool tuple
    (``page_axes``: where each array counts pages,
    ``PagedKVCache.page_axes``).

    ``page`` is traced, so one compile serves every swap-in; a page id
    of ``num_pages`` (the warmup call) is dropped by the scatter."""
    return tuple(
        arr.at[(slice(None),) * ax + (page,)].set(h, mode="drop")
        for arr, h, ax in zip(cache, host, page_axes))


def make_upload_program(cache):
    """Build the donating page swap-in program for this pool.

    Single-device pools jit ``_upload_page`` directly.  Tensor-parallel
    pools (``cache.mesh`` set) keep the HOST side of the wire format
    global — a spilled page plane always holds every KV head — and
    re-shard on install: the shard_map body slices each host plane to its
    shard's kv-head block (``cache.head_axes``: where a page's plane of
    the pool, and of an int8 scale row, counts heads) before the scatter
    into shard-local storage.  Spill ring,
    migration import and warmup all share this one program, so swap-in
    bytes and compile counts are identical at any shard count."""
    upload = functools.partial(_upload_page, page_axes=cache.page_axes)
    if getattr(cache, "mesh", None) is None:
        return jax.jit(_obs.tracing.named(upload, "pool_swap_in"),
                       donate_argnums=(0,))
    axis = cache.axis
    head_axes = cache.head_axes
    heads = cache.num_kv_heads // cache.mesh.shape[axis]

    def _sharded(pool, page, host):
        i = jax.lax.axis_index(axis)
        local = tuple(
            jax.lax.dynamic_slice_in_dim(h, i * heads, heads, axis=ax)
            for h, ax in zip(host, head_axes))
        return upload(pool, page, local)

    from jax.sharding import PartitionSpec
    rep = PartitionSpec()
    cspec = cache.pspecs
    sharded = jax.shard_map(
        _sharded, mesh=cache.mesh, in_specs=(cspec, rep, rep),
        out_specs=cspec, check_vma=False)   # as the engine step: see _tp_jit
    return jax.jit(_obs.tracing.named(sharded, "pool_swap_in"),
                   donate_argnums=(0,))


class HostSpillPool:
    """Fixed ring of host-RAM page slots + the swap-in upload program.

    Owns the device<->host page moves and the ``serving.kv.*`` telemetry;
    the *policy* (which page spills, which node swaps in, LRU order)
    lives in :class:`~paddle_tpu.inference.prefix_cache.PrefixCache`.
    """

    def __init__(self, cache, capacity: int):
        if getattr(cache, "latent", None) is not None:
            raise ValueError(
                "inference/kv_spill.py: the host spill ring copies per-head "
                "pages; a latent pool has no head axis: build the engine "
                "with kv_spill_pages=0")
        if getattr(cache, "recurrent", None) is not None:
            raise ValueError(
                "inference/kv_spill.py: the host spill ring holds pages; a "
                "slot's recurrent state is no page and a spilled prefix "
                "could not be resumed without it: build the engine with "
                "kv_spill_pages=0")
        self.cache = cache               # PagedKVCache (live arrays)
        self.capacity = int(capacity)
        self._free: List[int] = list(range(self.capacity - 1, -1, -1))
        # slot -> host page planes, same order as cache.arrays
        self._slots: Dict[int, Tuple[np.ndarray, ...]] = {}
        self._upload = make_upload_program(cache)
        self.spilled_pages = 0           # cumulative spills
        self.swapins = 0                 # cumulative swap-ins
        m = _obs.metrics
        self._c_spilled = m.counter("serving.kv.spilled_pages")
        self._c_swapins = m.counter("serving.kv.swapins")
        self._h_wait = m.histogram("serving.kv.swapin_wait_ms",
                                   bounds=_SWAPIN_BOUNDS)

    # ---- capacity ----
    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def resident(self) -> int:
        """Spilled pages currently held in the ring."""
        return len(self._slots)

    def stats(self) -> Dict[str, int]:
        return {"kv_spill_capacity": self.capacity,
                "kv_spill_resident": self.resident,
                "kv_spilled_pages": self.spilled_pages,
                "kv_swapins": self.swapins}

    # ---- device -> host (eviction) ----
    def spill(self, page_id: int) -> Optional[int]:
        """Copy device page ``page_id`` (all layers, K + V + scales) into
        a free ring slot and return the slot id; None when the ring is
        full (the caller may drop a colder spilled node and retry).

        The read is the spill tier's one intentional host<->device sync:
        it blocks until every already-dispatched write to the page has
        executed, so the host copy is exactly the bytes the pool held."""
        if not self._free:
            return None
        host = self.cache.page_planes(page_id)   # counts its sync
        slot = self._free.pop()
        self._slots[slot] = host
        self.spilled_pages += 1
        self._c_spilled.inc()
        return slot

    # ---- host -> device (admission) ----
    def swap_in(self, slot: int, page_id: int) -> None:
        """Upload slot ``slot``'s bytes into device page ``page_id`` and
        retire the slot.  Dispatch-only: the donating jit program was
        warmed at engine init, so a warm swap-in compiles nothing and
        syncs nothing — device dispatch order alone guarantees the page
        is filled before any later step reads it."""
        host = self._slots.pop(slot)
        t0 = time.perf_counter()
        self.cache.update(*self._upload(
            self.cache.arrays, jnp.int32(page_id),
            tuple(jnp.asarray(h) for h in host)))
        self._h_wait.observe((time.perf_counter() - t0) * 1e3)
        self._free.append(slot)
        self.swapins += 1
        self._c_swapins.inc()

    def peek(self, slot: int) -> Tuple[np.ndarray, ...]:
        """Read a spilled page's host planes WITHOUT retiring the slot
        (session migration, ISSUE 14: a spilled prefix page ships its
        host-ring bytes to the successor directly — no swap-in, no
        device round-trip)."""
        return self._slots[slot]

    def free_slot(self, slot: int) -> None:
        """Retire a spilled page without swapping it in (its node was
        dropped from the index — ring pressure or trie unlink)."""
        del self._slots[slot]
        self._free.append(slot)

    def warm(self) -> None:
        """Compile the upload program with an out-of-range page id (the
        scatter drops every write) so the first real swap-in — and every
        later one — is dispatch-only."""
        with _obs.startup.program("jit_pool_swap_in"):
            self.cache.update(*self._upload(
                self.cache.arrays,
                jnp.int32(self.cache.allocator.num_pages),
                self.cache.page_plane_zeros()))
