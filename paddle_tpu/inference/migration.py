"""Cross-replica KV session migration (ISSUE 14 tentpole, layer 1).

The fleet is self-healing (PR 12) but until now not loss-free: a drained
or killed replica took its sessions' KV with it, and every re-placed
session paid a full re-prefill on the survivor.  This module makes a
session's KV a *transferable* artifact:

- **Export** serializes a session's pages exactly as the pool stores
  them (PR 13: int8 page bytes + their fp32 absmax scale rows — a
  migration is a memcpy of quantized bytes, never a dequant round-trip;
  float pools ship their raw rows the same way).  An in-flight session
  exports the full pages its block table covers (one marked
  host<->device readback per page, on the control path — never the
  dispatch hot path); a *parked* session (between turns: its history
  lives in the prefix-cache index) exports its radix chain, and a
  SPILLED chain node ships its host-ring bytes directly — no swap-in,
  no device round-trip at all.
- **Import** installs pages on the successor through the existing
  seams: ``PageAllocator.acquire_page()`` (which reclaims idle cached
  pages under pressure, so an import can trigger eviction but never
  deadlock) plus the pre-warmed donating upload program the spill tier
  already uses, then indexes each page as a READY idle prefix-cache
  node.  The resumed request — replayed by the router's failover
  journal, or submitted here with ``resume=True`` — then admits with a
  near-full prefix hit: **zero re-prefilled tokens for migrated
  pages**, only the partial-page tail (and the final token's COW
  re-prefill) computes.
- **Abort safety**: a transfer interrupted at any point leaves no
  allocator references behind — pages already linked are complete,
  valid, evictable cache entries; the one in-flight page is released on
  failure; a truncated snapshot simply imports a shorter (still
  contiguous) chain.

The wire codec (``to_wire``/``from_wire``) is plain JSON with base64
plane payloads so the same snapshot travels python-object-direct
(in-process fleets) or over ``POST /migratez/export|import`` (real
deployments).
"""

from __future__ import annotations

import base64
import hashlib
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import observability as _obs
from .kv_spill import make_upload_program

__all__ = ["MigrationError", "export_session", "export_all",
           "import_session", "import_sessions", "warm", "record_handoff",
           "to_wire", "from_wire", "snapshot_digest", "SNAP_VERSION"]

SNAP_VERSION = 1


class MigrationError(RuntimeError):
    """A snapshot this engine cannot produce or install (geometry/dtype
    mismatch, prefix cache off, unknown session)."""


class _MigrationMetrics:
    """Registry handles resolved once per process (the PR 5 idiom)."""

    _instance = None

    def __init__(self):
        m = _obs.metrics
        self.exports = m.counter("serving.kv.migration_exports")
        self.imports = m.counter("serving.kv.migration_imports")
        self.pages_out = m.counter("serving.kv.migration_pages",
                                   direction="out")
        self.pages_in = m.counter("serving.kv.migration_pages",
                                  direction="in")
        self.aborts = m.counter("serving.kv.migration_aborts")
        self.rejected = m.counter("serving.kv.migration_rejected")
        # prefill->decode handoff (ISSUE 16): the continuous (not
        # loss-event) use of this plane
        # jaxlint: disable=JL006 -- bounded by construction: outcome callers pass ok/partial literals
        self.handoff_sessions = lambda o: m.counter(
            "serving.kv.handoff_sessions", outcome=o)
        self.handoff_reprefill = m.counter(
            "serving.kv.handoff_reprefill_tokens")

    @classmethod
    def get(cls) -> "_MigrationMetrics":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance


def record_handoff(sessions: Sequence[dict], result: dict) -> None:
    """Account one prefill->decode handoff import (ISSUE 16): compare
    the full pages the shipped snapshots cover against the pages this
    import actually installed (or found already indexed) and count the
    shortfall as re-prefill debt — ``serving.kv.handoff_reprefill_
    tokens`` stays 0 when every handed-off session admits with a full
    prefix hit.  ``sessions`` are wire-form snapshots (tokens and
    geometry ride in the clear); ``result`` is the bulk import totals."""
    mm = _MigrationMetrics.get()
    full_pages = 0
    page = 0
    for s in sessions:
        geo = s.get("geometry") or {}
        page = int(geo.get("page_size", 0) or 0) or page
        toks = s.get("tokens") or ()
        if page > 0:
            full_pages += len(toks) // page
    covered = int(result.get("imported", 0)) + \
        int(result.get("skipped", 0))
    short = max(0, full_pages - covered)
    n = int(result.get("sessions", len(sessions)))
    mm.handoff_sessions("ok" if short == 0 else "partial").inc(n)
    if short and page > 0:
        mm.handoff_reprefill.inc(short * page)


def _engine_counts(engine) -> Dict[str, int]:
    mc = getattr(engine, "_migration_counts", None)
    if mc is None:
        mc = {"migration_exports": 0, "migration_imports": 0,
              "migration_exported_pages": 0, "migration_imported_pages": 0,
              "migration_aborts": 0, "migration_rejected": 0}
        engine._migration_counts = mc
    return mc


# ---------------------------------------------------------------------------
# geometry / codec
# ---------------------------------------------------------------------------

def _refuse_unsnapshotted(engine) -> None:
    """A snapshot's pages, geometry and digest are per-head planes; a
    latent pool (one row a token, no head axis) is not exported, imported
    or warmed, nor is a stack whose slots hold a recurrent state."""
    if getattr(engine.g.cache, "latent", None) is not None:
        raise MigrationError(
            "inference/migration.py: session snapshots move per-head page "
            "planes; this engine serves a latent pool, which has none")
    if getattr(engine.g.cache, "recurrent", None) is not None:
        raise MigrationError(
            "inference/migration.py: a session snapshot is pages; this "
            "engine's slots also hold a recurrent state, which no snapshot "
            "carries")


def _geometry(engine) -> Dict[str, object]:
    g = engine.g
    cache = g.cache
    return {"layers": cache.num_layers,
            "kv_heads": cache.num_kv_heads,
            "page_size": cache.page_size,
            "head_dim": cache.head_dim,
            "dtype": "int8" if cache.quantized else str(cache.dtype)}


def _check_geometry(engine, snap: dict) -> None:
    mine = _geometry(engine)
    theirs = snap.get("geometry")
    if theirs != mine:
        raise MigrationError(
            f"snapshot geometry {theirs} does not match this engine's "
            f"{mine}; migration moves raw pool bytes and cannot convert")


def _wire_planes(planes) -> Tuple[np.ndarray, ...]:
    """A page as the pool (and the spill ring) holds it, ``(kv [layers, 2,
    kv_heads, page_size, head_dim][, k_scale, v_scale])``, in the
    snapshot's form: ``(k, v[, k_scale, v_scale])``, each ``[layers,
    kv_heads, ...]``.  The wire stays head-major, as it was before the pool
    became page-major, so a stored snapshot and its digest keep their
    meaning and ``SNAP_VERSION`` stands."""
    kv, *scales = planes
    return (kv[:, 0], kv[:, 1], *scales)


def _pool_planes(planes) -> Tuple[np.ndarray, ...]:
    """A snapshot's page back in the pool's form (``_wire_planes``
    undone), for the upload program."""
    k, v, *scales = planes
    return (np.stack([k, v], axis=1), *scales)


def _page_planes(engine, page_id: int) -> Tuple[np.ndarray, ...]:
    """One device page's raw planes in the snapshot's form — int8 pools
    ship ``(k int8, v int8, k_scale, v_scale)`` untouched.  The readback
    is a marked intentional sync on the migration control path
    (``page_planes`` counts it)."""
    return _wire_planes(engine.g.cache.page_planes(page_id))


def _encode_planes(planes) -> List[dict]:
    out = []
    for p in planes:
        p = np.ascontiguousarray(p)
        out.append({"dtype": str(p.dtype), "shape": list(p.shape),
                    "b64": base64.b64encode(p.tobytes()).decode("ascii")})
    return out


def _decode_planes(planes) -> Tuple[np.ndarray, ...]:
    """Accept either live numpy planes (in-process transfer) or the wire
    encoding (``{"dtype", "shape", "b64"}`` dicts)."""
    out = []
    for p in planes:
        if isinstance(p, np.ndarray):
            out.append(p)
        else:
            arr = np.frombuffer(base64.b64decode(p["b64"]),
                                dtype=np.dtype(p["dtype"]))
            out.append(arr.reshape(p["shape"]))
    return tuple(out)


def snapshot_digest(snap: dict) -> str:
    """Canonical blake2b integrity digest over a snapshot's semantic
    content (ISSUE 15 satellite): version, tokens, and every page's
    index/source plus each plane's dtype, shape and raw bytes — the
    SAME value whether the planes are live numpy arrays (in-process
    transfer) or their base64 wire encoding, so a digest stamped at
    export survives the codec and any truncation/corruption in between
    is detected at import."""
    h = hashlib.blake2b(digest_size=16)
    h.update(f"v{snap.get('version')}".encode())
    h.update(b"|t")
    h.update(",".join(str(int(t)) for t in snap.get("tokens", ()))
             .encode())
    pages = sorted(snap.get("pages", ()), key=lambda p: int(p["index"]))
    for pg in pages:
        h.update(f"|p{int(pg['index'])}:{pg.get('source', '')}"
                 .encode())
        for plane in _decode_planes(pg["planes"]):
            plane = np.ascontiguousarray(plane)
            h.update(f"{plane.dtype}{list(plane.shape)}".encode())
            h.update(plane.tobytes())
    return h.hexdigest()


def to_wire(snap: dict) -> dict:
    """A JSON-serializable copy of a snapshot (planes base64-encoded),
    integrity-stamped: a ``digest`` computed at export rides the wire so
    the importer can reject corrupt/truncated bytes before touching its
    allocator (hand-built snapshots get theirs stamped here)."""
    out = dict(snap)
    if "digest" not in out:
        out["digest"] = snapshot_digest(snap)
    out["pages"] = [{**pg, "planes": _encode_planes(pg["planes"])}
                    for pg in snap["pages"]]
    return out


def from_wire(snap: dict) -> dict:
    """Decode a wire snapshot back to live numpy planes (idempotent on
    an already-decoded snapshot)."""
    out = dict(snap)
    out["pages"] = [{**pg, "planes": _decode_planes(pg["planes"])}
                    for pg in snap.get("pages", ())]
    return out


# ---------------------------------------------------------------------------
# export
# ---------------------------------------------------------------------------

def export_session(engine, req_id: Optional[int] = None,
                   tokens: Optional[Sequence[int]] = None) -> dict:
    """Serialize one session's KV as a migration snapshot.

    ``req_id``: an IN-FLIGHT request — pages come from its block table
    (device readback of exactly the full pages its write cursor has
    covered), tokens are its prompt + drained output, and the snapshot
    carries the remaining generation budget so the successor can resume
    at the exact token offset.  Call on the engine thread only (it
    drains the pending window first so the output/position books are
    current).

    ``tokens``: a PARKED session — pages come from the prefix-cache
    chain matching the token history; spilled chain nodes ship their
    host-ring bytes directly (no swap-in).
    """
    _refuse_unsnapshotted(engine)
    if (req_id is None) == (tokens is None):
        raise ValueError("export_session takes exactly one of "
                         "req_id= or tokens=")
    mm = _MigrationMetrics.get()
    snap = {"version": SNAP_VERSION, "geometry": _geometry(engine),
            "pages": []}
    page = engine.g.page_size
    if req_id is not None:
        if engine._pending:
            engine._drain()          # sync the output/position books
        slot = next((b for b in range(engine.B)
                     if engine.slot_req[b] is not None
                     and engine.slot_req[b].req_id == req_id), None)
        if slot is None:
            raise MigrationError(f"request {req_id} is not in-flight")
        req = engine.slot_req[slot]
        # positions = tokens whose KV is materialized (the device's
        # write cursor; the last emitted token's KV is always pending)
        _obs.count_sync()
        n_ctx = int(np.asarray(engine.positions)[slot])
        toks = list(req.prompt) + list(req.output)
        n_full = min(n_ctx, len(toks)) // page
        pages = engine.g.cache.allocator.page_list(req_id)[:n_full]
        for i, pid in enumerate(pages):
            snap["pages"].append({"index": i, "source": "device",
                                  "planes": _page_planes(engine, pid)})
        snap.update(tokens=toks, prompt_len=len(req.prompt),
                    emitted=list(req.output),
                    max_new_tokens=req.max_new_tokens,
                    n_ctx=n_ctx, trace_id=req.trace_id)
    else:
        cache = engine.prefix_cache
        if cache is None:
            raise MigrationError("token-chain export needs the prefix "
                                 "cache (FLAGS_prefix_cache)")
        toks = list(tokens)
        for i, node in enumerate(cache.chain(toks)):
            if node.spill is not None:
                # spilled page: the bytes already live in host RAM —
                # ship the ring slot's planes directly, no swap-in
                planes = _wire_planes(engine.spill.peek(node.spill))
                snap["pages"].append({"index": i, "source": "spill",
                                      "planes": planes})
            elif node.ready:
                snap["pages"].append({"index": i, "source": "device",
                                      "planes": _page_planes(engine,
                                                             node.page)})
            else:
                break                # pending: producer still writing
        n_full = len(snap["pages"])
        snap.update(tokens=toks, prompt_len=len(toks), emitted=[],
                    max_new_tokens=0, n_ctx=n_full * page, trace_id=None)
    # per-request RNG state (ISSUE 15 satellite): the engine's sampling
    # is positionally keyed — fold_in(key(seed), token index) — so the
    # whole per-request "key state" is the seed + the derivation marker;
    # a successor with the identical config resumes the sampled stream
    # seed-deterministically from the exact token offset
    gc = engine.gen_cfg
    snap["sampling"] = {"do_sample": bool(gc.do_sample),
                        "seed": int(gc.seed),
                        "temperature": float(gc.temperature),
                        "top_k": int(gc.top_k),
                        "top_p": float(gc.top_p),
                        "positional": True}
    # integrity stamp (ISSUE 15 satellite): importers verify before
    # touching their allocator — corrupt or truncated bytes are
    # REJECTED, never half-installed
    snap["digest"] = snapshot_digest(snap)
    mm.exports.inc()
    mm.pages_out.inc(len(snap["pages"]))
    mc = _engine_counts(engine)
    mc["migration_exports"] += 1
    mc["migration_exported_pages"] += len(snap["pages"])
    return snap


def export_all(engine) -> List[dict]:
    """Snapshot every in-flight session (the drain-migration bulk path).
    Per-session isolation: one failed export is counted and skipped, the
    rest still ship."""
    if engine._pending:
        engine._drain()
    snaps = []
    for b in range(engine.B):
        req = engine.slot_req[b]
        if req is None or req.done:
            continue
        try:
            snaps.append(export_session(engine, req_id=req.req_id))
        except Exception:
            _MigrationMetrics.get().aborts.inc()
            _engine_counts(engine)["migration_aborts"] += 1
    return snaps


# ---------------------------------------------------------------------------
# import
# ---------------------------------------------------------------------------

def _uploader(engine):
    """The donating page-upload program.  One per engine, shared with
    the spill tier's (same function, same shapes) when spill is on —
    the spill pool warmed it at engine init; ``warm()`` covers the
    spill-off case at server warmup so a live import never compiles."""
    up = getattr(engine, "_mig_upload", None)
    if up is None:
        sp = engine.spill
        # make_upload_program re-shards on install under tensor-parallel
        # pools: snapshot page planes stay host-global on the wire (one
        # digest at any tp), each shard scatters only its kv-head block
        up = sp._upload if sp is not None else make_upload_program(
            engine.g.cache)
        engine._mig_upload = up
    return up


def warm(engine) -> None:
    """Compile the upload program with an out-of-range page id (every
    scatter write drops) so the first real import is dispatch-only."""
    _refuse_unsnapshotted(engine)
    cache = engine.g.cache
    with _obs.startup.program("jit_pool_swap_in"):
        cache.update(*_uploader(engine)(
            cache.arrays, jnp.int32(cache.allocator.num_pages),
            cache.page_plane_zeros()))


def import_session(engine, snap: dict, resume: bool = False) -> dict:
    """Install one snapshot's pages into this engine's prefix-cache
    index.  Each page either already exists on the chain (skipped — a
    concurrent admission or an earlier import beat us) or is acquired
    fresh (``acquire_page`` reclaims idle cached pages under pressure),
    uploaded by the pre-warmed donating program, and indexed as a READY
    idle node.  On ANY mid-transfer failure the in-flight page's
    reference is released and the pages already linked stay behind as
    complete, valid cache entries — a partial transfer leaves zero
    dangling allocator refs.

    ``resume=True`` additionally submits the continuation request (the
    full token history as prompt, the remaining budget as max_new) on
    this engine — its admission rides the just-imported chain, so decode
    resumes at the exact token offset with only the partial-page tail
    re-prefilled.  Returns ``{"imported", "skipped", "pages",
    "resume_req_id"}``.
    """
    _refuse_unsnapshotted(engine)
    cache = engine.prefix_cache
    if cache is None:
        raise MigrationError("import needs the prefix cache "
                             "(FLAGS_prefix_cache) on the successor")
    if snap.get("version") != SNAP_VERSION:
        raise MigrationError(f"unknown snapshot version "
                             f"{snap.get('version')!r}")
    _check_geometry(engine, snap)
    mm = _MigrationMetrics.get()
    mc = _engine_counts(engine)
    # integrity check (ISSUE 15 satellite): a digest-stamped snapshot
    # whose bytes no longer hash to it (truncated page list, corrupt
    # plane, bit-rot on the wire) is REJECTED before any allocator
    # state changes — zero pages installed, zero refs to leak.  Legacy
    # unstamped snapshots keep the structural contiguous-chain
    # semantics (a hand-built partial snapshot is not corruption).
    snap = from_wire(snap)   # decode planes ONCE (idempotent on live
    #                          snapshots): the digest check and the
    #                          install loop below share the arrays
    want = snap.get("digest")
    if want is not None and snapshot_digest(snap) != want:
        mm.rejected.inc()
        mc["migration_rejected"] += 1
        raise MigrationError(
            "snapshot integrity digest mismatch: the transfer was "
            "corrupted or truncated in flight; nothing was installed")
    alloc = engine.g.cache.allocator
    page = engine.g.page_size
    toks = list(snap["tokens"])
    up = _uploader(engine)
    imported = skipped = 0
    node = None                      # None = chain root
    pages = sorted(snap.get("pages", ()), key=lambda p: int(p["index"]))
    try:
        for pg in pages:
            i = int(pg["index"])
            if i != imported + skipped:
                break                # non-contiguous: chain semantics end
            key = tuple(toks[i * page:(i + 1) * page])
            if len(key) < page:
                break
            parent = node if node is not None else cache._root
            child = parent.children.get(key)
            if child is not None:
                node = child         # already indexed (live, spilled or
                skipped += 1         # pending): keep walking the chain
                continue
            pid = alloc.acquire_page()
            try:
                planes = _pool_planes(pg["planes"])    # decoded up front
                engine.g.cache.update(*up(
                    engine.g.cache.arrays, jnp.int32(pid),
                    tuple(jnp.asarray(p) for p in planes)))
                node = cache.install_node(node, key, pid)
            except BaseException:
                # the one in-flight page: give its reference back so an
                # aborted transfer leaves the allocator books balanced
                alloc.release_page(pid)
                raise
            imported += 1
    except Exception:
        mm.aborts.inc()
        mc["migration_aborts"] += 1
        mm.pages_in.inc(imported)
        mc["migration_imported_pages"] += imported
        raise
    mm.imports.inc()
    mm.pages_in.inc(imported)
    mc["migration_imports"] += 1
    mc["migration_imported_pages"] += imported
    out = {"imported": imported, "skipped": skipped,
           "pages": len(pages), "resume_req_id": None}
    # resume is meaningful only for an in-flight snapshot with budget
    # left (a parked session has nothing to continue)
    remaining = int(snap.get("max_new_tokens", 0) or 0) \
        - len(snap.get("emitted") or ())
    if resume and remaining >= 1:
        samp = snap.get("sampling")
        if isinstance(samp, dict) and samp.get("do_sample"):
            # sampled resume (ISSUE 15 satellite): seed-deterministic
            # only when this engine's positional sampling config is
            # IDENTICAL to the exporter's — otherwise keep the pages
            # (they are valid prefix-cache entries either way) but skip
            # the continuation rather than silently fork the stream
            gc = engine.gen_cfg
            mine = {"do_sample": bool(gc.do_sample),
                    "seed": int(gc.seed),
                    "temperature": float(gc.temperature),
                    "top_k": int(gc.top_k),
                    "top_p": float(gc.top_p),
                    "positional": True}
            if mine != samp:
                out["resume_skipped"] = "sampling-mismatch"
                return out
        req = engine.submit(toks, max_new_tokens=remaining,
                            trace_id=snap.get("trace_id"))
        out["resume_req_id"] = req.req_id
    return out


def import_sessions(engine, snaps: Sequence[dict],
                    resume: bool = False) -> dict:
    """Bulk import with per-snapshot isolation (the drain-migration
    receive path): one malformed/oversized snapshot is counted as an
    abort, the rest still install."""
    total = {"sessions": 0, "imported": 0, "skipped": 0, "aborted": 0}
    for snap in snaps:
        try:
            r = import_session(engine, snap, resume=resume)
        except Exception:
            total["aborted"] += 1
            continue
        total["sessions"] += 1
        total["imported"] += r["imported"]
        total["skipped"] += r["skipped"]
    return total
