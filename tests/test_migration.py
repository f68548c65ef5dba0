"""Zero-loss session continuity (ISSUE 14): KV export/import, the
/migratez transfer endpoints, digest DELTA sync, the router's journaled
failover resume, and drain-triggered fleet migration.

The load-bearing contract, asserted at every layer: a migrated /
resumed session's outputs bit-match a no-fault oracle, migrated pages
are IMPORTED (prefix hits), never recomputed, and an aborted transfer
leaves zero dangling allocator references behind.
"""

import asyncio
import json
import time

import pytest

import paddle_tpu as paddle
from paddle_tpu import flags
from paddle_tpu import observability as obs
from paddle_tpu.inference import ContinuousBatchingEngine, GenerationConfig
from paddle_tpu.inference import migration as mig
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.router import InprocReplica, ReplicaState, RouterServer
from paddle_tpu.serving import ServingServer

from test_serving_http import (MemWriter, completion_body, http_bytes,
                               split_response, sse_chunks)


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig.tiny())


def _engine(model, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("gen", GenerationConfig(max_new_tokens=24))
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_bucket", 8)
    kw.setdefault("prefix_cache", True)
    return ContinuousBatchingEngine(model, **kw)


PROMPT = list(range(1, 14))


@pytest.fixture(scope="module")
def oracle(model):
    eng = _engine(model)
    rid = eng.add_request(list(PROMPT))
    return eng.run()[rid]


def _books_balanced(eng):
    """No dangling allocator refs: with no active sequences, every
    allocated page is accounted for by the prefix-cache index."""
    alloc = eng.g.cache.allocator
    assert alloc.stats()["active_seqs"] == 0
    assert alloc.pages_in_use == eng.prefix_cache.cached_pages()


# ---------------------------------------------------------------------------
# layer 1: engine-level export / import
# ---------------------------------------------------------------------------

def test_export_import_resume_bit_matches_oracle(model, oracle):
    """Export a mid-stream session, import on a second engine, resume —
    the joined output equals the no-fault oracle and the resumed
    prefill skips every migrated page (import, not recompute)."""
    a = _engine(model)
    req = a.submit(list(PROMPT))
    for _ in range(64):
        a.step()
        if len(req.output) >= 10:
            break
    a._drain()
    assert not req.done and len(req.output) >= 10
    snap = mig.export_session(a, req_id=req.req_id)
    assert snap["pages"] and snap["n_ctx"] >= 8
    assert snap["emitted"] == req.output

    b = _engine(model)
    saved0 = b.g.cache.allocator.prefix_tokens_saved
    res = mig.import_session(b, snap, resume=True)
    assert res["imported"] == len(snap["pages"])
    assert res["skipped"] == 0
    out = b.run()[res["resume_req_id"]]
    assert snap["emitted"] + out == oracle
    # migrated pages were HIT, not recomputed
    saved = b.g.cache.allocator.prefix_tokens_saved - saved0
    assert saved >= res["imported"] * b.g.page_size
    assert b.stats()["migration_imported_pages"] == res["imported"]
    assert a.stats()["migration_exported_pages"] == len(snap["pages"])


def test_export_requires_exactly_one_selector(model):
    eng = _engine(model)
    with pytest.raises(ValueError):
        mig.export_session(eng)
    with pytest.raises(ValueError):
        mig.export_session(eng, req_id=0, tokens=[1, 2])
    with pytest.raises(mig.MigrationError):
        mig.export_session(eng, req_id=12345)     # not in-flight


def test_wire_codec_roundtrip(model):
    """to_wire/from_wire survive a real JSON hop byte-for-byte, on the
    int8 plane (scales included)."""
    import numpy as np
    eng = _engine(model, cache_dtype="int8")
    rid = eng.add_request(list(PROMPT), max_new_tokens=6)
    eng.run()
    snap = mig.export_session(eng, tokens=list(PROMPT))
    assert snap["pages"]
    wire = json.loads(json.dumps(mig.to_wire(snap)))
    back = mig.from_wire(wire)
    for pg, pg2 in zip(snap["pages"], back["pages"]):
        for p, p2 in zip(pg["planes"], pg2["planes"]):
            assert p.dtype == p2.dtype and p.shape == p2.shape
            assert np.array_equal(p, p2)
    assert back["geometry"]["dtype"] == "int8"


def test_import_geometry_mismatch_rejected(model):
    a = _engine(model)
    rid = a.add_request(list(PROMPT), max_new_tokens=4)
    a.run()
    snap = mig.export_session(a, tokens=list(PROMPT))
    b = _engine(model, page_size=16)
    with pytest.raises(mig.MigrationError):
        mig.import_session(b, snap)
    _books_balanced(b)


def test_import_without_prefix_cache_rejected(model):
    a = _engine(model)
    a.add_request(list(PROMPT), max_new_tokens=4)
    a.run()
    snap = mig.export_session(a, tokens=list(PROMPT))
    b = _engine(model, prefix_cache=False)
    with pytest.raises(mig.MigrationError):
        mig.import_session(b, snap)


def test_import_under_pool_pressure_evicts_never_deadlocks(model):
    """Satellite: an import into a full pool reclaims idle cached pages
    through the allocator's normal eviction seam and completes — it
    never deadlocks and never corrupts the books."""
    a = _engine(model, max_seq_len=64)
    req = a.submit(list(range(1, 25)), max_new_tokens=2)  # 3 full pages
    while not req.done:
        a.step()
    a._drain()
    snap = mig.export_session(a, tokens=list(range(1, 25)))
    assert len(snap["pages"]) == 3

    # B: a tiny pool, pre-filled with idle cached pages
    b = _engine(model, max_seq_len=64, num_pages=4)
    r0 = b.add_request(list(range(40, 57)), max_new_tokens=4)  # 2 pages idle
    b.run()
    evicted0 = b.g.cache.allocator.evicted_pages
    res = mig.import_session(b, snap)
    assert res["imported"] == 3
    assert b.g.cache.allocator.evicted_pages > evicted0   # import evicted
    _books_balanced(b)


def test_abort_mid_transfer_leaves_no_refs(model, oracle):
    """Satellite: a transfer that dies on page k leaves pages [0, k)
    installed as valid cache entries and NOTHING dangling — the books
    balance and a retry completes (skipping what landed)."""
    a = _engine(model)
    req = a.submit(list(PROMPT))
    for _ in range(64):
        a.step()
        if len(req.output) >= 12:
            break
    a._drain()
    snap = mig.export_session(a, req_id=req.req_id)
    assert len(snap["pages"]) >= 3

    b = _engine(model)
    alloc = b.g.cache.allocator
    real = alloc.acquire_page
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] == 3:
            raise MemoryError("chaos: transfer died on page 3")
        return real()

    alloc.acquire_page = flaky
    aborts0 = b.stats().get("migration_aborts", 0)
    with pytest.raises(MemoryError):
        mig.import_session(b, snap)
    alloc.acquire_page = real
    assert b.stats()["migration_aborts"] == aborts0 + 1
    assert b.stats()["migration_imported_pages"] == 2
    _books_balanced(b)                            # nothing leaked
    # retry: the two landed pages are skipped, the rest import
    res = mig.import_session(b, snap)
    assert res["skipped"] == 2
    assert res["imported"] == len(snap["pages"]) - 2
    _books_balanced(b)
    # and the resumed session still bit-matches
    r = b.submit(list(PROMPT) + list(snap["emitted"]),
                 max_new_tokens=24 - len(snap["emitted"]))
    while not r.done:
        b.step()
    b._drain()
    assert snap["emitted"] + r.output == oracle


def test_partial_snapshot_imports_contiguous_prefix(model):
    """An UNSTAMPED truncated page list (a hand-built partial snapshot,
    digest stripped) imports as a shorter contiguous chain;
    non-contiguous tails are dropped.  (A digest-stamped truncation is
    REJECTED instead — see the integrity tests below.)"""
    a = _engine(model)
    req = a.submit(list(range(1, 34)), max_new_tokens=2)  # 4 full pages
    while not req.done:
        a.step()
    a._drain()
    snap = mig.export_session(a, tokens=list(range(1, 34)))
    n = len(snap["pages"])
    assert n >= 4
    cut = dict(snap, pages=snap["pages"][: n // 2])
    cut.pop("digest")                 # hand-built partial, not corruption
    b = _engine(model)
    res = mig.import_session(b, cut)
    assert res["imported"] == n // 2
    _books_balanced(b)
    # a gap in the page list ends the chain (no orphan nodes)
    gappy = dict(snap, pages=[snap["pages"][0], snap["pages"][2]])
    gappy.pop("digest")
    c = _engine(model)
    res = mig.import_session(c, gappy)
    assert res["imported"] == 1
    _books_balanced(c)


def test_corrupt_snapshot_rejected_zero_refs(model):
    """ISSUE 15 satellite: export stamps a blake2b integrity digest;
    import verifies it BEFORE touching the allocator.  A truncated or
    bit-flipped snapshot is rejected — MigrationError, nothing
    installed, the allocator books balance, and the
    serving.kv.migration_rejected counter says so."""
    import numpy as np
    a = _engine(model)
    req = a.submit(list(range(1, 34)), max_new_tokens=2)
    while not req.done:
        a.step()
    a._drain()
    snap = mig.export_session(a, tokens=list(range(1, 34)))
    assert snap["digest"] == mig.snapshot_digest(snap)
    # the wire codec preserves both the digest and its validity
    wire = mig.to_wire(snap)
    assert wire["digest"] == snap["digest"]
    assert mig.snapshot_digest(mig.from_wire(wire)) == snap["digest"]

    rej0 = int(obs.metrics.counter("serving.kv.migration_rejected").value)
    b = _engine(model)
    free0 = b.g.cache.allocator.free_pages

    # truncated page list: the partial_transfer chaos shape
    cut = dict(snap, pages=snap["pages"][:2])
    with pytest.raises(mig.MigrationError, match="digest"):
        mig.import_session(b, cut)
    # corrupt plane bytes: bit-rot on the wire
    bad = mig.from_wire(json.loads(json.dumps(wire)))
    planes = list(bad["pages"][0]["planes"])
    flipped = np.array(planes[0], copy=True)
    flipped.flat[0] = np.bitwise_xor(
        flipped.flat[0], np.array(1, flipped.dtype)) \
        if flipped.dtype.kind in "iu" else flipped.flat[0] + 1.0
    planes[0] = flipped
    bad["pages"][0] = dict(bad["pages"][0], planes=tuple(planes))
    with pytest.raises(mig.MigrationError, match="digest"):
        mig.import_session(b, bad)

    # zero pages installed, zero refs leaked, rejections counted
    assert b.g.cache.allocator.free_pages == free0
    assert b.prefix_cache.cached_pages() == 0
    assert b.stats()["migration_rejected"] == 2
    assert int(obs.metrics.counter(
        "serving.kv.migration_rejected").value) == rej0 + 2
    # the intact snapshot still imports fine afterwards
    res = mig.import_session(b, snap)
    assert res["imported"] == len(snap["pages"])
    _books_balanced(b)


# ---------------------------------------------------------------------------
# digest delta sync (satellite 1)
# ---------------------------------------------------------------------------

def test_prefix_cache_digest_delta_unit(model):
    eng = _engine(model)
    cache = eng.prefix_cache
    assert cache.digest_epoch == 0
    assert cache.digest_delta(0) == ([], [])
    rid = eng.add_request(list(range(1, 26)), max_new_tokens=2)  # 3 pages
    eng.run()
    e1 = cache.digest_epoch
    assert e1 == 3
    adds, dels = cache.digest_delta(0)
    assert len(adds) == 3 and dels == []
    assert set(adds) == set(cache.digest(100))
    # future epoch / unknown history -> resync
    assert cache.digest_delta(e1 + 5) is None
    assert cache.digest_delta(e1) == ([], [])


def test_prefix_cache_digest_delta_eviction_and_overflow(model):
    flags.set_flags({"prefix_digest_log": 4})
    try:
        eng = _engine(model, max_seq_len=64, num_pages=5)
        cache = eng.prefix_cache
        eng.add_request(list(range(1, 18)), max_new_tokens=2)  # 2 pages
        eng.run()
        base = cache.digest_epoch
        # pressure: force eviction of the idle pages
        eng.add_request(list(range(30, 47)), max_new_tokens=8)
        eng.run()
        adds, dels = cache.digest_delta(base)
        assert dels                      # evictions advertised as dels
        # a client older than the 4-entry log must resync
        assert cache.digest_delta(0) is None
    finally:
        flags.set_flags({"prefix_digest_log": 4096})


def test_engine_prefix_digest_modes(model):
    eng = _engine(model)
    eng.add_request(list(range(1, 18)), max_new_tokens=2)
    eng.run()
    full = eng.prefix_digest()
    assert full["mode"] == "full" and full["hashes"]
    gen, epoch = full["gen"], full["epoch"]
    d = eng.prefix_digest(since=f"{gen}:{epoch}")
    assert d["mode"] == "delta" and d["adds"] == [] and d["dels"] == []
    # gen mismatch (another replica life) -> full
    assert eng.prefix_digest(since=f"bogus:{epoch}")["mode"] == "full"
    # malformed epoch -> full
    assert eng.prefix_digest(since=f"{gen}:x")["mode"] == "full"


def test_replica_state_applies_digest_deltas():
    class _C:
        id = "r0"

        def describe(self):
            return {"id": "r0"}

    obs.reset("router.")
    s = ReplicaState(_C())
    base = {"ready": True, "engine": {"waiting": 0, "slots_busy": 0}}
    s.apply_statusz({**base, "prefix_digest": {
        "page_size": 8, "gen": "g1", "epoch": 2, "mode": "full",
        "hashes": ["a", "b"]}})
    assert s.digest == frozenset(["a", "b"]) and s.digest_epoch == 2
    assert "digest_since=g1:2" in s.statusz_path()
    s.apply_statusz({**base, "prefix_digest": {
        "page_size": 8, "gen": "g1", "epoch": 5, "mode": "delta",
        "adds": ["c"], "dels": ["a"]}})
    assert s.digest == frozenset(["b", "c"]) and s.digest_epoch == 5
    # gen flip (replica restarted): delta ignored, full set replaces
    s.apply_statusz({**base, "prefix_digest": {
        "page_size": 8, "gen": "g2", "epoch": 1, "mode": "full",
        "hashes": ["z"]}})
    assert s.digest == frozenset(["z"]) and s.digest_gen == "g2"
    assert int(obs.metrics.counter("router.digest_sync",
                                   mode="delta").value) == 1
    assert int(obs.metrics.counter("router.digest_sync",
                                   mode="full").value) == 2


def test_router_poll_uses_delta_after_first_full(model):
    """End to end: the second statusz poll asks with digest_since and
    gets a delta; placement still scores the full held set."""
    obs.reset("router.")
    srv = ServingServer(_engine(model), slo=False,
                        flight_recorder=False).start()
    try:
        rep = InprocReplica("r0", srv)
        router = RouterServer([rep], health_interval_s=1e9)

        async def main():
            await router.poll_replicas()
            # grow the index between polls
            st, _, _ = await _do(router, "POST", "/v1/completions",
                                 completion_body(list(range(1, 18)), 4))
            assert st == 200
            await router.poll_replicas()
            await router.poll_replicas()
            return router.states[0]

        st = asyncio.run(main())
        assert st.digest                 # router holds the hashes
        assert int(obs.metrics.counter("router.digest_sync",
                                       mode="full").value) == 1
        assert int(obs.metrics.counter("router.digest_sync",
                                       mode="delta").value) >= 2
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# serving transfer endpoints (layer 2)
# ---------------------------------------------------------------------------

async def _do(server_or_router, method, path, body=None, headers=()):
    head = [f"{method} {path} HTTP/1.1", "Host: test"]
    head += [f"{k}: {v}" for k, v in headers]
    body = body or b""
    head.append(f"Content-Length: {len(body)}")
    raw = ("\r\n".join(head) + "\r\n\r\n").encode() + body
    r = asyncio.StreamReader()
    r.feed_data(raw)
    r.feed_eof()
    w = MemWriter()
    await server_or_router.handle(r, w)
    return split_response(w.buf)


def test_migratez_export_import_endpoints(model):
    """The HTTP transfer plane: export on A, import on B, follow-up
    traffic on B hits the migrated pages; truncated bodies abort with
    nothing installed."""
    a = ServingServer(_engine(model), slo=False,
                      flight_recorder=False).start()
    b = ServingServer(_engine(model), slo=False,
                      flight_recorder=False).start()
    try:
        async def main():
            st, _, resp = await _do(a, "POST", "/v1/completions",
                                    completion_body(list(PROMPT), 12))
            toks = json.loads(resp)["choices"][0]["token_ids"]
            full = list(PROMPT) + toks
            st, _, resp = await _do(
                a, "POST", "/migratez/export",
                json.dumps({"tokens": full}).encode())
            assert st == 200
            doc = json.loads(resp)
            assert doc["sessions"] and doc["sessions"][0]["pages"]
            wire = json.dumps({"sessions": doc["sessions"]}).encode()
            # truncated at an arbitrary byte: 400, nothing installed
            st, _, _ = await _do(b, "POST", "/migratez/import",
                                 wire[: len(wire) // 2])
            assert st == 400
            assert b.engine.prefix_cache.cached_pages() == 0
            st, _, resp = await _do(b, "POST", "/migratez/import", wire)
            assert st == 200
            res = json.loads(resp)
            assert res["imported"] >= 1 and res["aborted"] == 0
            # the migrated session's next turn hits on B
            st, _, resp = await _do(b, "POST", "/v1/completions",
                                    completion_body(list(PROMPT), 12))
            assert st == 200
            assert json.loads(resp)["choices"][0]["token_ids"] == toks
            return res

        res = asyncio.run(main())
        assert b.engine.stats()["prefix_hits"] >= 1
        assert b.engine.stats()["migration_imported_pages"] == \
            res["imported"]
        _books_balanced(b.engine)
    finally:
        a.close()
        b.close()


def test_migratez_import_refused_while_draining(model):
    b = ServingServer(_engine(model), slo=False,
                      flight_recorder=False).start()
    try:
        b.begin_drain()
        st, _, _ = asyncio.run(_do(
            b, "POST", "/migratez/import",
            json.dumps({"sessions": []}).encode()))
        assert st == 503
    finally:
        b.close()


def test_migratez_export_bad_body(model):
    a = ServingServer(_engine(model), slo=False,
                      flight_recorder=False).start()
    try:
        st, _, _ = asyncio.run(_do(a, "POST", "/migratez/export",
                                   b"{not json"))
        assert st == 400
        st, _, _ = asyncio.run(_do(a, "POST", "/migratez/export",
                                   json.dumps({}).encode()))
        assert st == 400                  # no selector
    finally:
        a.close()


def test_run_on_engine_seam(model):
    srv = ServingServer(_engine(model), slo=False,
                        flight_recorder=False).start()
    try:
        assert srv.run_on_engine(lambda eng: eng.B) == 2
        with pytest.raises(ZeroDivisionError):
            srv.run_on_engine(lambda eng: 1 / 0)
    finally:
        srv.close()
    with pytest.raises(RuntimeError):
        srv.run_on_engine(lambda eng: eng.B)      # engine down


# ---------------------------------------------------------------------------
# router: unary resume (satellite 2)
# ---------------------------------------------------------------------------

def test_unary_post_dispatch_death_resumes(model, oracle):
    """The PR 7 asymmetry, fixed: a unary request whose replica dies
    after dispatch re-runs on a greedy survivor and returns 200 with
    the oracle tokens — 502 only when replay is impossible."""
    obs.reset("router.")
    servers = [ServingServer(_engine(model), slo=False,
                             flight_recorder=False).start()
               for _ in range(2)]
    reps = [InprocReplica(f"r{i}", s) for i, s in enumerate(servers)]
    router = RouterServer(reps, health_interval_s=1e9)
    try:
        async def main():
            # place one warm unary request to learn the replica states
            st, h, _ = await _do(router, "POST", "/v1/completions",
                                 completion_body([9, 8, 7], 4))
            assert st == 200
            body = completion_body(list(PROMPT), 24)
            r = asyncio.StreamReader()
            r.feed_data(http_bytes("POST", "/v1/completions", body))
            r.feed_eof()
            w = MemWriter()
            task = asyncio.create_task(router.handle(r, w))
            # kill whichever replica is mid-generation on this request
            deadline = time.perf_counter() + 60
            victim = None
            while victim is None:
                assert time.perf_counter() < deadline
                for rep in reps:
                    if any(b is not None
                           for b in rep.server.engine.slot_req) and \
                            rep.server.engine.has_work():
                        victim = rep
                        break
                await asyncio.sleep(0.002)
            victim.kill()
            await asyncio.wait_for(task, 60)
            return split_response(w.buf)

        status, headers, body = asyncio.run(main())
        assert status == 200
        assert json.loads(body)["choices"][0]["token_ids"] == oracle
        assert int(obs.metrics.counter("router.resumes",
                                       outcome="unary").value) == 1
    finally:
        for s in servers:
            s.close()


def test_unary_death_without_journal_is_502(model):
    """Replay impossible (resume disabled): the unary post-dispatch
    death keeps its PR 7 502."""
    obs.reset("router.")
    flags.set_flags({"router_failover_resume": False})
    try:
        servers = [ServingServer(_engine(model), slo=False,
                                 flight_recorder=False).start()
                   for _ in range(2)]
        reps = [InprocReplica(f"r{i}", s) for i, s in enumerate(servers)]
        router = RouterServer(reps, health_interval_s=1e9)
        try:
            async def main():
                body = completion_body(list(PROMPT), 24)
                r = asyncio.StreamReader()
                r.feed_data(http_bytes("POST", "/v1/completions", body))
                r.feed_eof()
                w = MemWriter()
                task = asyncio.create_task(router.handle(r, w))
                deadline = time.perf_counter() + 60
                victim = None
                while victim is None:
                    assert time.perf_counter() < deadline
                    for rep in reps:
                        if any(b is not None
                               for b in rep.server.engine.slot_req) and \
                                rep.server.engine.has_work():
                            victim = rep
                            break
                    await asyncio.sleep(0.002)
                victim.kill()
                await asyncio.wait_for(task, 60)
                return split_response(w.buf)

            status, _, _ = asyncio.run(main())
            assert status == 502
        finally:
            for s in servers:
                s.close()
    finally:
        flags.set_flags({"router_failover_resume": True})


def test_journal_bounds_cap_memory():
    """The journal's two bounds: a stream past the per-entry token cap
    stops recording entirely (not just stops being resumable), and the
    LRU cap marks evicted entries non-resumable."""
    from paddle_tpu.router.journal import SessionJournal
    j = SessionJournal(cap=3, max_tokens=10)
    e = j.begin("t0", None, [1, 2], {"max_tokens": 100})
    j.record(e, range(8))
    assert e.resumable and len(e.emitted) == 8
    j.record(e, range(5))                 # crosses the cap
    assert not e.resumable and e.emitted == []
    j.record(e, range(1000))              # recording has STOPPED
    assert e.emitted == []
    first = j.begin("t1", None, [1], {})
    for i in range(3):
        j.begin(f"t{i + 2}", None, [1], {})
    assert len(j) == 3                    # LRU cap holds
    assert not first.resumable            # evicted -> PR 7 contract


# ---------------------------------------------------------------------------
# fleet: drain-triggered migration + chaos (layer 4)
# ---------------------------------------------------------------------------

STREAM_TOKENS = 112          # 5 prompt tokens + 112 of max_seq_len 128


def _fleet(model, chaos=None, **sup_kw):
    from paddle_tpu.fleet import FleetSupervisor, InprocReplicaHandle

    def factory():
        eng = _engine(model,
                      gen=GenerationConfig(max_new_tokens=STREAM_TOKENS))
        eng.add_request(list(range(1, 13)), max_new_tokens=4)
        eng.run()                          # warm both step programs
        return eng

    router = RouterServer([], allow_empty=True, health_interval_s=1e9,
                          dead_after=2, poll_timeout_s=0.5)
    wrap = chaos.wrap if chaos is not None else None
    sup_kw.setdefault("hot_ticks", 10**9)
    sup_kw.setdefault("cold_ticks", 10**9)
    sup_kw.setdefault("cooldown_s", 0.0)
    sup_kw.setdefault("drain_timeout_s", 30.0)
    sup = FleetSupervisor(
        router,
        lambda rid: InprocReplicaHandle(rid, factory, client_wrap=wrap),
        target=2, min_replicas=1, max_replicas=3,
        on_spawn=(chaos.register_handle if chaos is not None else None),
        **sup_kw)
    return sup, router


async def _converge(sup, router, deadline_s=240.0):
    deadline = time.perf_counter() + deadline_s
    while True:
        sup.tick()
        await router.poll_replicas()
        if sup.converged() and \
                len(router._candidates()) == sup.target:
            return
        assert time.perf_counter() < deadline, sup.state()
        await asyncio.sleep(0.05)


async def _stream_on_each(sup, router, chaos_clients=None):
    """One in-flight stream per replica; returns the gathered tasks.

    The streams run to ``STREAM_TOKENS``, most of what ``max_seq_len``
    leaves: the caller drains a victim right after this returns, and
    both streams must still be in flight then.  At 32 tokens that was two
    drain periods of the engines' threads after the wait's condition;
    beside five other test workers one replica's stream ended before the
    other's had sent 12, and the wait ran out ("streams never started")."""
    tasks = [asyncio.ensure_future(_do(
        router, "POST", "/v1/completions",
        completion_body([10 + i, 3, 5, 7, 11], STREAM_TOKENS, stream=True),
        headers=(("X-Session-Id", f"sess{i}"),))) for i in range(2)]
    deadline = time.perf_counter() + 60
    while True:
        # wait until each replica's stream is well past its first full
        # page, so an export has at least one page to ship
        busy = [s for s in sup._slots
                if s.handle.server is not None
                and any(st.sent >= 12
                        for st in s.handle.server._live)]
        if len(busy) >= 2:
            return tasks
        assert time.perf_counter() < deadline, "streams never started"
        await asyncio.sleep(0.005)


def test_drain_migration_ships_sessions_to_successor(model):
    """Scale-down with live sessions: the victim exports its in-flight
    sessions' pages to the successor before draining; the sessions'
    streams finish clean, and the migrated prefix serves follow-up
    turns on the successor (import, not recompute — engine stats)."""
    obs.reset("fleet.")
    sup, router = _fleet(model)
    try:
        async def drive():
            sup.start()
            await _converge(sup, router)
            tasks = await _stream_on_each(sup, router)
            sup.set_target(1)
            sup.tick()                     # victim drains NOW
            draining = [s for s in sup._slots if s.state == "draining"]
            assert len(draining) == 1
            results = await asyncio.gather(*tasks)
            for st, _, bd in results:
                assert st == 200
                chunks = sse_chunks(bd)
                finishes = [c["choices"][0]["finish_reason"]
                            for c in chunks
                            if c["choices"][0]["finish_reason"]]
                assert finishes[-1] in ("stop", "length")
            await _converge(sup, router)
            return draining[0].handle.id

        victim_id = asyncio.run(drive())
        assert int(obs.metrics.counter("fleet.migrations",
                                       outcome="ok").value) == 1
        migrated = int(obs.metrics.counter("fleet.migrated_pages").value)
        assert migrated >= 1
        # the survivor holds the imported pages
        surv = sup._slots[0].handle
        assert surv.id != victim_id
        st = surv.server.engine.stats()
        assert st["migration_imports"] >= 1
        assert st["migration_imported_pages"] == migrated
        _books_balanced(surv.server.engine)
    finally:
        sup.shutdown(drain=False, timeout_s=5.0)


def test_chaos_migrate_interrupt_and_partial_transfer(model):
    """The drain-migration fault kinds: an interrupted transfer
    installs nothing and leaks nothing; a partial (truncated) transfer
    no longer matches its export-stamped integrity digest, so the
    importer REJECTS it (ISSUE 15: migration failed + migration_rejected
    counted, zero pages installed) — and neither ever blocks the drain
    itself."""
    from paddle_tpu.fleet import ChaosController, ChaosPlan, FaultEvent
    obs.reset("fleet.")
    plan = ChaosPlan([FaultEvent(100, "migrate_interrupt", "fs0"),
                      FaultEvent(100, "migrate_interrupt", "fs1")])
    chaos = ChaosController(plan)
    sup, router = _fleet(model, chaos=chaos)
    try:
        async def drive():
            sup.start()
            await _converge(sup, router)
            tasks = await _stream_on_each(sup, router)
            chaos.advance(100)             # arm the one-shot fault
            sup.set_target(1)
            sup.tick()
            results = await asyncio.gather(*tasks)
            assert all(st == 200 for st, _, _ in results)
            await _converge(sup, router)

        asyncio.run(drive())
        assert int(obs.metrics.counter("fleet.migrations",
                                       outcome="failed").value) == 1
        assert int(obs.metrics.counter("fleet.migrated_pages").value) == 0
        surv = sup._slots[0].handle
        assert surv.server.engine.stats().get("migration_imports", 0) == 0
        _books_balanced(surv.server.engine)
    finally:
        sup.shutdown(drain=False, timeout_s=5.0)

    # partial transfer: the truncated snapshots fail their integrity
    # digests — the successor rejects them all (nothing installed, no
    # refs leaked) and the drain still completes clean
    obs.reset("fleet.")
    rej0 = int(obs.metrics.counter("serving.kv.migration_rejected").value)
    plan = ChaosPlan([FaultEvent(100, "partial_transfer", "fs0"),
                      FaultEvent(100, "partial_transfer", "fs1")])
    chaos = ChaosController(plan)
    sup, router = _fleet(model, chaos=chaos)
    try:
        async def drive():
            sup.start()
            await _converge(sup, router)
            tasks = await _stream_on_each(sup, router)
            chaos.advance(100)
            sup.set_target(1)
            sup.tick()
            results = await asyncio.gather(*tasks)
            assert all(st == 200 for st, _, _ in results)
            await _converge(sup, router)

        asyncio.run(drive())
        assert int(obs.metrics.counter("fleet.migrations",
                                       outcome="failed").value) == 1
        assert int(obs.metrics.counter(
            "serving.kv.migration_rejected").value) > rej0
        surv = sup._slots[0].handle
        assert surv.server.engine.stats().get("migration_imports", 0) == 0
        _books_balanced(surv.server.engine)
    finally:
        sup.shutdown(drain=False, timeout_s=5.0)


# ---------------------------------------------------------------------------
# slow tier: ProcessReplicaHandle's HTTP /migratez path over real sockets
# (ROADMAP: the in-process path is the only tier-1-gated one)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_process_replica_http_migrate_path_end_to_end():
    """Two launcher-spawned replica processes: ProcessReplicaHandle
    exports every live session from A over POST /migratez/export and
    imports into B over /migratez/import — the wire codec, the
    export-stamped integrity digest, and the successor's import books
    all exercised over real sockets (plus a corrupt-transfer rejection
    on the same path)."""
    import http.client
    import os
    import socket
    import subprocess
    import sys

    from paddle_tpu.fleet import ProcessReplicaHandle

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    ports = [free_port(), free_port()]
    argv = lambda port: [
        sys.executable, "-m", "paddle_tpu.serving", "--port", str(port),
        "--max-batch", "2", "--max-seq-len", "256", "--page-size", "8",
        "--prefill-bucket", "16", "--max-new-tokens", "64",
        "--prefix-cache", "--seed", "0"]
    procs = [subprocess.Popen(argv(p),
                              env={**os.environ, "JAX_PLATFORMS": "cpu"})
             for p in ports]
    handles = [ProcessReplicaHandle(f"p{i}", "127.0.0.1", p)
               for i, p in enumerate(ports)]
    handles[0].proc, handles[1].proc = procs
    try:
        deadline = time.time() + 600
        while not all(h.ready() for h in handles):
            assert time.time() < deadline, "replicas never became ready"
            assert all(p.poll() is None for p in procs), \
                "a replica died during warmup"
            time.sleep(0.5)

        # a long stream holds a live session on A while we export it
        conn = http.client.HTTPConnection("127.0.0.1", ports[0],
                                          timeout=120)
        conn.request("POST", "/v1/completions", json.dumps(
            {"prompt": list(range(1, 18)), "max_tokens": 48,
             "stream": True}).encode())
        resp = conn.getresponse()
        assert resp.status == 200
        # wait for a couple of drained chunks so >= 1 full page exists
        got = bytearray()
        while got.count(b"data: ") < 3:
            line = resp.fp.readline()
            assert line, "stream ended before enough chunks"
            got += line

        snaps = handles[0].export_sessions()
        assert len(snaps) == 1
        snap = snaps[0]
        assert snap["digest"]              # integrity-stamped on the wire
        assert snap["pages"], "no pages exported"
        assert snap["sampling"]["do_sample"] is False

        # corrupt transfer: truncated page list must be REJECTED by B
        cut = dict(snap, pages=snap["pages"][:1]) \
            if len(snap["pages"]) > 1 else None
        if cut is not None:
            res = handles[1].import_sessions([cut])
            assert res["sessions"] == 0 and res["aborted"] == 1

        # the intact snapshot installs
        res = handles[1].import_sessions([snap])
        assert res["sessions"] == 1
        assert res["imported"] >= 1
        conn.close()                       # done with A's stream

        # a follow-up turn on B rides the migrated pages (prefix hit,
        # not recompute) — and its drain refreshes the /statusz stats
        c2 = http.client.HTTPConnection("127.0.0.1", ports[1],
                                        timeout=120)
        c2.request("POST", "/v1/completions", json.dumps(
            {"prompt": snap["tokens"], "max_tokens": 4}).encode())
        r2 = c2.getresponse()
        assert r2.status == 200
        r2.read()
        c2.close()

        # B's books say imported (scraped off its real /statusz)
        c3 = http.client.HTTPConnection("127.0.0.1", ports[1],
                                        timeout=10)
        c3.request("GET", "/statusz")
        doc = json.loads(c3.getresponse().read())
        c3.close()
        eng = doc["engine"]
        assert eng.get("migration_imports", 0) >= 1
        assert eng.get("migration_imported_pages", 0) >= 1
        assert eng.get("prefix_hits", 0) >= 1      # served, not recomputed
        if cut is not None:
            assert eng.get("migration_rejected", 0) == 1
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
