"""Multi-replica router (ISSUE 7): placement, session affinity, SLO
aggregation, health and failover — all driven through in-process
transports (InprocReplica wraps real started ServingServers; no
sockets, so tier-1 stays offline).

The bit-identity oracle is a direct single-engine run: whatever path a
request takes through the router fleet, greedy outputs must match it
exactly (the PR 2/PR 4 contract, extended through one more hop).
"""

import asyncio
import json
import time

import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.inference import ContinuousBatchingEngine, GenerationConfig
from paddle_tpu.inference.prefix_cache import block_hashes
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.router import InprocReplica, Placer, ReplicaState, RouterServer
from paddle_tpu.serving import ServingServer, SLOController

from test_observability import parse_prometheus
from test_serving_http import (completion_body, http_bytes,
                               split_response, sse_chunks)


# ---------------------------------------------------------------------------
# fixtures / helpers
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig.tiny())


def _engine(model, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("gen", GenerationConfig(max_new_tokens=6))
    kw.setdefault("max_seq_len", 96)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_bucket", 8)
    return ContinuousBatchingEngine(model, **kw)


PROMPTS = ([1, 2, 3, 4, 5], [9, 8, 7], [4, 5, 6, 7])


@pytest.fixture(scope="module")
def oracle(model):
    eng = _engine(model)
    rids = [eng.add_request(p) for p in PROMPTS]
    out = eng.run()
    return {tuple(p): out[r] for p, r in zip(PROMPTS, rids)}


class Fleet:
    """N started replicas + a router over them, torn down together."""

    def __init__(self, model, n=2, policy="scored", prefix_cache=False,
                 slo=False, engine_kw=None, **router_kw):
        self.servers = [
            ServingServer(_engine(model, prefix_cache=prefix_cache,
                                  **(engine_kw or {})),
                          slo=(slo() if callable(slo) else slo),
                          flight_recorder=False).start()
            for _ in range(n)]
        self.replicas = [InprocReplica(f"r{i}", s)
                         for i, s in enumerate(self.servers)]
        router_kw.setdefault("health_interval_s", 1e9)
        self.router = RouterServer(self.replicas, policy=policy,
                                   **router_kw)

    def close(self):
        for s in self.servers:
            s.close()

    def engine(self, i):
        return self.servers[i].engine


async def do(router, method, path, body=None, headers=()):
    head = [f"{method} {path} HTTP/1.1", "Host: test"]
    head += [f"{k}: {v}" for k, v in headers]
    body = body or b""
    head.append(f"Content-Length: {len(body)}")
    raw = ("\r\n".join(head) + "\r\n\r\n").encode() + body
    r = asyncio.StreamReader()
    r.feed_data(raw)
    r.feed_eof()
    from test_serving_http import MemWriter
    w = MemWriter()
    await router.handle(r, w)
    return split_response(w.buf)


def completions_via(router, prompt, max_tokens, stream=False, headers=()):
    return do(router, "POST", "/v1/completions",
              completion_body(list(prompt), max_tokens, stream=stream),
              headers=headers)


# ---------------------------------------------------------------------------
# pure placement semantics (no engines)
# ---------------------------------------------------------------------------

class _FakeClient:
    def __init__(self, rid):
        self.id = rid

    def describe(self):
        return {"id": self.id, "transport": "fake"}


def _state(rid, hashes=(), page_size=8, queue=0, ready=True):
    s = ReplicaState(_FakeClient(rid))
    s.ok = True
    s.ready = ready
    s.page_size = page_size
    s.digest = frozenset(hashes)
    s.queue_depth = queue
    return s


def test_placement_scored_prefers_digest_holder():
    obs.reset("router.")
    prompt = list(range(1, 33))                  # 4 pages of 8
    hs = block_hashes(prompt, 8)
    a = _state("a", hashes=hs[:3])               # holds 3 leading pages
    b = _state("b")
    placer = Placer(policy="scored")
    choice, reason = placer.place(prompt, None, [b, a])
    assert choice.id == "a" and reason == "prefix"
    # load can outbid residency: 3 cached pages vs 4 queued requests
    a.queue_depth = 4
    placer2 = Placer(policy="scored")
    choice, reason = placer2.place(prompt, None, [b, a])
    assert choice.id == "b" and reason == "load"


def test_placement_routed_overlay_concentrates_shared_prefixes():
    """The instant prompt P routes to a replica, P's pages count as
    resident there — a second request sharing the prefix follows WITHOUT
    waiting for a /statusz poll to confirm the digest."""
    prompt = list(range(1, 33))
    a, b = _state("a"), _state("b")
    placer = Placer(policy="scored")
    first, _ = placer.place(prompt, None, [a, b])
    follow, reason = placer.place(prompt + [77, 78], None, [a, b])
    assert follow.id == first.id and reason == "prefix"


def test_placement_routed_overlay_ages_out_unconfirmed_credits():
    """An overlay credit the replica's digest never confirms (the pages
    were evicted replica-side, or never committed) stops scoring as a
    hit after two /statusz polls; a confirmed credit hands off to the
    digest and keeps scoring."""
    prompt = list(range(1, 33))
    hs = block_hashes(prompt, 8)
    a, b = _state("a"), _state("b")
    a.credit_routed(hs, cap=64)
    assert a.expected_hit_pages(hs) == 4
    unconfirmed = {"ready": True,
                   "prefix_digest": {"page_size": 8, "hashes": []}}
    a.apply_statusz(unconfirmed)   # poll 1: credit may predate admission
    assert a.expected_hit_pages(hs) == 4
    a.apply_statusz(unconfirmed)   # poll 2: still absent -> evicted, drop
    assert a.expected_hit_pages(hs) == 0 and not a.routed
    b.credit_routed(hs, cap=64)
    b.apply_statusz({"ready": True,
                     "prefix_digest": {"page_size": 8,
                                       "hashes": list(hs)}})
    assert not b.routed and b.expected_hit_pages(hs) == 4


def test_placement_session_affinity_and_lru_cap():
    prompt = list(range(1, 17))
    a, b = _state("a"), _state("b")
    placer = Placer(policy="scored", session_cap=2)
    pin, _ = placer.place(prompt, "s1", [a, b])
    # the pinned replica keeps the session even when the other looks
    # cheaper on load
    pin.queue_depth = 3
    again, reason = placer.place(prompt, "s1", [a, b])
    assert again.id == pin.id and reason == "affinity"
    # LRU cap: two fresh sessions evict s1
    placer.place(prompt, "s2", [a, b])
    placer.place(prompt, "s3", [a, b])
    assert placer.pinned("s1") is None
    assert placer.session_state()["evictions"] >= 1


def test_placement_round_robin_rotates():
    a, b = _state("a"), _state("b")
    placer = Placer(policy="round_robin")
    seq = [placer.place([1, 2, 3], None, [a, b])[0].id
           for _ in range(4)]
    assert seq == ["a", "b", "a", "b"]


# ---------------------------------------------------------------------------
# end-to-end: bit identity through the router
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", ["scored", "round_robin"])
def test_router_stream_bit_identical(model, oracle, policy):
    """Streamed and unary outputs through the router bit-match the
    direct single-engine oracle, whichever placement policy routed them;
    the response carries the router trace id on every chunk AND which
    replica served it."""
    fleet = Fleet(model, n=2, policy=policy)
    try:
        async def main():
            outs = await asyncio.gather(
                completions_via(fleet.router, PROMPTS[0], 6, stream=True),
                completions_via(fleet.router, PROMPTS[1], 6, stream=False),
                completions_via(fleet.router, PROMPTS[2], 6, stream=True))
            return outs

        (s0, h0, b0), (s1, h1, b1), (s2, h2, b2) = asyncio.run(main())
        assert (s0, s1, s2) == (200, 200, 200)
        for headers in (h0, h1, h2):
            assert headers["x-router-replica"] in ("r0", "r1")
        chunks = sse_chunks(b0)
        toks = [t for c in chunks for t in c["choices"][0]["token_ids"]]
        assert toks == oracle[tuple(PROMPTS[0])]
        assert b0.rstrip().endswith(b"data: [DONE]")
        # one trace context: every chunk id == X-Request-Id, router-minted
        ids = {c["id"] for c in chunks}
        assert ids == {h0["x-request-id"]}
        assert h0["x-request-id"].startswith("cmpl-rtr-")
        doc = json.loads(b1)
        assert doc["choices"][0]["token_ids"] == oracle[tuple(PROMPTS[1])]
        toks2 = [t for c in sse_chunks(b2)
                 for t in c["choices"][0]["token_ids"]]
        assert toks2 == oracle[tuple(PROMPTS[2])]
    finally:
        fleet.close()


def test_router_trace_id_propagates_to_replica_spans(model):
    """The router's X-Trace-Id reaches the replica engine: the replica
    response (relayed back) carries the router-minted id, so one request
    is ONE correlated trace lane across both processes."""
    fleet = Fleet(model, n=1)
    try:
        status, headers, body = asyncio.run(completions_via(
            fleet.router, PROMPTS[0], 4, stream=False,
            headers=(("X-Trace-Id", "tracked-abc123"),)))
        assert status == 200
        # the replica honored the propagated id end-to-end
        assert json.loads(body)["id"] == "tracked-abc123"
        assert headers["x-request-id"] == "tracked-abc123"
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# session affinity + prefix-aware placement with real caches
# ---------------------------------------------------------------------------

def test_session_affinity_routes_to_page_holding_replica(model):
    """Multi-turn session: every turn lands on the SAME replica, whose
    prefix cache serves the conversation history (hits observed in THAT
    replica's engine stats; the other replica never sees the session)."""
    obs.reset("router.")
    fleet = Fleet(model, n=2, prefix_cache=True,
                  engine_kw={"gen": GenerationConfig(max_new_tokens=4)})
    try:
        base = list(range(1, 33))                # 4 full pages of 8
        turns = [base,
                 base + list(range(40, 52)),     # history grows per turn
                 base + list(range(40, 64))]

        async def run_turns():
            outs = []
            for t in turns:
                outs.append(await completions_via(
                    fleet.router, t, 4, stream=False,
                    headers=(("X-Session-Id", "conv-1"),)))
            return outs

        outs = asyncio.run(run_turns())
        assert all(o[0] == 200 for o in outs)
        served = {o[1]["x-router-replica"] for o in outs}
        assert len(served) == 1                  # pinned to one replica
        holder = int(served.pop()[1:])
        other = 1 - holder
        hold_stats = fleet.engine(holder).stats()
        other_stats = fleet.engine(other).stats()
        # turns 2 and 3 hit the history pages on the holding replica
        assert hold_stats["prefix_hits"] >= 2
        assert hold_stats["prefix_tokens_saved"] >= 2 * len(base) - 8
        assert other_stats["prefix_hits"] == 0
        assert len(fleet.engine(other).completed) == 0
    finally:
        fleet.close()


def test_scored_placement_without_session_follows_prefix(model):
    """No session header at all: the routed-overlay digest still sends a
    shared-prefix follow-up to the replica that cached it."""
    fleet = Fleet(model, n=2, prefix_cache=True)
    try:
        shared = list(range(100, 132))           # 4 full pages

        async def main():
            a = await completions_via(fleet.router, shared, 4)
            b = await completions_via(
                fleet.router, shared + [7, 8, 9], 4)
            return a, b

        (sa, ha, _), (sb, hb, _) = asyncio.run(main())
        assert sa == 200 and sb == 200
        assert ha["x-router-replica"] == hb["x-router-replica"]
        holder = int(ha["x-router-replica"][1:])
        assert fleet.engine(holder).stats()["prefix_hits"] >= 1
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# health, readiness, failover
# ---------------------------------------------------------------------------

def test_router_does_not_route_to_unready_replica(model, oracle):
    """A cold (never-started) replica reports ready=false — the router
    places everything on the warm one."""
    fleet = Fleet(model, n=1)
    cold = ServingServer(_engine(model), slo=False, flight_recorder=False,
                         warmup=True)            # NOT started: not ready
    fleet.replicas.append(InprocReplica("r1", cold))
    fleet.router = RouterServer(fleet.replicas, policy="scored",
                                health_interval_s=1e9)
    try:
        async def main():
            outs = [await completions_via(fleet.router, PROMPTS[0], 6)
                    for _ in range(3)]
            ready = await do(fleet.router, "GET", "/readyz")
            statusz = await do(fleet.router, "GET", "/statusz")
            return outs, ready, statusz

        outs, ready, statusz = asyncio.run(main())
        for status, headers, body in outs:
            assert status == 200
            assert headers["x-router-replica"] == "r0"
            assert json.loads(body)["choices"][0]["token_ids"] == \
                oracle[tuple(PROMPTS[0])]
        assert ready[0] == 200                   # >= 1 replica ready
        doc = json.loads(statusz[2])
        states = {r["id"]: r["state"] for r in doc["replicas"]}
        assert states == {"r0": "ready", "r1": "warming"}
    finally:
        fleet.close()


def test_replica_warmup_readiness_and_zero_recompile_routing(model):
    """warmup=True: /readyz flips only after the bucket warmup compile,
    and warm routed traffic afterwards compiles NOTHING (the acceptance
    contract: the router never places live traffic on a cold engine)."""
    server = ServingServer(_engine(model), slo=False,
                           flight_recorder=False, warmup=True).start()
    fleet_router = RouterServer([InprocReplica("r0", server)],
                                health_interval_s=1e9)
    try:
        deadline = time.perf_counter() + 120
        while not server.ready():
            assert time.perf_counter() < deadline, "warmup never finished"
            time.sleep(0.02)
        assert asyncio.run(do(fleet_router, "GET", "/readyz"))[0] == 200

        with obs.assert_overhead(record=True) as rec:
            async def main():
                return await asyncio.gather(
                    completions_via(fleet_router, [6, 7, 8], 6,
                                    stream=True),
                    completions_via(fleet_router, [2, 4], 6))
            outs = asyncio.run(main())
        assert all(o[0] == 200 for o in outs)
        assert rec.compiles == 0                 # routed AND warm
    finally:
        server.close()


def _run_kill_mid_stream(fleet, prompt, max_tokens):
    """Start a stream, kill the serving replica after the first chunk,
    return (client bytes, victim id, survivor-check results)."""
    async def main():
        r = asyncio.StreamReader()
        r.feed_data(http_bytes(
            "POST", "/v1/completions",
            completion_body(list(prompt), max_tokens, stream=True)))
        r.feed_eof()
        from test_serving_http import MemWriter
        w = MemWriter()
        task = asyncio.create_task(fleet.router.handle(r, w))
        deadline = time.perf_counter() + 60
        while b"data: " not in w.buf:
            assert time.perf_counter() < deadline, "no first chunk"
            await asyncio.sleep(0.005)
        _, victim_headers, _ = split_response(w.buf)
        victim = victim_headers["x-router-replica"]
        # kill the serving replica mid-stream
        for rep in fleet.replicas:
            if rep.id == victim:
                rep.kill()
        await asyncio.wait_for(task, 30)         # no hang
        survivor_out = await completions_via(
            fleet.router, PROMPTS[1], 6, stream=False)
        healthz = await do(fleet.router, "GET", "/healthz")
        statusz = await do(fleet.router, "GET", "/statusz")
        return w.buf, victim, survivor_out, healthz, statusz

    return asyncio.run(main())


def test_failover_kill_replica_mid_stream_resumes(model):
    """ISSUE 14: killing a replica mid-stream no longer costs the
    stream — the journal replays the prompt + relayed tokens on the
    survivor and the client sees ONE unbroken SSE stream that
    bit-matches a no-fault oracle (no synthesized error for journaled
    greedy sessions), counted in router.resumes{outcome=resumed}."""
    obs.reset("router.")
    # the no-fault oracle for the full 64-token budget
    eng = _engine(model, gen=GenerationConfig(max_new_tokens=64))
    rid = eng.add_request(list(PROMPTS[0]))
    full_oracle = eng.run()[rid]
    fleet = Fleet(model, n=2)
    try:
        raw, victim, (s2, h2, b2), healthz, statusz = \
            _run_kill_mid_stream(fleet, PROMPTS[0], 64)
        status, headers, body = split_response(raw)
        assert status == 200                     # SSE head was out
        chunks = sse_chunks(body)
        finishes = [c["choices"][0]["finish_reason"] for c in chunks
                    if c["choices"][0]["finish_reason"]]
        toks = [t for c in chunks for t in c["choices"][0]["token_ids"]]
        # the zero-loss contract: no error finish, full bit-match
        assert finishes and finishes[-1] in ("stop", "length"), finishes
        assert toks == full_oracle
        assert body.rstrip().endswith(b"data: [DONE]")
        assert obs.metrics.counter("router.resumes",
                                   outcome="resumed").value >= 1
        assert obs.metrics.counter("router.failover",
                                   phase="stream").value >= 1
        # the very next request succeeds on the survivor
        assert s2 == 200 and h2["x-router-replica"] != victim
        assert healthz[0] == 200                 # fleet still alive
        doc = json.loads(statusz[2])
        dead = {r["id"]: r for r in doc["replicas"]}[victim]
        assert dead["state"] in ("suspect", "dead")
        assert doc["resume"]["outcomes"]["resumed"] >= 1
    finally:
        fleet.close()


def test_failover_kill_mid_stream_without_journal_synthesizes_error(
        model, oracle):
    """With FLAGS_router_failover_resume off, the PR 7 contract holds
    verbatim: clean termination (finish_reason 'error' + [DONE], never
    a silent truncation), counted in router.failover — while the next
    request flows to the survivor and still bit-matches the oracle."""
    obs.reset("router.")
    from paddle_tpu import flags as _flags
    _flags.set_flags({"router_failover_resume": False})
    try:
        fleet = Fleet(model, n=2)
        try:
            raw, victim, (s2, h2, b2), healthz, _statusz = \
                _run_kill_mid_stream(fleet, PROMPTS[0], 64)
            status, headers, body = split_response(raw)
            assert status == 200                 # SSE head was out
            chunks = sse_chunks(body)
            # clean termination: an explicit error finish, then [DONE]
            assert chunks[-1]["choices"][0]["finish_reason"] == "error"
            assert body.rstrip().endswith(b"data: [DONE]")
            assert obs.metrics.counter("router.failover",
                                       phase="stream").value >= 1
            assert obs.metrics.counter("router.resumes",
                                       outcome="resumed").value == 0
            # the very next request succeeds on the survivor
            assert s2 == 200
            assert h2["x-router-replica"] != victim
            assert json.loads(b2)["choices"][0]["token_ids"] == \
                oracle[tuple(PROMPTS[1])]
            assert healthz[0] == 200             # fleet still alive
        finally:
            fleet.close()
    finally:
        _flags.set_flags({"router_failover_resume": True})


def test_replica_rejoin_resets_staleness_and_traces(model):
    """ISSUE 12 satellite: a dead->live transition emits ONE
    router.replica_rejoin instant + counter AND clears the routed
    overlay, so a rejoined replica is never scored on pre-death
    credits — only on the fresh digest it just advertised."""
    obs.reset("router.")
    # prefix cache ON: a digest-less replica clears its overlay on
    # every poll anyway, which would mask what this test asserts
    fleet = Fleet(model, n=2, prefix_cache=True)
    rejoins = obs.metrics.counter("router.replica_rejoins")
    try:
        async def main():
            await fleet.router.poll_replicas()
            st = fleet.router.states[0]
            assert rejoins.value == 0          # first poll is no rejoin
            # a single-poll suspect BLIP is not a rejoin either: the
            # replica never stopped serving, its overlay stays valid
            st.credit_routed(["blip"], cap=16)
            st.mark_failed()
            await fleet.router.poll_replicas()
            assert st.ok and int(rejoins.value) == 0
            assert "blip" in st.routed
            # credit phantom overlay entries, then kill the replica
            st.credit_routed(["h1", "h2", "h3"], cap=16)
            fleet.replicas[0].kill()
            for _ in range(3):                 # fails past dead_after
                await fleet.router.poll_replicas()
            assert not st.ok and st.fails >= 3
            assert st.routed                   # stale credits linger...
            obs.TRACER.start()
            fleet.replicas[0].revive()
            await fleet.router.poll_replicas()
            events = list(obs.TRACER._events)
            obs.TRACER.stop()
            return st, events

        st, events = asyncio.run(main())
        assert st.ok                           # rejoined
        assert st.routed == {}                 # ...and are gone on rejoin
        assert int(rejoins.value) == 1         # exactly one per rejoin
        marks = [e for e in events
                 if e.get("name") == "router.replica_rejoin"]
        assert len(marks) == 1
        assert marks[0]["args"]["replica"] == st.id
        # a healthy re-poll is NOT a rejoin
        asyncio.run(fleet.router.poll_replicas())
        assert int(rejoins.value) == 1
    finally:
        fleet.close()


def test_failover_at_connect_replaces_transparently(model, oracle):
    """A replica dead BEFORE dispatch: the router re-places the request
    on the next candidate — the client sees a plain 200."""
    obs.reset("router.")
    fleet = Fleet(model, n=2)
    try:
        async def main():
            warm = await completions_via(fleet.router, PROMPTS[2], 6)
            first = warm[1]["x-router-replica"]
            # kill the OTHER replica so the scored/load choice may well
            # pick the dead one next — the router must recover silently
            for rep in fleet.replicas:
                if rep.id != first:
                    rep.kill()
            outs = [await completions_via(fleet.router, PROMPTS[0], 6)
                    for _ in range(3)]
            return first, outs

        first, outs = asyncio.run(main())
        for status, headers, body in outs:
            assert status == 200
            assert headers["x-router-replica"] == first
            assert json.loads(body)["choices"][0]["token_ids"] == \
                oracle[tuple(PROMPTS[0])]
    finally:
        fleet.close()


def test_wedged_replica_stream_head_times_out_502(model):
    """A replica that accepts the dispatch but never writes a response
    head (process wedged, socket alive) must fail the STREAM request
    within ``poll_timeout_s`` — a 502 and a failover count, never a
    client hang (the unary path stays untimed: its head legitimately
    waits out the whole generation)."""
    obs.reset("router.")
    fleet = Fleet(model, n=1, poll_timeout_s=0.2)
    try:
        real = fleet.replicas[0]

        class Wedged:
            """Health polls (GET) pass through so the replica stays a
            placement candidate; completions (POST) connect fine and
            then never produce a byte."""
            id = real.id

            async def open(self, method, path, headers=(), body=b""):
                if method == "GET":
                    return await real.open(method, path, headers, body)
                return asyncio.StreamReader(), (lambda: None)

            def describe(self):
                return real.describe()

        fleet.router.states[0].client = Wedged()
        t0 = time.perf_counter()
        status, headers, body = asyncio.run(completions_via(
            fleet.router, PROMPTS[0], 4, stream=True))
        took = time.perf_counter() - t0
        assert status == 502
        assert took < 5.0, f"wedged head should time out fast, took {took}"
        assert obs.metrics.counter("router.failover",
                                   phase="stream").value >= 1
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# aggregated SLO shedding
# ---------------------------------------------------------------------------

def test_router_sheds_when_every_replica_burns(model):
    """Fleet-wide admission: when every live replica's burn window says
    shed, the router 503s BEFORE dispatch, with Retry-After derived from
    the soonest replica's live burn window and mirrored in the body."""
    obs.reset("serving.")
    obs.reset("router.")
    mk_slo = lambda: SLOController(ttft_ms=100.0, itl_ms=0.0,  # noqa: E731
                                   quantile=0.95, burn=2.0,
                                   min_samples=8, window=64)
    fleet = Fleet(model, n=2, slo=mk_slo)
    try:
        ttft = obs.metrics.histogram("serving.ttft_ms")
        for _ in range(32):                      # both replicas burn (the
            ttft.observe(5000.0)                 # in-process registry is
                                                 # fleet-shared)
        async def main():
            await fleet.router.poll_replicas()
            shed = await completions_via(fleet.router, [1, 2, 3], 2)
            statusz = await do(fleet.router, "GET", "/statusz")
            return shed, statusz

        (status, headers, body), statusz = asyncio.run(main())
        assert status == 503
        err = json.loads(body)["error"]
        assert err["type"] == "overloaded_error"
        ra = int(headers["retry-after"])
        assert 1 <= ra <= 60
        assert err["retry_after_s"] == ra
        assert obs.metrics.counter("router.shed").value >= 1
        assert obs.metrics.counter("router.slo_decision",
                                   decision="shed").value >= 1
        doc = json.loads(statusz[2])
        assert all(r["slo"]["decision"] == "shed"
                   for r in doc["replicas"])
        # neither engine ever saw the request
        assert all(len(fleet.engine(i).completed) == 0 for i in (0, 1))
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# endpoints
# ---------------------------------------------------------------------------

def test_router_metrics_healthz_statusz(model):
    obs.reset("router.")
    fleet = Fleet(model, n=2)
    try:
        async def main():
            c = await completions_via(fleet.router, PROMPTS[0], 4)
            m = await do(fleet.router, "GET", "/metrics")
            h = await do(fleet.router, "GET", "/healthz")
            s = await do(fleet.router, "GET", "/statusz")
            nf = await do(fleet.router, "GET", "/nope")
            bad = await do(fleet.router, "GET", "/v1/completions")
            return c, m, h, s, nf, bad

        c, m, h, s, nf, bad = asyncio.run(main())
        assert c[0] == 200
        assert m[0] == 200
        fams = parse_prometheus(m[2].decode())
        for fam in ("paddle_tpu_router_requests",
                    "paddle_tpu_router_placement",
                    "paddle_tpu_router_request_ms"):
            assert fam in fams, fam
        # the in-process fleet registry aggregates the replicas' serving
        # series in the SAME scrape
        assert "paddle_tpu_serving_ttft_ms" in fams
        assert h[0] == 200
        assert json.loads(h[2])["replicas_up"] == 2
        doc = json.loads(s[2])
        assert doc["policy"] == "scored"
        assert len(doc["replicas"]) == 2
        assert {r["state"] for r in doc["replicas"]} == {"ready"}
        assert doc["sessions"]["cap"] > 0
        # ISSUE 10: fleet-aggregated sentinel view (polled from each
        # replica's statusz anomalies section)
        assert set(doc["anomalies"]) == {"total", "by_replica", "recent"}
        assert set(doc["anomalies"]["by_replica"]) == \
            {r["id"] for r in doc["replicas"]}
        assert nf[0] == 404 and bad[0] == 405
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# launchers (argparse surface only — no sockets, no model build)
# ---------------------------------------------------------------------------

def test_launcher_arg_surfaces():
    from paddle_tpu.router.__main__ import build_parser as router_parser
    from paddle_tpu.router.__main__ import parse_replicas
    from paddle_tpu.serving.__main__ import apply_flag_sets
    from paddle_tpu.serving.__main__ import build_parser as serve_parser

    s = serve_parser().parse_args(
        ["--port", "8001", "--preset", "tiny", "--prefix-cache",
         "--set", "serving_slo_ttft_ms=500"])
    assert s.port == 8001 and s.prefix_cache and not s.no_warmup

    from paddle_tpu import flags
    old = flags.get_flags(["serving_slo_ttft_ms"])
    try:
        apply_flag_sets(s.flag_sets)
        assert flags.flag("serving_slo_ttft_ms") == 500.0
    finally:
        flags.set_flags(old)
    with pytest.raises(SystemExit):
        apply_flag_sets(["no_such_flag_ever=1"])

    r = router_parser().parse_args(
        ["--replica", "127.0.0.1:8001", "--replica", "h2:8002",
         "--policy", "round_robin"])
    reps = parse_replicas(r.replicas)
    assert [x.id for x in reps] == ["r0", "r1"]
    assert (reps[1].host, reps[1].port) == ("h2", 8002)
    with pytest.raises(SystemExit):
        parse_replicas(["nocolon"])


# ---------------------------------------------------------------------------
# poison quarantine + cascade breaker (ISSUE 15)
# ---------------------------------------------------------------------------

def test_poison_quarantine_unit_fake_clock():
    """Strike/TTL/absolution semantics on an injected clock: strikes
    accumulate per signature, progress resets them (the innocent
    co-flier contract), striking out quarantines for the TTL, and
    expiry re-admits on probation."""
    from paddle_tpu.router.quarantine import (PoisonQuarantine,
                                              request_signature)
    obs.reset("router.quarantine")
    clock = [0.0]
    q = PoisonQuarantine(strikes=2, ttl_s=10.0, clock=lambda: clock[0])
    sig = request_signature([1, 2, 3], {"max_tokens": 8})
    # same prompt, same sampling => same signature; different => not
    assert sig == request_signature([1, 2, 3], {"max_tokens": 8,
                                                "stream": True})
    assert sig != request_signature([1, 2, 3], {"max_tokens": 9})
    assert sig != request_signature([1, 2, 4], {"max_tokens": 8})

    # innocent co-flier: strike, progress, strike, progress — never out
    assert not q.strike(sig)
    q.progress(sig)
    assert not q.strike(sig)
    q.progress(sig)
    assert not q.quarantined(sig)
    # poison: two strikes with NO progress in between => quarantined
    assert not q.strike(sig)
    assert q.strike(sig)
    assert q.quarantined(sig)
    # progress cannot un-quarantine (the verdict holds for the TTL)
    q.progress(sig)
    assert q.quarantined(sig)
    assert q.refuse(sig) >= 1
    # TTL expiry re-admits
    clock[0] = 10.1
    assert not q.quarantined(sig)
    # stale strikes expire too (anchor = last strike)
    sig2 = request_signature([7], {})
    q.strike(sig2)
    clock[0] = 30.0
    assert not q.strike(sig2)            # old strike aged out: count is 1
    c = obs.metrics.counter
    assert int(c("router.quarantine", action="quarantined").value) == 1
    assert int(c("router.quarantine", action="strike").value) >= 4
    # disabled quarantine never strikes
    off = PoisonQuarantine(strikes=0, ttl_s=10.0)
    assert not off.strike(sig) and not off.quarantined(sig)


def test_poison_request_quarantined_fleet_survives(model):
    """ISSUE 15 tentpole e2e: a request that kills its replica AT
    DISPATCH (the chaos `poison` fault) kills at most
    FLAGS_router_poison_strikes replicas, ends quarantined with a clean
    503 + `quarantined` error body, its re-submit is refused
    deterministically, and a concurrent healthy stream still
    bit-matches the no-fault oracle."""
    from paddle_tpu.fleet import ChaosController, ChaosPlan, FaultEvent
    obs.reset("router.")
    eng = _engine(model, gen=GenerationConfig(max_new_tokens=64))
    rid = eng.add_request(list(PROMPTS[0]))
    full_oracle = eng.run()[rid]

    servers = [ServingServer(
        _engine(model, gen=GenerationConfig(max_new_tokens=64)),
        slo=False, flight_recorder=False).start() for _ in range(3)]
    replicas = [InprocReplica(f"r{i}", s)
                for i, s in enumerate(servers)]
    poison = [6, 6, 6, 6]
    plan = ChaosPlan([FaultEvent(0, "poison",
                                 " ".join(str(t) for t in poison))])
    chaos = ChaosController(plan)
    router = RouterServer([chaos.wrap(r) for r in replicas],
                          health_interval_s=1e9)
    chaos.advance(0)                     # arm the poison prompt
    try:
        async def main():
            await router.poll_replicas()
            # a healthy long stream in flight while the poison lands
            r = asyncio.StreamReader()
            r.feed_data(http_bytes(
                "POST", "/v1/completions",
                completion_body(list(PROMPTS[0]), 64, stream=True)))
            r.feed_eof()
            from test_serving_http import MemWriter
            w = MemWriter()
            ht = asyncio.create_task(router.handle(r, w))
            deadline = time.perf_counter() + 60
            while b"data: " not in w.buf:
                assert time.perf_counter() < deadline, "no first chunk"
                await asyncio.sleep(0.005)
            p1 = await completions_via(router, poison, 8, stream=True)
            await asyncio.wait_for(ht, 60)
            p2 = await completions_via(router, poison, 8, stream=False)
            statusz = await do(router, "GET", "/statusz")
            return w.buf, p1, p2, statusz

        raw, (p1st, _, p1body), (p2st, _, p2body), statusz = \
            asyncio.run(main())
        # the poison killed exactly poison_strikes replicas, then the
        # quarantine refused to feed it a third
        from paddle_tpu import flags as _flags
        strikes = int(_flags.flag("router_poison_strikes"))
        assert len(chaos.poison_kills) == strikes
        assert p1st == 503
        doc = json.loads(p1body)
        assert doc["error"]["type"] == "quarantined"
        assert doc["error"]["quarantined"] is True
        assert doc["error"]["retry_after_s"] >= 1
        # the re-submit is a deterministic clean refusal: 0 new kills
        assert p2st == 503
        assert json.loads(p2body)["error"]["type"] == "quarantined"
        assert len(chaos.poison_kills) == strikes
        c = obs.metrics.counter
        assert int(c("router.quarantine",
                     action="quarantined").value) == 1
        assert int(c("router.quarantine", action="strike").value) >= 2
        assert int(c("router.quarantine", action="refused").value) >= 2
        # the concurrent healthy stream is untouched (or resumed):
        # bit-identical to the no-fault oracle either way
        status, _, body = split_response(raw)
        assert status == 200
        chunks = sse_chunks(body)
        finishes = [c["choices"][0]["finish_reason"] for c in chunks
                    if c["choices"][0]["finish_reason"]]
        toks = [t for c in chunks
                for t in c["choices"][0]["token_ids"]]
        assert finishes and finishes[-1] in ("stop", "length")
        assert toks == full_oracle
        # statusz carries the quarantine state
        qdoc = json.loads(statusz[2])["quarantine"]
        assert qdoc["quarantined"] == 1 and qdoc["refused_total"] >= 2
    finally:
        for s in servers:
            s.close()


def test_breaker_open_sheds_new_admissions(model):
    """An OPEN cascade breaker sheds new router admissions with a
    jittered Retry-After (counted router.slo_decision{decision=
    breaker}); closing it re-admits."""
    from paddle_tpu.fleet import CascadeBreaker
    obs.reset("router.")
    fleet = Fleet(model, n=1)
    clock = [0.0]
    br = CascadeBreaker(threshold=1, window_s=60.0, cooldown_s=60.0,
                        clock=lambda: clock[0])
    br.record_death()
    assert br.state == "open"
    fleet.router.breaker = br
    try:
        st, hd, body = asyncio.run(
            completions_via(fleet.router, PROMPTS[0], 4))
        assert st == 503
        doc = json.loads(body)
        assert doc["error"]["breaker"] == "open"
        assert 1 <= doc["error"]["retry_after_s"] <= 60
        assert "retry-after" in hd
        assert int(obs.metrics.counter(
            "router.slo_decision", decision="breaker").value) == 1
        # half-open / closed re-admit
        clock[0] = 61.0
        br.update()
        assert br.state == "half_open"
        st2, _, b2 = asyncio.run(
            completions_via(fleet.router, PROMPTS[0], 4))
        assert st2 == 200
        assert json.loads(b2)["choices"][0]["token_ids"]
    finally:
        fleet.close()


def test_breaker_parks_resume_until_half_open_probe_closes(model):
    """ISSUE 15: a mid-stream death while the breaker is OPEN does not
    replay — the journal entry PARKS; once the cooldown passes, the
    half-open breaker releases it as the probe; the probe survives,
    the breaker closes, and the client's stream is STILL unbroken and
    bit-identical to the no-fault oracle."""
    from paddle_tpu.fleet import CascadeBreaker
    obs.reset("router.")
    eng = _engine(model, gen=GenerationConfig(max_new_tokens=64))
    rid = eng.add_request(list(PROMPTS[0]))
    full_oracle = eng.run()[rid]
    fleet = Fleet(model, n=2)
    br = CascadeBreaker(threshold=1, window_s=60.0, cooldown_s=0.25)
    fleet.router.breaker = br
    try:
        async def main():
            r = asyncio.StreamReader()
            r.feed_data(http_bytes(
                "POST", "/v1/completions",
                completion_body(list(PROMPTS[0]), 64, stream=True)))
            r.feed_eof()
            from test_serving_http import MemWriter
            w = MemWriter()
            task = asyncio.create_task(fleet.router.handle(r, w))
            deadline = time.perf_counter() + 60
            while b"data: " not in w.buf:
                assert time.perf_counter() < deadline, "no first chunk"
                await asyncio.sleep(0.005)
            _, victim_headers, _ = split_response(w.buf)
            victim = victim_headers["x-router-replica"]
            # the death trips the breaker BEFORE the router can resume
            br.record_death()
            assert br.state == "open"
            for rep in fleet.replicas:
                if rep.id == victim:
                    rep.kill()
            # drive time-based transitions like the supervisor tick
            saw_parked = False
            while not task.done():
                br.update()
                if fleet.router._parked > 0:
                    saw_parked = True
                await asyncio.sleep(0.02)
            await task
            return w.buf, saw_parked

        raw, saw_parked = asyncio.run(main())
        status, _, body = split_response(raw)
        assert status == 200
        assert saw_parked                    # the resume really parked
        chunks = sse_chunks(body)
        finishes = [c["choices"][0]["finish_reason"] for c in chunks
                    if c["choices"][0]["finish_reason"]]
        toks = [t for c in chunks
                for t in c["choices"][0]["token_ids"]]
        assert finishes and finishes[-1] in ("stop", "length")
        assert toks == full_oracle           # unbroken, bit-identical
        assert br.state == "closed"          # the probe closed it
        assert obs.metrics.counter("router.resumes",
                                   outcome="resumed").value >= 1
    finally:
        fleet.close()


def test_sampled_session_resumes_on_matching_seeded_survivor(model):
    """ISSUE 15 satellite: the greedy-only resume eligibility is
    lifted — positional sampling keys make a SAMPLED replay bit-exact
    on a survivor with the identical seeded config, so a mid-stream
    kill resumes seed-deterministically and matches the no-fault
    sampled oracle."""
    obs.reset("router.")
    gen = GenerationConfig(max_new_tokens=48, do_sample=True,
                           temperature=0.9, top_k=16, seed=11)
    eng = _engine(model, gen=GenerationConfig(**gen.__dict__))
    rid = eng.add_request(list(PROMPTS[0]))
    full_oracle = eng.run()[rid]
    fleet = Fleet(model, n=2,
                  engine_kw={"gen": GenerationConfig(**gen.__dict__)})
    try:
        raw, victim, (s2, h2, b2), healthz, statusz = \
            _run_kill_mid_stream(fleet, PROMPTS[0], 48)
        status, headers, body = split_response(raw)
        assert status == 200
        chunks = sse_chunks(body)
        finishes = [c["choices"][0]["finish_reason"] for c in chunks
                    if c["choices"][0]["finish_reason"]]
        toks = [t for c in chunks for t in c["choices"][0]["token_ids"]]
        assert finishes and finishes[-1] in ("stop", "length"), finishes
        assert toks == full_oracle           # sampled, still bit-exact
        assert obs.metrics.counter("router.resumes",
                                   outcome="resumed").value >= 1
        doc = json.loads(statusz[2])
        # the replicas advertise the full positional sampling config
        for rep in doc["replicas"]:
            assert rep["greedy"] is False
    finally:
        fleet.close()
