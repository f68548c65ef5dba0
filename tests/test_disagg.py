"""Disaggregated prefill/decode serving (ISSUE 16): role-specialized
fleets on the migration plane.

The router routes new streams onto prefill replicas with a 1-token
budget cap, ships the finished prefix to a decode successor over the
PR 14 export/import plane, and splices the decode leg into the SAME
client stream via the replay journal — bit-identical to a mixed-fleet
run, with zero re-prefilled full pages.  The supervisor grows replica
ROLES and autoscales each on its own pressure signal (prefill on queue
depth, decode on resident load), plus a proactive rebalance that moves
sessions off an SLO-burning replica before it sheds.

Everything tier-1 runs in-process (InprocReplica / fake handles); the
real-socket handoff lives in the slow tier at the bottom.
"""

import asyncio
import json
import time

import pytest

import paddle_tpu as paddle
from paddle_tpu import flags
from paddle_tpu import observability as obs
from paddle_tpu.fleet import FleetSupervisor
from paddle_tpu.fleet.supervisor import READY, STARTING, parse_roles
from paddle_tpu.inference import ContinuousBatchingEngine, GenerationConfig
from paddle_tpu.inference.prefix_cache import block_hashes
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.router import InprocReplica, Placer, ReplicaState, RouterServer
from paddle_tpu.router.journal import SessionJournal
from paddle_tpu.router.quarantine import PoisonQuarantine
from paddle_tpu.serving import ServingServer

from test_fleet import Clock, FakeHandle, _mark_live
from test_serving_http import (MemWriter, completion_body,
                               split_response, sse_chunks)


# ---------------------------------------------------------------------------
# pure units: roles / journal / scoring / bounds
# ---------------------------------------------------------------------------

def test_parse_roles():
    assert parse_roles("") is None
    assert parse_roles("  ") is None
    assert parse_roles("prefill=1,decode=2") == {"prefill": 1, "decode": 2}
    assert parse_roles("decode=1, mixed=2 ,decode=1") == \
        {"decode": 2, "mixed": 2}
    with pytest.raises(ValueError):
        parse_roles("turbo=1")
    with pytest.raises(ValueError):
        parse_roles("prefill=0")
    with pytest.raises(ValueError):
        parse_roles("prefill")
    with pytest.raises(ValueError):
        parse_roles("prefill=two")


def test_journal_capped_body_caps_budget_only():
    j = SessionJournal(cap=4, max_tokens=64)
    e = j.begin("t1", None, [1, 2, 3], {"prompt": [1, 2, 3],
                                        "max_tokens": 24,
                                        "stream": True}, )
    doc = json.loads(e.capped_body(1).decode())
    assert doc["prompt"] == [1, 2, 3]
    assert doc["max_tokens"] == 1
    assert doc["stream"] is True
    # the journal's own budget is untouched: the decode leg still knows
    # the client asked for 24
    j.record(e, [7])
    assert e.remaining() == 23
    resume = json.loads(e.resume_body().decode())
    assert resume["prompt"] == [1, 2, 3, 7]
    assert resume["max_tokens"] == 23


class _FakeClient:
    def __init__(self, rid):
        self.id = rid

    def describe(self):
        return {"id": self.id, "transport": "fake"}


def _state(rid, hashes=(), spilled=(), page_size=8, role="mixed"):
    s = ReplicaState(_FakeClient(rid))
    s.ok = True
    s.ready = True
    s.page_size = page_size
    s.digest = frozenset(hashes)
    s.spilled = frozenset(spilled)
    s.role = role
    return s


def test_expected_hits_counts_spilled_run_members():
    h = [f"h{i}" for i in range(4)]
    s = _state("r0", hashes=h[:3], spilled=[h[1]])
    assert s.expected_hits(h) == (3, 1)
    assert s.expected_hit_pages(h) == 3
    # an overlay credit outranks a stale spill mark: the page was just
    # re-routed here and the admission swap-in re-promotes it
    s.credit_routed([h[1]])
    assert s.expected_hits(h) == (3, 0)


def test_spill_scoring_resident_beats_spilled_beats_absent():
    obs.reset("router.")
    prompt = list(range(1, 17))                   # 2 pages of 8
    hs = block_hashes(prompt, 8)
    resident = _state("res", hashes=hs)
    spilled = _state("spill", hashes=hs, spilled=hs)
    absent = _state("none")
    placer = Placer(policy="scored")
    choice, reason = placer.place(prompt, None,
                                  [absent, spilled, resident])
    assert (choice.id, reason) == ("res", "prefix")
    choice, _ = placer.place(prompt, None, [absent, spilled])
    assert choice.id == "spill"                   # swap-in beats recompute
    # a spilled prefix must still lose to a resident one under load the
    # spill weight cannot explain away
    assert placer.spill_weight == pytest.approx(
        float(flags.flag("router_spill_hit_weight")))


def test_statusz_parses_role_and_spilled():
    s = _state("r0")
    s.apply_statusz({"ready": True, "role": "decode",
                     "engine": {"queue_depth": 0},
                     "prefix_digest": {"page_size": 8,
                                       "hashes": ["aa", "bb"],
                                       "spilled": ["bb"],
                                       "epoch": 1, "gen": "g1"}})
    assert s.role == "decode"
    assert s.digest == frozenset({"aa", "bb"})
    assert s.spilled == frozenset({"bb"})
    d = s.describe(dead_after=3)
    assert d["role"] == "decode" and d["spilled_entries"] == 1
    # a poll without a digest resets the spill set too
    s.apply_statusz({"ready": True, "engine": {"queue_depth": 0}})
    assert s.spilled == frozenset() and s.role == "mixed"


def test_overlay_credit_cap_evicts_oldest():
    obs.reset("router.")
    s = _state("r0")
    ev = obs.metrics.counter("router.overlay_evictions")
    s.credit_routed(["a", "b"], cap=3)
    s.credit_routed(["c", "d"], cap=3)
    assert list(s.routed) == ["b", "c", "d"]      # "a" (oldest) evicted
    assert int(ev.value) == 1
    # re-crediting refreshes recency instead of duplicating
    s.credit_routed(["b"], cap=3)
    s.credit_routed(["e"], cap=3)
    assert list(s.routed) == ["d", "b", "e"]
    # the default cap comes from the flag (old hard cap preserved)
    assert int(flags.flag("router_overlay_cap")) == 4096


def test_quarantine_read_verbs_sweep_expired_records():
    obs.reset("router.")
    clock = Clock()
    q = PoisonQuarantine(strikes=3, ttl_s=10.0, cap=100, clock=clock)
    q.strike("aaa")
    q.strike("bbb")
    assert len(q) == 2
    # expired strike records are shed by a READ on an unrelated
    # signature (a refuse-only workload never calls a write verb)
    clock.t = 20.0
    assert not q.quarantined("zzz")
    assert len(q) == 0
    # the sweep is time-gated: non-expired records survive reads
    q.strike("ccc")
    clock.t = 21.0
    for _ in range(5):
        q.progress("zzz")
    assert len(q) == 1


def test_quarantine_cap_bounds_signature_table():
    obs.reset("router.")
    clock = Clock()
    q = PoisonQuarantine(strikes=50, ttl_s=1e9, cap=2, clock=clock)
    for sig in ("s1", "s2", "s3", "s4"):
        q.strike(sig)
    assert len(q) == 2                            # oldest evicted first
    assert int(flags.flag("router_quarantine_cap")) == 4096


# ---------------------------------------------------------------------------
# the disaggregated handoff, end to end over real engines (in-process)
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


PROMPT = list(range(1, 17))                       # 2 full pages of 8


@pytest.fixture(scope="module")
def oracle(model):
    eng = _engine(model, gen=GenerationConfig(max_new_tokens=64))
    rid = eng.add_request(list(PROMPT))
    return eng.run()[rid]


class RoleFleet:
    """Role-tagged started replicas + a router, torn down together."""

    def __init__(self, model, roles, engine_kw=None, **router_kw):
        self.servers = []
        for i, role in enumerate(roles):
            kw = dict((engine_kw or {}).get(i, {}))
            self.servers.append(
                ServingServer(_engine(model, prefix_cache=True, **kw),
                              role=role, flight_recorder=False).start())
        self.replicas = [InprocReplica(f"r{i}", s)
                         for i, s in enumerate(self.servers)]
        router_kw.setdefault("health_interval_s", 1e9)
        self.router = RouterServer(self.replicas, policy="scored",
                                   **router_kw)

    def close(self):
        for s in self.servers:
            s.close()


async def do(router, method, path, body=None, headers=()):
    head = [f"{method} {path} HTTP/1.1", "Host: test"]
    head += [f"{k}: {v}" for k, v in headers]
    body = body or b""
    head.append(f"Content-Length: {len(body)}")
    raw = ("\r\n".join(head) + "\r\n\r\n").encode() + body
    r = asyncio.StreamReader()
    r.feed_data(raw)
    r.feed_eof()
    w = MemWriter()
    await router.handle(r, w)
    return split_response(w.buf)


def _stream_tokens(body):
    chunks = sse_chunks(body)
    toks = [t for c in chunks for t in c["choices"][0]["token_ids"]]
    finishes = [c["choices"][0]["finish_reason"] for c in chunks
                if c["choices"][0]["finish_reason"]]
    ids = {c["id"] for c in chunks}
    return toks, finishes, ids


def test_handoff_end_to_end_bit_identical_stream(model, oracle):
    """The tentpole contract: a new stream prefills on the prefill
    replica (1-token leg), the finished prefix ships to the decode
    replica as ready prefix-cache pages, and the decode leg splices
    into ONE client stream — bit-identical to a mixed run, with ZERO
    re-prefilled full pages on the successor."""
    obs.reset("router.")
    obs.reset("serving.kv.handoff")
    fleet = RoleFleet(model, ["prefill", "decode", "mixed"])
    try:
        async def main():
            await fleet.router.poll_replicas()
            assert [s.role for s in fleet.router.states] == \
                ["prefill", "decode", "mixed"]
            resp = await do(fleet.router, "POST", "/v1/completions",
                            completion_body(PROMPT, 24, stream=True))
            statusz = await do(fleet.router, "GET", "/statusz")
            return resp, statusz

        (status, headers, body), statusz = asyncio.run(main())
        assert status == 200
        toks, finishes, ids = _stream_tokens(body)
        assert toks == oracle[:24]                # bit-identical splice
        assert finishes == ["length"]             # ONE finish, no error
        assert len(ids) == 1                      # one completion id
        assert body.rstrip().endswith(b"data: [DONE]")
        assert int(obs.metrics.counter("router.handoff",
                                       outcome="ok").value) == 1
        assert int(obs.metrics.counter("router.resumes",
                                       outcome="handoff").value) == 1
        # the migration plane actually carried the prefix
        assert fleet.servers[0].engine.stats().get(
            "migration_exports", 0) >= 1
        assert fleet.servers[1].engine.stats().get(
            "migration_imports", 0) >= 1
        assert int(obs.metrics.counter("serving.kv.handoff_sessions",
                                       outcome="ok").value) == 1
        assert int(obs.metrics.counter(
            "serving.kv.handoff_reprefill_tokens").value) == 0
        doc = json.loads(statusz[2])
        assert doc["handoff"]["enabled"] is True
        assert doc["handoff"]["outcomes"]["ok"] == 1
        assert doc["resume"]["outcomes"]["handoff"] == 1
    finally:
        fleet.close()


def test_warm_handoffs_compile_nothing_and_serve_the_mixed_tokens(model):
    """Once two streams have gone through prefill replica, KV handoff and
    decode replica (the second still compiles the importer's
    ``pool_swap_in`` once), a third with another prompt compiles nothing
    on either side, re-prefills no full page and reads as the same prompt
    served by one mixed engine."""
    obs.reset("router.")
    obs.reset("serving.kv.handoff")
    second = [p + 20 for p in PROMPT]
    eng = _engine(model, gen=GenerationConfig(max_new_tokens=24))
    rid = eng.add_request(list(second))
    mixed = eng.run()[rid]
    fleet = RoleFleet(model, ["prefill", "decode"])
    try:
        async def main():
            await fleet.router.poll_replicas()
            for warm_prompt in (PROMPT, [p + 40 for p in PROMPT]):
                warm = await do(fleet.router, "POST", "/v1/completions",
                                completion_body(warm_prompt, 24, stream=True))
            with obs.assert_overhead(record=True) as rec:
                resp = await do(fleet.router, "POST", "/v1/completions",
                                completion_body(second, 24, stream=True))
            return warm, resp, rec.compiles

        (wstatus, _, _), (status, _, body), compiles = asyncio.run(main())
        assert (wstatus, status) == (200, 200)
        assert compiles == 0
        assert _stream_tokens(body)[0] == mixed
        assert int(obs.metrics.counter("router.handoff",
                                       outcome="ok").value) == 3
        assert int(obs.metrics.counter(
            "serving.kv.handoff_reprefill_tokens").value) == 0
    finally:
        fleet.close()


def test_handoff_pins_session_to_decode_target(model, oracle):
    """After a handoff the session's KV lives on the decode replica:
    the pin moves there, and the NEXT turn of the same session bypasses
    the prefill arm entirely (affinity + resident prefix beat phase
    specialization)."""
    obs.reset("router.")
    fleet = RoleFleet(model, ["prefill", "decode"])
    try:
        async def main():
            await fleet.router.poll_replicas()
            r1 = await do(fleet.router, "POST", "/v1/completions",
                          completion_body(PROMPT, 12, stream=True),
                          headers=[("X-Session-Id", "sess-1")])
            pinned = fleet.router.placer.pinned("sess-1")
            await fleet.router.poll_replicas()
            r2 = await do(fleet.router, "POST", "/v1/completions",
                          completion_body(PROMPT, 12, stream=True),
                          headers=[("X-Session-Id", "sess-1")])
            return r1, pinned, r2

        (s1, h1, b1), pinned, (s2, h2, b2) = asyncio.run(main())
        assert s1 == 200 and s2 == 200
        toks1, _, _ = _stream_tokens(b1)
        toks2, _, _ = _stream_tokens(b2)
        assert toks1 == oracle[:12]
        assert toks2 == oracle[:12]
        assert pinned == "r1"                     # moved to the decode end
        assert h2["x-router-replica"] == "r1"     # pinned turn stays there
        # exactly ONE handoff: the pinned second turn never re-entered
        # the prefill arm
        assert int(obs.metrics.counter("router.handoff",
                                       outcome="ok").value) == 1
    finally:
        fleet.close()


def test_handoff_import_failure_falls_back_never_drops_stream(
        model, oracle):
    """A decode successor that cannot take the pages (geometry
    mismatch: different page size) fails the import — the router
    counts import_failed and re-prefills on the mixed replica instead.
    The client sees one unbroken bit-identical stream either way."""
    obs.reset("router.")
    fleet = RoleFleet(model, ["prefill", "decode", "mixed"],
                      engine_kw={1: {"page_size": 16,
                                     "prefill_bucket": 16}})
    try:
        async def main():
            await fleet.router.poll_replicas()
            return await do(fleet.router, "POST", "/v1/completions",
                            completion_body(PROMPT, 24, stream=True))

        status, headers, body = asyncio.run(main())
        assert status == 200
        toks, finishes, ids = _stream_tokens(body)
        assert toks == oracle[:24]
        assert finishes == ["length"]
        assert len(ids) == 1
        assert int(obs.metrics.counter("router.handoff",
                                       outcome="import_failed").value) == 1
        assert int(obs.metrics.counter("router.handoff",
                                       outcome="ok").value) == 0
        # the fallback leg is a plain journal resume, not a handoff
        assert int(obs.metrics.counter("router.resumes",
                                       outcome="resumed").value) == 1
        assert int(obs.metrics.counter("router.resumes",
                                       outcome="handoff").value) == 0
        # nothing installed on the mismatched decode replica
        assert fleet.servers[1].engine.stats().get(
            "migration_imports", 0) == 0
    finally:
        fleet.close()


def test_unary_requests_bypass_the_prefill_arm(model, oracle):
    """Handoff is a STREAMING optimization: a unary completion on a
    role fleet places normally (any replica, no capped leg) and
    bit-matches the oracle."""
    obs.reset("router.")
    fleet = RoleFleet(model, ["prefill", "decode"])
    try:
        async def main():
            await fleet.router.poll_replicas()
            return await do(fleet.router, "POST", "/v1/completions",
                            completion_body(PROMPT, 6, stream=False))

        status, headers, body = asyncio.run(main())
        assert status == 200
        assert json.loads(body)["choices"][0]["token_ids"] == oracle[:6]
        for outcome in ("ok", "export_failed", "import_failed",
                        "no_successor"):
            assert int(obs.metrics.counter(
                "router.handoff", outcome=outcome).value) == 0
    finally:
        fleet.close()


def test_handoff_flag_off_restores_mixed_routing(model, oracle):
    """FLAGS_router_prefill_handoff=False: a role fleet degrades to
    plain scored placement — still correct, no capped legs."""
    obs.reset("router.")
    flags.set_flags({"router_prefill_handoff": False})
    try:
        fleet = RoleFleet(model, ["prefill", "decode"])
        try:
            async def main():
                await fleet.router.poll_replicas()
                return await do(fleet.router, "POST", "/v1/completions",
                                completion_body(PROMPT, 12, stream=True))

            status, _headers, body = asyncio.run(main())
            assert status == 200
            toks, _, _ = _stream_tokens(body)
            assert toks == oracle[:12]
            assert int(obs.metrics.counter("router.handoff",
                                           outcome="ok").value) == 0
        finally:
            fleet.close()
    finally:
        flags.set_flags({"router_prefill_handoff": True})


# ---------------------------------------------------------------------------
# supervisor: role slots, per-role autoscale, proactive rebalance
# ---------------------------------------------------------------------------

def _role_sup(roles, clock=None, **kw):
    handles = {}
    spawned = []                                  # (rid, role) per spawn

    def spawner(rid, role):
        h = FakeHandle(rid)
        handles.setdefault(rid, []).append(h)
        spawned.append((rid, role))
        return h

    router = RouterServer([], allow_empty=True, health_interval_s=1e9,
                          dead_after=2)
    kw.setdefault("min_replicas", 1)
    kw.setdefault("max_replicas", 8)
    kw.setdefault("hot_ticks", 10**9)
    kw.setdefault("cold_ticks", 10**9)
    kw.setdefault("cooldown_s", 0.0)
    kw.setdefault("backoff_base_s", 1.0)
    kw.setdefault("backoff_max_s", 8.0)
    kw.setdefault("backoff_reset_s", 100.0)
    kw.setdefault("restart_budget", 2)
    kw.setdefault("drain_timeout_s", 10.0)
    kw.setdefault("rebalance", False)
    sup = FleetSupervisor(router, spawner, roles=roles,
                          clock=clock or Clock(), **kw)
    return sup, router, handles, spawned


def test_role_fleet_spawns_role_slots_and_gauges():
    obs.reset("fleet.")
    clock = Clock()
    sup, router, handles, spawned = _role_sup(
        {"prefill": 1, "decode": 2}, clock=clock)
    assert sup._spawner_roleful            # (rid, role) spawner detected
    sup.start()
    assert sup.target == 3
    assert sorted(spawned) == [("fs0", "decode"), ("fs1", "decode"),
                               ("fs2", "prefill")]
    for hs in handles.values():
        hs[0].ready_now = True
    sup.tick()
    assert int(obs.metrics.gauge("fleet.role", role="decode").value) == 2
    assert int(obs.metrics.gauge("fleet.role", role="prefill").value) == 1
    assert sup.state()["roles"] == {"prefill": 1, "decode": 2}
    # a crash-restart keeps the slot's role sticky
    handles["fs0"][0].die()
    sup.tick()                                    # -> BACKOFF
    clock.t = 50.0
    sup.tick()                                    # respawn
    assert spawned[-1] == ("fs0", "decode")


def test_legacy_single_arg_spawner_not_roleful():
    router = RouterServer([], allow_empty=True, health_interval_s=1e9)
    sup = FleetSupervisor(router, lambda rid: FakeHandle(rid), target=1,
                          min_replicas=1, max_replicas=2,
                          hot_ticks=10**9, cold_ticks=10**9,
                          cooldown_s=0.0, rebalance=False)
    assert not sup._spawner_roleful
    assert sup.roles is None


def test_role_autoscale_prefill_on_queue_decode_on_load():
    """Each role scales on ITS signal: prefill on admission queue depth
    (TTFT pressure), decode on resident load (ITL pressure) — a loaded
    decode fleet must not grow the prefill fleet and vice versa."""
    obs.reset("fleet.")
    clock = Clock()
    sup, router, handles, spawned = _role_sup(
        {"prefill": 1, "decode": 1}, clock=clock, hot_ticks=1,
        max_replicas=6, scale_up_load=2.0)
    sup.start()                                   # fs0 decode, fs1 prefill
    handles["fs0"][0].ready_now = True
    handles["fs1"][0].ready_now = True
    sup.tick()
    # decode under resident load (inflight, no queue): decode grows,
    # prefill (queue empty) does NOT
    _mark_live(router, "fs0", role="decode", inflight=5)
    _mark_live(router, "fs1", role="prefill", inflight=5)
    actions = sup.tick()
    assert sup.roles == {"prefill": 1, "decode": 2}
    assert ("scale_up", ("decode", 2)) in actions
    assert "fs2" in handles and spawned[-1] == ("fs2", "decode")
    handles["fs2"][0].ready_now = True
    # the pressure is relieved while the new capacity lands — otherwise
    # the still-hot signal scales decode again the moment fs2 registers
    _mark_live(router, "fs0", role="decode", inflight=0)
    sup.tick()                                    # fs2 registers: settled
    # prefill under queue pressure: prefill grows, decode (now idle)
    # does not
    _mark_live(router, "fs0", role="decode", inflight=0, queue_depth=0)
    _mark_live(router, "fs2", role="decode", inflight=0, queue_depth=0)
    _mark_live(router, "fs1", role="prefill", inflight=0, queue_depth=9)
    actions = sup.tick()
    assert sup.roles == {"prefill": 2, "decode": 2}
    assert ("scale_up", ("prefill", 2)) in actions
    assert sup.target == 4


def test_role_autoscale_floor_never_drops_a_phase():
    obs.reset("fleet.")
    clock = Clock()
    sup, router, handles, _spawned = _role_sup(
        {"prefill": 1, "decode": 2}, clock=clock, cold_ticks=1,
        scale_down_load=100.0)
    sup.start()
    for hs in handles.values():
        hs[0].ready_now = True
    sup.tick()
    for rid, role in (("fs0", "decode"), ("fs1", "decode"),
                      ("fs2", "prefill")):
        _mark_live(router, rid, role=role)
    sup.tick()                                    # everything is cold
    # decode shrank to its floor of 1; prefill CANNOT go below 1
    assert sup.roles["decode"] == 1
    for _ in range(6):
        clock.t += 1.0
        sup.tick()
    assert sup.roles == {"prefill": 1, "decode": 1}
    assert sup.target == 2


class MigHandle(FakeHandle):
    """FakeHandle with a working migration plane."""

    def __init__(self, rid):
        super().__init__(rid)
        self.export_result = [{"tokens": list(range(16)),
                               "pages": [0, 1]}]
        self.exports = 0
        self.imports = []

    def export_sessions(self):
        self.exports += 1
        return list(self.export_result)

    def import_sessions(self, snaps):
        self.imports.append(snaps)
        return {"sessions": len(snaps), "imported": 2, "skipped": 0,
                "aborted": 0}


def test_rebalance_moves_pins_off_shedding_replica():
    """Proactive rebalance: the first READY slot the router reports
    shedding gets its sessions' KV pre-staged on an admitting peer and
    their pins re-pointed — at most once per cooldown window."""
    obs.reset("fleet.")
    clock = Clock()
    handles = {}

    def spawner(rid):
        h = MigHandle(rid)
        handles.setdefault(rid, []).append(h)
        return h

    router = RouterServer([], allow_empty=True, health_interval_s=1e9,
                          dead_after=2)
    sup = FleetSupervisor(router, spawner, target=2, min_replicas=1,
                          max_replicas=4, hot_ticks=10**9,
                          cold_ticks=10**9, cooldown_s=0.0,
                          migrate_on_drain=True, rebalance=True,
                          rebalance_cooldown_s=50.0, clock=clock)
    sup.start()
    handles["fs0"][0].ready_now = True
    handles["fs1"][0].ready_now = True
    sup.tick()
    router.placer.pin("sess-a", "fs0")
    router.placer.pin("sess-b", "fs0")
    router.placer.pin("sess-c", "fs1")
    _mark_live(router, "fs0", slo_decision="shed")
    _mark_live(router, "fs1")
    actions = sup.tick()
    assert ("rebalance", ("fs0", "fs1")) in actions
    assert handles["fs0"][0].exports == 1
    assert handles["fs1"][0].imports            # peer received the pages
    assert router.placer.pinned("sess-a") == "fs1"
    assert router.placer.pinned("sess-b") == "fs1"
    assert router.placer.pinned("sess-c") == "fs1"
    assert int(obs.metrics.counter("fleet.rebalances",
                                   outcome="ok").value) == 1
    # cooldown: still shedding, but the valve opens once per window
    sup.tick()
    assert handles["fs0"][0].exports == 1
    clock.t = 60.0
    sup.tick()
    assert handles["fs0"][0].exports == 2
    assert sup.state()["rebalance"]["outcomes"]["ok"] == 2


def test_rebalance_skips_empty_source_and_aborted_import():
    obs.reset("fleet.")
    clock = Clock()
    handles = {}

    def spawner(rid):
        h = MigHandle(rid)
        handles.setdefault(rid, []).append(h)
        return h

    router = RouterServer([], allow_empty=True, health_interval_s=1e9,
                          dead_after=2)
    sup = FleetSupervisor(router, spawner, target=2, min_replicas=1,
                          max_replicas=4, hot_ticks=10**9,
                          cold_ticks=10**9, cooldown_s=0.0,
                          migrate_on_drain=True, rebalance=True,
                          rebalance_cooldown_s=0.0, clock=clock)
    sup.start()
    handles["fs0"][0].ready_now = True
    handles["fs1"][0].ready_now = True
    sup.tick()
    router.placer.pin("sess-a", "fs0")
    _mark_live(router, "fs0", slo_decision="shed")
    _mark_live(router, "fs1")
    # nothing resident on the source: skipped, pins stay
    handles["fs0"][0].export_result = []
    sup.tick()
    assert router.placer.pinned("sess-a") == "fs0"
    assert int(obs.metrics.counter("fleet.rebalances",
                                   outcome="skipped").value) == 1
    # the peer aborts every snapshot (geometry mismatch): failed, pins
    # stay — in-flight streams were never touched either way
    handles["fs0"][0].export_result = [{"tokens": [1, 2], "pages": [0]}]
    handles["fs1"][0].import_sessions = lambda snaps: {
        "sessions": 0, "imported": 0, "skipped": 0, "aborted": len(snaps)}
    clock.t += 1.0
    sup.tick()
    assert router.placer.pinned("sess-a") == "fs0"
    assert int(obs.metrics.counter("fleet.rebalances",
                                   outcome="failed").value) == 1


def test_fleet_signals_aggregate_per_role():
    router = RouterServer([], allow_empty=True, health_interval_s=1e9)
    router.add_replica(_FakeClient("p0"))
    router.add_replica(_FakeClient("d0"))
    router.add_replica(_FakeClient("d1"))
    for s, role, q, infl in zip(router.states,
                                ("prefill", "decode", "decode"),
                                (4, 0, 0), (0, 3, 5)):
        s.ok = True
        s.ready = True
        s.role = role
        s.queue_depth = q
        s.inflight = infl
    sig = router.fleet_signals()
    assert sig["roles"]["prefill"]["mean_queue_depth"] == 4.0
    assert sig["roles"]["prefill"]["placeable"] == 1
    assert sig["roles"]["decode"]["mean_load"] == 4.0
    assert sig["roles"]["decode"]["placeable"] == 2


# ---------------------------------------------------------------------------
# slow tier: the handoff over real sockets (launcher-spawned processes)
# ---------------------------------------------------------------------------

def _spawn_replicas(specs):
    """specs: [(role, extra_argv)] -> (procs, ports)."""
    import os
    import socket
    import subprocess
    import sys

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    ports = [free_port() for _ in specs]
    procs = []
    for port, (role, extra) in zip(ports, specs):
        argv = [sys.executable, "-m", "paddle_tpu.serving",
                "--port", str(port), "--role", role,
                "--max-batch", "2", "--max-seq-len", "256",
                "--prefill-bucket", "16", "--max-new-tokens", "64",
                "--prefix-cache", "--seed", "0"] + list(extra)
        procs.append(subprocess.Popen(
            argv, env={**os.environ, "JAX_PLATFORMS": "cpu"}))
    return procs, ports


def _await_ready(procs, handles, deadline_s=600):
    deadline = time.time() + deadline_s
    while not all(h.ready() for h in handles):
        assert time.time() < deadline, "replicas never became ready"
        assert all(p.poll() is None for p in procs), \
            "a replica died during warmup"
        time.sleep(0.5)


def _proc_statusz(port):
    import http.client
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    c.request("GET", "/statusz")
    doc = json.loads(c.getresponse().read())
    c.close()
    return doc


def _proc_completion(port, prompt, max_tokens):
    import http.client
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    c.request("POST", "/v1/completions", json.dumps(
        {"prompt": list(prompt), "max_tokens": max_tokens}).encode())
    r = c.getresponse()
    assert r.status == 200
    doc = json.loads(r.read())
    c.close()
    return doc["choices"][0]["token_ids"]


@pytest.mark.slow
def test_disagg_handoff_over_real_sockets():
    """Satellite 3: two launcher-spawned processes in prefill/decode
    roles — the capped prefill leg, the HTTP /migratez handoff, and the
    decode leg, spliced into one unbroken bit-identical client stream
    over real sockets."""
    from paddle_tpu.fleet import ProcessReplicaHandle
    from paddle_tpu.router import HttpReplica

    obs.reset("router.")
    procs, ports = _spawn_replicas([
        ("prefill", ["--page-size", "8"]),
        ("decode", ["--page-size", "8"])])
    handles = [ProcessReplicaHandle(f"p{i}", "127.0.0.1", p)
               for i, p in enumerate(ports)]
    handles[0].proc, handles[1].proc = procs
    try:
        _await_ready(procs, handles)
        router = RouterServer(
            [HttpReplica(f"p{i}", "127.0.0.1", p)
             for i, p in enumerate(ports)],
            policy="scored", health_interval_s=1e9)

        async def main():
            await router.poll_replicas()
            assert [s.role for s in router.states] == \
                ["prefill", "decode"]
            return await do(router, "POST", "/v1/completions",
                            completion_body(list(range(1, 18)), 24,
                                            stream=True))

        status, headers, body = asyncio.run(main())
        assert status == 200
        toks, finishes, ids = _stream_tokens(body)
        assert finishes == ["length"]
        assert len(ids) == 1
        assert body.rstrip().endswith(b"data: [DONE]")
        assert len(toks) == 24
        # bit-identity: the same request unary on the prefill process
        # (its cache still holds the prefix) must produce the same ids
        assert toks == _proc_completion(ports[0], range(1, 18), 24)
        assert int(obs.metrics.counter("router.handoff",
                                       outcome="ok").value) == 1
        assert int(obs.metrics.counter("router.resumes",
                                       outcome="handoff").value) == 1
        # the plane's books, scraped off the real /statusz endpoints
        assert _proc_statusz(ports[0])["engine"].get(
            "migration_exports", 0) >= 1
        assert _proc_statusz(ports[1])["engine"].get(
            "migration_imports", 0) >= 1
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()


@pytest.mark.slow
def test_disagg_handoff_interrupt_falls_back_over_real_sockets():
    """Satellite 3, interrupt path: the decode successor cannot import
    (mismatched --page-size -> geometry rejection over real HTTP) — the
    stream re-prefills on the mixed replica and the client still sees
    one unbroken stream."""
    from paddle_tpu.fleet import ProcessReplicaHandle
    from paddle_tpu.router import HttpReplica

    obs.reset("router.")
    procs, ports = _spawn_replicas([
        ("prefill", ["--page-size", "8"]),
        ("decode", ["--page-size", "16"]),       # geometry mismatch
        ("mixed", ["--page-size", "8"])])
    handles = [ProcessReplicaHandle(f"p{i}", "127.0.0.1", p)
               for i, p in enumerate(ports)]
    for h, p in zip(handles, procs):
        h.proc = p
    try:
        _await_ready(procs, handles)
        router = RouterServer(
            [HttpReplica(f"p{i}", "127.0.0.1", p)
             for i, p in enumerate(ports)],
            policy="scored", health_interval_s=1e9)

        async def main():
            await router.poll_replicas()
            return await do(router, "POST", "/v1/completions",
                            completion_body(list(range(1, 18)), 24,
                                            stream=True))

        status, headers, body = asyncio.run(main())
        assert status == 200
        toks, finishes, ids = _stream_tokens(body)
        assert finishes == ["length"]             # never a dropped stream
        assert len(ids) == 1
        assert len(toks) == 24
        assert toks == _proc_completion(ports[2], range(1, 18), 24)
        assert int(obs.metrics.counter("router.handoff",
                                       outcome="import_failed").value) == 1
        assert int(obs.metrics.counter("router.resumes",
                                       outcome="resumed").value) == 1
        # nothing installed on the mismatched decode process
        assert _proc_statusz(ports[1])["engine"].get(
            "migration_imports", 0) == 0
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
