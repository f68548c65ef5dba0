"""Perf attribution + regression sentinel (ISSUE 10): per-phase step
cost accounting on the engine, EWMA+MAD drift detection over the live
registry (injected TTFT shift + recompile burst caught; steady traffic
clean), anomaly-reason flight-recorder dumps carrying the offending
series, the per-reason dump rate limit, and the metrics-catalog drift
gate."""

import json
import re
import time

import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.inference import ContinuousBatchingEngine, GenerationConfig
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability.sentinel import Drift


# ---------------------------------------------------------------------------
# drift detector unit semantics
# ---------------------------------------------------------------------------

def test_drift_zero_baseline_first_nonzero_sample_is_not_anomalous():
    """A baseline learned at exactly 0 (idle queue) must not flag the
    first real sample: the absolute deviation floor holds the threshold
    up where the relative floor collapses to 0."""
    d = Drift(alpha=0.3, k=4.0, min_samples=3)
    for _ in range(6):
        assert d.update(0.0) is None
    assert d.update(1.0) is None          # first queued request: normal
    assert d.update(60.0) is not None     # a real pile-up still fires


def test_drift_warmup_then_fires_on_shift():
    d = Drift(alpha=0.3, k=4.0, min_samples=5)
    # warmup: nothing may fire regardless of values
    assert d.update(100.0) is None
    for _ in range(4):
        assert d.update(100.0) is None
    # steady continuation: still quiet
    for v in (101.0, 99.0, 102.0, 100.0):
        assert d.update(v) is None
    # a 3x level shift fires immediately
    ratio = d.update(300.0)
    assert ratio is not None and ratio > 1.0
    assert d.fired == 1


def test_drift_adapts_to_persistent_shift():
    """A persistent shift becomes the new normal: the detector flags the
    transition, not the new steady state forever."""
    d = Drift(alpha=0.4, k=4.0, min_samples=3)
    for _ in range(6):
        d.update(10.0)
    fires = sum(d.update(30.0) is not None for _ in range(30))
    assert 1 <= fires < 30            # flagged, then re-based
    assert d.update(30.0) is None     # the new normal is quiet


def test_drift_noisy_but_stable_is_quiet():
    d = Drift(alpha=0.2, k=4.0, min_samples=5)
    vals = [100.0, 104.0, 97.0, 102.0, 99.0] * 10
    assert all(d.update(v) is None for v in vals)
    assert d.fired == 0


# ---------------------------------------------------------------------------
# sentinel sweeps over the registry
# ---------------------------------------------------------------------------

def _sentinel(**kw):
    kw.setdefault("min_samples", 4)
    kw.setdefault("interval_s", 0.0)
    return obs.Sentinel(**kw)


def test_sentinel_detects_injected_ttft_shift():
    obs.reset("serving.ttft_ms")
    obs.reset("observability.anomaly")
    s = _sentinel()
    h = obs.metrics.histogram("serving.ttft_ms")
    for _ in range(6):                      # baseline sweeps
        h.observe(100.0)
        h.observe(102.0)
        assert s.check() == []
    h.observe(300.0)                        # injected 3x regression
    h.observe(310.0)
    found = s.check()
    assert any(a["series"] == "serving.ttft_ms" and a["kind"] == "drift"
               for a in found)
    # counters + bounded history carry the verdict
    assert obs.metrics.counter("observability.anomaly",
                               series="serving.ttft_ms",
                               kind="drift").value >= 1
    assert s.anomalies_total >= 1
    assert s.state()["recent"][-1]["series"] == "serving.ttft_ms"


def test_sentinel_detects_warm_recompile_burst():
    s = _sentinel(min_samples=3)
    for _ in range(4):                      # compile-free warm sweeps
        assert s.check() == []
    # injected warm-compile burst (a genuinely fresh XLA program)
    jax.jit(lambda x: x * 3.25 - 11)(jnp.ones((4,)))
    found = s.check()
    assert any(a["series"] == "jit.backend_compiles"
               and a["kind"] == "burst" for a in found)


def test_sentinel_compile_during_warmup_not_anomalous():
    """Compiles BEFORE the warm window completes are cold-start work,
    not a regression."""
    s = _sentinel(min_samples=3)
    jax.jit(lambda x: x * 5.25 + 13)(jnp.ones((4,)))
    assert s.check() == []                  # sweep sees the compile: warm
    for _ in range(10):                     # resets, then warms cleanly
        assert s.check() == []


def test_sentinel_steady_workload_zero_anomalies():
    """False-positive guard: a steady synthetic workload (jittery but
    stationary TTFT/ITL/queue) produces ZERO anomalies."""
    obs.reset("serving.ttft_ms")
    obs.reset("serving.itl_ms")
    s = _sentinel(min_samples=4)
    ttft = obs.metrics.histogram("serving.ttft_ms")
    itl = obs.metrics.histogram("serving.itl_ms")
    q = obs.metrics.gauge("serving.queue_depth_now")
    import random
    rng = random.Random(0)
    for i in range(40):
        for _ in range(3):
            ttft.observe(80.0 + rng.uniform(-8, 8))
            itl.observe(12.0 + rng.uniform(-1.5, 1.5))
        q.set(2 + (i % 2))
        assert s.check() == [], f"false positive at sweep {i}"
    assert s.anomalies_total == 0


def test_sentinel_anomaly_flight_dump_carries_series(tmp_path):
    """The anomaly dump contract: reason 'anomaly', and the dumped ring
    contains the sentinel's instant event naming the offending series."""
    tr = obs.Tracer()
    fr = obs.FlightRecorder(path=str(tmp_path / "fr.json"),
                            min_interval_s=60.0, tracer=tr)
    fr.attach()
    try:
        obs.reset("serving.itl_ms")
        s = _sentinel(min_samples=4, tracer=tr, flight_recorder=fr)
        h = obs.metrics.histogram("serving.itl_ms")
        for _ in range(6):
            h.observe(10.0)
            assert s.check() == []
        h.observe(50.0)                     # 5x ITL regression
        found = s.check()
        assert found
        # the dump runs on a background thread (it must never stall the
        # engine loop): wait for it to land
        deadline = time.time() + 10
        while fr.last_dump is None and time.time() < deadline:
            time.sleep(0.01)
        assert fr.last_dump is not None
        doc = json.loads(open(fr.last_dump).read())
        assert doc["metadata"]["reason"] == "anomaly"
        instants = [e for e in doc["traceEvents"]
                    if e.get("name") == "observability.anomaly"]
        assert any(e["args"]["series"] == "serving.itl_ms"
                   for e in instants)
    finally:
        fr.detach()


# ---------------------------------------------------------------------------
# flight-recorder per-reason dump rate limit (ISSUE 10 satellite)
# ---------------------------------------------------------------------------

def test_dump_storm_yields_one_file_per_window(tmp_path):
    tr = obs.Tracer()
    fr = obs.FlightRecorder(path=str(tmp_path / "storm.json"),
                            min_interval_s=60.0, tracer=tr)
    dumps = obs.metrics.counter("flight_recorder.dumps")
    supp = obs.metrics.counter("flight_recorder.suppressed_dumps")
    d0, s0 = dumps.value, supp.value
    paths = {fr.dump(reason="anomaly") for _ in range(10)}
    assert len(paths) == 1                   # the storm collapsed
    assert dumps.value == d0 + 1 and supp.value == s0 + 9
    assert len(list(tmp_path.glob("*.json"))) == 1
    # a DIFFERENT reason is never shadowed
    other = fr.dump(reason="watchdog-x")
    assert other != paths.pop()
    assert dumps.value == d0 + 2


def test_dump_rate_limit_window_expires(tmp_path):
    tr = obs.Tracer()
    fr = obs.FlightRecorder(path=str(tmp_path / "w.json"),
                            min_interval_s=0.05, tracer=tr)
    p1 = fr.dump(reason="anomaly")
    assert fr.dump(reason="anomaly") == p1   # inside the window
    time.sleep(0.06)
    assert fr.dump(reason="anomaly") == p1   # same path, fresh write
    assert obs.metrics.counter("flight_recorder.dumps").value >= 2


def test_dump_rate_limit_disabled(tmp_path):
    tr = obs.Tracer()
    fr = obs.FlightRecorder(path=str(tmp_path / "n.json"),
                            min_interval_s=0.0, tracer=tr)
    supp = obs.metrics.counter("flight_recorder.suppressed_dumps")
    s0 = supp.value
    for _ in range(3):
        fr.dump(reason="anomaly")
    assert supp.value == s0


# ---------------------------------------------------------------------------
# per-phase step attribution on the live engine
# ---------------------------------------------------------------------------

def _tiny_engine(model, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("gen", GenerationConfig(max_new_tokens=6))
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_bucket", 8)
    return ContinuousBatchingEngine(model, **kw)


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig.tiny())


def test_engine_attributes_prefill_and_decode_phases(model):
    obs.reset("serving.")
    # every step's gather folds the stamps since the last one: the run
    # spans a fold a step, the last of which are decode-only (the prompts
    # are through after two steps)
    eng = _tiny_engine(model, metrics=True)
    for p in ([1, 2, 3, 4, 5, 6, 7, 8, 9], [4, 5, 6]):
        eng.add_request(p)
    out = eng.run()
    assert all(len(v) == 6 for v in out.values())
    pre = obs.metrics.histogram("serving.step_ms", phase="prefill")
    dec = obs.metrics.histogram("serving.step_ms", phase="decode")
    drn = obs.metrics.histogram("serving.step_ms", phase="drain")
    assert pre.count > 0 and dec.count > 0 and drn.count > 0
    # every dispatch is attributed: phase counts tile the step counter
    steps = obs.metrics.counter("serving.steps").value
    assert pre.count + dec.count == steps
    assert drn.count == obs.metrics.counter("serving.drains").value
    assert obs.metrics.gauge("serving.tokens_per_sec",
                             phase="decode").value > 0
    # the gauge is of the LAST fold: prefill went idle before the final
    # gather, so its rate reads 0 rather than its last active step's forever
    assert obs.metrics.gauge("serving.tokens_per_sec",
                             phase="prefill").value == 0.0
    # EWMA cost table keyed by (phase, bucket)
    base = eng.attribution.baselines()
    assert "decode/T1" in base and "prefill/T8" in base
    assert base["decode/T1"]["n"] == dec.count
    assert base["decode/T1"]["ewma_ms"] > 0


def test_engine_attribution_off_with_metrics_off(model):
    obs.reset("serving.step_ms")
    eng = _tiny_engine(model, metrics=False)
    eng.add_request([1, 2, 3])
    eng.run()
    assert eng.attribution is None
    assert obs.metrics.histogram("serving.step_ms",
                                 phase="decode").count == 0


def test_spec_engine_attributes_fused_phase(model):
    obs.reset("serving.step_ms")
    eng = _tiny_engine(model, metrics=True, spec_decode="fused", spec_k=4)
    eng.add_request([1, 2, 3, 4, 5])
    out = eng.run()
    assert all(len(v) == 6 for v in out.values())
    fused = obs.metrics.histogram("serving.step_ms", phase="fused_k")
    assert fused.count > 0
    assert "fused_k/T4" in eng.attribution.baselines()
    # drain-credited tokens give the fused lane a throughput reading
    assert obs.metrics.gauge("serving.tokens_per_sec",
                             phase="fused_k").value > 0


def test_warm_steps_with_attribution_zero_compiles_zero_syncs(
        model, monkeypatch):
    """The acceptance criterion: attribution enabled (its stamps folded at
    every gather), warm engine steps under the bound on steps in flight
    still perform ZERO XLA compiles and ZERO marked device syncs."""
    from paddle_tpu.inference import generation
    monkeypatch.setattr(generation, "MAX_STEPS_IN_FLIGHT", 64)
    eng = _tiny_engine(model, metrics=True)
    eng.add_request([1, 2, 3])
    eng.run()                                 # warm the T pair
    eng.add_request([7, 8, 9])
    with obs.assert_overhead(max_compiles=0, max_syncs=0):
        for _ in range(6):
            eng.step()
    assert obs.metrics.histogram("serving.step_ms",
                                 phase="decode").count > 0


def test_inflight_requests_table(model):
    eng = _tiny_engine(model, metrics=True, max_batch=1)
    r1 = eng.add_request([1, 2, 3], max_new_tokens=4)
    r2 = eng.add_request([4, 5, 6, 7], max_new_tokens=4)  # queued behind
    eng.step()
    rows = eng.inflight_requests()
    assert {r["req_id"] for r in rows} == {r1, r2}
    assert rows[0]["req_id"] == r1            # oldest first
    states = {r["req_id"]: r["state"] for r in rows}
    assert states[r2] == "queued"
    assert all(r["age_s"] is not None and r["age_s"] >= 0 for r in rows)
    assert rows[0]["prompt_tokens"] == 3 and rows[0]["trace_id"] is None
    eng.run()
    assert eng.inflight_requests() == []


# ---------------------------------------------------------------------------
# metrics catalog drift gate (ISSUE 10 satellite)
# ---------------------------------------------------------------------------

def test_every_emitted_family_is_documented():
    """Every family this test process has created (minus throwaway
    t<digit>… test families and custom StepTimer names) must be in the
    catalog — an emitted-but-undocumented series fails tier-1."""
    test_fam = re.compile(r"^t\d")
    extra = [n for n in obs.catalog.undocumented()
             if not test_fam.match(n)]
    assert extra == [], f"undocumented metric families: {extra}"


def test_docs_metrics_md_matches_generator():
    import pathlib
    doc = pathlib.Path(__file__).resolve().parent.parent / \
        "docs" / "metrics.md"
    assert doc.read_text() == obs.catalog.generate_markdown(), \
        "docs/metrics.md is stale — regenerate with " \
        "`python -m paddle_tpu.observability.catalog`"


def test_catalog_covers_new_series():
    for fam in ("serving.step_ms", "serving.tokens_per_sec",
                "observability.anomaly",
                "flight_recorder.suppressed_dumps"):
        assert fam in obs.catalog.CATALOG


# ---------------------------------------------------------------------------
# router-side fleet aggregation
# ---------------------------------------------------------------------------

def test_replica_state_folds_anomalies_from_statusz():
    from paddle_tpu.router.placement import ReplicaState

    class FakeClient:
        id = "r0"

        def describe(self):
            return {"id": "r0", "transport": "fake"}

    s = ReplicaState(FakeClient())
    rec = {"series": "serving.ttft_ms", "kind": "drift", "t": 1.0}
    s.apply_statusz({"ready": True,
                     "anomalies": {"anomalies_total": 3,
                                   "recent": [rec]}})
    assert s.anomaly_total == 3
    assert s.anomalies_recent == [rec]
    assert s.describe(dead_after=3)["anomalies"] == 3
    # a statusz without the section resets cleanly (older replica)
    s.apply_statusz({"ready": True})
    assert s.anomaly_total == 0 and s.anomalies_recent == []


def test_router_statusz_aggregates_fleet_anomalies():
    from paddle_tpu.router.placement import ReplicaState
    from paddle_tpu.router.server import RouterServer

    class FakeClient:
        def __init__(self, rid):
            self.id = rid

        def describe(self):
            return {"id": self.id, "transport": "fake"}

        async def open(self, *a, **k):
            raise ConnectionRefusedError

    router = RouterServer([FakeClient("a"), FakeClient("b")])
    recs = [{"series": "serving.ttft_ms", "kind": "drift", "t": 2.0},
            {"series": "jit.backend_compiles", "kind": "burst", "t": 1.0}]
    router.states[0].apply_statusz(
        {"ready": True, "anomalies": {"anomalies_total": 2,
                                      "recent": recs}})
    router.states[1].apply_statusz(
        {"ready": True, "anomalies": {"anomalies_total": 1,
                                      "recent": [recs[0]]}})
    agg = router.statusz()["anomalies"]
    assert agg["total"] == 3
    assert agg["by_replica"] == {"a": 2, "b": 1}
    assert len(agg["recent"]) == 3
    assert {r["replica"] for r in agg["recent"]} == {"a", "b"}
    # merged tail is time-ordered
    ts = [r["t"] for r in agg["recent"]]
    assert ts == sorted(ts)


# ---------------------------------------------------------------------------
# sentinel in the serving server (statusz surfacing)
# ---------------------------------------------------------------------------

def test_serving_statusz_surfaces_sentinel_and_latency(model):
    from paddle_tpu.serving import ServingServer

    eng = _tiny_engine(model, metrics=True)
    eng.add_request([1, 2, 3, 4, 5])
    eng.run()
    sentinel = _sentinel(min_samples=4)
    server = ServingServer(eng, flight_recorder=False, sentinel=sentinel)
    try:
        doc = server.statusz()
        assert doc["anomalies"]["checks"] == sentinel.checks
        assert "recent" in doc["anomalies"]
        lat = doc["latency"]
        assert "serving.ttft_ms" in lat
        assert lat["serving.ttft_ms"]["count"] >= 1
        assert {"count", "p50", "p95", "p99"} <= set(
            lat["serving.ttft_ms"])
        assert any(k.startswith("serving.step_ms{") for k in lat)
        assert "decode/T1" in doc["attribution"]
        assert isinstance(doc["inflight_requests"], list)
        assert doc["flight_recorder"] is None
    finally:
        server.close()


def test_serving_server_builds_sentinel_from_flag(model):
    from paddle_tpu.serving import ServingServer

    server = ServingServer(_engine_for_flagtest(model),
                           flight_recorder=False)
    try:
        from paddle_tpu import flags
        want = flags.flag("serving_sentinel") and obs.metrics_enabled()
        assert (server.sentinel is not None) == want
        off = ServingServer(_engine_for_flagtest(model),
                            flight_recorder=False, sentinel=False)
        assert off.sentinel is None
        off.close()
    finally:
        server.close()


def _engine_for_flagtest(model):
    return _tiny_engine(model, metrics=True)
