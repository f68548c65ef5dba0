"""Unified metrics + tracing runtime (ISSUE 5): registry semantics,
Chrome-trace export, the assert_overhead contract, serving per-request
telemetry (TTFT/ITL/queue/occupancy), the PretrainStep StepTimer, and the
collective watchdog's heartbeat gauge + timeout fire path."""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import flags
from paddle_tpu import observability as obs
from paddle_tpu.inference import ContinuousBatchingEngine, GenerationConfig
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_gauge_and_labels():
    c = obs.metrics.counter("t9.hits")
    c0 = c.value
    c.inc()
    c.inc(3)
    assert obs.metrics.counter("t9.hits").value == c0 + 4  # same series
    assert obs.metrics.counter("t9.hits", shard="a") is not \
        obs.metrics.counter("t9.hits", shard="b")          # labeled split
    g = obs.metrics.gauge("t9.depth")
    g.set(7)
    snap = obs.snapshot()
    assert snap["counters"]["t9.hits"] == c0 + 4
    assert snap["gauges"]["t9.depth"] == 7.0
    assert "t9.hits{shard=a}" in snap["counters"]


def test_histogram_summary_and_percentiles():
    h = obs.metrics.histogram("t9.lat_ms")
    for v in (1.5, 2.5, 3.5, 100.0):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 4 and s["min"] == 1.5 and s["max"] == 100.0
    assert s["mean"] == pytest.approx((1.5 + 2.5 + 3.5 + 100.0) / 4)
    # p50 must land in the bucket holding the 2nd observation (2, 5]
    assert 1.5 <= s["p50"] <= 5.0
    assert s["p99"] <= 100.0
    # buckets are [le, count] pairs summing to the observation count
    assert sum(c for _, c in h.nonzero_buckets()) == 4


def test_prometheus_text_format():
    obs.metrics.counter("t9.prom_total").inc(2)
    obs.metrics.histogram("t9.prom_ms").observe(3.0)
    text = obs.prometheus_text()
    assert "# TYPE paddle_tpu_t9_prom_total counter" in text
    assert "paddle_tpu_t9_prom_total 2" in text
    assert "paddle_tpu_t9_prom_ms_count 1" in text
    assert 'le="+Inf"' in text


def test_reset_zeroes_in_place_keeping_handles_live():
    c = obs.metrics.counter("t9reset.n")
    h = obs.metrics.histogram("t9reset.ms")
    c.inc(5)
    h.observe(1.0)
    obs.reset("t9reset.")
    assert c.value == 0 and h.count == 0
    # the CRITICAL property: handles resolved before the reset still
    # record into the registry (the serving engine caches its series)
    c.inc()
    h.observe(2.0)
    assert obs.metrics.counter("t9reset.n").value == 1
    assert obs.metrics.histogram("t9reset.ms").count == 1


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_tracer_spans_and_chrome_export(tmp_path):
    tr = obs.Tracer()
    tr.start()
    with tr.span("outer", rows=2) as outer:
        with tr.span("inner"):
            time.sleep(0.002)
        outer.set_metadata(tokens=7)      # a count known only inside it
    tr.event("retro", time.perf_counter() - 1.0, 0.5, tid="lane")
    tr.instant("marker")
    tr.stop()
    path = tr.export_chrome_trace(str(tmp_path / "t.json"))
    doc = json.loads(open(path).read())
    names = [e["name"] for e in doc["traceEvents"]]
    assert "outer" in names and "inner" in names and "retro" in names
    xs = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    assert xs["outer"]["dur"] >= xs["inner"]["dur"] > 0
    assert xs["outer"]["args"] == {"rows": 2, "tokens": 7}
    assert "args" not in xs["inner"]
    assert xs["retro"]["dur"] == pytest.approx(0.5e6)
    # named lanes get a thread_name metadata event
    assert any(e["ph"] == "M" and e["args"]["name"] == "lane"
               for e in doc["traceEvents"])
    assert doc["metadata"]["dropped_events"] == 0


def test_tracer_disabled_is_inert():
    tr = obs.Tracer()
    with tr.span("nope"):
        pass
    tr.event("nope2", 0.0, 1.0)
    assert tr._events == []


def test_tracer_event_cap():
    tr = obs.Tracer(max_events=3)
    tr.start()
    for i in range(6):
        tr.instant(f"e{i}")
    assert len(tr._events) == 3 and tr.dropped == 3


def test_tracer_cap_drops_counted_in_registry():
    """ISSUE 6 satellite: hitting FLAGS_trace_max_events is no longer a
    silent drop — every dropped event bumps tracing.dropped_events, so a
    /metrics scrape shows a tracer that stopped recording mid-run."""
    ctr = obs.metrics.counter("tracing.dropped_events")
    before = ctr.value
    tr = obs.Tracer(max_events=2)
    tr.start()
    for i in range(7):
        tr.instant(f"d{i}")
    assert tr.dropped == 5
    assert ctr.value == before + 5


def test_tracer_ring_records_while_stopped():
    """The flight-recorder seam: an attached bounded ring receives every
    event even with the flat export buffer stopped, and the deque bound
    caps memory."""
    from collections import deque
    ring = deque(maxlen=3)
    tr = obs.Tracer()
    assert not tr.enabled
    tr.attach_ring(ring)
    assert tr.enabled                    # ring-only recording is "on"
    for i in range(6):
        tr.instant(f"r{i}")
    assert tr._events == []              # flat buffer untouched
    assert [e["name"] for e in ring] == ["r3", "r4", "r5"]
    tr.detach_ring()
    assert not tr.enabled
    tr.instant("after")
    assert len(ring) == 3                # nothing recorded after detach


# ---------------------------------------------------------------------------
# cardinality guard (ISSUE 6 satellite: FLAGS_metrics_max_series)
# ---------------------------------------------------------------------------

def test_metric_registry_cardinality_guard():
    old = flags.get_flags(["metrics_max_series"])
    flags.set_flags({"metrics_max_series": 4})
    try:
        dropped = obs.metrics.counter("metrics.dropped_series")
        d0 = dropped.value
        series = [obs.metrics.counter("t9cap.reqs", tenant=f"t{i}")
                  for i in range(10)]
        # first 4 label sets are real series; the rest fold into ONE
        # __overflow__ series instead of growing the registry
        assert len({id(s) for s in series}) == 5
        overflow = series[-1]
        assert overflow is series[4]
        assert dict(overflow.labels) == {"series": "__overflow__"}
        assert dropped.value == d0 + 6
        # the overflow series still records (folded, not lost)
        for s in series:
            s.inc()
        assert overflow.value == 6
        snap = obs.snapshot()
        assert "t9cap.reqs{series=__overflow__}" in snap["counters"]
        assert sum(1 for k in snap["counters"]
                   if k.startswith("t9cap.reqs{")) == 5
        # unlabeled base series and repeat lookups of existing labeled
        # series are never capped
        assert obs.metrics.counter("t9cap.reqs") is not overflow
        assert obs.metrics.counter("t9cap.reqs", tenant="t0") is series[0]
        # histograms guard independently per (kind, family)
        hs = [obs.metrics.histogram("t9cap.lat_ms", tenant=f"t{i}")
              for i in range(6)]
        assert len({id(h) for h in hs}) == 5
        hs[-1].observe(1.0)
        assert obs.metrics.histogram(
            "t9cap.lat_ms", tenant="t99").count == 1   # same overflow series
    finally:
        flags.set_flags(old)


# ---------------------------------------------------------------------------
# Prometheus exposition conformance (ISSUE 6 satellite): a strict
# line-format parser accepts the whole registry's output
# ---------------------------------------------------------------------------

_PROM_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_PROM_VALUE = r"(?:[-+]?[0-9]*\.?[0-9]+(?:[eE][-+]?[0-9]+)?|[+-]Inf|NaN)"


def _parse_prom_labels(s):
    """Strict label-body scan: k="v" pairs, values may contain escaped
    backslash / quote / newline and nothing raw."""
    import re
    labels = {}
    i = 0
    while i < len(s):
        m = re.match(r"[a-zA-Z_][a-zA-Z0-9_]*", s[i:])
        assert m, f"bad label name at {s[i:]!r}"
        k = m.group(0)
        i += len(k)
        assert s[i] == "=" and s[i + 1] == '"', f"bad label syntax {s!r}"
        i += 2
        v = []
        while True:
            c = s[i]
            if c == "\\":
                nxt = s[i + 1]
                assert nxt in ("\\", '"', "n"), f"bad escape \\{nxt}"
                v.append({"\\": "\\", '"': '"', "n": "\n"}[nxt])
                i += 2
            elif c == '"':
                i += 1
                break
            else:
                assert c != "\n", "raw newline in label value"
                v.append(c)
                i += 1
        labels[k] = "".join(v)
        if i < len(s):
            assert s[i] == ",", f"expected ',' at {s[i:]!r}"
            i += 1
    return labels


def parse_prometheus(text):
    """Strict exposition-format parser: HELP then TYPE exactly once per
    family, every sample belongs to the most recent family, histogram
    ladders are cumulative and end at le="+Inf" == _count.  Returns
    {family: {"type", "help", "samples": [(name, labels, value)]}}."""
    import re
    assert text.endswith("\n"), "exposition must end with a newline"
    families, cur = {}, None
    for ln in text.splitlines():
        if ln.startswith("# HELP "):
            m = re.fullmatch(rf"# HELP ({_PROM_NAME}) (.*)", ln)
            assert m, f"bad HELP line: {ln!r}"
            name = m.group(1)
            assert name not in families, f"duplicate family {name}"
            families[name] = {"help": m.group(2), "type": None,
                              "samples": []}
            cur = name
        elif ln.startswith("# TYPE "):
            m = re.fullmatch(
                rf"# TYPE ({_PROM_NAME}) "
                r"(counter|gauge|histogram|summary|untyped)", ln)
            assert m, f"bad TYPE line: {ln!r}"
            assert m.group(1) == cur, "TYPE must follow its HELP"
            assert families[cur]["type"] is None, "duplicate TYPE"
            families[cur]["type"] = m.group(2)
        elif ln.startswith("#"):
            continue
        else:
            m = re.fullmatch(
                rf"({_PROM_NAME})(?:\{{(.*)\}})? ({_PROM_VALUE})", ln)
            assert m, f"bad sample line: {ln!r}"
            name = m.group(1)
            labels = _parse_prom_labels(m.group(2)) if m.group(2) else {}
            fam = name
            for suffix in ("_bucket", "_sum", "_count"):
                if name.endswith(suffix) and name[:-len(suffix)] == cur:
                    fam = cur
            assert fam == cur, f"sample {name} outside its family group"
            families[fam]["samples"].append((name, labels, m.group(3)))
    for name, fam in families.items():
        assert fam["type"] is not None, f"family {name} missing TYPE"
        if fam["type"] != "histogram":
            continue
        groups = {}
        for sname, labels, value in fam["samples"]:
            key = tuple(sorted((k, v) for k, v in labels.items()
                               if k != "le"))
            groups.setdefault(key, {"buckets": [], "sum": None,
                                    "count": None})
            g = groups[key]
            if sname == name + "_bucket":
                g["buckets"].append((labels["le"], float(value)))
            elif sname == name + "_sum":
                g["sum"] = float(value)
            elif sname == name + "_count":
                g["count"] = float(value)
        for key, g in groups.items():
            assert g["sum"] is not None and g["count"] is not None
            les = [le for le, _ in g["buckets"]]
            assert les[-1] == "+Inf", "ladder must end at +Inf"
            bounds = [float(le) for le in les[:-1]]
            assert bounds == sorted(bounds), "le bounds must ascend"
            cums = [c for _, c in g["buckets"]]
            assert cums == sorted(cums), "bucket counts must be cumulative"
            assert cums[-1] == g["count"], "+Inf bucket != _count"
    return families


def test_prometheus_exposition_conformance():
    """Golden conformance: awkward label values round-trip through the
    escaper, HELP/TYPE emitted once per family, and the ENTIRE process
    registry (every series every test has created) parses strictly."""
    awkward = 'a"b\\c\nd,e={}'
    obs.metrics.counter("t9conf.reqs_total", path=awkward).inc(3)
    obs.metrics.gauge("t9conf.depth").set(2.5)
    h = obs.metrics.histogram("t9conf.lat_ms")
    for v in (0.5, 3.0, 7000.0):
        h.observe(v)
    obs.metrics.set_help("t9conf.reqs_total", "requests\nby path\\slash")
    fams = parse_prometheus(obs.prometheus_text())
    fam = fams["paddle_tpu_t9conf_reqs_total"]
    assert fam["type"] == "counter"
    assert fam["help"] == "requests\\nby path\\\\slash"   # escaped once
    (name, labels, value), = fam["samples"]
    assert labels == {"path": awkward} and value == "3"   # round-trip
    assert fams["paddle_tpu_t9conf_depth"]["type"] == "gauge"
    hist = fams["paddle_tpu_t9conf_lat_ms"]
    assert hist["type"] == "histogram"
    counts = [s for s in hist["samples"]
              if s[0] == "paddle_tpu_t9conf_lat_ms_count"]
    assert counts[0][2] == "3"


# ---------------------------------------------------------------------------
# assert_overhead — the generalized warm-path contract
# ---------------------------------------------------------------------------

def test_assert_overhead_counts_compiles_and_syncs():
    with obs.assert_overhead(record=True) as rec:
        jax.jit(lambda x: x * 1.25 + 9)(jnp.ones((5,)))
        obs.count_sync()
    assert rec.compiles >= 1 and rec.syncs == 1
    with pytest.raises(AssertionError, match="compile"):
        with obs.assert_overhead():
            jax.jit(lambda x: x * 2.25 - 7)(jnp.ones((6,)))
    with pytest.raises(AssertionError, match="sync"):
        with obs.assert_overhead():
            obs.count_sync()
    with obs.assert_overhead(max_syncs=2):
        obs.count_sync(2)


def test_assert_overhead_matches_jit_assert_no_recompiles():
    """Both read the same registry series — one compile system."""
    from paddle_tpu.jit import assert_no_recompiles
    with obs.assert_overhead(record=True) as a, \
            assert_no_recompiles(record=True) as b:
        jax.jit(lambda x: x - 0.125)(jnp.ones((7,)))
    assert a.compiles == b.compiles >= 1


# ---------------------------------------------------------------------------
# serving engine telemetry
# ---------------------------------------------------------------------------

def _tiny_engine(**kw):
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    return ContinuousBatchingEngine(
        model, max_batch=2, gen=GenerationConfig(max_new_tokens=6),
        max_seq_len=64, page_size=8, prefill_bucket=8, **kw)


def test_engine_request_lifecycle_histograms():
    obs.reset("serving.")
    eng = _tiny_engine(metrics=True)
    rids = [eng.add_request(p) for p in ([1, 2, 3], [4, 5], [6, 7, 8, 9])]
    out = eng.run()
    total = sum(len(out[r]) for r in rids)
    ttft = obs.metrics.histogram("serving.ttft_ms")
    itl = obs.metrics.histogram("serving.itl_ms")
    assert ttft.count == len(rids)           # one TTFT per request
    assert itl.count == total - len(rids)    # one ITL per later token
    assert ttft.min >= 0 and itl.min >= 0
    assert obs.metrics.counter("serving.tokens_generated").value == total
    assert obs.metrics.counter(
        "serving.requests_completed").value == len(rids)
    assert obs.metrics.histogram("serving.queue_wait_ms").count == len(rids)
    occ = obs.metrics.histogram("serving.batch_occupancy")
    assert occ.count > 0 and 0.0 < occ.max <= 1.0
    # pool gauges folded in from the allocator at drain time
    assert obs.metrics.gauge("serving.peak_pages_in_use").value > 0


def test_engine_eos_does_not_inflate_itl(monkeypatch):
    """Frozen-repeat commits after a device-side EOS are trimmed from the
    output — they must not be timed either: the per-token invariant
    itl.count == tokens - requests holds on EOS-terminating traffic, with
    three steps in flight behind the one that sampled the EOS."""
    from paddle_tpu.inference import generation
    monkeypatch.setattr(generation, "MAX_STEPS_IN_FLIGHT", 4)
    monkeypatch.setattr(generation._InFlight, "landed", lambda self: False)
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    prompt = [1, 2, 3, 4, 5]
    # discover a token greedy decode actually emits mid-stream, then use
    # it as the EOS id so the sequence terminates before its budget
    probe = ContinuousBatchingEngine(
        model, max_batch=2, gen=GenerationConfig(max_new_tokens=8),
        max_seq_len=64, page_size=8, prefill_bucket=8, metrics=False)
    r = probe.add_request(prompt)
    eos = probe.run()[r][2]                  # 3rd generated token
    obs.reset("serving.")
    eng = ContinuousBatchingEngine(
        model, max_batch=2,
        gen=GenerationConfig(max_new_tokens=8, eos_token_id=int(eos)),
        max_seq_len=64, page_size=8, prefill_bucket=8, metrics=True)
    rid = eng.add_request(prompt)            # EOS lands mid flight
    out = eng.run()
    assert out[rid][-1] == eos and len(out[rid]) < 8   # terminated early
    assert obs.metrics.counter(
        "serving.tokens_generated").value == len(out[rid])
    assert obs.metrics.histogram("serving.ttft_ms").count == 1
    assert obs.metrics.histogram("serving.itl_ms").count == \
        len(out[rid]) - 1


def test_engine_latency_is_stamped_when_tokens_reach_the_host(monkeypatch):
    """A token exists for the client when the gather that carries it
    returns, one step after its own.  Here every step takes the device
    ``block_s`` to hand over (the wait sits where the host's is: in the
    gather's transfer): TTFT includes it, and every gap is a TRUE gap of
    one step — none is a share of a burst, none the whole catch-up of
    several steps (which is what sheds a healthy replica through the ITL
    SLO) and none the ~0 between two dispatches."""
    import time
    from paddle_tpu.inference import generation
    block_s = 0.05
    to_host = generation._InFlight.to_host

    def slow_to_host(self):
        time.sleep(block_s)
        to_host(self)

    # one step a gather: the oldest, when the bound asks for it
    monkeypatch.setattr(generation._InFlight, "landed", lambda self: False)
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    eng = ContinuousBatchingEngine(
        model, max_batch=2, gen=GenerationConfig(max_new_tokens=20),
        max_seq_len=64, page_size=8, prefill_bucket=8, metrics=True)
    eng.add_request([1, 2, 3])
    eng.run()                                # compiles: not timed below
    obs.reset("serving.")
    monkeypatch.setattr(generation._InFlight, "to_host", slow_to_host)
    rid = eng.add_request([1, 2, 3])
    out = eng.run()
    ttft = obs.metrics.histogram("serving.ttft_ms")
    itl = obs.metrics.histogram("serving.itl_ms")
    assert ttft.count == 1 and itl.count == len(out[rid]) - 1 == 19
    assert ttft.min >= block_s * 1e3         # visible only after its gather
    # a token a gather: each gap is one hand-over and the host's work
    # (a loaded machine may stretch one, never to the catch-up of many)
    assert itl.min >= block_s * 1e3
    assert itl.max < itl.sum / 4


@pytest.mark.parametrize("engine_kw", [{}, {"prefix_cache": True}],
                         ids=["plain", "prefix_cache"])
def test_engine_serves_the_same_tokens_with_metrics_on_and_off(engine_kw):
    """Telemetry observes the engine and decides nothing: the same
    requests give the same tokens with the registry on and off."""
    prompts = ([1, 2, 3, 4, 5, 6, 7, 8, 9], [1, 2, 3, 4, 5, 6, 7, 8, 4],
               [6, 7, 8, 9], [4, 5])
    outs = []
    for on in (False, True):
        eng = _tiny_engine(metrics=on, **engine_kw)
        rids = [eng.add_request(list(p)) for p in prompts]
        done = eng.run()
        outs.append([done[r] for r in rids])
    assert outs[0] == outs[1] and all(len(o) == 6 for o in outs[0])


def test_engine_metrics_off_records_nothing():
    obs.reset("serving.")
    eng = _tiny_engine(metrics=False)
    rids = [eng.add_request([1, 2, 3]), eng.add_request([4, 5])]
    out = eng.run()
    assert all(len(out[r]) == 6 for r in rids)   # behavior unchanged
    assert obs.metrics.counter("serving.tokens_generated").value == 0
    assert obs.metrics.histogram("serving.ttft_ms").count == 0
    assert obs.metrics.counter("serving.requests_total").value == 0


def test_engine_warm_steps_zero_compiles_zero_syncs(monkeypatch):
    """The ISSUE 5 overhead contract, telemetry-asserted: warm engine
    steps with metrics ON perform ZERO XLA compiles and ZERO marked
    host<->device syncs while the steps in flight stay under their bound
    (a marked sync is a gather that had to WAIT: for the bound, for want
    of anything to dispatch, or for a caller who needs it settled)."""
    from paddle_tpu.inference import generation
    monkeypatch.setattr(generation, "MAX_STEPS_IN_FLIGHT", 64)
    eng = _tiny_engine(metrics=True)
    for p in ([1, 2, 3], [4, 5]):
        eng.add_request(p)
    eng.run()                                 # warm the T-pair programs
    for p in ([9, 8, 7], [2, 3]):
        eng.add_request(p)
    with obs.assert_overhead(max_compiles=0, max_syncs=0):
        for _ in range(6):
            eng.step()
    out = eng.run()
    assert all(len(v) == 6 for v in out.values())


def test_engine_request_spans_in_trace(tmp_path):
    obs.tracer.start()
    try:
        eng = _tiny_engine(metrics=True)
        rid = eng.add_request([1, 2, 3, 4, 5])
        eng.run()
    finally:
        obs.tracer.stop()
    path = obs.export_chrome_trace(str(tmp_path / "serve.json"))
    doc = json.loads(open(path).read())
    names = [e["name"] for e in doc["traceEvents"]]
    # the live step span with the counts at its dispatch: five prompt
    # tokens in one mixed step, then one decode token a step
    steps = [e["args"] for e in doc["traceEvents"]
             if e["name"] == "engine.step"]
    assert [(a["kind"], a["T"], a["rows"], a["q_tokens"])
            for a in steps[:2]] == [("mixed", 8, 1, 5), ("decode", 1, 1, 1)]
    assert {"engine.admit", "engine.build", "engine.h2d", "engine.dispatch",
            "engine.drain", "engine.drain.wait"} <= set(names)
    for phase in ("queued", "prefill", "decode"):
        assert f"req{rid}.{phase}" in names, names
    # the lifecycle phases tile the request's wall time in order
    spans = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    q, p, d = (spans[f"req{rid}.{s}"] for s in ("queued", "prefill",
                                                "decode"))
    assert q["ts"] <= p["ts"] <= d["ts"]
    assert d["args"]["generated"] == 6


# ---------------------------------------------------------------------------
# train StepTimer
# ---------------------------------------------------------------------------

def test_pretrain_steptimer_records_warm_steps_without_syncs():
    from paddle_tpu.models.pretrain import ParallelConfig, PretrainStep

    obs.reset("train.")
    ps = PretrainStep(LlamaConfig.tiny(), ParallelConfig())
    state = ps.init_state(seed=0)
    rng = np.random.default_rng(0)
    ids, labels = ps.shard_batch(
        rng.integers(0, 256, (2, 16)).astype(np.int32),
        rng.integers(0, 256, (2, 16)).astype(np.int32))
    state, loss = ps.train_step(state, ids, labels)      # compile step
    rc_warmup = obs.metrics.counter("train.recompiles").value
    assert rc_warmup >= 1
    with obs.assert_overhead(max_compiles=0, max_syncs=0):
        for _ in range(3):
            state, loss = ps.train_step(state, ids, labels)
    jax.block_until_ready(loss)
    assert obs.metrics.counter("train.steps").value == 4
    h = obs.metrics.histogram("train.step_ms")
    assert h.count == 3                     # warm steps only, compile excluded
    assert obs.metrics.gauge("train.tokens_per_sec").value > 0
    # recompile count did NOT grow over the warm steps
    assert obs.metrics.counter("train.recompiles").value == rc_warmup


def test_steptimer_attributes_compiles_per_step():
    obs.reset("t9train.")
    t = obs.StepTimer("t9train")
    t.begin_step()
    jax.jit(lambda x: x + 17.5)(jnp.ones((3,)))          # a "step" compile
    t.tick(tokens=32)
    t.begin_step()
    t.tick(tokens=32)                                    # warm step
    assert obs.metrics.counter("t9train.recompiles").value >= 1
    assert obs.metrics.counter("t9train.steps").value == 2
    assert obs.metrics.histogram("t9train.step_ms").count == 1


# ---------------------------------------------------------------------------
# watchdog (ISSUE 5 satellite: heartbeat gauge + the timeout fire path)
# ---------------------------------------------------------------------------

def test_watchdog_timeout_fires_and_counts():
    from paddle_tpu.distributed.watchdog import CommTaskManager

    fired_before = obs.metrics.counter("watchdog.timeouts").value
    old = flags.get_flags(["comm_timeout_s"])
    flags.set_flags({"comm_timeout_s": 0})
    m = CommTaskManager()
    m.poll_interval = 0.05
    m.start()
    try:
        m.begin("t9-hung-collective")
        deadline = time.time() + 5.0
        while not m.timed_out and time.time() < deadline:
            time.sleep(0.05)
    finally:
        m.shutdown()
        flags.set_flags(old)
    assert m.timed_out and m.timed_out[0].name == "t9-hung-collective"
    assert obs.metrics.counter("watchdog.timeouts").value > fired_before
    assert not m.outstanding()               # fired task was removed


def test_watchdog_heartbeat_gauge_ages():
    from paddle_tpu.distributed.watchdog import CommTaskManager

    m = CommTaskManager()
    m.poll_interval = 0.05
    m.start()
    try:
        tid = m.begin("t9-live")
        assert obs.metrics.gauge("watchdog.last_heartbeat_age_s").value == 0
        deadline = time.time() + 5.0
        while obs.metrics.gauge("watchdog.last_heartbeat_age_s").value \
                <= 0 and time.time() < deadline:
            time.sleep(0.05)
        assert obs.metrics.gauge("watchdog.last_heartbeat_age_s").value > 0
        assert obs.metrics.gauge("watchdog.outstanding_tasks").value == 1
        m.end(tid)
        assert obs.metrics.gauge("watchdog.outstanding_tasks").value == 0
    finally:
        m.shutdown()


# ---------------------------------------------------------------------------
# one-system integration: cache_stats <-> registry
# ---------------------------------------------------------------------------

def test_cache_stats_reads_registry_series():
    import paddle_tpu.jit as pjit

    before = pjit.cache_stats()["jit"]["backend_compiles"]
    jax.jit(lambda x: x * 0.375)(jnp.ones((9,)))
    stats = pjit.cache_stats()
    assert stats["jit"]["backend_compiles"] > before
    assert stats["jit"]["backend_compiles"] == \
        obs.metrics.counter("jit.backend_compiles").value
    # serving counters are the same registry series too
    assert stats["serving"]["prefix_hits"] == \
        obs.metrics.counter("serving.prefix_hits").value
