"""The engine gathers each step's results as they land (ISSUE 37): no drain
cadence.  Every dispatch starts the copy of ITS OWN arrays to the host,
every ``step()`` delivers the steps that have landed, oldest first, and the
host waits for the device only with ``MAX_STEPS_IN_FLIGHT`` steps out, with
nothing to dispatch, or when asked for a settled engine.

The hazards are those of steps in flight PAST a retirement, so most cases
here make them as deep as they can be: ``_late`` makes no step ever look
landed, so each is gathered only when the bound forces it, and the bound is
raised so that several steps ride behind every retirement."""

import asyncio
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.inference import (ContinuousBatchingEngine, GenerationConfig,
                                  LlamaGenerator, generation, migration)
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import metrics
from paddle_tpu.serving import ServingServer

from test_serving_http import (completion_body, http_bytes, mem_conn,
                               split_response, sse_chunks)

GEOMETRY = dict(max_batch=3, max_seq_len=128, page_size=8, prefill_bucket=8)
LENS = (21, 5, 9, 30, 17, 3, 12)
NEW = (12, 3, 9, 14, 1, 7, 12)


def _llama():
    paddle.seed(7)
    return LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=2,
                                             max_position_embeddings=128))


def _falcon_h1():
    from paddle_tpu.models.falcon_h1 import (FalconH1Config,
                                             FalconH1ForCausalLM)
    paddle.seed(0)
    return FalconH1ForCausalLM(FalconH1Config.tiny())


def _sarvam_mla():
    from paddle_tpu.models.sarvam_mla import (SarvamMlaConfig,
                                              SarvamMlaForCausalLM)
    paddle.seed(0)
    return SarvamMlaForCausalLM(SarvamMlaConfig.tiny())


_MODELS = {}


def _model(name):
    if name not in _MODELS:
        _MODELS[name] = {"llama": _llama, "falcon_h1": _falcon_h1,
                         "sarvam_mla": _sarvam_mla}[name]()
    return _MODELS[name]


def _prompts(vocab=256):
    rng = np.random.default_rng(1)
    out = [[int(t) for t in rng.integers(1, vocab, n)] for n in LENS]
    out[0] = (out[0][:4] * 8)[:LENS[0]]     # something for the drafter
    out[3] = out[0][:16] + out[3][16:]      # a shared prefix for the cache
    return out


def _gen(sample=False, eos=None):
    return GenerationConfig(max_new_tokens=16, do_sample=sample,
                            temperature=0.9, top_k=20, seed=3,
                            eos_token_id=eos)


def _late(monkeypatch, depth):
    """No step ever looks landed and ``depth`` may be in flight: a step is
    gathered only when the bound forces it, ``depth - 1`` steps later."""
    monkeypatch.setattr(generation, "MAX_STEPS_IN_FLIGHT", depth)
    monkeypatch.setattr(generation._InFlight, "landed", lambda self: False)


def _alone(model, gen, prompts, new, **kw):
    """Each request through an engine of its own that holds nothing else
    and is settled before and after: what no neighbour, no predecessor in
    its slot and no step in flight past a retirement can have touched."""
    out = []
    for p, n in zip(prompts, new):
        eng = ContinuousBatchingEngine(model, gen=gen, **kw)
        rid = eng.submit(p, max_new_tokens=n).req_id
        out.append(eng.run()[rid])
    return out


# ---------------------------------------------------------------------------
# (a) the tokens are the tokens
# ---------------------------------------------------------------------------

VARIANTS = {
    "plain": ("llama", {}, {}),
    "prefix_cache": ("llama", dict(prefix_cache=True), {}),
    "ngram": ("llama", dict(spec_decode="ngram", spec_k=4), {}),
    "fused_k": ("llama", dict(spec_decode="fused", spec_k=4), {}),
    "ngram_prefix_cache": ("llama", dict(spec_decode="ngram", spec_k=4,
                                         prefix_cache=True), {}),
    "int8_kv": ("llama", dict(cache_dtype="int8"), dict(cache_dtype="int8")),
    "tp2": ("llama", dict(tensor_parallel=2), {}),
    "falcon_h1_tiny": ("falcon_h1", dict(page_size=16, prefill_bucket=16,
                                         max_seq_len=256),
                       dict(page_size=16, prefill_bucket=16,
                            max_seq_len=256)),
    "latent_pool": ("sarvam_mla", dict(page_size=16, prefill_bucket=64,
                                       max_seq_len=256, prefix_cache=True),
                    dict(page_size=16, prefill_bucket=64, max_seq_len=256)),
}
_ORACLES = {}


def _oracle(variant, sample):
    family, _, alone_kw = VARIANTS[variant]
    key = (family, tuple(sorted(alone_kw.items())), sample)
    if key not in _ORACLES:
        _ORACLES[key] = _alone(_model(family), _gen(sample), _prompts(), NEW,
                               **{**GEOMETRY, **alone_kw})
    return _ORACLES[key]


# the families beyond Llama's are served greedy in their cells
CASES = [(v, s) for v in VARIANTS for s in (False, True)
         if not s or VARIANTS[v][0] == "llama"]


@pytest.mark.parametrize("depth", [2, 4])
@pytest.mark.parametrize(
    "variant,sample", CASES,
    ids=[f"{v}-{'sampled' if s else 'greedy'}" for v, s in CASES])
def test_every_request_gets_the_tokens_it_gets_alone(monkeypatch, variant,
                                                     sample, depth):
    """Seven requests through three slots, handed in while others run, with
    every step gathered as late as the bound allows: each gets the tokens
    an engine that holds it alone gives it (for the speculative lanes, the
    prefix cache and the tensor-parallel engine that engine is the plain
    one), greedy and sampled."""
    family, kw, _ = VARIANTS[variant]
    want = _oracle(variant, sample)
    _late(monkeypatch, depth)
    eng = ContinuousBatchingEngine(_model(family), gen=_gen(sample),
                                   **{**GEOMETRY, **kw})
    rids = []
    for i, (p, n) in enumerate(zip(_prompts(), NEW)):
        rids.append(eng.submit(p, max_new_tokens=n).req_id)
        if i % 2:
            eng.step()
    done = eng.run()
    assert [done[r] for r in rids] == want
    assert [len(done[r]) for r in rids] == list(NEW)
    assert not eng._pending


def test_the_plain_engine_gives_generates_tokens():
    model = _llama()
    prompts = _prompts()[:3]
    g = LlamaGenerator(model, max_batch=3, max_seq_len=128, page_size=8,
                       prefill_bucket=8)
    want = g.generate(prompts, GenerationConfig(max_new_tokens=9))
    eng = ContinuousBatchingEngine(model, gen=GenerationConfig(
        max_new_tokens=9), **GEOMETRY)
    rids = [eng.add_request(p) for p in prompts]
    done = eng.run()
    assert [done[r] for r in rids] == [list(w) for w in want]


# ---------------------------------------------------------------------------
# (b) a slot handed on while its predecessor's steps are still in flight
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("eos", [False, True], ids=["budget", "eos"])
def test_a_new_request_gets_none_of_its_predecessors_tokens(monkeypatch, eos):
    """One slot, steps gathered three steps late: when B is admitted, steps
    dispatched for A past its last token are still in flight over B's slot.
    Their rows are A's frozen repeats with A's commit marks: B gets none of
    them and exactly its own ``max_new_tokens``; so does C behind B."""
    model = _llama()
    pa, pb, pc = _prompts()[1], _prompts()[2], _prompts()[5]
    gen = _gen()
    if eos:
        # A ends on a token of its own greedy answer, mid flight
        alone = _alone(model, gen, [pa], [8], **{**GEOMETRY, "max_batch": 1})
        gen = _gen(eos=alone[0][2])
    want = _alone(model, gen, [pa, pb, pc], [8, 5, 6],
                  **{**GEOMETRY, "max_batch": 1})
    if eos:
        assert len(want[0]) == 3
    _late(monkeypatch, 4)
    eng = ContinuousBatchingEngine(model, gen=gen,
                                   **{**GEOMETRY, "max_batch": 1})
    a = eng.submit(pa, max_new_tokens=8)
    b = eng.submit(pb, max_new_tokens=5)
    c = eng.submit(pc, max_new_tokens=6)
    behind = {}            # request -> its predecessor's steps in flight
    admit = eng._admit

    def spy():
        before = eng.slot_req[0]
        n = admit()
        now = eng.slot_req[0]
        if n and now is not before:
            behind[now.req_id] = sum(
                e.reqs[0] is not None and e.reqs[0] is not now
                for e in eng._pending)
        return n

    monkeypatch.setattr(eng, "_admit", spy)
    done = eng.run()
    assert [done[r.req_id] for r in (a, b, c)] == want
    assert len(b.output) == 5 and len(c.output) == 6
    # the hazard was there: each successor was admitted under steps that
    # still held its predecessor's row
    assert behind[b.req_id] >= 2 and behind[c.req_id] >= 2, behind


# ---------------------------------------------------------------------------
# (c) a gather never waits on a newer step than it delivers
# ---------------------------------------------------------------------------

def test_only_the_landed_prefix_is_delivered_and_nothing_waits(monkeypatch):
    monkeypatch.setattr(generation, "MAX_STEPS_IN_FLIGHT", 8)
    landed = set()
    monkeypatch.setattr(generation._InFlight, "landed",
                        lambda self: id(self) in landed)
    pulled = []
    to_host = generation._InFlight.to_host
    monkeypatch.setattr(generation._InFlight, "to_host",
                        lambda self: pulled.append(id(self)) or to_host(self))
    eng = ContinuousBatchingEngine(_llama(), gen=_gen(), metrics=True,
                                   **GEOMETRY)
    req = eng.submit(_prompts()[1], max_new_tokens=12)
    blocked = {why: metrics.counter("serving.gather_blocked", reason=why)
               for why in generation.GATHER_BLOCKS}
    before = {why: c.value for why, c in blocked.items()}
    for _ in range(3):
        eng.step()
    first, second, third = eng._pending
    # the NEWER steps are ready, the oldest is not: nothing is delivered,
    # nothing is pulled to the host, nothing waits
    landed.update((id(second), id(third)))
    with obs.assert_overhead(max_compiles=0, max_syncs=0):
        assert eng._gather() == []
    assert pulled == [] and len(eng._pending) == 3 and req.output == []
    # the oldest lands: the whole landed prefix goes, in order, and the
    # step dispatched after it stays out
    landed.add(id(first))
    with obs.assert_overhead(max_compiles=0, max_syncs=0):
        eng.step()
    assert pulled == [id(first), id(second), id(third)]
    assert len(eng._pending) == 1 and len(req.output) == 3
    assert {why: c.value for why, c in blocked.items()} == before


def test_at_the_bound_the_oldest_step_alone_is_waited_for(monkeypatch):
    _late(monkeypatch, 2)
    eng = ContinuousBatchingEngine(_llama(), gen=_gen(), metrics=True,
                                   **GEOMETRY)
    req = eng.submit(_prompts()[1], max_new_tokens=12)
    bound = metrics.counter("serving.gather_blocked", reason="bound")
    eng.step()
    eng.step()
    assert len(eng._pending) == 2
    b0 = bound.value
    with obs.assert_overhead(max_compiles=0, max_syncs=1):
        eng.step()
    # one step gathered (the prompt's only chunk: its first token), the
    # newer one and the one just dispatched still out
    assert bound.value == b0 + 1
    assert len(eng._pending) == 2 and len(req.output) == 1
    eng.run()
    assert len(req.output) == 12


# ---------------------------------------------------------------------------
# (d) latency is stamped a token
# ---------------------------------------------------------------------------

class _Recorder:
    def __init__(self):
        self.seen = []

    def observe(self, v):
        self.seen.append(v)


def test_itl_holds_one_true_gap_a_token(monkeypatch):
    """Steps gathered one at a time: a request of n tokens observes one
    TTFT and n - 1 gaps that add up to the time between its first and last
    token; nothing is observed for the frozen repeats behind its last."""
    _late(monkeypatch, 3)
    eng = ContinuousBatchingEngine(_llama(), gen=_gen(), metrics=True,
                                   **GEOMETRY)
    itl, ttft = _Recorder(), _Recorder()
    eng._obs.itl, eng._obs.ttft = itl, ttft
    req = eng.submit(_prompts()[1], max_new_tokens=9)
    eng.run()
    assert len(req.output) == 9
    assert len(ttft.seen) == 1 and len(itl.seen) == 8
    assert all(g > 0 for g in itl.seen)
    assert sum(itl.seen) == pytest.approx(
        (req.t_last - req.t_first) * 1e3, rel=1e-6)


def test_tokens_that_land_together_are_not_given_their_average(monkeypatch):
    """Three steps held back and released at once reach the host in one
    gather: the client gets their tokens together, so the gaps are one
    real one and two of nothing, not three thirds."""
    monkeypatch.setattr(generation, "MAX_STEPS_IN_FLIGHT", 8)
    hold = [False]
    monkeypatch.setattr(generation._InFlight, "landed",
                        lambda self: not hold[0])
    eng = ContinuousBatchingEngine(_llama(), gen=_gen(), metrics=True,
                                   **GEOMETRY)
    itl = _Recorder()
    eng._obs.itl = itl
    req = eng.submit(_prompts()[1], max_new_tokens=12)
    eng.step()
    eng.step()                             # the first token: no gap yet
    assert len(req.output) == 1 and itl.seen == []
    hold[0] = True
    for _ in range(2):
        eng.step()
    assert len(req.output) == 1 and len(eng._pending) == 3
    hold[0] = False
    eng.step()
    assert len(req.output) == 4 and len(itl.seen) == 3
    assert itl.seen[0] > 0 and itl.seen[1:] == [0.0, 0.0]


# ---------------------------------------------------------------------------
# (e) the warm-step contract
# ---------------------------------------------------------------------------

def test_warm_steps_under_the_bound_compile_nothing_and_sync_nothing(
        monkeypatch):
    """Through both members of the T=8 family and the T=1 program, with
    requests retired and slots handed on along the way: no compile, and no
    marked sync while the queue stays under its bound (landed steps are
    gathered on the way: that waits for nothing)."""
    monkeypatch.setattr(generation, "MIN_GEMM_ROWS", 8)
    monkeypatch.setattr(generation, "MAX_STEPS_IN_FLIGHT", 64)
    eng = ContinuousBatchingEngine(
        _llama(), gen=GenerationConfig(max_new_tokens=4), metrics=True,
        **{**GEOMETRY, "max_batch": 8})
    for p in ([1, 2, 3], [4, 5]):
        eng.add_request(p)
    eng.run()
    assert sorted(eng._step_family(8)) == eng.g.row_buckets(8)
    seen = []
    inner = eng.g.gemm_rows
    monkeypatch.setattr(eng.g, "gemm_rows",
                        lambda t, n: seen.append(inner(t, n)) or seen[-1])
    drains = metrics.counter("serving.drains")
    d0 = drains.value
    arrivals = [[5], [8], [16, 16, 16], [8, 8, 8], [], [], [], [3], [], []]
    with obs.assert_overhead(max_compiles=0, max_syncs=0):
        for lens in arrivals:
            for n in lens:
                eng.add_request(list(range(1, n + 1)))
            eng.step()
    assert set(seen) >= set(eng.g.row_buckets(8)) | {8}
    assert drains.value > d0               # and gathered on the way
    assert all(len(v) == 4 for v in eng.run().values())


# ---------------------------------------------------------------------------
# (f) the front end
# ---------------------------------------------------------------------------

def test_the_server_streams_a_chunk_a_step_and_settles_when_asked(
        monkeypatch):
    """A stream of n tokens arrives a step at a time (here exactly: each
    step is gathered alone, when the bound asks for it, so no machine's
    load can land two together), an export in mid stream finds a settled
    engine, and the idle flush leaves nothing in flight."""
    _late(monkeypatch, 2)
    eng = ContinuousBatchingEngine(_llama(), gen=_gen(), metrics=True,
                                   **GEOMETRY)
    server = ServingServer(eng, slo=False, flight_recorder=False).start()
    n = 24
    try:
        async def main():
            r, w = mem_conn(http_bytes(
                "POST", "/v1/completions",
                completion_body(_prompts()[1], n, stream=True)))
            task = asyncio.create_task(server.handle(r, w))
            deadline = time.perf_counter() + 60
            while w.buf.count(b"data: ") < 4:
                assert time.perf_counter() < deadline, "no chunks"
                await asyncio.sleep(0.002)
            # mid stream: an export sees a settled engine
            snaps = await asyncio.get_running_loop().run_in_executor(
                None, server.export_sessions)
            await task
            return w.buf, snaps

        raw, snaps = asyncio.run(main())
        status, _, body = split_response(raw)
        assert status == 200
        chunks = [c["choices"][0]["token_ids"] for c in sse_chunks(body)]
        chunks = [c for c in chunks if c]
        assert sum(map(len, chunks)) == n
        assert len(chunks) > n / 2, [len(c) for c in chunks]
        # the export's settle is the one place two steps land together
        assert sorted(map(len, chunks))[-2:] in ([1, 1], [1, 2])
        # the export, mid stream, settled the engine and found the session
        assert len(snaps) == 1
        # the idle flush: nothing is left in flight once the stream is done
        deadline = time.perf_counter() + 30
        while server.run_on_engine(lambda e: len(e._pending)):
            assert time.perf_counter() < deadline
            time.sleep(0.01)
    finally:
        server.close()


def test_export_settles_the_engine(monkeypatch):
    _late(monkeypatch, 4)
    eng = ContinuousBatchingEngine(_llama(), gen=_gen(), metrics=True,
                                   prefix_cache=True, **GEOMETRY)
    req = eng.submit(_prompts()[0], max_new_tokens=12)
    settle = metrics.counter("serving.gather_blocked", reason="settle")
    for _ in range(6):
        eng.step()
    assert len(eng._pending) == 4
    s0 = settle.value
    snaps = migration.export_all(eng)
    assert not eng._pending and settle.value == s0 + 1
    # three chunks of the prompt, then three decode steps: four tokens
    assert len(req.output) == 4
    assert len(snaps) == 1 and len(snaps[0]["tokens"]) == 21 + 4


# ---------------------------------------------------------------------------
# (g) the counters
# ---------------------------------------------------------------------------

def test_drains_rise_with_every_delivery_and_blocks_only_when_made_to_wait(
        monkeypatch):
    monkeypatch.setattr(generation, "MAX_STEPS_IN_FLIGHT", 8)
    ready = [True]           # or "oldest": the oldest step alone has landed
    eng = ContinuousBatchingEngine(_llama(), gen=_gen(), metrics=True,
                                   **GEOMETRY)
    monkeypatch.setattr(
        generation._InFlight, "landed",
        lambda self: self is eng._pending[0] if ready[0] == "oldest"
        else ready[0])
    drains = metrics.counter("serving.drains")
    blocked = {why: metrics.counter("serving.gather_blocked", reason=why)
               for why in generation.GATHER_BLOCKS}
    in_flight = metrics.histogram("serving.steps_in_flight")

    def reading():
        return (drains.value,
                tuple(blocked[w].value for w in generation.GATHER_BLOCKS))

    eng.submit(_prompts()[1], max_new_tokens=12)
    d0, b0 = reading()
    n0 = in_flight.count
    eng.step()                              # nothing in flight to gather
    assert reading() == (d0, b0)
    for i in range(1, 5):                   # each delivers the one before
        eng.step()
        assert reading() == (d0 + i, b0)
    assert in_flight.count == n0 + 5
    ready[0] = False
    eng.step()
    eng.step()                              # nothing landed: no delivery
    assert reading() == (d0 + 4, b0) and len(eng._pending) == 3
    eng._drain()                            # a caller wants it settled
    assert reading() == (d0 + 5, (b0[0], b0[1], b0[2] + 1))
    assert not eng._pending
    assert eng._drain() == [] and reading()[0] == d0 + 5    # nothing to do
    ready[0] = False
    for _ in range(3):
        eng.step()
    ready[0] = "oldest"
    while eng.has_work():                   # a step a step, two behind
        eng.step()
    # the step that gathered the request's last token had nothing left to
    # dispatch: it waited for the steps still out (frozen repeats) and
    # left nothing in flight
    assert not eng._pending
    assert reading()[1] == (b0[0], b0[1] + 1, b0[2] + 1)
    text = obs.prometheus_text()
    assert 'paddle_tpu_serving_gather_blocked{reason="idle"}' in text
    assert "serving_steps_in_flight_bucket" in text
