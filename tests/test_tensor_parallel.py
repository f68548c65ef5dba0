"""Tensor-parallel fused engine step (ISSUE 18).

tp>1 shards the WHOLE serving step over the 'mp' mesh axis — attention
by kv head, grouped MoE by expert, cache pools shard-local — while
norms/embedding/sampling stay replicated, so every token is
BIT-IDENTICAL to the tp=1 single-device oracle.  Asserted here at every
layer: greedy and sampled parity matrices, prefix-cache hits, both
speculative modes, int8 pages, a mid-stream migration onto a survivor
with a DIFFERENT tp degree, and the serving overhead contract (warm tp
steps: zero compiles, zero marked syncs).  All on the 8-device virtual
CPU mesh (conftest).
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.inference import ContinuousBatchingEngine, GenerationConfig
from paddle_tpu.inference import migration as mig
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

PROMPTS = ([1, 2, 3, 4, 5, 6, 7], [9, 8, 7], [4, 4, 2, 2, 6, 6])


@pytest.fixture(scope="module")
def model():
    """tiny(): qh=4, kvh=2 — shardable at tp=2."""
    paddle.seed(7)
    return LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=2,
                                             max_position_embeddings=128))


@pytest.fixture(scope="module")
def model4():
    """Wider head config divisible by 4 — the tp∈{1,2,4} matrix model."""
    paddle.seed(7)
    return LlamaForCausalLM(LlamaConfig.tiny(
        num_attention_heads=8, num_key_value_heads=4,
        num_hidden_layers=2, max_position_embeddings=128))


def _engine(model, tp=1, **kw):
    kw.setdefault("max_batch", 3)
    kw.setdefault("gen", GenerationConfig(max_new_tokens=12))
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_bucket", 8)
    return ContinuousBatchingEngine(model, tensor_parallel=tp, **kw)


def _run(model, tp=1, prompts=PROMPTS, **kw):
    eng = _engine(model, tp=tp, **kw)
    rids = [eng.add_request(list(p)) for p in prompts]
    out = eng.run()
    return [out[r] for r in rids], eng


# ---------------------------------------------------------------------------
# greedy + sampled parity vs the tp=1 oracle
# ---------------------------------------------------------------------------

def test_tp2_greedy_bit_matches_tp1(model):
    base, _ = _run(model, tp=1)
    got, eng = _run(model, tp=2)
    assert got == base
    st = eng.stats()
    assert st["tp"] == 2 and st["pool_bytes"] > 0
    assert eng.g.mesh is not None and eng.g.mesh.shape["mp"] == 2


def test_tp4_greedy_bit_matches_tp1(model4):
    base, _ = _run(model4, tp=1)
    got, eng = _run(model4, tp=4)
    assert got == base and eng.g.tp == 4


@pytest.mark.parametrize("tp", [2, 4])
def test_tp_pool_holds_the_bytes_of_tp1_a_share_on_each_shard(model4, tp):
    """Page ids and block tables are host-global: at the same ``num_pages``
    a tensor-parallel engine's pool is the single-device engine's bytes,
    split by KV head, ``1/tp`` of them on each device of the mesh."""
    one = _engine(model4, tp=1, num_pages=24)
    eng = _engine(model4, tp=tp, num_pages=24)
    assert eng.stats()["pool_bytes"] == one.stats()["pool_bytes"] \
        == one.g.pool_bytes > 0
    for whole, pool in zip(one.g.cache.arrays, eng.g.cache.arrays):
        assert pool.shape == whole.shape and pool.dtype == whole.dtype
        shards = pool.addressable_shards
        assert len(shards) == tp
        assert all(s.data.nbytes * tp == whole.nbytes for s in shards)


def test_sampled_seed_determinism_parity_matrix(model4):
    """Same seed → byte-identical sampled streams at every tp degree;
    a different seed still diverges (sampling is real, not degenerate)."""
    outs = {}
    for seed in (0, 42):
        gc = GenerationConfig(max_new_tokens=10, do_sample=True,
                              temperature=0.8, top_k=16, top_p=0.9,
                              seed=seed)
        for tp in (1, 2, 4):
            outs[(seed, tp)], _ = _run(model4, tp=tp, gen=gc)
        assert outs[(seed, 2)] == outs[(seed, 1)], seed
        assert outs[(seed, 4)] == outs[(seed, 1)], seed
    assert outs[(0, 1)] != outs[(42, 1)]


def test_tp_requires_divisible_heads_and_devices(model):
    with pytest.raises(ValueError, match="num_kv_heads"):
        _engine(model, tp=3)          # kvh=2 % 3 != 0 (3 devices exist)
    with pytest.raises(ValueError, match="devices"):
        _engine(model, tp=16)         # virtual mesh has 8


# ---------------------------------------------------------------------------
# prefix cache, speculative decode, int8 pages — every serving program
# ---------------------------------------------------------------------------

def test_tp_prefix_cache_hits_bit_match(model):
    shared = [3, 1, 4, 1, 5, 9, 2, 6]
    prompts = [shared + [t] for t in (11, 12, 13)]
    base, _ = _run(model, tp=1, prompts=prompts, prefix_cache=True)
    got, eng = _run(model, tp=2, prompts=prompts, prefix_cache=True)
    assert got == base
    # the shared prefix was HIT on the sharded pool, not recomputed
    assert eng.g.cache.allocator.prefix_tokens_saved >= len(shared)


@pytest.mark.parametrize("mode", ["ngram", "fused"])
def test_tp_spec_decode_bit_match(model, mode):
    prompts = ([1, 4, 1, 4, 1, 4, 1, 4, 1], [5, 6, 7, 5, 6, 7, 5, 6])
    gc = GenerationConfig(max_new_tokens=16)
    base, _ = _run(model, tp=1, prompts=prompts, gen=gc,
                   spec_decode=mode, spec_k=4)
    got, eng = _run(model, tp=2, prompts=prompts, gen=gc,
                    spec_decode=mode, spec_k=4)
    assert got == base
    assert eng.stats()["spec_decode_enabled"]


def test_tp_int8_pages_bit_match(model):
    base, _ = _run(model, tp=1, cache_dtype="int8")
    got, eng = _run(model, tp=2, cache_dtype="int8")
    assert got == base
    # per-(kv-head, page) scales shard with their heads: the pool and
    # its two scale planes
    assert len(eng.g.cache.arrays) == 3 and len(eng.g.cache.pspecs) == 3


def test_tp_moe_grouped_expert_sharding_bit_match():
    """Experts shard over 'mp' through the grouped kernels (discard-
    group dispatch + ordered gather combine) — still bit-identical."""
    paddle.seed(7)
    m = LlamaForCausalLM(LlamaConfig.mixtral_tiny(
        num_hidden_layers=2, max_position_embeddings=128))
    base, _ = _run(m, tp=1)
    got, eng = _run(m, tp=2)
    assert got == base
    assert eng.g._moe_shards == 2     # the sharded path actually ran


# ---------------------------------------------------------------------------
# overhead contract: warm tp steps compile nothing, sync nothing
# ---------------------------------------------------------------------------

def test_tp_warm_steps_zero_compiles_zero_syncs(model, monkeypatch):
    from paddle_tpu.inference import generation
    monkeypatch.setattr(generation, "MAX_STEPS_IN_FLIGHT", 64)
    eng = _engine(model, tp=2, gen=GenerationConfig(max_new_tokens=16))
    for p in PROMPTS:
        eng.add_request(list(p))
    eng.run()                          # warm the sharded bucket programs
    with obs.assert_overhead(max_compiles=0, max_syncs=0):
        for p in PROMPTS:
            eng.add_request(list(p))
        for _ in range(12):            # under the bound: nothing waits,
            eng.step()                 # landed steps are gathered all the same
    out = eng.run()
    assert all(len(v) == 16 for v in out.values())


# ---------------------------------------------------------------------------
# migration across tp degrees: one wire format, any shard count
# ---------------------------------------------------------------------------

PROMPT = list(range(1, 14))


@pytest.mark.parametrize("tp_from,tp_to", [(2, 1), (1, 2), (2, 2)])
def test_midstream_kill_resume_across_tp_degrees(model, tp_from, tp_to):
    """Kill a tp=X replica mid-stream, resume the session on a tp=Y
    survivor: snapshots carry host-GLOBAL planes under one digest, the
    importer re-shards on upload, and the joined stream bit-matches the
    no-fault oracle."""
    oracle_out, _ = _run(model, tp=1, prompts=[PROMPT],
                         gen=GenerationConfig(max_new_tokens=24),
                         prefix_cache=True)
    a = _engine(model, tp=tp_from, prefix_cache=True,
                gen=GenerationConfig(max_new_tokens=24))
    req = a.submit(list(PROMPT))
    for _ in range(64):
        a.step()
        if len(req.output) >= 10:
            break
    a._drain()
    assert not req.done and len(req.output) >= 10
    snap = mig.export_session(a, req_id=req.req_id)

    b = _engine(model, tp=tp_to, prefix_cache=True,
                gen=GenerationConfig(max_new_tokens=24))
    res = mig.import_session(b, snap, resume=True)
    assert res["imported"] == len(snap["pages"]) and res["skipped"] == 0
    out = b.run()[res["resume_req_id"]]
    assert snap["emitted"] + out == oracle_out[0]


def test_snapshot_digests_tp_invariant(model):
    """The integrity digest is computed over host-GLOBAL planes: a tp=2
    export of the same session bytes-matches a tp=1 export, so digests
    verify and dedup across mixed-tp fleets."""
    snaps = []
    for tp in (1, 2):
        eng = _engine(model, tp=tp, prefix_cache=True,
                      gen=GenerationConfig(max_new_tokens=24))
        req = eng.submit(list(PROMPT))
        for _ in range(10):            # the same steps dispatched by both:
            eng.step()                 # settled, the same tokens committed
        eng._drain()
        assert not req.done and len(req.output) == 9
        snaps.append(mig.export_session(eng, req_id=req.req_id))
    assert snaps[0]["pages"] and snaps[0]["digest"] == snaps[1]["digest"]
    assert mig.snapshot_digest(snaps[0]) == mig.snapshot_digest(snaps[1])


# ---------------------------------------------------------------------------
# satellites: weighted router placement + engine-kwargs threading
# ---------------------------------------------------------------------------

def test_router_capacity_weighted_rank():
    from paddle_tpu.router.placement import (ReplicaState, capacity_score,
                                             weighted_rank)

    def rep(name, role, load, tp=1, pool=0):
        s = ReplicaState(type("_C", (), {"id": name})())
        s.role, s.tp, s.pool_bytes = role, tp, pool
        s.queue_depth = load
        return s

    small = rep("small", "decode", 2)
    big = rep("big", "decode", 2, tp=4, pool=2 << 30)
    pf = rep("pf", "prefill", 0, tp=4, pool=4 << 30)
    assert capacity_score(small) == 0.0          # vanilla tp=1: no-op
    assert capacity_score(big) == pytest.approx(5.0)
    key = weighted_rank({"decode": 0, "prefill": 2}, capacity_weight=1.0)
    order = sorted([pf, small, big], key=key)
    # role tier dominates capacity; within the tier the big replica
    # wins despite equal load
    assert [s.id for s in order] == ["big", "small", "pf"]
    # weight 0 restores the pure (role, load) order: equal-load peers
    # rank identically regardless of advertised capacity
    key0 = weighted_rank({"decode": 0}, capacity_weight=0.0)
    assert key0(big) == key0(small)


def test_engine_kwargs_single_threading_path(model):
    """ISSUE 18 satellite: one named-kwargs dict from argparse to the
    engine — the serving launcher, the fleet spawner and the in-process
    handle all consume the SAME builder, so a new knob cannot silently
    drop on one path."""
    from paddle_tpu.fleet.supervisor import InprocReplicaHandle
    from paddle_tpu.serving.__main__ import build_parser, engine_kwargs

    args = build_parser().parse_args(
        ["--tensor-parallel", "2", "--cache-dtype", "int8",
         "--max-batch", "3", "--page-size", "8"])
    kw = engine_kwargs(args)
    assert kw["tensor_parallel"] == 2 and kw["cache_dtype"] == "int8"
    assert kw["max_batch"] == 3 and kw["page_size"] == 8
    # "auto" means engine-side default resolution, not a literal dtype
    args2 = build_parser().parse_args(["--cache-dtype", "auto"])
    assert engine_kwargs(args2)["cache_dtype"] is None

    built = {}

    def factory(**ekw):
        built.update(ekw)
        return _engine(model, tp=ekw.pop("tensor_parallel", 1),
                       **{k: v for k, v in ekw.items()
                          if k not in ("cache_dtype",)})

    h = InprocReplicaHandle("r0", factory,
                            engine_kwargs={"tensor_parallel": 2,
                                           "cache_dtype": None,
                                           "max_batch": 2})
    h.spawn()
    try:
        import time
        deadline = time.perf_counter() + 180.0
        while not h.ready():
            assert time.perf_counter() < deadline, "replica never ready"
            time.sleep(0.05)
        assert built["tensor_parallel"] == 2 and built["max_batch"] == 2
        assert h.server.engine.g.tp == 2
    finally:
        h.kill()
