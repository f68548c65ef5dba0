"""Speculative decoding subsystem tests (ISSUE 9): device-side n-gram
drafter units, engine bit-identity vs the spec-off oracle (ngram AND
fused modes, mixed spec/non-spec batches, EOS-inside-draft, sampling),
warm-step overhead contract (zero compiles, zero syncs), KV/block-table
tail rollback, and the spec telemetry surfaces.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import flags
from paddle_tpu.inference import (ContinuousBatchingEngine, GenerationConfig,
                                  LlamaGenerator, resolve_spec_config)
from paddle_tpu.inference import speculative as sp
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

import jax.numpy as jnp

SPEC_KEYS = ("spec_steps", "spec_drafted_tokens", "spec_accepted_tokens",
             "spec_rejected_tokens")


# ---------------------------------------------------------------------------
# drafter units (pure device functions)
# ---------------------------------------------------------------------------

def _lookup(hist_rows, hist_lens, recents, k, nmax):
    S = max(len(r) for r in hist_rows)
    hist = np.full((len(hist_rows), S), int(sp.HIST_PAD), np.int32)
    for i, r in enumerate(hist_rows):
        hist[i, :len(r)] = r
    rec = np.stack([sp.recent_window(r, nmax) for r in recents])
    d, dl = sp.lookup_drafts(jnp.asarray(hist),
                             jnp.asarray(np.asarray(hist_lens, np.int32)),
                             jnp.asarray(rec), k, nmax)
    return np.asarray(d), np.asarray(dl)


def test_lookup_longest_match_most_recent_occurrence():
    h = [1, 2, 3, 4, 1, 2, 3, 9]
    # context ...1,2,3 occurs ending at p=3 and p=7; the LAST one wins
    d, dl = _lookup([h], [8], [[5, 1, 2, 3]], k=4, nmax=3)
    assert dl[0] == 1 and d[0, 0] == 9
    # context 1,2 -> last occurrence at p=6, continuation 3, 9
    d, dl = _lookup([h], [8], [[1, 2]], k=4, nmax=3)
    assert dl[0] == 2 and list(d[0, :2]) == [3, 9]
    # same-length matches: recency wins — suffix 2,3 ends at p=3 AND p=7
    d, dl = _lookup([h], [8], [[2, 3]], k=4, nmax=3)
    assert dl[0] == 1 and d[0, 0] == 9
    # longest match beats recency: 1,2,3 (len 3) at p=3 vs 2,3 (len 2)
    # at p=7 in a history where the later occurrence breaks the trigram
    h2 = [1, 2, 3, 4, 5, 2, 3, 7]
    d, dl = _lookup([h2], [8], [[1, 2, 3]], k=4, nmax=3)
    assert dl[0] == 3 and list(d[0]) == [4, 5, 2]


def test_lookup_no_match_and_padding_never_matches():
    h = [1, 2, 3, 4]
    d, dl = _lookup([h, h], [4, 0], [[7, 8], []], k=4, nmax=3)
    assert dl[0] == 0                     # context absent from history
    assert dl[1] == 0                     # empty history, empty context


def test_lookup_draft_clamped_to_history_tail():
    h = [9, 5, 6, 9, 5]                   # context 9,5 -> p=2? last at p=...
    # occurrences of [9,5]: end p=2 (h[0:2]) and p=5 is past length; the
    # match ending at p=2 proposes h[2:5] = 6,9,5 but hist_len-p caps it
    d, dl = _lookup([h], [5], [[9, 5]], k=8, nmax=2)
    assert dl[0] == 3 and list(d[0, :3]) == [6, 9, 5]


def test_accept_length_and_eos_clamp():
    toks = jnp.asarray(np.array([[7, 10, 11, 12], [7, 10, 11, 12],
                                 [0, 0, 0, 0]], np.int32))
    samp = jnp.asarray(np.array([[10, 11, 99, 55], [10, 99, 11, 55],
                                 [1, 2, 3, 4]], np.int32))
    ql = jnp.asarray(np.array([4, 4, 0], np.int32))
    nc = np.asarray(sp.accept_length(toks, samp, ql))
    assert list(nc) == [3, 2, 0]          # 2 drafts+bonus / 1+bonus / inert
    nc2, hit = sp.eos_clamp(samp, jnp.asarray(nc), 11)
    assert list(np.asarray(nc2)) == [2, 2, 0]
    assert list(np.asarray(hit)) == [True, False, False]


def test_shift_append_window():
    rec = jnp.asarray(np.array([[-2, 1, 2]], np.int32))
    out = jnp.asarray(np.array([[5, 6, 7, 8]], np.int32))
    got = np.asarray(sp.shift_append(rec, out,
                                     jnp.asarray(np.array([2], np.int32))))
    assert list(got[0]) == [2, 5, 6]
    same = np.asarray(sp.shift_append(rec, out,
                                      jnp.asarray(np.array([0], np.int32))))
    assert list(same[0]) == [-2, 1, 2]    # n_commit 0: untouched


def test_spec_history_drain_aligned_updates():
    h = sp.SpecHistory(2, 8)
    h.reset_row(0, [1, 2, 3])
    a, l = h.device_arrays()
    assert list(np.asarray(a)[0, :3]) == [1, 2, 3]
    b, _ = h.device_arrays()
    assert b is a                         # clean: no re-upload
    h.extend_row(0, [4, 5])
    a2, l2 = h.device_arrays()
    assert list(np.asarray(a2)[0, :5]) == [1, 2, 3, 4, 5]
    assert int(np.asarray(l2)[0]) == 5
    h.extend_row(0, list(range(10, 20)))  # overflow: clamped to capacity
    _, l3 = h.device_arrays()
    assert int(np.asarray(l3)[0]) == 8


def test_resolve_spec_config():
    assert resolve_spec_config("") is None
    assert resolve_spec_config(False) is None
    assert resolve_spec_config(True).mode == "ngram"
    c = resolve_spec_config("fused", k=8)
    assert c.mode == "fused" and c.k == 8
    with pytest.raises(ValueError, match="spec_decode"):
        resolve_spec_config("bogus")
    with pytest.raises(ValueError, match="spec_k"):
        resolve_spec_config("ngram", k=1)
    # flag-driven default path (the engine's spec_decode=None)
    flags.set_flags({"spec_decode": "ngram", "spec_k": 6})
    try:
        c = resolve_spec_config(None)
        assert c is not None and c.mode == "ngram" and c.k == 6
    finally:
        flags.set_flags({"spec_decode": "", "spec_k": 4})
    assert resolve_spec_config(None) is None


# ---------------------------------------------------------------------------
# engine bit-identity vs the spec-off oracle
# ---------------------------------------------------------------------------

def _tiny_model(layers=2, maxpos=256):
    paddle.seed(7)
    cfg = LlamaConfig.tiny(num_hidden_layers=layers,
                           max_position_embeddings=maxpos)
    return LlamaForCausalLM(cfg)


def _run(model, prompts, *, spec, k=4, max_new=16, eos=None, max_batch=3,
         num_pages=None, do_sample=False, seed=0,
         prefix_cache=False, staggered=0):
    gc = GenerationConfig(max_new_tokens=max_new, do_sample=do_sample,
                          eos_token_id=eos, seed=seed)
    eng = ContinuousBatchingEngine(
        model, max_batch=max_batch, gen=gc, max_seq_len=128, page_size=8,
        prefill_bucket=8, num_pages=num_pages,
        prefix_cache=prefix_cache, spec_decode=spec, spec_k=k)
    rids = [eng.add_request(p) for p in prompts[:len(prompts) - staggered]]
    if staggered:
        # mixed spec/non-spec batches: later prompts arrive while earlier
        # rows are already deep in (speculative) decode, forcing bucket
        # steps (prefill + decode col-0) BETWEEN spec steps
        for _ in range(6):
            eng.step()
        rids += [eng.add_request(p) for p in prompts[-staggered:]]
    out = eng.run()
    return [out[r] for r in rids], eng


PROMPTS = [[3, 14, 15, 9, 2, 6], [5, 3],
           [1, 2, 3, 4, 1, 2, 3, 4, 1, 2, 3]]


@pytest.mark.parametrize("mode,k", [("ngram", 4), ("ngram", 8),
                                    ("fused", 4), ("fused", 8)])
def test_engine_spec_bit_matches_oracle(mode, k):
    """Acceptance: greedy spec-on outputs bit-match the spec-off oracle
    at K in {4, 8} for both modes."""
    model = _tiny_model()
    base, e0 = _run(model, PROMPTS, spec="", max_new=24)
    st0 = e0.stats()
    assert not st0["spec_decode_enabled"]
    assert all(k_ not in st0 for k_ in SPEC_KEYS)
    got, e1 = _run(model, PROMPTS, spec=mode, k=k, max_new=24)
    assert got == base
    st = e1.stats()
    assert st["spec_decode_enabled"] and st["spec_mode"] == mode
    assert st["spec_steps"] > 0
    if mode == "ngram":
        assert st["spec_drafted_tokens"] == \
            st["spec_accepted_tokens"] + st["spec_rejected_tokens"]


def test_engine_spec_mixed_batches_bit_match():
    """Mixed spec/non-spec traffic: a request admitted mid-decode forces
    prefill bucket steps between speculative steps; outputs still
    bit-match an identically staggered spec-off engine."""
    model = _tiny_model()
    prompts = PROMPTS + [[9, 9, 4, 2]]
    base, _ = _run(model, prompts, spec="", max_new=20, max_batch=4,
                   staggered=1)
    for mode in ("ngram", "fused"):
        got, _ = _run(model, prompts, spec=mode, max_new=20, max_batch=4,
                      staggered=1)
        assert got == base, f"{mode} diverged on staggered admission"


def test_engine_spec_eos_inside_draft():
    """EOS emitted INSIDE a multi-token speculative window must cut the
    commit at the EOS (inclusive) exactly like sequential decoding."""
    model = _tiny_model()
    base, _ = _run(model, PROMPTS, spec="", max_new=24)
    # pick an EOS that appears mid-stream (index >= 2) so with K=4/8 it
    # falls strictly inside a multi-token dispatch window
    eos = base[0][3]
    base_eos, _ = _run(model, PROMPTS, spec="", max_new=24, eos=eos)
    for mode in ("ngram", "fused"):
        got, _ = _run(model, PROMPTS, spec=mode, max_new=24, eos=eos)
        assert got == base_eos, f"{mode} EOS-inside-draft diverged"


def test_engine_spec_with_prefix_cache_shared_pages_safe():
    """Spec decode + prefix cache: rejected draft KV writes must never
    corrupt pages shared with a sibling request (page-aligned prefix
    sharing + COW full-match).  Outputs bit-match the everything-off
    oracle for every request, including the COW re-hit."""
    model = _tiny_model()
    S = list(range(1, 25))                # 3 full pages of 8
    prompts = [S + [30, 31], S + [40], S[:16], S + [30, 31]]
    base, _ = _run(model, prompts, spec="", max_new=16, max_batch=2)
    got, eng = _run(model, prompts, spec="ngram", max_new=16, max_batch=2,
                    prefix_cache=True)
    assert got == base
    st = eng.stats()
    assert st["prefix_hits"] >= 1         # sharing actually happened
    assert st["spec_steps"] > 0           # and spec actually ran
    alloc = eng.g.cache.allocator
    assert alloc.free_pages + eng.prefix_cache.evictable_pages() \
        == alloc.num_pages


def test_engine_spec_sampling_runs_and_is_seed_deterministic():
    """Sampled configs are distribution-correct (accept-iff-equal), not
    bit-matching the sequential key stream — but the same seed must give
    the same outputs run to run, and budgets must be respected."""
    model = _tiny_model()
    a, _ = _run(model, PROMPTS, spec="ngram", max_new=12, do_sample=True,
                seed=11)
    b, _ = _run(model, PROMPTS, spec="ngram", max_new=12, do_sample=True,
                seed=11)
    assert a == b
    assert all(len(x) == 12 for x in a)


def test_engine_spec_undersized_pool_never_crashes():
    """Pool pressure under speculative overestimated growth: sequences
    finalize early instead of crashing and every page recycles."""
    model = _tiny_model()
    got, eng = _run(model, [[1, 2, 3, 4, 5], [7, 8, 9]], spec="ngram",
                    k=8, max_new=40, max_batch=2, num_pages=4)
    assert all(len(g) >= 1 for g in got)
    alloc = eng.g.cache.allocator
    assert alloc.free_pages == alloc.num_pages


def test_engine_spec_rollback_bounds_page_overshoot():
    """Every gather resyncs the host's length bound to the tokens it has
    gathered plus the most the steps still in flight may commit (K each),
    and truncates surplus tail pages: a low-acceptance workload at K=8
    must not let the host's safe-by-overestimate growth run away past
    that bound + one page, and no page a step in flight may write is
    given back (the tokens are the spec-off engine's)."""
    model = _tiny_model()
    gc = GenerationConfig(max_new_tokens=48, do_sample=False)
    eng = ContinuousBatchingEngine(
        model, max_batch=1, gen=gc, max_seq_len=128, page_size=8,
        prefill_bucket=8, spec_decode="ngram", spec_k=8)
    rid = eng.add_request([3, 14, 15, 9, 2, 6])
    eng.step()                            # prefill
    alloc = eng.g.cache.allocator
    checked = 0
    while eng.has_work():
        eng.step()
        req = eng.slot_req[0]
        if req is not None:
            ctx = alloc.context_len(req.req_id)
            true_len = len(req.prompt) + len(req.output)
            assert int(eng.host_lens[0]) <= min(
                true_len + 8 * len(eng._pending), 128)
            assert ctx <= true_len + 8 * len(eng._pending) + 8, \
                f"tail rollback failed: ctx {ctx} vs true {true_len}"
            checked += 1
    eng._drain()
    assert checked > 0
    assert len(eng.completed[rid]) == 48
    assert alloc.free_pages == alloc.num_pages
    plain = ContinuousBatchingEngine(
        model, max_batch=1, gen=gc, max_seq_len=128, page_size=8,
        prefill_bucket=8)
    want = plain.add_request([3, 14, 15, 9, 2, 6])
    assert plain.run()[want] == eng.completed[rid]


# ---------------------------------------------------------------------------
# overhead contract: warm spec steps compile nothing, sync nothing
# ---------------------------------------------------------------------------

def test_warm_spec_steps_zero_compiles_zero_syncs(monkeypatch):
    """ISSUE 9 satellite: telemetry-asserted via assert_overhead — warm
    speculative steps (both modes) trigger ZERO XLA compiles and ZERO
    marked host<->device syncs while the steps in flight stay under their
    bound (landed steps are gathered on the way, which waits for nothing:
    the drafter's table grows and is uploaded again between them)."""
    from paddle_tpu import observability as obs
    from paddle_tpu.inference import generation

    monkeypatch.setattr(generation, "MAX_STEPS_IN_FLIGHT", 64)
    model = _tiny_model()
    for mode in ("ngram", "fused"):
        gc = GenerationConfig(max_new_tokens=32, do_sample=False)
        eng = ContinuousBatchingEngine(
            model, max_batch=2, gen=gc, max_seq_len=128, page_size=8,
            prefill_bucket=8, spec_decode=mode, spec_k=4)
        # warmup: one full lifecycle compiles the bucket step + the spec
        # program (+ drafter upload paths)
        eng.add_request([1, 2, 3])
        eng.add_request([4, 5, 6, 7, 8, 9])
        eng.run()
        with obs.assert_overhead(max_compiles=0, max_syncs=0):
            eng.add_request([5, 6, 7])
            eng.add_request([1, 4, 1, 4, 1, 4, 1, 4, 1])
            # under the bound nothing waits; eight steps, so that neither
            # request can have its 32 tokens (two steps of prefill, then at
            # most 4 a step) and no step finds nothing to dispatch, which
            # is a reason to wait
            for _ in range(8):
                eng.step()
        out = eng.run()
        assert all(len(v) == 32 for v in out.values()), mode


# ---------------------------------------------------------------------------
# telemetry surfaces
# ---------------------------------------------------------------------------

def test_spec_metrics_registry_and_stats_agree():
    from paddle_tpu import observability as obs

    m = obs.metrics
    base = {k: int(m.counter("serving.spec." + k).value)
            for k in ("drafted_tokens", "accepted_tokens",
                      "rejected_tokens")}
    h0 = m.histogram("serving.spec.accept_len").summary()["count"] or 0
    model = _tiny_model()
    got, eng = _run(model, PROMPTS, spec="ngram", k=4, max_new=24)
    st = eng.stats()
    for short, key in (("drafted_tokens", "spec_drafted_tokens"),
                       ("accepted_tokens", "spec_accepted_tokens"),
                       ("rejected_tokens", "spec_rejected_tokens")):
        delta = int(m.counter("serving.spec." + short).value) - base[short]
        assert delta == st[key], (short, delta, st[key])
    h1 = m.histogram("serving.spec.accept_len").summary()["count"]
    assert h1 - h0 > 0                    # accept_len observed per dispatch
    # the drain surfaces the same numbers engine-side
    assert eng.last_stats["spec_steps"] == st["spec_steps"]


def test_generator_path_untouched_by_spec_flag():
    """LlamaGenerator.generate never consults the spec lane even when the
    process-wide flag is on (like the prefix cache, spec is an ENGINE
    feature); flag restored afterwards."""
    model = _tiny_model()
    flags.set_flags({"spec_decode": "ngram"})
    try:
        gen = LlamaGenerator(model, max_batch=2, max_seq_len=64,
                             page_size=8, prefill_bucket=8)
        outs = gen.generate([[1, 2, 3, 4, 5], [7, 8]],
                            GenerationConfig(max_new_tokens=4))
        assert all(len(o) == 4 for o in outs)
        # engine picks the flag up by default
        gc = GenerationConfig(max_new_tokens=4, do_sample=False)
        eng = ContinuousBatchingEngine(model, max_batch=2, gen=gc,
                                       max_seq_len=64, page_size=8,
                                       prefill_bucket=8)
        assert eng.spec is not None and eng.spec.mode == "ngram"
    finally:
        flags.set_flags({"spec_decode": ""})
