#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

Drives the two normal entry points once, at the published widths of
``LlamaConfig.llama2_7b()`` (hidden 4096, 32 heads x 128, intermediate
11008, vocab 32000, bf16; only DEPTH is cut, to what fits one 16 GB chip
with the state each phase holds — see PERF.md "Bring-up"), with random
weights made from ``--seed``:

  python chip_smoke.py              one chip: kernels, train, serve
  python chip_smoke.py --chips 4    four chips: ONLY the two sharded paths
                                    (dp2 x mp2 train step, tensor_parallel=4
                                    engine) and their one-device comparisons

Every phase prints one JSON line; the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failed check or raised phase, or a backend that is not a TPU, is a
non-zero exit with no ok line.  One process: it touches JAX itself and
starts no child.  Every figure it prints is a bring-up observation, not a
benchmark result.

``--rehearse`` is the builder's CPU rehearsal (on-chip-measurement guide,
section 2, step 1 and 2): the same code at a tiny size with the Pallas
kernels in interpret mode.  It never prints an ok line.
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import http.client
import json
import sys
import threading
import time

FLASH_TOL = 3e-2     # max|kernel - reference| / max(1, max|reference|)
PAGED_TOL = 3e-2
LOSS_TOL = 2e-2      # |loss_mesh - loss_one| / |loss_one|, every step


class Sizes:
    """What is cut for one chip (depth) and what the rehearsal shrinks."""

    def __init__(self, rehearse: bool, chips: int):
        from paddle_tpu.models.llama import LlamaConfig
        self.rehearse = rehearse
        if rehearse:
            heads = 4 if chips == 4 else 2      # head_dim stays 128
            self.model = lambda n: LlamaConfig(
                vocab_size=512, hidden_size=128 * heads,
                intermediate_size=256 * heads, num_hidden_layers=n,
                num_attention_heads=heads, max_position_embeddings=1024,
                dtype="float32")
            self.train_layers, self.serve_layers = 2, 2
            self.train_batch, self.seq, self.train_steps = 2, 256, 2
            self.prompt_lens, self.new_tokens = (20, 70, 130), 4
            self.stagger_s = 0.01
        else:
            self.model = lambda n: LlamaConfig.llama2_7b(num_hidden_layers=n)
            # from compiled.memory_analysis() for a described v5e (PERF.md):
            # train L=3 b=2 remat = 13.6 GiB (L=4 is 16.1); a serve layer
            # costs 0.94 GiB (model + stacked copy + pool + temp), L=12 =
            # 12.0 GiB of 15.75.  tensor_parallel=4 holds a third copy of
            # the weights on the first device while it replicates: L=8
            self.train_layers = 3
            self.serve_layers = 12 if chips == 1 else 8
            self.train_batch, self.seq, self.train_steps = 2, 2048, 4
            self.prompt_lens, self.new_tokens = (50, 150, 300, 450, 600), 32
            # a request every 0.15 s: the last joins while the first
            # still decodes (32 tokens took about 1.5 s on the chip)
            self.stagger_s = 0.15


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def mem_stat(key: str, dev=None):
    """A ``memory_stats()`` field of a device (None off the chip)."""
    import jax
    return ((dev or jax.devices()[0]).memory_stats() or {}).get(key)


def peak_bytes():
    return mem_stat("peak_bytes_in_use")


def rel_err(got, ref) -> tuple:
    import numpy as np
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    check(np.isfinite(got).all(), "kernel output is not finite")
    diff = float(np.max(np.abs(got - ref)))
    return diff, float(np.max(np.abs(ref)))


# ------------------------------------------------------------ kernels ---

def phase_kernels(sz: Sizes, seed: int, on_tpu: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu import flags
    from paddle_tpu import observability as obs
    from paddle_tpu.kernels import autotune
    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.kernels import paged_attention as pa
    from paddle_tpu.kernels.paged_pool_writes import pool_of_heads
    from paddle_tpu.serving.__main__ import build_parser

    cfg = sz.model(1)
    dt = jnp.dtype(cfg.dtype)
    geo = build_parser().parse_args([])        # the launcher's geometry
    B, page, max_len = geo.max_batch, geo.page_size, geo.max_seq_len
    qh, kvh, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    W = max_len // page
    n_pages = B * W
    rng = np.random.default_rng(seed)

    def rnd(*shape):
        return jnp.asarray(rng.standard_normal(shape), dt)

    kc, vc = rnd(kvh, n_pages, page, d), rnd(kvh, n_pages, page, d)
    bt = jnp.asarray(rng.permutation(n_pages).reshape(B, W), jnp.int32)
    paged = {}
    t0 = time.perf_counter()
    c0 = obs.backend_compiles()
    for T in (1, 8, geo.prefill_bucket):      # decode, spec-verify, chunk
        q, kn, vn = rnd(B, T, qh, d), rnd(B, T, kvh, d), rnd(B, T, kvh, d)
        ctx = jnp.asarray(rng.integers(0, max_len - T, B), jnp.int32)
        ql = jnp.asarray(rng.integers(1, T + 1, B), jnp.int32)

        def kern(q, kc, vc, bt, ctx, ql, kn, vn):
            return pa.ragged_paged_attention(q, pool_of_heads(kc, vc), bt,
                                             ctx, q_lens=ql, k_new=kn,
                                             v_new=vn)

        def ref(q, kc, vc, bt, ctx, ql, kn, vn):
            with jax.default_matmul_precision("highest"):
                return pa._reference_ragged_paged_attention(
                    q, kc, vc, bt, ctx, ql, kn, vn)[0]

        args = (q, kc, vc, bt, ctx, ql, kn, vn)
        low = jax.jit(kern).lower(*args)
        if on_tpu:
            check("tpu_custom_call" in low.as_text(),
                  f"paged T={T}: no Pallas kernel in the lowered program")
        got, want = low.compile()(*args), jax.jit(ref)(*args)
        valid = np.arange(T)[None, :] < np.asarray(ql)[:, None]   # [B, T]
        diff, mag = rel_err(np.asarray(got, np.float32)[valid],
                            np.asarray(want, np.float32)[valid])
        check(diff <= PAGED_TOL * max(1.0, mag),
              f"paged T={T}: max|diff| {diff} vs max|ref| {mag}")
        paged[f"T={T}"] = {"max_abs_diff": diff, "max_abs_ref": mag}
    t_paged = time.perf_counter() - t0

    # the delta-rule call of a linear-attention place (kernels/kda.py) at
    # Solar-Open2's mixer widths: decode slots, a chunk, an idle slot and a
    # fresh one, packed; against its float32 oracle
    from paddle_tpu.kernels import kda
    kh, kd, kchunk = (2, 16, 8) if sz.rehearse else (64, 128, 64)
    kql = np.asarray([1, 0, kchunk, 1, 3, 1, 1, 1], np.int32)
    krows = 64 if sz.rehearse else 256
    f32 = jnp.float32

    def unit(*shape):
        x = jnp.asarray(rng.standard_normal(shape), f32)
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    kargs = (jnp.asarray(rng.standard_normal((len(kql), kh, kd, kd)), f32),
             (unit(krows, kh, kd) * kd ** -0.5).astype(dt),
             unit(krows, kh, kd), jnp.asarray(
                 rng.standard_normal((krows, kh, kd)), f32),
             -jnp.exp(jnp.asarray(
                 rng.standard_normal((krows, kh, kd)) * 2 - 2, f32)),
             2 * jax.nn.sigmoid(jnp.asarray(
                 rng.standard_normal((krows, kh)), f32)),
             jnp.asarray(np.cumsum(kql) - kql, jnp.int32), jnp.asarray(kql),
             jnp.asarray(np.arange(len(kql)) == 4))
    low = jax.jit(lambda *a: kda.ragged_kda_update(*a, chunk=kchunk)) \
        .lower(*kargs)
    if on_tpu:
        check("tpu_custom_call" in low.as_text(),
              "kda: no Pallas kernel in the lowered program")
    got_o, got_s = low.compile()(*kargs)
    want_o, want_s = jax.jit(lambda *a: kda._reference_ragged_kda_update(
        *a, kchunk))(*kargs)
    kda_out = {}
    for name, a, b in (("o", got_o, want_o), ("state", got_s, want_s)):
        diff, mag = rel_err(a, b)
        check(diff <= PAGED_TOL * max(1.0, mag),
              f"kda {name}: max|diff| {diff} vs max|ref| {mag}")
        kda_out[name] = {"max_abs_diff": diff, "max_abs_ref": mag}
    check(bool(jnp.array_equal(got_s[1], kargs[0][1])),
          "kda: an idle slot's state moved")

    # flash fwd + bwd at the train step's shapes
    S, Bt = sz.seq, sz.train_batch
    q, k, v = rnd(Bt, S, qh, d), rnd(Bt, S, kvh, d), rnd(Bt, S, kvh, d)
    g = rnd(Bt, S, qh, d)

    def fwd_bwd(attn):
        def f(q, k, v, g):
            out, vjp = jax.vjp(lambda a, b, c: attn(a, b, c), q, k, v)
            return (out,) + vjp(g)
        return f

    def ref_attn(a, b, c):
        with jax.default_matmul_precision("highest"):
            return fa._reference_attention(a, b, c, True)

    t0 = time.perf_counter()
    low = jax.jit(fwd_bwd(
        lambda a, b, c: fa._flash_attention_arrays(a, b, c, True))
    ).lower(q, k, v, g)
    if on_tpu:
        check(low.as_text().count("tpu_custom_call") >= 3,
              "flash: fwd + dq + dkv Pallas kernels not all in the program")
    got = low.compile()(q, k, v, g)
    want = jax.jit(fwd_bwd(ref_attn))(q, k, v, g)
    flash = {}
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        diff, mag = rel_err(a, b)
        check(diff <= FLASH_TOL * max(1.0, mag),
              f"flash {name}: max|diff| {diff} vs max|ref| {mag}")
        flash[name] = {"max_abs_diff": diff, "max_abs_ref": mag}
    t_flash = time.perf_counter() - t0
    emit(phase="kernels", dtype=str(dt),
         paged_shapes={"q": [B, "T", qh, d],
                       "cache": [kvh, n_pages, page, d], "block_table": [B, W]},
         paged=paged, paged_tol=PAGED_TOL, paged_seconds=round(t_paged, 2),
         paged_tiles={"page_size": page},
         kda_shapes={"tokens": [krows, kh, kd], "slots": len(kql),
                     "chunk": kchunk}, kda=kda_out,
         flash_shapes={"q": [Bt, S, qh, d], "kv": [Bt, S, kvh, d],
                       "causal": True},
         flash=flash, flash_tol=FLASH_TOL,
         flash_seconds_incl_autotune=round(t_flash, 2),
         flash_tiles=autotune.entries("flash_fwd") or "default (512, 512)",
         flash_tune_ms=autotune.measured("flash_fwd"),
         compiles=obs.backend_compiles() - c0,
         compared_with="XLA references at matmul precision 'highest'",
         peak_bytes_in_use=peak_bytes())


# -------------------------------------------------------------- train ---

def run_train(sz: Sizes, seed: int, pc_kw: dict, on_tpu: bool) -> dict:
    """``PretrainStep`` on a fixed seeded batch: one warm-up (the compile)
    plus ``train_steps`` steps, each blocked on."""
    import jax
    import numpy as np

    from paddle_tpu import observability as obs
    from paddle_tpu.models.pretrain import ParallelConfig, PretrainStep

    cfg = sz.model(sz.train_layers)
    ps = PretrainStep(cfg, ParallelConfig(remat=True, **pc_kw))
    t0 = time.perf_counter()
    state = ps.init_state(seed=seed)
    jax.block_until_ready(state)
    t_init = time.perf_counter() - t0
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, cfg.vocab_size,
                       (sz.train_batch, sz.seq + 1)).astype(np.int32)
    ids, labels = ps.shard_batch(ids[:, :-1], ids[:, 1:])

    t0 = time.perf_counter()
    state, loss = ps.train_step(state, ids, labels)
    losses = [float(jax.block_until_ready(loss))]
    t_first = time.perf_counter() - t0
    step_s = []
    with obs.assert_overhead(record=True) as ov:
        for _ in range(sz.train_steps):
            t0 = time.perf_counter()
            state, loss = ps.train_step(state, ids, labels)
            losses.append(float(jax.block_until_ready(loss)))
            step_s.append(time.perf_counter() - t0)
    check(all(np.isfinite(losses)), f"train: loss not finite: {losses}")
    check(losses[-1] < losses[0],
          f"train: loss did not go down: {losses}")
    check(ov.compiles == 0,
          f"train: {ov.compiles} compile(s) after the first step")
    kernels = None
    if on_tpu:
        abstract = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding),
            (state, ids, labels))
        kernels = ps.lowered_step(*abstract).as_text().count(
            "tpu_custom_call")
        check(kernels >= 3, "train: the flash kernels (fwd, dq, dkv) are "
              f"not in the step program ({kernels} tpu_custom_call)")
    return dict(
        depth=cfg.num_hidden_layers, params=cfg.num_params(),
        batch=[sz.train_batch, sz.seq], parallel={"remat": True, **pc_kw},
        init_seconds=round(t_init, 2),
        first_step_seconds_incl_compile=round(t_first, 2),
        step_seconds=[round(s, 4) for s in step_s], losses=losses,
        compiles_after_first=ov.compiles, tpu_custom_calls=kernels,
        state=state)


def phase_train(sz: Sizes, seed: int, on_tpu: bool) -> None:
    out = run_train(sz, seed, {}, on_tpu)
    out.pop("state")
    emit(phase="train", **out, peak_bytes_in_use=peak_bytes())


# -------------------------------------------------------------- serve ---

def prompts_for(sz: Sizes, vocab: int, seed: int) -> list:
    import numpy as np
    rng = np.random.default_rng(seed + 1)
    return [rng.integers(1, vocab, n).tolist() for n in sz.prompt_lens]


def build_engine(sz: Sizes, seed: int, **extra):
    """Seeded model + engine with the launcher's defaults
    (``python -m paddle_tpu.serving``), depth cut."""
    import paddle_tpu as paddle
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.models.llama import LlamaForCausalLM
    from paddle_tpu.serving.__main__ import build_parser, engine_kwargs

    paddle.seed(seed)
    model = LlamaForCausalLM(sz.model(sz.serve_layers))
    kw = engine_kwargs(build_parser().parse_args([]))
    kw.update(extra)
    return ContinuousBatchingEngine(model, **kw), kw


def run_direct(eng, prompts, new_tokens) -> list:
    ids = [eng.add_request(p, max_new_tokens=new_tokens) for p in prompts]
    out = eng.run()
    return [list(out[i]) for i in ids]


def kernel_in_step(eng, on_tpu: bool):
    """tpu_custom_call count of the T=1 and T=bucket step programs."""
    if not on_tpu:
        return None
    counts = {}
    for T in (1, eng.g.prefill_bucket):
        n = eng.lowered_step(T).as_text().count("tpu_custom_call")
        check(n >= 1, f"serve: no paged kernel in the T={T} step program")
        counts[f"T={T}"] = n
    return counts


def post_stream(host, port, prompt, new_tokens, sent=None) -> list:
    """One streamed /v1/completions; its token ids."""
    conn = http.client.HTTPConnection(host, port, timeout=600)
    conn.request("POST", "/v1/completions", json.dumps(
        {"prompt": prompt, "max_tokens": new_tokens, "stream": True}))
    if sent is not None:
        sent.set()
    resp = conn.getresponse()
    body = resp.read().decode()
    conn.close()
    check(resp.status == 200, f"/v1/completions -> {resp.status}: "
          f"{body[:200]}")
    check("data: [DONE]" in body, "stream did not terminate")
    toks = []
    for ln in body.splitlines():
        if ln.startswith("data: ") and ln != "data: [DONE]":
            toks += json.loads(ln[6:])["choices"][0]["token_ids"]
    return toks


async def natural_wave(host, port, prompts, new_tokens, stagger_s) -> list:
    """The prompts as concurrent streamed requests, one every
    ``stagger_s``: the engine's own admission, later requests joining
    while earlier ones decode."""
    loop = asyncio.get_running_loop()

    def client(i):
        time.sleep(i * stagger_s)
        return post_stream(host, port, prompts[i], new_tokens)

    return list(await asyncio.gather(
        *[loop.run_in_executor(None, client, i)
          for i in range(len(prompts))]))


async def gated_wave(srv, host, port, prompts, new_tokens) -> list:
    """The second check: the engine thread is parked on a control op
    until every request is in the inbox, so the wave is admitted in one
    step — the step composition of ``engine.run()`` over all prompts."""
    loop = asyncio.get_running_loop()
    parked, gate = threading.Event(), threading.Event()
    sent = [threading.Event() for _ in prompts]

    def hold(eng):
        parked.set()
        gate.wait(300)

    park = loop.run_in_executor(
        None, lambda: srv.run_on_engine(hold, timeout_s=330))
    await loop.run_in_executor(None, parked.wait, 60)
    check(parked.is_set(), "engine thread did not take the control op")
    futs = [loop.run_in_executor(None, post_stream, host, port, prompts[i],
                                 new_tokens, sent[i])
            for i in range(len(prompts))]
    await loop.run_in_executor(None, lambda: [e.wait(60) for e in sent])
    await asyncio.sleep(0.3)            # request bytes -> inbox
    gate.set()
    await park
    return list(await asyncio.gather(*futs))


def serve_http(sz, eng, prompts, solo, together) -> dict:
    from paddle_tpu import observability as obs
    from paddle_tpu.distributed.watchdog import get_comm_task_manager
    from paddle_tpu.serving import ServingServer

    # the server as ``python -m paddle_tpu.serving`` builds it
    # (serve_forever): warmup on, the default SLO controller, flight
    # recorder and sentinel, the process watchdog
    srv = ServingServer(eng, model_name="llama2_7b", warmup=True,
                        watchdog=get_comm_task_manager())
    check(srv.slo is not None, "the launcher's SLO controller is missing")

    def get(host, port, path):
        conn = http.client.HTTPConnection(host, port, timeout=60)
        conn.request("GET", path)
        resp = conn.getresponse()
        body = resp.read().decode()
        conn.close()
        return resp.status, body

    async def main():
        loop = asyncio.get_running_loop()
        c0 = obs.backend_compiles()
        t0 = time.perf_counter()
        host, port = await srv.start_http("127.0.0.1", 0)
        while not srv.ready():          # warmup = the step-program compiles
            check(srv.engine_alive(), "engine thread died during warmup")
            await asyncio.sleep(0.05)
        t_warm = time.perf_counter() - t0
        c_warm = obs.backend_compiles() - c0
        waves, secs = {}, {}
        try:
            t0 = time.perf_counter()
            waves["first"] = await natural_wave(
                host, port, prompts, sz.new_tokens, sz.stagger_s)
            secs["first"] = time.perf_counter() - t0
            with obs.assert_overhead(record=True) as ov:
                t0 = time.perf_counter()
                waves["second"] = await natural_wave(
                    host, port, prompts, sz.new_tokens, sz.stagger_s)
                secs["second"] = time.perf_counter() - t0
                t0 = time.perf_counter()
                waves["gated"] = await gated_wave(
                    srv, host, port, prompts, sz.new_tokens)
                secs["gated"] = time.perf_counter() - t0
            status, body = await loop.run_in_executor(
                None, get, host, port, "/metrics")
            slo = srv.slo.state()
        finally:
            await srv.stop_http()
        check(status == 200 and "serving_tokens_generated" in body,
              f"/metrics -> {status}")
        for name, wave in waves.items():
            check(all(len(t) == sz.new_tokens for t in wave),
                  f"serve {name} wave: short stream {[len(t) for t in wave]}")
            # a naturally arriving request is held to its solo run — unless
            # the direct runs already showed that a prompt's tokens depend
            # on what it is batched with: then only the gated wave has an
            # oracle of its own step composition, and the rest is a finding
            want = together if name == "gated" else solo
            if name == "gated" or together == solo:
                check(wave == want, f"serve {name} wave: streamed tokens "
                      f"differ from the direct engine.run(): {wave} vs "
                      f"{want}")
            # a prompt is prefilled in the same T=bucket chunks whatever
            # it is batched with, so its first token has one right value
            check([t[0] for t in wave] == [t[0] for t in solo],
                  f"serve {name} wave: first tokens {[t[0] for t in wave]} "
                  f"differ from the solo runs' {[t[0] for t in solo]}")
        check(ov.compiles == 0,
              f"serve: {ov.compiles} compile(s) in the warm waves")
        check(slo["shed_total"] == 0, f"serve: the SLO controller shed "
              f"{slo['shed_total']} request(s): {slo}")
        return dict(streams_equal_to_solo_run={
                        k: sum(a == b for a, b in zip(w, solo))
                        for k, w in waves.items()},
                    slo={k: slo[k] for k in ("ttft_ms", "itl_ms", "terms",
                                             "decision", "shed_total")},
                    served_warmup_seconds_incl_compile=round(t_warm, 2),
                    served_warmup_compiles=c_warm,
                    stagger_seconds=sz.stagger_s,
                    wave_seconds={k: round(v, 2) for k, v in secs.items()},
                    warm_wave_compiles=ov.compiles,
                    metrics_bytes=len(body))

    return asyncio.run(main())


def phase_serve(sz: Sizes, seed: int, on_tpu: bool) -> None:
    from paddle_tpu import observability as obs

    # the oracles: direct engine.run()s of an identically seeded model —
    # each prompt alone, and all of them admitted in one step
    c0 = obs.backend_compiles()
    t0 = time.perf_counter()
    eng, kw = build_engine(sz, seed)
    t_build = time.perf_counter() - t0
    prompts = prompts_for(sz, eng.g.config.vocab_size, seed)
    t0 = time.perf_counter()
    solo = [run_direct(eng, [p], sz.new_tokens)[0] for p in prompts]
    t_solo = time.perf_counter() - t0
    c_oracle = obs.backend_compiles() - c0
    t0 = time.perf_counter()
    together = run_direct(eng, prompts, sz.new_tokens)
    t_together = time.perf_counter() - t0
    # rows share nothing but the step program: with the same lengths in
    # the same slots, a prompt's tokens must not change with the CONTENT
    # of its neighbours (bf16 results may change with the program a step
    # runs, T=1 or T=bucket — reported below, not a failure)
    others = prompts_for(sz, eng.g.config.vocab_size, seed + 100)
    keep_first = run_direct(eng, prompts[:1] + others[1:], sz.new_tokens)
    keep_rest = run_direct(eng, others[:1] + prompts[1:], sz.new_tokens)
    check(keep_first[0] == together[0] and keep_rest[1:] == together[1:],
          "serve: a prompt's tokens changed with the content of the rows "
          f"beside it: {keep_first[0]} / {keep_rest[1:]} vs {together}")
    del eng
    gc.collect()
    freed = mem_stat("bytes_in_use")

    eng, _ = build_engine(sz, seed)
    kernels = kernel_in_step(eng, on_tpu)
    out = serve_http(sz, eng, prompts, solo, together)
    n_req = 3 * len(prompts)
    emit(phase="serve", depth=sz.serve_layers,
         engine={k: v for k, v in kw.items() if k != "gen"},
         prompt_lens=list(sz.prompt_lens), new_tokens=sz.new_tokens,
         requests=n_req, tokens_streamed=n_req * sz.new_tokens,
         build_seconds=round(t_build, 2),
         solo_oracle_seconds_incl_cold_compile=round(t_solo, 2),
         together_oracle_seconds=round(t_together, 2),
         oracle_compiles=c_oracle, bytes_in_use_after_oracle_freed=freed,
         **out,
         tokens_depend_on_neighbours_content=False,
         tokens_depend_on_step_composition=together != solo,
         tpu_custom_calls=kernels, peak_bytes_in_use=peak_bytes())


# -------------------------------------------------------- four chips ---

def device_report() -> list:
    import jax
    return [{"id": dev.id,
             "bytes_in_use": mem_stat("bytes_in_use", dev),
             "peak_bytes_in_use": mem_stat("peak_bytes_in_use", dev)}
            for dev in jax.devices()]


def check_spread(tree, what: str, on_tpu: bool) -> dict:
    """All four devices hold shards of ``tree`` and (on a chip) live
    bytes; at least one leaf is actually split, not replicated."""
    import jax
    leaves = jax.tree_util.tree_leaves(tree)
    devs = set()
    split = 0
    for leaf in leaves:
        shards = leaf.addressable_shards
        devs |= {s.device.id for s in shards}
        split += any(s.data.shape != leaf.shape for s in shards)
    check(len(devs) == 4, f"{what}: shards on devices {sorted(devs)} only")
    check(split > 0, f"{what}: nothing is sharded, only replicated")
    report = device_report()
    if on_tpu:
        check(all((r["bytes_in_use"] or 0) > 0 for r in report),
              f"{what}: a device holds no live bytes: {report}")
    return {"devices_with_shards": sorted(devs), "split_leaves": split,
            "leaves": len(leaves), "per_device": report}


def phase_train4(sz: Sizes, seed: int, on_tpu: bool) -> None:
    one = run_train(sz, seed, {}, on_tpu)
    one.pop("state")
    gc.collect()
    mesh = run_train(sz, seed, {"dp": 2, "mp": 2}, on_tpu)
    spread = check_spread(mesh.pop("state"), "train dp2xmp2", on_tpu)
    worst = max(abs(a - b) / abs(b)
                for a, b in zip(mesh["losses"], one["losses"]))
    check(worst <= LOSS_TOL, f"train dp2xmp2 vs one device: losses "
          f"{mesh['losses']} vs {one['losses']} (worst rel {worst})")
    emit(phase="train_dp2_mp2", one_device=one, mesh=mesh,
         worst_rel_loss_diff=worst, loss_tol=LOSS_TOL, **spread)
    gc.collect()


def phase_serve4(sz: Sizes, seed: int, on_tpu: bool) -> None:
    eng, kw = build_engine(sz, seed, tensor_parallel=1)
    prompts = prompts_for(sz, eng.g.config.vocab_size, seed)
    t0 = time.perf_counter()
    one = run_direct(eng, prompts, sz.new_tokens)
    t_one = time.perf_counter() - t0
    del eng
    gc.collect()
    eng, _ = build_engine(sz, seed, tensor_parallel=4)
    kernels = kernel_in_step(eng, on_tpu)
    t0 = time.perf_counter()
    four = run_direct(eng, prompts, sz.new_tokens)
    t_four = time.perf_counter() - t0
    check(eng.stats()["tp"] == 4, "engine does not report tp=4")
    check(four == one, f"tensor_parallel=4 tokens differ from "
          f"tensor_parallel=1: {four} vs {one}")
    spread = check_spread((eng.g.cache.arrays, eng.g.params),
                          "engine tp=4", on_tpu)
    emit(phase="serve_tp4", depth=sz.serve_layers,
         engine={k: v for k, v in kw.items()
                 if k not in ("gen", "tensor_parallel")},
         prompt_lens=list(sz.prompt_lens), new_tokens=sz.new_tokens,
         tp1_seconds_incl_compile=round(t_one, 2),
         tp4_seconds_incl_compile=round(t_four, 2),
         tokens_identical=True, tpu_custom_calls=kernels, **spread)


# --------------------------------------------------------------- main ---

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at a tiny size, kernels in "
                         "interpret mode; never prints an ok line")
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.rehearse:
        print(f"chip_smoke: no accelerator (jax found {device}); this "
              "script proves the chip path and does not run without one",
              file=sys.stderr)
        return 1
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, jax found {device}", file=sys.stderr)
        return 1

    from paddle_tpu import flags
    from paddle_tpu import observability as obs
    from paddle_tpu.kernels import (autotune, flash_attention,  # noqa: F401
                                    paged_attention)  # (define their flags)
    if args.rehearse:
        check(not on_tpu, "--rehearse is the CPU rehearsal")
        flags.set_flags({"flash_attention_interpret": True,
                         "paged_attention_interpret": True})
    sz = Sizes(args.rehearse, args.chips)
    emit(phase="start", device=device, chips=args.chips, seed=args.seed,
         rehearsal=args.rehearse,
         compile_cache=jax.config.jax_compilation_cache_dir,
         autotune_cache=autotune._cache_path())

    t0 = time.perf_counter()
    phases = (phase_kernels, phase_train, phase_serve) if args.chips == 1 \
        else (phase_train4, phase_serve4)
    for phase in phases:
        phase(sz, args.seed, on_tpu)
        gc.collect()
    fallbacks = sum(c.value for c in obs.find("kernels.reference_fallbacks"))
    check(fallbacks == 0, f"{fallbacks} trace(s) took an XLA reference "
          "instead of a Pallas kernel")
    emit(phase="done", seconds=round(time.perf_counter() - t0, 1),
         compiles=obs.backend_compiles(), reference_fallbacks=fallbacks)
    if args.rehearse:
        print("chip_smoke: CPU rehearsal passed (no ok line: not a chip "
              "run)", file=sys.stderr)
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
