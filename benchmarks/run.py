"""Per-config benchmark harness (BASELINE.md: "Measurement harness to live
in benchmarks/ of this repo with per-config JSON results").

Usage:
    python benchmarks/run.py [config ...] [--cpu] [--fused-gather=0|1]
                             [--trace=PATH] [--gate]
configs: resnet gpt2 llama dit moe decode serve http_serve router_serve
         fleet_chaos spec_decode kv_quant disagg tp_serve router_shard
         all (default: all)

--gate compares each fresh result against the committed
results/<config>.json (benchmarks/check.py guardbands), stamps the
verdict into the result as "regression_gate", and exits nonzero on any
regression.  A PASSING result replaces the committed record; a FAILING
one is written to results/<config>_rejected.json and the baseline is
kept, so a re-run cannot compare regressed-vs-regressed and go green.
An UNCOMPARABLE one (platform mismatch, errored config) lands in
results/<config>_skipped.json, also keeping the baseline — a CPU smoke
under --gate never clobbers a chip capture.  (A valid result over an
error-record baseline does replace it: that is recovery, and the gate
compares against the error record's preserved "previous" first.)

--fused-gather pins FLAGS_grouped_matmul_fused_gather for the run (A/B of
the in-kernel MoE dispatch gather; the =0 arm writes <config>_nofuse.json).

--trace=PATH records the run's host spans (engine steps, per-request
serving lifecycles, train steps, profiler RecordEvents) through the
observability tracer and dumps a Chrome-trace/perfetto JSON to PATH
(multi-config runs write PATH's stem + `_<config>` per config).

Each config writes benchmarks/results/<config>.json, stamped with a full
observability snapshot (`"metrics"`: the registry JSON) and
`"jit_cache_stats"` (ISSUE 5) so every per-PR record carries its
compile/serving/train telemetry.  The driver-facing single-line bench
stays `bench.py` at the repo root; this harness is the full BASELINE
ladder, config 1 (ResNet-50 dygraph) included.
"""

import json
import os
import pathlib
import sys
import time

# `--cpu` (or PADDLE_TPU_BENCH_CPU=1) pins the CPU backend: JAX_PLATFORMS is
# set before jax is imported, and the per-config subprocesses inherit it
CPU_PINNED = "--cpu" in sys.argv or bool(os.environ.get("PADDLE_TPU_BENCH_CPU"))
if CPU_PINNED:
    sys.argv = [a for a in sys.argv if a != "--cpu"]
    os.environ["JAX_PLATFORMS"] = "cpu"

# `--fused-gather=0|1` A/B toggle (the ROADMAP chip-capture queue item):
# pins FLAGS_grouped_matmul_fused_gather for the whole run, so
#     python benchmarks/run.py moe --fused-gather=1
#     python benchmarks/run.py moe --fused-gather=0
# is the one-command A/B of the in-kernel dispatch gather vs the
# materialized-permutation path (the fused arm does not compile on a TPU
# today: see the flag's help in kernels/grouped_matmul.py).  Set via env
# so the per-config subprocesses inherit it before paddle_tpu imports; the
# B arm writes <config>_nofuse.json so the arms never clobber each other.
FUSED_GATHER = None
for _a in [a for a in sys.argv if a.startswith("--fused-gather")]:
    sys.argv.remove(_a)
    _v = _a.split("=", 1)[1] if "=" in _a else "1"
    FUSED_GATHER = _v.lower() not in ("0", "false", "no", "off")
    os.environ["FLAGS_grouped_matmul_fused_gather"] = \
        "1" if FUSED_GATHER else "0"
RESULT_SUFFIX = "_nofuse" if FUSED_GATHER is False else ""

# `--trace=PATH`: dump a Chrome-trace of the run (ISSUE 5).  Parsed here so
# the supervised subprocesses inherit it via argv forwarding.
TRACE_PATH = None
for _a in [a for a in sys.argv if a.startswith("--trace")]:
    sys.argv.remove(_a)
    TRACE_PATH = _a.split("=", 1)[1] if "=" in _a else "trace.json"

# `--gate`: regression gate (ISSUE 10) — each fresh result is compared
# against the committed results/<config>.json BEFORE overwriting it, the
# verdict is stamped into the result as "regression_gate", and the run
# exits nonzero on any regression.  `python -m benchmarks.check` is the
# standalone (no-bench-run) form of the same comparison.
GATE = "--gate" in sys.argv
if GATE:
    sys.argv = [a for a in sys.argv if a != "--gate"]

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
RESULTS = pathlib.Path(__file__).resolve().parent / "results"

# per-process cache of the static-analysis stamp (ISSUE 8): the package
# tree cannot change mid-run, so one analysis serves every config
_LINT_STAMP = None

# per-process cache of the provenance stamp (ISSUE 10 satellite): git SHA
# + tree state + timestamp, so a results file traces back to the commit
# that produced it (the commit cannot change mid-run either)
_PROVENANCE = None


def _provenance():
    global _PROVENANCE
    if _PROVENANCE is None:
        import platform as _platform
        import subprocess

        def _git(*args):
            try:
                return subprocess.run(
                    ["git", "-C", str(ROOT), *args], capture_output=True,
                    text=True, timeout=10).stdout.strip()
            except Exception:
                return ""
        _PROVENANCE = {
            "git_sha": _git("rev-parse", "HEAD") or "unknown",
            "git_dirty": bool(_git("status", "--porcelain")),
            "timestamp_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                           time.gmtime()),
            "python": sys.version.split()[0],
            "hostname": _platform.node(),
        }
    return _PROVENANCE


def _on_tpu():
    import jax
    return jax.devices()[0].platform == "tpu"


def run_resnet():
    """BASELINE config 1: ResNet-50 dygraph single-device imgs/sec +
    compiled (to_static) imgs/sec; correctness = finite decreasing loss."""
    import jax
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as opt
    from paddle_tpu.jit import InputSpec, to_static
    from paddle_tpu.vision.models import resnet18, resnet50

    on_tpu = _on_tpu()
    # CPU smoke: resnet18 at 32px keeps the eager per-op path tractable
    batch, size, steps = (32, 224, 3) if on_tpu else (2, 32, 2)
    paddle.seed(0)
    model = (resnet50 if on_tpu else resnet18)(num_classes=1000)
    # lr sized for a from-scratch bench run: 0.1 diverges at batch 32 in the
    # first steps (round-4 review finding); the criterion is a DECREASING loss
    optimizer = opt.Momentum(0.02, parameters=model.parameters())
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(
        rng.standard_normal((batch, 3, size, size)).astype("float32"))
    y = paddle.to_tensor(rng.integers(0, 1000, batch).astype("int64"))
    loss_fn = nn.CrossEntropyLoss()

    def train_step(xb, yb, fwd=None):
        loss = loss_fn((fwd or model)(xb), yb)
        loss.backward()
        optimizer.step()
        optimizer.clear_grad()
        return loss

    loss0 = float(train_step(x, y)._data)           # warmup + first loss
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = train_step(x, y)
    jax.block_until_ready(loss._data)
    eager_ips = batch * steps / (time.perf_counter() - t0)

    # compiled train: to_static forward = ONE tape node (compiled fwd+bwd);
    # also the convergence check — loss must drop on the overfit batch
    fwd = to_static(model, input_spec=[
        InputSpec([batch, 3, size, size], "float32")])
    train_step(x, y, fwd)
    t0 = time.perf_counter()
    for _ in range(steps * 3):
        loss = train_step(x, y, fwd)
    jax.block_until_ready(loss._data)
    compiled_train_ips = batch * steps * 3 / (time.perf_counter() - t0)
    for _ in range(20):
        loss = train_step(x, y, fwd)
    loss_last = float(loss._data)

    if on_tpu:  # one profiled step (BASELINE config 1 hotspot evidence)
        import bench as _bench
        prof = _bench._profile_one_step(
            "resnet", lambda: train_step(x, y, fwd)._data)
    else:
        prof = {}

    model.eval()
    infer = to_static(lambda xb: model(xb),
                      input_spec=[InputSpec([batch, 3, size, size],
                                            "float32")])
    out = infer(x)
    jax.block_until_ready(out._data)
    t0 = time.perf_counter()
    for _ in range(steps * 6):
        out = infer(x)
    jax.block_until_ready(out._data)
    compiled_ips = batch * steps * 6 / (time.perf_counter() - t0)
    return {
        "config": "resnet50_dygraph" if on_tpu else "resnet18_dygraph_smoke",
        "eager_train_imgs_per_sec": round(eager_ips, 2),
        "compiled_train_imgs_per_sec": round(compiled_train_ips, 2),
        "compiled_infer_imgs_per_sec": round(compiled_ips, 2),
        "loss_first": round(loss0, 4), "loss_last": round(loss_last, 4),
        "loss_decreased": bool(loss_last < loss0),
        "finite": bool(np.isfinite([loss0, loss_last]).all()),
        "batch": batch, "image_size": size,
        **prof,
    }


def run_llama():
    import bench
    mk, b, s_, st, pce = _llama_args()
    return {"config": "llama_hybrid",
            **bench._run_config(mk, b, s_, st, on_tpu=_on_tpu(),
                                pc_extra=pce)}


def _llama_args():
    import bench
    if _on_tpu():
        return bench._tpu_configs()[0]
    return bench._cpu_smoke_config()


def run_gpt2():
    import bench
    return {"config": "gpt2_compiled_vs_eager",
            **bench._run_gpt2_compiled_vs_eager(_on_tpu())}


def run_dit():
    import bench
    return {"config": "dit_diffusion", **bench._run_dit(_on_tpu())}


def run_moe():
    import bench
    return {"config": "moe_expert_parallel", **bench._run_moe(_on_tpu())}


def run_decode():
    import bench
    return {"config": "serving_decode", **bench._run_decode(_on_tpu())}


def run_longctx():
    """Long-context single-chip: 16k-token train step through the flash
    kernel's KV-streaming path (SURVEY §5.7; the multi-chip story is the
    sep axis + ring attention, proven on the virtual mesh)."""
    import jax
    import numpy as np

    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.models.pretrain import ParallelConfig, PretrainStep

    on_tpu = _on_tpu()
    if on_tpu:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                          intermediate_size=2816, num_hidden_layers=8,
                          num_attention_heads=16, num_key_value_heads=16,
                          max_position_embeddings=16384, dtype="bfloat16")
        batch, seq, steps = 1, 16384, 6
    else:
        cfg = LlamaConfig.tiny()
        batch, seq, steps = 1, 64, 2
    pc = ParallelConfig(remat=on_tpu, loss_chunks=16 if on_tpu else 1,
                        m_dtype="bfloat16" if on_tpu else "float32")
    ps = PretrainStep(cfg, pc)
    state = ps.init_state(seed=0)
    rng = np.random.default_rng(0)
    ids, labels = ps.shard_batch(
        rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32),
        rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32))
    state, loss = ps.train_step(state, ids, labels)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, loss = ps.train_step(state, ids, labels)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    tps = batch * seq * steps / dt
    import bench
    peak = bench._peak_flops(jax.devices()[0])
    # flops_per_token is 6N (dense-decoder convention); at 16k the
    # attention matmuls are no longer negligible — add the PaLM-appendix
    # 6*L*s*H term (causal average s/2 keys, x2 for QK+AV, x3 fwd+bwd)
    attn = 6.0 * cfg.num_hidden_layers * seq * cfg.hidden_size / 2
    fpt = ps.flops_per_token(False) + attn
    return {
        "config": "longctx_16k",
        "longctx_seq": seq,
        "longctx_tok_per_sec": round(tps, 1),
        "longctx_mfu": round(tps * fpt / peak, 4),
        "longctx_mfu_excl_attn": round(
            tps * ps.flops_per_token(False) / peak, 4),
        "longctx_loss": round(float(loss), 4),
    }


def run_grad_comm():
    """ISSUE 3: one-command grad_comm A/B (`python benchmarks/run.py
    grad_comm --cpu`) — auto (XLA psum oracle) vs bucketed fp32 ring vs
    EQuARX-style int8 ring gradient sync; step time + bytes moved per
    collective.  Needs a dp axis: forces an 8-device host platform before
    the backend initializes (it affects the CPU platform only, and is too
    late only in `--inproc all` single-process runs, where the A/B then
    records a needs-devices note instead)."""
    import bench
    bench._force_host_devices()
    return {"config": "grad_comm_ab", **bench._run_grad_comm(_on_tpu())}


def run_serve_prefix():
    """ISSUE 4: one-command prefix-cache A/B (`python benchmarks/run.py
    serve_prefix --cpu`) — continuous-batching engine on a 50%
    shared-prefix traffic mix, cache on vs off.  Besides the usual
    results/serve_prefix.json, stamps results/prefix_cache.json as the
    canonical A/B record (tok/s both arms, hit rate, pages saved)."""
    import bench
    out = {"config": "serve_prefix", **bench._run_serve_prefix(_on_tpu())}
    RESULTS.mkdir(exist_ok=True)
    (RESULTS / "prefix_cache.json").write_text(
        json.dumps(out, indent=2) + "\n")
    return out


def run_spec_decode():
    """ISSUE 9: speculative-decoding A/B (`python benchmarks/run.py
    spec_decode --cpu`) — the continuous-batching engine on a
    repetitive-suffix mix, spec OFF vs prompt-lookup ngram verification
    and fused K-step decode at K in {4, 8}.  Stamps every arm's tok/s,
    acceptance rate, committed tokens-per-dispatch and the bit-match
    flag vs the off arm into results/spec_decode.json."""
    import bench
    return {"config": "spec_decode", **bench._run_spec_decode(_on_tpu())}


def run_serve():
    """ISSUE 5: serving observability A/B (`python benchmarks/run.py serve
    --cpu`) — continuous-batching engine with metrics ON vs OFF: TTFT/ITL/
    queue-wait/occupancy histograms from the registry, warm steps asserted
    at zero compiles, and the on arm within the 2% tok/s overhead
    contract.  Combine with --trace=PATH for a loadable Chrome-trace of
    the run's request lifecycles."""
    import bench
    return {"config": "serve_observability",
            **bench._run_serve_metrics(_on_tpu())}


def run_router_serve():
    """ISSUE 7: multi-replica router A/B (`python benchmarks/run.py
    router_serve --cpu`) — two serving replicas (prefix cache on) behind
    the RouterServer on the 50%-shared mix: prefix-aware scored
    placement (residency digest + session/overlay affinity) vs
    round-robin.  Stamps both arms' tok/s, fleet prefix hit rate,
    tokens saved, per-replica hit split, warm-compile and failover
    counters into results/router_serve.json; outputs must bit-match
    across arms (greedy placement-invariance)."""
    import bench
    return {"config": "router_serve", **bench._run_router_serve(_on_tpu())}


def run_http_serve():
    """ISSUE 6: HTTP front door A/B (`python benchmarks/run.py http_serve
    --cpu`) — concurrent streaming clients against the real-socket
    asyncio server, full observability plane ON (metrics + SLO admission
    + flight-recorder ring) vs OFF.  Reports client-measured TTFT and
    inter-chunk latency (the drain-cadence arrival rhythm a user sees)
    next to the engine-measured serving.ttft_ms/itl_ms histograms, and
    stamps the shed / dropped-series / dropped-trace-events guard
    counters into results/http_serve.json alongside the automatic
    registry snapshot."""
    import bench
    return {"config": "http_serve", **bench._run_http_serve(_on_tpu())}


def run_fleet_chaos():
    """ISSUE 12: supervised-fleet churn under chaos (`python
    benchmarks/run.py fleet_chaos --cpu`) — a 2→3→1 replica scenario
    where the FleetSupervisor's closed loop does all the driving: the
    load ramp trips the queue signal and scales to 3, a seeded fault
    plan SIGKILLs a replica mid-stream (crash-restart converges the
    fleet back), and the idle cool-down drains to 1 via the graceful
    drain protocol.  Gated stamps: zero hard failures beyond the
    synthesized-error contract, survivor bit-identity vs the
    direct-engine oracle, convergence, 0 warm compiles."""
    import bench
    return {"config": "fleet_chaos", **bench._run_fleet_chaos(_on_tpu())}


def run_kv_quant():
    """ISSUE 13: quantized-KV-plane A/B (`python benchmarks/run.py
    kv_quant --cpu`) — cache-fp pool vs int8 pool at equal pool bytes on
    the 50%-shared serve_prefix mix, spill ring on.  Gated stamps:
    resident-session high-water >= 1.8x on the int8 arm
    (kv_quant_capacity_match) and int8 bit-stability run-to-run
    (kv_quant_int8_bit_stable_match); tok/s both arms, spill/swap-in
    counts and the output-agreement fraction ride along."""
    import bench
    return {"config": "kv_quant", **bench._run_kv_quant(_on_tpu())}


def run_tp_serve():
    """ISSUE 18: tensor-parallel serving A/B (`python benchmarks/run.py
    tp_serve --cpu`) — tp=2 (kv-head-sharded fused engine step over the
    'mp' mesh) vs the tp=1 oracle at equal total pool bytes on the
    50%-shared mix.  Gated stamps: bit-identical outputs across arms
    (tp_serve_tp_bit_match) and zero warm compiles on BOTH arms
    (tp_serve_warm_zero_compile_match); per-arm tok/s rides along
    observationally (CPU-mesh collectives are pure overhead).  Needs an
    'mp' axis: forces a multi-device host platform before the backend
    initializes (it affects the CPU platform only)."""
    import bench
    bench._force_host_devices()
    return {"config": "tp_serve", **bench._run_tp_serve(_on_tpu())}


def run_disagg():
    """ISSUE 16: disaggregated prefill/decode serving A/B (`python
    benchmarks/run.py disagg --cpu`) — 2 prefill + 2 decode replicas vs
    4 mixed replicas behind the router on the 50%-shared streaming mix
    with more clients than fleet slots.  The prefill fleet runs the
    1-token capped leg, the finished prefix ships to a decode replica
    over the migration plane and the router splices both legs into one
    stream.  Gated stamps: bit-identical outputs across arms with zero
    re-prefilled full pages and zero warm compiles
    (disagg_handoff_match), and a p95 TTFT-or-ITL win at equal replica
    count (disagg_beats_mixed)."""
    import bench
    return {"config": "disagg", **bench._run_disagg(_on_tpu())}


def run_router_shard():
    """ISSUE 19: sharded-control-plane A/B (`python benchmarks/run.py
    router_shard --cpu`) — the 50%-shared session mix on ONE router vs
    a THREE-router fleet sharing a membership store, spray-balanced,
    with a router killed at the halfway barrier, plus a third arm with
    the digest sketch forced on.  Gated stamps: bit-identical outputs
    across all arms (router_shard_zero_loss_match), at most one forward
    hop per request, fleet hit rate within 10% of single-router, the
    ring span moved to the survivors, sketch-vs-exact hit-rate delta,
    and FLAT sketch wire bytes next to the page-scaled exact digest."""
    import bench
    return {"config": "router_shard", **bench._run_router_shard(_on_tpu())}


CONFIGS = {"resnet": run_resnet, "llama": run_llama, "gpt2": run_gpt2,
           "dit": run_dit, "moe": run_moe, "decode": run_decode,
           "longctx": run_longctx, "grad_comm": run_grad_comm,
           "serve_prefix": run_serve_prefix, "spec_decode": run_spec_decode,
           "serve": run_serve,
           "http_serve": run_http_serve, "router_serve": run_router_serve,
           "kv_quant": run_kv_quant, "fleet_chaos": run_fleet_chaos,
           "disagg": run_disagg, "tp_serve": run_tp_serve,
           "router_shard": run_router_shard}


def _supervise(names, timeout):
    """Run each config in its own subprocess with a hard timeout.

    One process per chip at a time: the parent stays off jax, and a fresh
    process per config bounds a hung backend to one config and hands the
    next one a fresh PJRT client.
    """
    import subprocess
    failed = 0
    for name in names:
        t0 = time.time()
        path = RESULTS / f"{name}{RESULT_SUFFIX}.json"
        prev = _parse(path)  # snapshot BEFORE the child can clobber it
        cmd = [sys.executable, os.path.abspath(__file__), "--inproc", name]
        if CPU_PINNED:
            cmd.append("--cpu")
        if GATE:
            cmd.append("--gate")
        if FUSED_GATHER is not None:
            # the child derives its flag AND its result-file suffix from
            # argv — without this the B arm would write <name>.json and
            # clobber the fused arm's record
            cmd.append(f"--fused-gather={1 if FUSED_GATHER else 0}")
        if TRACE_PATH is not None:
            # each child runs ONE config, so the per-config suffix must be
            # applied HERE — forwarding the bare path would have every
            # child overwrite the same file
            tp = pathlib.Path(TRACE_PATH)
            if len(names) > 1:
                tp = tp.with_name(tp.stem + f"_{name}" + tp.suffix)
            cmd.append(f"--trace={tp}")
        try:
            child = subprocess.Popen(cmd)
        except Exception as e:
            failed += 1
            _write_error(path, name, f"{type(e).__name__}: {e}", t0, prev)
            continue
        # Poll instead of a blocking wait: a child may write a fresh valid
        # result and THEN hang in PJRT client teardown at exit (observed
        # mode) — kill it as soon as its result lands rather than burning
        # the full timeout on a run that already succeeded.
        err = None
        try:
            while True:
                rc = child.poll()
                if rc is not None:
                    err = None if rc == 0 else f"subprocess exited rc={rc}"
                    break
                if time.time() - t0 > timeout:
                    err = f"timeout after {timeout}s (hung backend?)"
                    break
                if _fresh_ok(path, t0):
                    time.sleep(5)   # grace for trailing stdout, then reap
                    break
                time.sleep(5)
        finally:
            # never leave a child holding the TPU — incl. on KeyboardInterrupt
            if child.poll() is None:
                child.kill()
                child.wait()
        if err is not None and _fresh_ok(path, t0):
            err = None              # result landed; only the exit failed
        rej = RESULTS / f"{name}{RESULT_SUFFIX}_rejected.json"
        if err is not None and GATE and _fresh_ok(rej, t0):
            # the child's nonzero exit was the regression gate, not an
            # infra failure: the rejected candidate landed beside the
            # (untouched) baseline — do NOT clobber the baseline with an
            # error record
            failed += 1
            print(f"{name}: REGRESSION GATE FAIL (candidate at {rej}; "
                  "baseline kept)")
            continue
        if err is not None:
            failed += 1
            _write_error(path, name, err, t0, prev)
    return 1 if failed else 0


def _write_error(path, name, err, t0, prev):
    """Record a failure, keeping the newest NON-error numbers visible.

    ``prev`` is the pre-run snapshot: if it is itself an error record, hoist
    its ``previous`` so consecutive failures never nest unboundedly.
    """
    fresh = _parse(path)  # the child may have written its own error record
    try:  # prefer the child's specific exception over a generic rc string
        if fresh["error"] and path.stat().st_mtime >= t0:
            err = fresh["error"]
    except (TypeError, KeyError, OSError):
        pass
    record = {"config": name, "error": err,
              "wall_s": round(time.time() - t0, 2)}
    for cand in (fresh, prev):
        if isinstance(cand, dict) and "error" not in cand:
            record["previous"] = cand
            break
        if isinstance(cand, dict) and "previous" in cand:
            record["previous"] = cand["previous"]
            break
    path.write_text(json.dumps(record, indent=2) + "\n")
    print(f"{name}: ERROR {err}")


def _parse(path):
    try:
        return json.loads(path.read_text())
    except Exception:
        return None


def _fresh_ok(path, t0):
    """True if path holds an error-free result written after t0."""
    try:
        if path.stat().st_mtime < t0:
            return False
    except OSError:
        return False
    obj = _parse(path)
    return isinstance(obj, dict) and "error" not in obj


def main(argv):
    inproc = "--inproc" in argv
    timeout = int(os.environ.get("LADDER_TIMEOUT_S", "2400"))
    names = [a for a in argv if a != "--inproc"] or ["all"]
    if "all" in names:
        names = list(CONFIGS)
    unknown = [n for n in names if n not in CONFIGS]
    if unknown:  # fail fast, not after a 2400s child timeout
        print(f"unknown config(s): {unknown}; have {sorted(CONFIGS)}")
        return 2
    RESULTS.mkdir(exist_ok=True)
    if not inproc:
        return _supervise(names, timeout)
    failed = 0
    for name in names:
        if TRACE_PATH is not None:
            # (re)start per config, clearing the buffer: each exported
            # trace holds exactly its own config's spans (engine steps,
            # request lifecycles, train steps, RecordEvents)
            from paddle_tpu import observability as _obs
            _obs.tracer.start()
        t0 = time.perf_counter()
        try:
            result = CONFIGS[name]()
            result["wall_s"] = round(time.perf_counter() - t0, 2)
        except Exception as e:  # record the failure, keep the ladder going
            import traceback
            traceback.print_exc()
            result = {"config": name, "error": f"{type(e).__name__}: {e}",
                      "wall_s": round(time.perf_counter() - t0, 2)}
            failed += 1
        # provenance stamp: CPU smoke runs must never read as TPU numbers,
        # and A/B arms must record which dispatch-gather mode they ran
        try:
            import jax
            dev = jax.devices()[0]
            result.setdefault("platform", dev.platform)
            result.setdefault("device_kind",
                              getattr(dev, "device_kind", "?"))
        except Exception:
            pass
        try:
            import paddle_tpu.kernels.grouped_matmul  # registers the flag
            from paddle_tpu import flags as _flags
            result.setdefault("grouped_matmul_fused_gather",
                              bool(_flags.flag("grouped_matmul_fused_gather")))
        except Exception:
            pass
        # observability stamp (ISSUE 5): every result carries the full
        # registry snapshot + compile-cache telemetry of its process
        try:
            import paddle_tpu.jit as _pjit
            from paddle_tpu import observability as _obs
            result["metrics"] = _obs.snapshot()
            result["jit_cache_stats"] = _pjit.cache_stats()
            if TRACE_PATH is not None:
                tp = pathlib.Path(TRACE_PATH)
                if len(names) > 1:   # one file per config, never clobbered
                    tp = tp.with_name(tp.stem + f"_{name}" + tp.suffix)
                result["trace_path"] = _obs.export_chrome_trace(str(tp))
        except Exception as e:
            result.setdefault("metrics_error",
                              f"{type(e).__name__}: {str(e)[:120]}")
        # static-analysis stamp (ISSUE 8): the analyzer version + finding
        # count over the package this result was produced by, so a bench
        # record also certifies the tree was invariant-clean.  Computed
        # once per process (the tree cannot change mid-run) and reused
        # for every config's result.
        global _LINT_STAMP
        if _LINT_STAMP is None:
            try:
                from paddle_tpu import analysis as _lint
                rep = _lint.package_report()
                _LINT_STAMP = {
                    "analyzer": rep["analyzer"], "version": rep["version"],
                    "findings": len(rep["findings"]),
                    "suppressed": rep["suppressed"],
                    "counts": rep["counts"]}
            except Exception as e:
                _LINT_STAMP = {
                    "error": f"{type(e).__name__}: {str(e)[:120]}"}
        result["static_analysis"] = _LINT_STAMP
        # provenance stamp (ISSUE 10 satellite): which commit, when,
        # which interpreter — a results file is now traceable
        result["provenance"] = _provenance()
        path = RESULTS / f"{name}{RESULT_SUFFIX}.json"
        if GATE:
            # regression gate (ISSUE 10): compare against the committed
            # record; the verdict rides the result.  A FAILING candidate
            # is written to <name>_rejected.json and the baseline file is
            # left untouched — overwriting it would make a re-run compare
            # regressed-vs-regressed and go green (regression laundering)
            from benchmarks import check as _check
            baseline = _check.load_result(path)
            verdict = _check.gate_result(result, baseline)
            bail = next((n for n in verdict["notes"]
                         if n.startswith("skipped:")), None)
            if not verdict["pass"]:
                failed += 1
                for r in verdict["regressions"]:
                    print(f"{name}: REGRESSION {r['key']}: "
                          f"{r['baseline']} -> {r['candidate']} "
                          f"— {r['why']}")
                path = RESULTS / f"{name}{RESULT_SUFFIX}_rejected.json"
                print(f"{name}: gate FAIL — candidate -> {path}; "
                      "baseline kept")
            elif bail and baseline is not None and \
                    "baseline is an error record" not in bail:
                # the comparison bailed (platform mismatch, candidate
                # error): an UNCOMPARABLE candidate must not replace the
                # baseline either — a CPU smoke under --gate would
                # silently clobber a TPU capture.  (A valid candidate
                # over an error-record baseline IS written: recovery.)
                path = RESULTS / f"{name}{RESULT_SUFFIX}_skipped.json"
                print(f"{name}: gate SKIPPED ({bail[9:].strip()}) — "
                      f"candidate -> {path}; baseline kept")
        path.write_text(json.dumps(result, indent=2) + "\n")
        print(f"{name}: {json.dumps(result)}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
