"""Global flag registry.

TPU-native analog of the reference's exported-flag system
(paddle/common/flags.h + flags.cc: ``PHI_DEFINE_EXPORTED_*`` registry, settable
from env ``FLAGS_x=...`` or ``paddle.set_flags``).  Here the registry is a plain
Python dict seeded from the environment at import time; C++ components read the
same values through ``paddle_tpu.native`` when loaded.
"""

from __future__ import annotations

import os
from typing import Any, Dict

_DEFS: Dict[str, dict] = {}
_VALUES: Dict[str, Any] = {}


def define_flag(name: str, default: Any, help_str: str = "", flag_type: type | None = None) -> None:
    """Register a flag. Env var ``FLAGS_<name>`` overrides the default."""
    if flag_type is None:
        flag_type = type(default)
    _DEFS[name] = {"default": default, "help": help_str, "type": flag_type}
    env = os.environ.get("FLAGS_" + name)
    if env is not None:
        _VALUES[name] = _parse(env, flag_type)
    else:
        _VALUES[name] = default


def _parse(text: str, flag_type: type) -> Any:
    if flag_type is bool:
        return text.lower() in ("1", "true", "yes", "on")
    return flag_type(text)


def get_flags(flags=None) -> Dict[str, Any]:
    if flags is None:
        return dict(_VALUES)
    if isinstance(flags, str):
        flags = [flags]
    return {f: _VALUES[f] for f in flags}


def set_flags(flags: Dict[str, Any]) -> None:
    for k, v in flags.items():
        if k.startswith("FLAGS_"):
            k = k[len("FLAGS_"):]
        if k not in _DEFS:
            raise ValueError(f"Unknown flag {k!r}; known flags: {sorted(_DEFS)}")
        _VALUES[k] = _parse(v, _DEFS[k]["type"]) if isinstance(v, str) else _DEFS[k]["type"](v)


def flag(name: str) -> Any:
    return _VALUES[name]


# ---- core flag set (subset of the reference's 183; grows as subsystems land) ----
define_flag("check_nan_inf", False, "Check every op output for NaN/Inf (debug).")
define_flag("check_nan_inf_level", 0, "0: error on nan/inf; >=1: report statistics only.")
define_flag("use_deterministic_ops", False, "Prefer deterministic XLA lowering.")
define_flag("default_dtype", "float32", "Default floating point dtype.")
define_flag("eager_op_jit", True, "Cache per-op jitted executables in eager mode.")
define_flag("log_memory_stats", False, "Log live buffer stats after each op.")
define_flag("enable_async_trace", False, "Collective watchdog tracing.")
define_flag("comm_timeout_s", 600, "Collective/barrier watchdog timeout in seconds.")
# jaxlint: disable=JL004 -- reference-API parity: user scripts set_flags this; XLA/PJRT owns device memory so the value is intentionally unread
define_flag("allocator_strategy", "auto_growth", "Kept for API parity; XLA/PJRT owns device memory.")
define_flag("tpu_matmul_precision", "default", "jax matmul precision: default|high|highest.")
define_flag("flash_attention_block_q", 512, "Pallas flash attention query block.")
define_flag("flash_attention_block_kv", 512, "Pallas flash attention kv block.")
define_flag("autotune_enable", True,
            "Measure-and-cache Pallas kernel tilings on TPU "
            "(kernels/autotune.py; the phi autotune cache analog).")
define_flag("autotune_cache_path", "",
            "Override the on-disk autotune cache location "
            "(default: autotune.json beside the compile cache, in "
            "paddle_tpu.CACHE_DIR).")
define_flag("to_static_cache_size", 64,
            "Max guard-cache entries per to_static function (LRU eviction;"
            " <=0 = unbounded). Reference: the SOT guard-tree cache cap.")
define_flag("eager_jit_cache_size", 4096,
            "Max cached per-op jitted executables in the eager dispatch "
            "seam (core/autograd _jit_cache/_vjp_cache; LRU; <=0 = "
            "unbounded).")
define_flag("grad_comm_bucket_mb", 4,
            "Fused gradient-bucket size in MB (fp32 elements) for the "
            "ring grad collectives (ParallelConfig.grad_comm='ring'/"
            "'ring_int8'; DDP-style per-dtype fusion, a leaf never spans "
            "two buckets).")
define_flag("grad_comm_block_size", 256,
            "Values per fp32 scale block in the int8 ring grad collective "
            "(distributed/quantized_collectives.py; the EQuARX blockwise-"
            "quantization granularity).")
define_flag("prefix_cache", False,
            "Serving engine: share KV pages across requests with a common "
            "page-aligned token prefix (radix index + ref-counted pages + "
            "copy-on-write + LRU eviction; inference/prefix_cache.py). "
            "Off is bit-identical to the uncached engine; on, greedy "
            "outputs still bit-match the cache-off oracle.")
define_flag("prefix_cache_min_pages", 1,
            "Minimum cached-prefix length IN PAGES for an admission to "
            "take a prefix-cache hit; shorter matches prefill from "
            "scratch (guards against sharing overhead on tiny matches).")
define_flag("kv_cache_dtype", "auto",
            "Serving KV page-pool storage dtype: 'auto' follows the "
            "model dtype, 'fp32'/'float32'/'bf16'/'bfloat16' force a "
            "float pool, 'int8' stores pages quantized with per-(layer, "
            "kv-head, page) fp32 absmax scales (ISSUE 13) — the ragged "
            "paged-attention kernel dequantizes on its VMEM slot right "
            "after the page DMA and the batched commit requantizes per "
            "page, so ~4x more resident tokens fit the same HBM bytes.  "
            "Greedy outputs stay bit-stable run-to-run and within the "
            "documented quantization tolerance of a float pool.")
define_flag("kv_spill_pages", 0,
            "Capacity (in pages) of the pinned-host-RAM spill ring for "
            "LRU-evicted prefix-cache pages (inference/kv_spill.py): "
            "under memory pressure an idle cached page spills its KV "
            "bytes to host RAM instead of dropping, and a later "
            "admission that matches it swaps it back in asynchronously "
            "— eviction becomes a DMA instead of a re-prefill.  0 = off "
            "(evictions drop, the pre-ISSUE-13 behavior).  Requires the "
            "prefix cache.")
define_flag("serving_tensor_parallel", 1,
            "Tensor-parallel shard count for the serving engine (engine "
            "kwarg tensor_parallel=): >1 shards the WHOLE fused engine "
            "step over an 'mp' mesh axis — attention by kv-head (each "
            "shard's ragged kernel only sees its heads' page planes), "
            "grouped MoE by expert, RMS-norm/embedding/sampling "
            "replicated — so greedy and seeded-sampling outputs stay "
            "bit-identical to the tp=1 single-device oracle.  The paged "
            "KV pool stores [num_kv_heads/mp, ...] per shard while page "
            "ids, block tables, the prefix cache, the spill ring and "
            "migration snapshots stay host-global.  num_kv_heads and "
            "num_attention_heads must be divisible by the shard count "
            "and the process must have at least that many devices.")
define_flag("spec_decode", "",
            "Speculative decoding mode for the serving engine "
            "(inference/speculative.py): '' = off (bit-identical to the "
            "plain engine), 'ngram' = prompt-lookup speculation — a "
            "host-rebuilt token-history table drives a DEVICE-side n-gram "
            "drafter, and K tokens are verified in ONE mixed-mode ragged "
            "dispatch at the T=spec_k bucket with device-resident "
            "longest-accepted-prefix acceptance, 'fused' = K sequential "
            "decode steps fused into one jitted dispatch (the self-draft "
            "degenerate case; amortizes host->device dispatch latency). "
            "Greedy outputs in both modes bit-match the spec-off oracle.")
define_flag("spec_k", 4,
            "Tokens per speculative dispatch: the verify step runs at the "
            "T=spec_k query bucket ('ngram' proposes spec_k-1 draft "
            "tokens per step), 'fused' commits up to spec_k tokens per "
            "dispatch.  Bucketed so warm spec steps never recompile.")
define_flag("spec_ngram_max", 3,
            "Longest n-gram context the device-side drafter matches "
            "against the request's prompt+output history (longest match "
            "wins, most recent occurrence breaks ties; shorter contexts "
            "are fallbacks).  History is rebuilt host-side at drain time "
            "only — spec steps issue zero extra host<->device syncs.")
define_flag("metrics", True,
            "Process-wide metrics registry collection on the serving/train "
            "hot paths (paddle_tpu/observability/): per-request TTFT/ITL "
            "histograms, StepTimer train telemetry, pool gauges.  The "
            "overhead contract (warm steps: zero recompiles, zero added "
            "device syncs) is telemetry-asserted in tests; 0 disables every "
            "hot-path instrumentation site.")
define_flag("trace_max_events", 200000,
            "Cap on buffered Chrome-trace events in the observability "
            "tracer (observability/tracing.py); overflow is counted in the "
            "exported file's metadata instead of growing without bound.")
define_flag("trace_sample_rate", 1.0,
            "Fraction of request traces the span exporter ships to the "
            "fleet collector (observability/collector.py), decided per "
            "trace id by stable hash so every process keeps or drops the "
            "SAME traces.  Anomalous / shed / failover / handoff traces "
            "are tail-kept regardless of the rate; 0 disables export "
            "entirely (the exporter never attaches).")
define_flag("trace_export_events", 8192,
            "Bound on pending span-export events buffered per process "
            "(observability/collector.py SpanExporter ring).  The tracer's "
            "offer into the ring is one deque append — overflow evicts "
            "oldest and bumps observability.collector.export_dropped, "
            "never blocks the engine or event loop.")
define_flag("trace_export_batch", 512,
            "Max span events per export batch shipped to the collector; a "
            "flush splits larger backlogs into multiple batches.")
define_flag("trace_export_interval_s", 0.5,
            "Seconds between span-export flushes from each process's "
            "exporter thread to the fleet collector (host-side daemon "
            "thread, off the dispatch path).")
define_flag("trace_collector", "",
            "host:port of the fleet trace collector's HTTP ingest "
            "(POST /collectz on the router / fleet launcher).  Non-empty "
            "makes `python -m paddle_tpu.serving` start a span exporter "
            "over direct HTTP; empty, a fleet-spawned replica exports "
            "over the control-plane store when one is configured, else "
            "tracing stays process-local.")
define_flag("trace_clock_drift_ms", 5.0,
            "Clock-offset drift threshold for the collector's NTP-style "
            "handshake (observability/collector.py ClockSync): a fresh "
            "midpoint measurement differing from the held offset by more "
            "than this (and not explained by round-trip jitter) replaces "
            "it and bumps observability.collector.clock_resyncs.")
define_flag("metrics_max_series", 512,
            "Cap on LABELED series per metric family in the registry "
            "(observability/metrics.py).  A family at the cap folds every "
            "further label set into one {series=__overflow__} series and "
            "bumps metrics.dropped_series instead of growing unbounded "
            "(per-request label explosion guard for long-lived serving).")
define_flag("serving_slo_ttft_ms", 2000.0,
            "HTTP front door TTFT SLO target in ms (serving/slo.py): the "
            "serving.ttft_ms quantile FLAGS_serving_slo_quantile must stay "
            "under this.  <=0 disables the TTFT term.")
define_flag("serving_slo_itl_ms", 200.0,
            "HTTP front door inter-token-latency SLO target in ms "
            "(serving.itl_ms histogram).  <=0 disables the ITL term.")
define_flag("serving_slo_quantile", 0.95,
            "SLO quantile: the fraction of observations that must meet the "
            "TTFT/ITL targets (0.95 = a 5% violation budget).")
define_flag("serving_slo_burn", 2.0,
            "Load-shed threshold as a multiple of the SLO violation "
            "budget: observed violation rate > burn * (1 - quantile) "
            "sheds new requests with 503; > 1x budget marks them "
            "'queue' (admitted, counted as at-risk).")
define_flag("serving_slo_min_samples", 64,
            "Minimum fresh histogram observations in the current window "
            "before SLO burn decisions activate (cold start admits).")
define_flag("serving_slo_window", 512,
            "Observations per SLO decision window: burn is computed over "
            "deltas since the window base, rebased every this-many.")
define_flag("router_placement", "scored",
            "Multi-replica router placement policy (paddle_tpu/router/): "
            "'scored' = expected prefix-hit pages (residency digest) minus "
            "load, with session affinity; 'round_robin' = naive rotation, "
            "no affinity (the A/B baseline arm).")
define_flag("router_health_interval_s", 2.0,
            "Seconds between router health polls of each replica "
            "(/healthz + /readyz + /statusz); consecutive failures back "
            "the poll off exponentially up to 8x this interval.")
define_flag("router_dead_after", 3,
            "Consecutive failed health polls before the router marks a "
            "replica dead (new traffic re-routes; polling continues so a "
            "recovered replica rejoins).")
define_flag("router_poll_timeout_s", 5.0,
            "Per-request timeout for router health polls, the connect "
            "phase of proxied completions, and a STREAMING completion's "
            "response head (written at admission, so slower means the "
            "replica is wedged); a unary head waits out generation "
            "unbounded.")
define_flag("router_digest_max", 4096,
            "Cap on prefix-residency digest entries a replica advertises "
            "via /statusz (breadth-first from the radix root, so a "
            "truncated digest keeps the leading pages placement scores).")
define_flag("router_session_cap", 4096,
            "Max tracked session-affinity pins in the router (LRU "
            "eviction; an evicted session is re-placed by score, which "
            "the residency digest steers back to its page-holding "
            "replica).")
define_flag("router_hit_weight", 1.0,
            "Placement score weight per expected prefix-hit TOKEN "
            "(digest match x page_size).")
define_flag("router_load_weight", 1.0,
            "Placement score penalty weight per queued/busy request on a "
            "replica, in page_size token units (one queued request "
            "offsets one cached page at 1.0).")
define_flag("router_capacity_weight", 1.0,
            "Weight folding a replica's advertised capacity (tensor-"
            "parallel degree + KV pool GiB from /statusz) into router "
            "ordering: handoff/fallback ranking and scored placement "
            "subtract capacity_weight * ((tp - 1) + pool_bytes/GiB) so a "
            "tp=4 replica legitimately outranks a tp=1 one at equal "
            "role/load.  0 restores the pure lexicographic role>load "
            "rank; homogeneous fleets order identically at any weight.")
define_flag("serving_sentinel", True,
            "Online regression sentinel (observability/sentinel.py) in the "
            "serving front door: EWMA+MAD drift detectors over TTFT/ITL, "
            "per-phase step_ms, warm recompiles, queue depth and spec "
            "accept rate, swept from the engine loop.  Anomalies bump "
            "observability.anomaly{series,kind}, land as tracer instant "
            "events, trigger a rate-limited flight-recorder dump (reason "
            "'anomaly') and surface in /statusz.  Detectors need "
            "FLAGS_sentinel_min_samples warm sweeps before they can fire, "
            "so short-lived processes never false-positive.")
define_flag("sentinel_alpha", 0.2,
            "EWMA smoothing factor for the sentinel's baseline mean and "
            "absolute-deviation trackers (observability/sentinel.py); "
            "smaller adapts slower and flags longer after a level shift.")
define_flag("sentinel_k", 4.0,
            "Sentinel anomaly threshold: a sample is anomalous when "
            "|value - ewma| > k * max(deviation, 10% of the baseline) — "
            "the EWMA analog of a k-MAD robust outlier test.")
define_flag("sentinel_min_samples", 16,
            "Observations a sentinel detector must fold into its baseline "
            "before it may flag anomalies (cold-start guard: a fresh "
            "process learns its own normal first).")
define_flag("sentinel_interval_s", 1.0,
            "Minimum seconds between sentinel sweeps when driven from the "
            "serving engine loop (Sentinel.maybe_check); each sweep reads "
            "only host-side registry series — never a device sync.")
define_flag("sentinel_history", 64,
            "Bounded count of recent anomaly records the sentinel retains "
            "for /statusz (oldest evicted first; the counters keep the "
            "full totals).")
define_flag("fleet_drain_timeout_s", 30.0,
            "Bound on a replica's graceful drain: after admission stops "
            "(SIGTERM or /drainz), in-flight requests get this many "
            "seconds to finish before the supervisor (or the replica's "
            "own shutdown path) stops waiting and exits/kills anyway.")
define_flag("fleet_restart_budget", 3,
            "Consecutive crash-restarts the fleet supervisor grants one "
            "replica slot before marking it permanently failed (counted "
            "in fleet.replicas{state=failed}; a replica that stays ready "
            "past FLAGS_fleet_backoff_reset_s earns its budget back).")
define_flag("fleet_backoff_base_s", 0.5,
            "First crash-restart delay; doubles per consecutive restart "
            "up to FLAGS_fleet_backoff_max_s.")
define_flag("fleet_backoff_max_s", 30.0,
            "Cap on the exponential crash-restart backoff.")
define_flag("fleet_backoff_reset_s", 60.0,
            "A replica continuously ready this long has its restart "
            "count (and so its backoff and budget) reset at the next "
            "crash — an old flap must not doom a now-stable replica.")
define_flag("fleet_min_replicas", 1,
            "Autoscaler floor: scale-down never drains below this.")
define_flag("fleet_max_replicas", 8,
            "Autoscaler ceiling: scale-up never spawns above this.")
define_flag("fleet_scale_up_load", 4.0,
            "Autoscale-up threshold on mean placeable-replica load "
            "(router in-flight + polled queue depth, requests): hot "
            "when above this OR when every placeable replica is "
            "shedding its SLO.")
define_flag("fleet_scale_down_load", 0.5,
            "Autoscale-down threshold on mean placeable-replica load: "
            "cold only below this with zero shedding and a quiet "
            "anomaly stream (hysteresis gap vs fleet_scale_up_load).")
define_flag("fleet_hot_ticks", 3,
            "Consecutive hot supervisor ticks required before a "
            "scale-up (hysteresis: one burst must not grow the fleet).")
define_flag("fleet_cold_ticks", 10,
            "Consecutive cold supervisor ticks required before a "
            "scale-down (cold evidence is cheaper than a re-warmup).")
define_flag("fleet_scale_cooldown_s", 30.0,
            "Minimum seconds between autoscale actions in either "
            "direction, so a burst cannot flap the fleet.")
define_flag("fleet_tick_interval_s", 1.0,
            "Seconds between fleet-supervisor control-loop ticks when "
            "run_forever paces itself (tests tick explicitly).")
define_flag("fleet_migrate_on_drain", True,
            "Session-continuity migration (ISSUE 14): when the fleet "
            "supervisor drains a replica for scale-down, the victim "
            "exports its live sessions' KV pages to a supervisor-chosen "
            "READY successor (inference/migration.py) before admission "
            "closes, so the sessions' next turns / failover resumes hit "
            "the successor's prefix cache instead of re-prefilling.  "
            "Best-effort: a failed migration never blocks the drain.")
define_flag("router_failover_resume", True,
            "Journaled failover resume (ISSUE 14): an unplanned replica "
            "death mid-stream re-places the session on a survivor and "
            "REPLAYS its emitted tokens as a prefill (prefix-cache hits "
            "make the replay cheap), continuing the client's SSE stream "
            "with no synthesized error — greedy sessions only (replay "
            "is bit-exact there).  Post-dispatch unary deaths re-run "
            "the same way instead of 502.  Off restores the PR 7 "
            "synthesized-error failover contract.")
define_flag("router_journal_cap", 512,
            "Max in-flight requests the router's replay journal tracks "
            "(LRU; an evicted entry's stream falls back to the "
            "synthesized-error failover path).")
define_flag("router_journal_max_tokens", 4096,
            "Per-request cap on journaled emitted tokens: a stream that "
            "outgrows it is marked non-resumable (bounded memory; the "
            "synthesized-error contract still applies to it).")
define_flag("router_poison_strikes", 2,
            "Poison-request quarantine (ISSUE 15): a replica death "
            "strikes every journaled request in-flight on it whose "
            "current flight had relayed ZERO tokens (the death happened "
            "at/near their dispatch — the poison shape; a mid-stream "
            "request is a victim, not a suspect).  A request signature "
            "(prompt-ids hash + sampling config) that accumulates this "
            "many strikes without progress in between (a relayed token "
            "absolves) is quarantined: replay stops and new submits are "
            "refused 503 with a 'quarantined' error body.  "
            "0 disables the quarantine.")
define_flag("router_quarantine_ttl_s", 300.0,
            "Seconds a quarantined request signature stays refused (and "
            "seconds an un-quarantined signature's strikes persist).  A "
            "latent kernel bug fixed by a restart should not ban the "
            "prompt forever — TTL expiry re-admits it on probation.")
define_flag("router_breaker_park_timeout_s", 20.0,
            "How long a journaled failover resume parks while the "
            "fleet's cascade breaker is open before giving up and "
            "falling back to the synthesized-error contract (the "
            "journal entry waits for a half-open probe slot or a "
            "closed breaker; it never replays into an open one).")
define_flag("fleet_cascade_threshold", 3,
            "Cascade breaker (ISSUE 15): replica deaths inside "
            "FLAGS_fleet_cascade_window_s that trip the breaker OPEN — "
            "failover resume parks, new router admissions shed with "
            "jittered Retry-After, crash restarts continue.  "
            "0 disables the breaker.")
define_flag("fleet_cascade_window_s", 30.0,
            "Sliding window (seconds) the cascade breaker counts "
            "replica deaths over.")
define_flag("fleet_cascade_cooldown_s", 10.0,
            "Seconds an OPEN cascade breaker waits (with no further "
            "deaths) before going HALF-OPEN: one parked resume is "
            "released as a probe; its survival closes the breaker, "
            "another death re-opens it.")
define_flag("serving_queue_timeout_s", 0.0,
            "Queue-expiry shedding (ISSUE 15): a request still waiting "
            "in the engine inbox (never admitted, zero prefill spent) "
            "past this many seconds is retired instead of burning a "
            "prefill on a client that already gave up: unary replies "
            "504; a stream (SSE head already out) gets a finish frame "
            "with finish_reason=queue_expired "
            "(serving.http.queue_expired).  <=0 disables expiry.")
define_flag("prefix_digest_log", 4096,
            "Capacity of the prefix cache's digest change log (adds/"
            "evictions per epoch) backing /statusz digest DELTA sync: a "
            "router polling with digest_since gets only the changes "
            "since its confirmed epoch instead of the full re-shipped "
            "set; a request older than the log forces a full resync.  "
            "0 disables delta sync (every poll ships the full set).")
define_flag("flight_recorder_min_interval_s", 30.0,
            "Per-REASON rate limit on flight-recorder dumps: repeat dumps "
            "with the same reason inside this window are suppressed "
            "(counted in flight_recorder.suppressed_dumps) so a flapping "
            "anomaly detector cannot write an unbounded stream of trace "
            "files.  <=0 disables the limit.")
define_flag("flight_recorder_events", 4096,
            "Bounded ring of recent trace spans kept by the crash flight "
            "recorder (observability/flight_recorder.py); the ring is "
            "dumped as a Chrome trace on watchdog timeout / SIGTERM / "
            "unhandled crash.")
define_flag("flight_recorder_snapshot_s", 10.0,
            "Seconds between periodic registry snapshots folded into the "
            "flight-recorder ring (each is one instant event).")
define_flag("flight_recorder_path", "flight_record.json",
            "Base path for flight-recorder dumps; the trigger reason is "
            "suffixed to the stem so a SIGTERM dump never clobbers a "
            "watchdog-timeout dump.")
define_flag("serving_role", "mixed",
            "Disaggregated serving role (ISSUE 16) this replica "
            "advertises via /statusz: 'prefill' replicas take new "
            "requests and run chunked prefill at full occupancy, "
            "'decode' replicas adopt handed-off sessions and run the "
            "generation leg, 'mixed' does both (the classic fleet).  "
            "The role is a routing preference, not an engine "
            "capability — any role can serve any request.")
define_flag("router_prefill_handoff", True,
            "Prefill->decode handoff (ISSUE 16): with prefill-role "
            "replicas in the fleet, the router caps a new stream's "
            "first leg at one token on a prefill replica, ships the "
            "finished prefix KV over /migratez to a decode successor, "
            "and splices the decode leg into the SAME client stream "
            "(journal replay semantics; 0 re-prefilled full pages on "
            "the successor).  Off routes by role preference only, "
            "with no mid-stream handoff.")
define_flag("router_handoff_timeout_s", 30.0,
            "Bound on each handoff transfer call (/migratez export and "
            "import); past it the router falls back to re-prefilling "
            "the session on a mixed replica — the stream never drops.")
define_flag("router_spill_hit_weight", 0.5,
            "Placement score multiplier for an expected prefix hit "
            "whose page is SPILLED to the replica's host ring (ISSUE "
            "16 satellite): swap-in costs a page upload, not a "
            "re-prefill, so a spilled hit scores between resident "
            "(1.0) and absent (0.0).")
define_flag("router_overlay_cap", 4096,
            "Global LRU cap on each replica's routed-overlay credit "
            "map (the optimistic just-routed prefix hashes scored "
            "before the next /statusz confirms them); evictions count "
            "in router.overlay_evictions.")
define_flag("router_quarantine_sweep_s", 5.0,
            "Min seconds between TTL sweeps of the poison-quarantine "
            "signature table on the read path (quarantined/progress "
            "checks); strikes always sweep inline.  Bounds the table "
            "even when no new strikes arrive.  <=0 sweeps every read.")
define_flag("router_quarantine_cap", 4096,
            "Max tracked poison-quarantine signatures (oldest evicted "
            "first; router.quarantine_entries gauges the table).")
define_flag("fleet_roles", "",
            "Role-specialized fleet spec (ISSUE 16): comma-separated "
            "role=target pairs, e.g. 'prefill=1,decode=2'.  Empty "
            "grows a classic mixed fleet.  Per-role autoscaling moves "
            "each role's target independently: prefill on queue depth "
            "/ TTFT burn, decode on resident sessions / ITL burn.")
define_flag("fleet_rebalance", True,
            "Proactive hot-session rebalance (ISSUE 16): when a READY "
            "replica is shedding on SLO burn while a same-role peer "
            "still admits, the supervisor exports the burner's "
            "sessions, pre-stages them on the peer via the migration "
            "plane, and re-points the router's session pins — the "
            "burner cools instead of melting.  In-flight streams "
            "finish out on the source (drain semantics).")
define_flag("fleet_rebalance_cooldown_s", 10.0,
            "Min seconds between proactive rebalances (one victim per "
            "pass; the cooldown lets the SLO window react before the "
            "supervisor moves more state).")
define_flag("router_digest_sketch", True,
            "Ship the prefix-residency digest as a counting-Bloom "
            "sketch (ISSUE 19) once the exact chain-hash set grows "
            "past router_digest_sketch_threshold entries: per-poll "
            "digest bytes stay flat (m/8 bitmap bytes) instead of "
            "O(resident pages), and expected_hit_tokens becomes a "
            "bounded over-estimate with false-positive rate "
            "(1-e^{-kn/m})^k.  Below the threshold (and with the flag "
            "off) the exact hash list ships as before.")
define_flag("router_digest_sketch_threshold", 2048,
            "Resident-page count above which a sketch replaces the "
            "exact digest in /statusz (exact mode stays the default "
            "for small caches, where precision is free).")
define_flag("router_digest_sketch_bits", 65536,
            "Bloom filter width m in bits (wire form is m/8 bytes, "
            "base64-encoded; 64 KiB bits = 8 KiB raw).")
define_flag("router_digest_sketch_hashes", 4,
            "Bloom hash count k (indices derived from one blake2b "
            "via double hashing).")
define_flag("controlplane_heartbeat_ttl_s", 5.0,
            "Router-liveness TTL in the membership store (ISSUE 19): "
            "a router whose last heartbeat is older than this drops "
            "out of the consistent-hash ring and its span moves to "
            "survivors.")
define_flag("controlplane_heartbeat_interval_s", 1.0,
            "Seconds between router heartbeats / membership refreshes "
            "against the store (the background cp loop cadence).")
define_flag("controlplane_vnodes", 64,
            "Virtual nodes per router on the consistent-hash ring; "
            "more vnodes = smoother span split on membership change.")
define_flag("controlplane_journal_ttl_s", 120.0,
            "TTL on journal records a router replicates into the "
            "membership store for cross-router failover resume; past "
            "it an orphaned record is swept (the client has long "
            "since given up).")
define_flag("controlplane_store_max_keys", 65536,
            "Hard cap on membership-store keys (oldest-set evicted "
            "first); bounds store memory under session churn.")
define_flag("use_native_dataloader", False,
            "Route DataLoader prefetch through the C++ ring-buffer engine "
            "(native/ringbuf.cc). Off by default: with in-process thread "
            "workers, reference passing beats slot serialization (measured "
            "3.5x on 224x224 batches); the native engine is for feeder "
            "processes / multi-host input pipelines.")
