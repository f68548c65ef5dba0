"""Metric series catalog: the documented surface of the registry.

One table maps every metric family the package can emit to its kind,
label set and meaning.  ``docs/metrics.md`` is GENERATED from this table
(``python -m paddle_tpu.observability.catalog``), and a tier-1 drift test
asserts (a) every family the test process actually created is cataloged
and (b) the committed markdown matches the generator's output — an
emitted-but-undocumented series, or a stale doc, is a test failure, not a
review nitpick (ISSUE 10 satellite).

Keep entries in the family's home module order; the generator groups by
dotted prefix.

``SPANS`` is the same for the tracer's live spans: the closed vocabulary
of ``Tracer.span`` names with the arguments each carries, generated into
the same file and held by the same kind of drift test.
"""

from __future__ import annotations

from typing import Dict, Optional

from . import metrics as _metrics

__all__ = ["CATALOG", "SPANS", "undocumented", "generate_markdown",
           "apply_help"]

# family -> (kind, labels, meaning)
CATALOG: Dict[str, tuple] = {
    # ---- serving: request lifecycle (PR 5) ----
    "serving.requests_total": (
        "counter", "", "requests submitted to the engine"),
    "serving.requests_completed": (
        "counter", "", "requests retired by the engine"),
    "serving.tokens_generated": (
        "counter", "", "generated tokens retired across all requests"),
    "serving.prefill_tokens": (
        "counter", "", "prompt tokens prefilled (post prefix-cache trim)"),
    "serving.steps": ("counter", "", "engine dispatches"),
    "serving.drains": (
        "counter", "", "gathers that delivered at least one step's "
        "results to the host (every step in steady state: there is no "
        "drain cadence)"),
    "serving.gather_blocked": (
        "counter", "reason", "gathers that had to wait for the device: "
        "bound (`MAX_STEPS_IN_FLIGHT` steps were out and the next "
        "dispatch needs room), idle (nothing to dispatch), settle (a "
        "caller needs the host's books to be the device's: session "
        "export, the emergency drain under pool pressure, `_drain()`)"),
    "serving.steps_in_flight": (
        "histogram", "", "steps dispatched and not yet gathered, "
        "observed after each dispatch (never over `MAX_STEPS_IN_FLIGHT`)"),
    "serving.queue_wait_ms": (
        "histogram", "", "enqueue -> admission wait per request"),
    "serving.ttft_ms": (
        "histogram", "", "enqueue -> first token on the host, per "
        "request (stamped when the gather that carries it returns)"),
    "serving.itl_ms": (
        "histogram", "", "inter-token latency per generated token after "
        "the first, stamped when the token reaches the host: a plain "
        "step brings a request one token, so an observation is a true "
        "gap; the tokens of one speculative step share their span"),
    "serving.queue_depth": (
        "histogram", "", "waiting-queue depth observed at each step"),
    "serving.queue_depth_now": (
        "gauge", "", "live waiting-queue depth"),
    "serving.batch_occupancy": (
        "histogram", "", "busy slots / max_batch per step"),
    # ---- serving: the rows of the expert GEMMs (PR 27; PR 33) ----
    "serving.moe_held_rows": (
        "histogram", "",
        "(token, choice) entries of one plain step that fell on experts "
        "this chip holds, summed over the layers: held or, where every "
        "expert is held (the grouped path on one device), live: the "
        "entries of the rows that hold a token (a model whose "
        "`MoeSpec.held` is under the router's width counts the entries "
        "routed here; counted on the device, read at the drain that "
        "exists)"),
    "serving.moe_rows_laid_out": (
        "histogram", "",
        "rows the grouped expert GEMM laid out for those entries in the "
        "same step, summed over the layers: every held expert's entries "
        "rounded up to whole row tiles, at least one tile each (held or, "
        "where every expert is held, live: the tiles behind them are "
        "skipped); `moe_held_rows / moe_rows_laid_out` is the occupancy "
        "of the tiles that are multiplied"),
    "serving.moe_expert_rows_max": (
        "histogram", "",
        "the (token, choice) entries of the FULLEST expert in one plain "
        "step, summed over the layers as the two above (divide by the "
        "layers that have experts for a layer's): what a straggler among "
        "many small experts shows beside `moe_held_rows / experts`.  "
        "Counted on the device where every expert is held and the grouped "
        "path runs on one device (a third number beside the two above, a "
        "max over the plan's per-expert counts) and read where the step's "
        "counts land (the gather); observed while a tracer or a profiler "
        "listens, one observation a step, none otherwise and none where a "
        "chip holds a share of the experts. "
        "Where the router reads the attention's input "
        "(`MoeSpec.router_input`) its operations run before the attention "
        "call under the device scope `moe_router` (`moe_router/router/...`), "
        "apart from the experts' `moe/experts/...`"),
    # ---- serving: what the pool holds of a token (PR 31) ----
    "serving.kv_bytes_per_token": (
        "gauge", "",
        "pool bytes one cached token costs over the layers that KEEP "
        "PAGES (`pool_bytes / (num_pages x page_size)`; "
        "`inference/kv_cache.py::LayerPlanes`: a linear-attention place "
        "keeps none): per-head K and V "
        "pages, or a latent pool's one row `[c | k_r]` a layer (5,760 for "
        "five latent layers of 512 + 64 in bf16; 7,040 where each also "
        "keeps an index key of 128; 16,384 for four layers of 8 KV heads "
        "x 128, and for eight layers of 4; 4,096 for the ONE softmax layer "
        "in four of 8 KV heads x 128)"),
    # ---- serving: a learned index over the latent pool (PR 39) ----
    "serving.index_bytes_per_token": (
        "gauge", "",
        "the part of `serving.kv_bytes_per_token` that is a learned "
        "index's keys (`models.decoder_spec.LatentIndex`: one key of "
        "`dim` a token a layer, the pool's third plane): 1,280 for five "
        "layers of 128 in bf16; 0 for a stack without an index"),
    "serving.index_pairs": (
        "histogram", "",
        "pairs (query token, cached or own token) a learned index scores "
        "in ONE layer of a plain step, the sum over the working slots of "
        "`q x ctx + q (q + 1) / 2` (`engine.step`'s `index_pairs`; one "
        "observation a step while a tracer or a profiler listens, none "
        "for a stack without an index)"),
    "serving.selected_keys": (
        "histogram", "",
        "keys the learned index chooses in ONE layer of a plain step: "
        "`min(position + 1, top_k)` summed over the step's query tokens "
        "(`engine.step`'s `selected_keys`; observed as "
        "`serving.index_pairs` is): what the sparse latent call's softmax "
        "runs over, whatever the walk multiplies"),
    # ---- serving: what one copy of the paged call moves (PR 35) ----
    "serving.kv_copy_bytes": (
        "gauge", "",
        "bytes ONE DMA descriptor of the paged-attention call moves: a "
        "page's K and V of every KV head the shard holds, one layer, "
        "which the page-major pool keeps as one contiguous run (32,768 at "
        "4 KV heads x 16 tokens x 128 in bf16, 65,536 at 8; the head-major "
        "pool before PR 35 moved 4,096, one head's K or V); a latent "
        "pool's largest copy, half a page's compressed rows.  "
        "`engine.step`'s `page_copies` x this = the bytes one layer's "
        "call fetches"),
    # ---- serving: what a slot holds besides pages (PR 34) ----
    "serving.state_bytes_per_slot": (
        "gauge", "",
        "bytes of recurrent state one slot holds over the layers that "
        "have one, besides its pages, fixed and by slot "
        "(`inference/kv_cache.py::RecurrentState`, `LayerPlanes`): a "
        "mixer's float32 state and its "
        "convolution's carried rows (16,900,096 for four layers of a "
        "state-space mixer of 32 "
        "heads x 128 x 256 and three rows of 5,120 in bf16; 13,025,280 "
        "for three linear-attention layers of 64 heads x 128 x 128 and "
        "three rows of 24,576); 0 for a "
        "stack that keeps pages alone.  A linear-attention place's "
        "operations run under the device scope `linear_attn` (its "
        "convolution `linear_attn/conv`, the decay, beta and the output "
        "gate's projections `linear_attn/gates`, the delta-rule call "
        "`linear_attn/kda`: `kernels/kda.py`, the custom call "
        "`ragged_kda_update`), a state-space mixer's under `ssm`; a gated "
        "softmax place's gate under `attention/out_gate`"),
    "serving.state_resets": (
        "counter", "",
        "slots whose recurrent state is zeroed on the device by the step "
        "that runs their first chunk: one an admission to a stack that "
        "has such a state, none otherwise"),
    # ---- serving: per-phase step attribution (PR 10) ----
    "serving.step_ms": (
        "histogram", "phase=prefill|decode|spec_verify|cow_copy|drain",
        "per-phase dispatch-to-dispatch engine step wall time "
        "(observability/attribution.py; folded at drains)"),
    "serving.tokens_per_sec": (
        "gauge", "phase=...",
        "per-phase throughput over the last drained window"),
    # ---- serving: KV pool + prefix cache (PR 2/4) ----
    "serving.pages_in_use": ("gauge", "", "allocated KV pages"),
    "serving.peak_pages_in_use": (
        "gauge", "", "high-water allocated KV pages"),
    "serving.active_seqs": ("gauge", "", "sequences holding pages"),
    "serving.prefix_cached_pages": (
        "gauge", "", "radix-indexed shared KV pages"),
    "serving.prefix_evictable_pages": (
        "gauge", "", "idle cached pages the LRU pool could reclaim"),
    "serving.prefix_digest_epoch": (
        "gauge", "", "prefix-digest change epoch (ISSUE 14 delta sync: "
        "every index insert/eviction bumps it; routers confirm an epoch "
        "and poll for only the changes since)"),
    "serving.prefix_hits": (
        "counter", "", "admissions that attached a cached prefix"),
    "serving.prefix_tokens_saved": (
        "counter", "", "prompt tokens skipped via cached prefixes"),
    "serving.cow_copies": (
        "counter", "", "copy-on-write page privatizations"),
    "serving.evicted_pages": (
        "counter", "", "cached pages reclaimed under memory pressure"),
    # ---- serving: quantized KV plane + host spill tier (PR 13) ----
    "serving.kv.quant_bytes_saved": (
        "counter", "", "pool bytes the int8 KV plane saves vs an "
        "equal-page fp32 pool (stamped once per cache construction)"),
    "serving.kv.spilled_pages": (
        "counter", "", "LRU-evicted prefix-cache pages demoted to the "
        "pinned-host-RAM spill ring instead of dropped"),
    "serving.kv.swapins": (
        "counter", "", "spilled pages swapped back into the device pool "
        "by an admission match"),
    "serving.kv.swapin_wait_ms": (
        "histogram", "", "host time dispatching one spilled page's "
        "swap-in upload (dispatch-only; no device sync)"),
    # ---- serving: session migration (ISSUE 14) ----
    "serving.kv.migration_exports": (
        "counter", "", "session snapshots exported (inference/"
        "migration.py: raw pool bytes — int8 pages ship quantized, "
        "spilled pages ship their host-ring bytes)"),
    "serving.kv.migration_imports": (
        "counter", "", "session snapshots imported and indexed as "
        "ready prefix-cache pages via acquire_page + the pre-warmed "
        "donating upload"),
    "serving.kv.migration_pages": (
        "counter", "direction=out|in", "KV pages moved by session "
        "migration"),
    "serving.kv.migration_aborts": (
        "counter", "", "transfers that failed mid-flight (the in-flight "
        "page's allocator ref is released; already-linked pages stay "
        "valid cache entries)"),
    "serving.kv.migration_rejected": (
        "counter", "", "snapshots refused by the blake2b integrity "
        "check at import (ISSUE 15: corrupt or truncated bytes — "
        "nothing installed, zero allocator refs leaked)"),
    # ---- serving: disaggregated prefill/decode handoff (ISSUE 16) ----
    "serving.kv.handoff_sessions": (
        "counter", "outcome=ok|partial", "prefill->decode handoffs "
        "imported on this replica (the /migratez/import handoff path): "
        "ok = every full page under the journaled tokens arrived, "
        "partial = the decode leg re-prefills the shortfall"),
    "serving.kv.handoff_reprefill_tokens": (
        "counter", "", "tokens the decode leg re-prefills because "
        "their pages did NOT survive the handoff (the disagg bench "
        "gates this at zero)"),
    # ---- serving: tensor-parallel engine step (ISSUE 18) ----
    "serving.tp.degree": (
        "gauge", "", "tensor-parallel shard count of the serving engine "
        "(FLAGS_serving_tensor_parallel; 1 = single-device step).  The "
        "whole fused step is shard_map-sharded over the 'mp' mesh axis "
        "— attention by kv-head, grouped MoE by expert — with outputs "
        "bit-identical to tp=1"),
    "serving.tp.shard_pool_bytes": (
        "gauge", "", "per-shard KV page-pool bytes (host-global pool "
        "bytes / tp): each shard stores only its kv heads' page planes "
        "and int8 scale rows"),
    # ---- serving: speculative decoding (PR 9) ----
    "serving.spec.drafted_tokens": (
        "counter", "", "draft tokens dispatched for verification"),
    "serving.spec.accepted_tokens": (
        "counter", "", "draft tokens accepted by the verifier"),
    "serving.spec.rejected_tokens": (
        "counter", "", "draft tokens rolled back"),
    "serving.spec.accept_len": (
        "histogram", "", "tokens committed per speculative dispatch "
        "beyond the first"),
    # ---- serving: HTTP front door (PR 6) ----
    "serving.http.requests": ("counter", "", "HTTP requests accepted"),
    "serving.http.streams": ("counter", "", "streaming completions"),
    "serving.http.responses": (
        "counter", "code=...", "responses by status code"),
    "serving.http.inflight": ("gauge", "", "open HTTP requests"),
    "serving.http.request_ms": (
        "histogram", "", "HTTP request wall time"),
    "serving.http.slo_decision": (
        "counter", "decision=admit|queue|shed", "SLO-burn admission "
        "decisions"),
    "serving.http.shed": (
        "counter", "", "requests shed with 503 + Retry-After"),
    "serving.http.queue_expired": (
        "counter", "", "requests retired from the engine inbox past "
        "FLAGS_serving_queue_timeout_s BEFORE dispatch (ISSUE 15: "
        "zero prefill spent on a client that already gave up; unary = "
        "504, stream = finish_reason queue_expired)"),
    # ---- router fleet plane (PR 7) ----
    "router.requests": ("counter", "", "router requests accepted"),
    "router.streams": ("counter", "", "router streaming completions"),
    "router.responses": (
        "counter", "code=...", "router responses by status code"),
    "router.inflight": ("gauge", "", "open router requests"),
    "router.request_ms": ("histogram", "", "router request wall time"),
    "router.placement": (
        "counter", "reason=affinity|prefix|load|round_robin",
        "placement decisions by reason"),
    "router.prefix_hit_pages": (
        "histogram", "", "expected prefix-hit depth of scored "
        "placements"),
    "router.session_pins": ("gauge", "", "live session-affinity pins"),
    "router.session_evictions": (
        "counter", "", "LRU-evicted session pins"),
    "router.failover": (
        "counter", "phase=connect|stream", "requests that hit a dead "
        "replica"),
    "router.slo_decision": (
        "counter", "decision=admit|shed|unavailable|breaker",
        "fleet admission decisions (breaker = shed because the cascade "
        "breaker is open, ISSUE 15)"),
    "router.shed": ("counter", "", "fleet-wide sheds"),
    "router.health_polls": (
        "counter", "result=ok|fail", "replica /statusz polls"),
    "router.replicas": (
        "gauge", "state=ready|warming|suspect|dead|draining",
        "replica count by health state"),
    "router.replica_rejoins": (
        "counter", "", "dead/suspect -> live replica transitions (each "
        "also lands as a router.replica_rejoin tracer instant; the "
        "rejoined replica's routed-overlay staleness is reset)"),
    # ---- router: failover resume + digest delta sync (ISSUE 14) ----
    "router.resumes": (
        "counter",
        "outcome=resumed|unary|handoff|finished|ineligible|exhausted",
        "journaled failover-resume outcomes: resumed = a dead stream "
        "continued on a survivor (unbroken client stream), unary = a "
        "post-dispatch unary death re-ran, handoff = a disaggregated "
        "prefill->decode splice completed (ISSUE 16), finished = only "
        "the finish frame was lost, ineligible = replay impossible "
        "(PR 7 synthesized-error/502 contract applied), exhausted = "
        "replay attempted but no survivor could finish it"),
    "router.journal_entries": (
        "gauge", "", "in-flight requests tracked by the replay journal"),
    "router.journal_evictions": (
        "counter", "", "journal entries LRU-evicted past "
        "FLAGS_router_journal_cap (their streams fall back to the "
        "synthesized-error contract)"),
    "router.digest_sync": (
        "counter", "mode=full|delta|sketch", "prefix-digest syncs by "
        "mode: delta = only adds/evictions since the confirmed epoch "
        "rode the poll; full = complete set re-ship (first poll, "
        "replica restart, or change-log miss); sketch = a counting-"
        "Bloom membership bitmap replaced the exact set (ISSUE 19: the "
        "cache grew past FLAGS_router_digest_sketch_threshold — "
        "expected_hit_tokens becomes a bounded estimate, per-poll "
        "digest bytes stay flat)"),
    # ---- poison quarantine (ISSUE 15) ----
    "router.quarantine": (
        "counter", "action=strike|quarantined|refused",
        "poison-request crash attribution (router/quarantine.py): "
        "strike = a journaled request was in flight on a dying "
        "replica, quarantined = a signature struck out "
        "(FLAGS_router_poison_strikes deaths with no relayed token in "
        "between), refused = a quarantined signature's submit/replay "
        "answered 503 instead of another corpse"),
    "router.quarantine_entries": (
        "gauge", "", "request signatures currently tracked by the "
        "quarantine (strikes + quarantined; TTL-bounded, capped at "
        "FLAGS_router_quarantine_cap, swept every "
        "FLAGS_router_quarantine_sweep_s on the read verbs)"),
    # ---- router: disaggregated prefill/decode serving (ISSUE 16) ----
    "router.handoff": (
        "counter", "outcome=ok|export_failed|import_failed|no_successor",
        "prefill->decode KV handoffs (router/server.py): ok = the "
        "finished prefix shipped to a decode successor and the stream "
        "spliced, export_failed / import_failed = the migration plane "
        "refused (the stream re-prefills on a fallback replica "
        "instead — never dropped), no_successor = no replay-exact "
        "peer was placeable"),
    "router.overlay_entries": (
        "gauge", "", "routed-overlay credits across all replica views "
        "(optimistic digest entries awaiting /statusz confirmation)"),
    "router.overlay_evictions": (
        "counter", "", "overlay credits LRU-evicted past "
        "FLAGS_router_overlay_cap (bounds the per-replica credit map "
        "on long-running routers)"),
    # ---- fleet lifecycle supervisor (PR 12) ----
    "fleet.replicas": (
        "gauge", "state=starting|ready|draining|backoff|failed",
        "supervised replica slots by lifecycle state "
        "(fleet/supervisor.py; failed = restart budget exhausted, "
        "permanently down)"),
    "fleet.target_replicas": (
        "gauge", "", "the autoscaler's current fleet-size target"),
    "fleet.replica_restarts": (
        "counter", "", "crash-restarts performed (after exponential "
        "backoff, within FLAGS_fleet_restart_budget)"),
    "fleet.crashes": (
        "counter", "kind=exit|wedged|router",
        "deaths detected: process/engine exit, a wedge (the router "
        "reports it dead while the process is still alive — the "
        "SIGSTOP shape; the supervisor kills and restarts it), or a "
        "supervised ROUTER slot death (ISSUE 19: restarted through "
        "the same backoff/budget, but never fed to the cascade "
        "breaker — a router death is a ring failover, not lost "
        "serving capacity)"),
    "fleet.scale_events": (
        "counter", "direction=up|down",
        "autoscale actions taken after hysteresis + cooldown"),
    "fleet.drains": (
        "counter", "outcome=clean|timeout|died",
        "graceful drains: clean (in-flight finished inside "
        "FLAGS_fleet_drain_timeout_s), timeout (bound expired, "
        "hard-killed), died (replica crashed mid-drain)"),
    "fleet.migrations": (
        "counter", "outcome=ok|skipped|failed",
        "drain-triggered session migrations (ISSUE 14): ok = the "
        "victim's live sessions shipped to the chosen successor, "
        "skipped = nothing to ship / no successor / transport without "
        "a migration path, failed = the transfer died mid-flight "
        "(best-effort: never blocks the drain)"),
    "fleet.migrated_pages": (
        "counter", "", "KV pages installed on successors by "
        "drain-triggered migrations"),
    # ---- fleet: role-specialized replicas (ISSUE 16) ----
    "fleet.role": (
        "gauge", "role=prefill|decode|mixed",
        "non-failed supervised slots by serving role "
        "(FLAGS_fleet_roles; a plain fleet is all-mixed)"),
    "fleet.rebalances": (
        "counter", "outcome=ok|skipped|failed",
        "proactive session rebalances (ISSUE 16): an SLO-burning "
        "replica's resident sessions pre-staged on an admitting "
        "same-role-or-mixed peer BEFORE the shed, their router pins "
        "re-pointed; in-flight streams finish out on the source"),
    # ---- sharded control plane (ISSUE 19) ----
    "router.forwarded": (
        "counter", "outcome=out|received|fallback",
        "consistent-hash ownership forwards (router/server.py): out = "
        "this router relayed a session it doesn't own one hop to its "
        "ring owner, received = it served a request forwarded to it "
        "(the X-Router-Forwarded loop guard: never re-forwarded), "
        "fallback = the owner was unreachable so the request was "
        "served locally instead of dropped"),
    "router.ring_moves": (
        "counter", "", "consistent-hash ring rebuilds observed by this "
        "router (a membership change: a router joined, or one's "
        "heartbeat expired and its session span moved to survivors)"),
    "fleet.router_restarts": (
        "counter", "", "supervised router-slot crash-restarts (after "
        "exponential backoff, within FLAGS_fleet_restart_budget)"),
    "controlplane.routers": (
        "gauge", "", "non-failed supervised router slots "
        "(fleet/supervisor.py; the in-process rt0 is not a slot)"),
    "controlplane.store_ops": (
        "counter", "op=set|get|cas|del|hb|members",
        "membership-store operations served, by protocol verb "
        "(controlplane/store.py)"),
    "controlplane.store_keys": (
        "gauge", "", "keys resident in the membership store (TTL-swept "
        "on writes and membership reads, LRU-capped at "
        "FLAGS_controlplane_store_max_keys)"),
    "controlplane.store_evictions": (
        "counter", "", "store keys LRU-evicted past "
        "FLAGS_controlplane_store_max_keys"),
    "controlplane.members": (
        "gauge", "", "live routers on the consistent-hash ring as seen "
        "by this router (unexpired router/ heartbeats, self included)"),
    "controlplane.ring_epoch": (
        "gauge", "", "epoch of the shared cp/ring record (CAS-bumped "
        "once per membership change; every router converges to the "
        "winner's epoch)"),
    "controlplane.heartbeats": (
        "counter", "", "liveness stamps written to the store "
        "(TTL FLAGS_controlplane_heartbeat_ttl_s; expiry IS the death "
        "signal)"),
    "controlplane.journal_replicated": (
        "counter", "", "in-flight journal records mirrored to the "
        "store under journal/<session_id> (TTL "
        "FLAGS_controlplane_journal_ttl_s) so a session's NEXT owner "
        "can resume its stream after this router dies"),
    "controlplane.takeovers": (
        "counter", "outcome=resumed|stale|failed",
        "cross-router journal adoptions after a membership change: "
        "resumed = the new owner replayed the dead router's journal "
        "and continued the stream bit-identically, stale = the store "
        "record didn't match the incoming request (different prompt / "
        "own record / nothing emitted), failed = adoption began but "
        "the replay could not complete"),
    "fleet.breaker_state": (
        "gauge", "", "cascade-breaker state (fleet/breaker.py, ISSUE "
        "15): 0=closed, 1=half-open (one parked resume probing), "
        "2=open (resumes park, router admissions shed, restarts "
        "continue); every transition also lands as a fleet.breaker "
        "tracer instant and CLOSED->OPEN dumps the flight recorder"),
    # ---- regression sentinel (PR 10) ----
    "observability.anomaly": (
        "counter", "series=...,kind=drift|burst",
        "sentinel anomalies by watched series and detector kind "
        "(observability/sentinel.py; each also lands as a tracer "
        "instant event and a rate-limited flight-recorder dump)"),
    # ---- distributed tracing (ISSUE 20) ----
    "serving.trace.critical_path_ms": (
        "histogram", "phase=queue|prefill|transfer|decode|replay",
        "per-request critical-path breakdown computed at timeline "
        "assembly (observability/collector.py): an interval sweep over "
        "the clock-aligned spans where gaps ride the ongoing phase, so "
        "the phases sum exactly to the trace extent — what the client "
        "measured as TTFT + stream time"),
    "observability.collector.export_batches": (
        "counter", "", "span batches shipped by this process's "
        "SpanExporter (store set / HTTP POST / in-proc ingest)"),
    "observability.collector.export_spans": (
        "counter", "", "span events shipped in export batches"),
    "observability.collector.export_dropped": (
        "counter", "", "span events evicted from the bounded export "
        "ring before a flush could ship them "
        "(FLAGS_trace_export_events)"),
    "observability.collector.sampled_out": (
        "counter", "", "span events skipped by head sampling "
        "(FLAGS_trace_sample_rate; tail-kept anomaly/handoff/failover "
        "lanes ship regardless)"),
    "observability.collector.export_errors": (
        "counter", "", "export batch sends that raised (transport "
        "down; the batch is dropped, serving is never blocked)"),
    "observability.collector.clock_resyncs": (
        "counter", "", "clock-offset re-estimations adopted because "
        "the midpoint drifted past FLAGS_trace_clock_drift_ms beyond "
        "the handshake's rtt/2 uncertainty"),
    "observability.collector.batches": (
        "counter", "", "export batches ingested by the collector"),
    "observability.collector.spans": (
        "counter", "", "span events ingested by the collector"),
    "observability.collector.traces": (
        "gauge", "", "distinct trace ids currently held in the "
        "collector's bounded span store (LRU past max_traces)"),
    "observability.collector.processes": (
        "gauge", "", "exporting processes the collector has seen "
        "(each with its own clock-offset estimate)"),
    "observability.collector.fleet_dumps": (
        "counter", "", "fleet-correlated anomaly dumps written (every "
        "registered flight-recorder ring plus the collector's aligned "
        "spans for the anomalous window, merged into ONE file)"),
    # ---- train loop (PR 5 StepTimer, default name) ----
    "train.steps": ("counter", "", "train steps dispatched"),
    "train.step_ms": (
        "histogram", "", "warm train-step wall time (compile-bearing "
        "steps excluded)"),
    "train.tokens_per_sec": (
        "gauge", "", "throughput of the last warm train step"),
    "train.recompiles": (
        "counter", "", "XLA backend compiles attributed to train steps"),
    "train.grad_comm_bytes": (
        "counter", "", "analytic gradient-sync traffic"),
    "train.collectives": (
        "gauge", "", "all-reduces in the compiled train step (scheduled "
        "HLO, read once the step program is built: "
        "`PretrainStep.count_collectives`)"),
    "train.collectives_async": (
        "gauge", "", "of those, the asynchronous ones: start/done pairs "
        "that run under other work (several TPU chips: "
        "`models/pretrain.py::_ASYNC_SUMS`); the rest stop the core"),
    # ---- kernels (PR 21) ----
    "kernels.reference_fallbacks": (
        "counter", "kernel=flash_attention",
        "traces on a TPU backend in which a Pallas kernel gave way to its "
        "XLA reference (kernels/flash_attention.py logs the rule that "
        "failed, once per shape); chip_smoke.py asserts zero on its paths"),
    # ---- compile telemetry (PR 2/5) ----
    "jit.backend_compiles": (
        "counter", "", "process-wide XLA backend compiles"),
    "jit.backend_compile_ms": (
        "histogram", "", "XLA backend compile durations"),
    "jit.to_static_compiles": (
        "counter", "", "to_static guard-cache compiles"),
    "jit.to_static_evictions": (
        "counter", "", "to_static guard-cache LRU evictions"),
    "jit.to_static_bucket_pads": (
        "counter", "", "to_static bucket-padding events"),
    # ---- observability runtime guards (PR 5/6) ----
    "host.device_syncs": (
        "counter", "", "marked intentional host<->device syncs "
        "(count_sync; assert_overhead bounds these)"),
    "metrics.dropped_series": (
        "counter", "", "label sets folded into {series=__overflow__} by "
        "the FLAGS_metrics_max_series cardinality guard"),
    "tracing.dropped_events": (
        "counter", "", "trace events dropped at the "
        "FLAGS_trace_max_events cap"),
    "flight_recorder.dumps": (
        "counter", "", "flight-recorder dump files written"),
    "flight_recorder.suppressed_dumps": (
        "counter", "", "dumps swallowed by the per-reason rate limit "
        "(FLAGS_flight_recorder_min_interval_s)"),
    # ---- profiler frontend (PR 5) ----
    "profiler.host_events_ms": (
        "histogram", "event=...,type=...", "RecordEvent span durations"),
    # ---- collective watchdog (PR 5) ----
    "watchdog.timeouts": ("counter", "", "watchdog timeout fires"),
    "watchdog.outstanding_tasks": (
        "gauge", "", "collectives currently in flight"),
    "watchdog.last_heartbeat_age_s": (
        "gauge", "", "seconds since the last collective completed"),
}


# live span -> (category, lane, sinks, arguments, where it is recorded).
# Every span is in the profiler's trace while a session runs.  ``sinks``
# says how far it goes in the tracer's own: "fleet" = ring, Chrome buffer
# and the fleet exporter (the two whole-step spans only: what the
# collector gets of the ``engine`` and ``train`` lanes must not grow with
# the phases); "local" = ring and Chrome buffer; "profiler" = neither (the
# serving loop's own spans recur at the poll rate while a server idles and
# would flush the flight recorder's ring).  Arguments are values the site
# already has at that boundary: a span costs no read of an array or of
# the device.
SPANS: Dict[str, tuple] = {
    "engine.step": (
        "serving", "engine", "fleet",
        "step, kind=decode|mixed|spec|idle, T, rows, q_tokens, gemm_rows, "
        "kv_read_tokens, attn_rows, page_copies, index_pairs, "
        "selected_keys, ssm_slots, ssm_tokens, slots, waiting",
        "one `ContinuousBatchingEngine.step` call, whole: `step` its "
        "running number, `T` the program's query bucket (K in the "
        "speculative lane, 0 when nothing was dispatched), `rows` the "
        "slots with work, `q_tokens` the query tokens they hold (in the "
        "speculative lane rows x K, what the dispatch may verify), "
        "`gemm_rows` the rows the dispatched program's per-token GEMMs "
        "run over (the smallest row bucket that holds `q_tokens`; "
        "slots x T for a dense dispatch, slots x K in the speculative "
        "lane, 0 when idle), so `q_tokens / gemm_rows` is the occupancy "
        "those GEMMs see, `kv_read_tokens` the key tokens the step's "
        "attention reads, summed over the layers with each layer's "
        "window applied (the host knows every row's context and query "
        "length), `attn_rows` the query rows the paged kernel's row tiles "
        "cover for each KV head in one layer's call (every slot with work "
        "covers its `q_len x group` rows in whole tiles; "
        "`kernels.paged_geometry.attn_rows`: tiles of at most 256 rows in "
        "the per-head kernel (`row_tile`); a latent stack's call has one "
        "row of keys for all heads, so `group` is the number of query "
        "heads, a slot covers `q_len x heads` rows, and the tile is the "
        "latent call's own (`latent_row_tile`: at most 256 rows, one a "
        "step of the tile loop, in the dense call; whole query tokens in "
        "at most 128 rows, two a step, in the sparse call), so `q_tokens x "
        "group / attn_rows` is to attention what `q_tokens / gemm_rows` "
        "is to the GEMMs, `page_copies` the DMA descriptors one layer's "
        "paged call starts (every slot with work fetches whole blocks of "
        "pages, one copy a page of `serving.kv_copy_bytes`: working slots "
        "x the blocks their walk reaches x the pages of a block; "
        "`kernels.paged_geometry.page_copies`; for a layer that sees the "
        "whole context where the stack has one; three copies a page in a "
        "latent stack's call), `index_pairs` and `selected_keys` (a stack "
        "with a learned index only) the pairs (query token, key) the index "
        "scores and the keys it chooses in ONE layer of this step (`q x "
        "ctx + q (q + 1) / 2` a working slot; `min(position + 1, top_k)` "
        "a query token), `ssm_slots` and `ssm_tokens` (a stack whose "
        "slots hold a recurrent state only: a state-space mixer's or a "
        "linear-attention place's) the slots whose recurrent state each "
        "such layer's call reads and writes in this step (those with "
        "work) and the tokens they scan, `slots` the batch B, `waiting` "
        "the queue behind it"),
    "engine.admit": (
        "serving", "engine", "local", "admitted, waiting",
        "`_admit`: waiting requests into free slots, their pages and the "
        "uploads of the new rows' state"),
    "engine.grow": (
        "serving", "engine", "local", "pages",
        "the page-growth loop before a dispatch (`pages` allocated)"),
    "engine.build": (
        "serving", "engine", "local", "",
        "the per-row loop that fills the step's `ql`, `decode`, `commit` "
        "and `chunk`"),
    "engine.h2d": (
        "serving", "engine", "local", "arrays",
        "host-to-device uploads of the step's inputs and the `tokens_in` "
        "composition; the block table (and the speculative lane's write "
        "caps) when they changed"),
    "engine.dispatch": (
        "serving", "engine", "local", "program",
        "the call of a jitted program: `serve_step_T<bucket>`, "
        "`serve_spec_verify_K<k>`, `pool_cow_copy`"),
    "engine.drain": (
        "serving", "engine", "local",
        "steps, in_flight, blocked, tokens, held_rows",
        "`_gather`: `steps` dispatches gathered (those that had landed; "
        "more only where it had to wait), `in_flight` left out, "
        "`blocked` why it waited for the device (`bound`, `idle`, "
        "`settle`; empty: it did not), `tokens` delivered to their "
        "requests, `held_rows` the (token, choice) entries of those "
        "steps that fell on experts held here (0 where the step does not "
        "count them: a dense model, the dense mixture with every expert "
        "held, the tensor-parallel grouped arm)"),
    "engine.drain.wait": (
        "serving", "engine", "local", "",
        "the gathered steps' arrays to numpy: the only place the host "
        "waits for the device, and only in a gather that is `blocked` "
        "(the copies started at dispatch)"),
    "engine.drain.retire": (
        "serving", "engine", "local", "retired",
        "the per-row bookkeeping after the wait (`retired` requests "
        "finished)"),
    "serve.intake": (
        "serving", "engine", "profiler", "",
        "`ServingServer._engine_loop`: the inbox sweep, control "
        "operations and queue-expiry shedding"),
    "serve.publish": (
        "serving", "engine", "local", "tokens, streams",
        "`_publish`: fresh tokens pushed to their streams"),
    "serve.idle": (
        "serving", "engine", "profiler", "",
        "the engine thread waiting for work (`_wake.wait`)"),
    "serve.housekeeping": (
        "serving", "engine", "profiler", "",
        "flight-recorder snapshot and sentinel check between steps"),
    "train.step": (
        "train", "train", "fleet", "tokens",
        "one `PretrainStep.train_step` call, whole"),
    "train.shard_batch": (
        "train", "train", "local", "",
        "placing a host batch on the mesh (only when the caller passed "
        "host arrays)"),
    "train.dispatch": (
        "train", "train", "local", "",
        "the call of the jitted `pretrain_step` program"),
    # ---- set-up by phase (PR 36): `observability/startup.py` opens each
    # as a span AND, until the log is sealed at ready, keeps it as a record
    # on the process's own age (`/statusz`'s `startup` block) ----
    "startup.import": (
        "startup", "startup", "local", "began_age_s",
        "`paddle_tpu/__init__.py`, top to bottom (jax's own import when "
        "nothing imported it before).  In the log only, recorded after the "
        "fact from two clock readings: no tracer exists at the top.  "
        "`began_age_s` the process's age when the import began: the "
        "interpreter's start and whatever the caller imported first"),
    "startup.model_init": (
        "startup", "startup", "local", "family, layers, params",
        "the constructor of `LlamaForCausalLM`, `CohereMoeForCausalLM`, "
        "`SarvamMlaForCausalLM` or `FalconH1ForCausalLM`: the random "
        "initialisation, leaf by leaf, that a launcher's checkpoint or a "
        "caller's own weights then replace (`params` the parameters it "
        "made)"),
    "startup.engine_build": (
        "startup", "startup", "local", "slots, pages, pool_bytes",
        "`ContinuousBatchingEngine.__init__`, whole; holds "
        "`startup.stack_params`, `startup.pool_alloc` and the pool "
        "programs' first calls"),
    "startup.stack_params": (
        "startup", "startup", "local", "",
        "`model.serving_params()` in `LlamaGenerator.__init__`: the "
        "engine's stacked copy of the weights (dispatch: the device's "
        "part ends in whichever phase first waits for it)"),
    "startup.pool_alloc": (
        "startup", "startup", "local", "pages",
        "the paged pool, the latent pool or the recurrent state beside it "
        "(`PagedKVCache`, `RecurrentState`)"),
    "startup.train_build": (
        "startup", "startup", "local", "dp, mp, layers",
        "`PretrainStep.__init__`: the mesh and the template layer"),
    "startup.program": (
        "startup", "startup", "local",
        "program, T, rows, cache_hit, compile_s|cache_read_s",
        "one jitted program made ready: a member of a step family in "
        "`_step_family` (`startup.lower` and `startup.compile` inside), or "
        "the FIRST call of a program compiled by its call (`PretrainStep`'s "
        "step, the speculative lanes' programs, `pool_cow_copy`, "
        "`pool_swap_in`).  `program` the `jit_<name>` a trace shows, `T` "
        "and `rows` its query bucket and GEMM rows (a train step: sequence "
        "length and tokens), `cache_hit` whether the persistent cache held "
        "every module it compiled, with the cache's `cache_read_s` or the "
        "backend's `compile_s`"),
    "startup.lower": (
        "startup", "startup", "local", "",
        "`lowered_step(T, rows)`: tracing and lowering, Python, paid on "
        "every start whatever the cache holds"),
    "startup.compile": (
        "startup", "startup", "local",
        "cache_hit, compile_s|cache_read_s",
        "`Lowered.compile()`: the backend's compile or the persistent "
        "cache's read, and the load"),
    "startup.warm": (
        "startup", "startup", "local", "",
        "`ServingServer._warm`: one junk request through both step "
        "families on the engine thread, before `/readyz` flips"),
}


def undocumented(families: Optional[Dict[str, str]] = None) -> list:
    """Families present in the registry but missing from the catalog.
    ``train.*``-shaped StepTimer families with custom names are the
    caller's to exclude (tests use throwaway ``t9...`` names)."""
    if families is None:
        families = _metrics.REGISTRY.families()
    return sorted(n for n in families if n not in CATALOG)


def apply_help() -> None:
    """Attach every catalog entry's meaning as the family's Prometheus
    ``# HELP`` text."""
    for name, (_kind, _labels, help_text) in CATALOG.items():
        _metrics.REGISTRY.set_help(name, help_text)


def generate_markdown() -> str:
    """Render docs/metrics.md from the catalog (grouped by family
    prefix), byte-for-byte reproducible so the drift test can compare."""
    groups: Dict[str, list] = {}
    for name, (kind, labels, help_text) in CATALOG.items():
        groups.setdefault(name.split(".", 1)[0], []).append(
            (name, kind, labels, help_text))
    lines = [
        "# Metric series catalog",
        "",
        "Every registry family `paddle_tpu` emits, generated from",
        "`paddle_tpu/observability/catalog.py`",
        "(`python -m paddle_tpu.observability.catalog` rewrites this",
        "file; a tier-1 drift test keeps it honest).  Scrape them live",
        "from a serving replica's `/metrics` (strict Prometheus text,",
        "dots sanitized to underscores) or grab the JSON snapshot",
        "stamped into every bench result under `\"metrics\"`.",
        "",
        "`train.*` rows describe the default `StepTimer(\"train\")`;",
        "a custom timer name replaces the prefix.",
    ]
    for prefix in sorted(groups):
        lines += ["", f"## `{prefix}.*`", "",
                  "| series | kind | labels | meaning |",
                  "|---|---|---|---|"]
        for name, kind, labels, help_text in groups[prefix]:
            lbl = f"`{labels}`" if labels else "—"
            lines.append(f"| `{name}` | {kind} | {lbl} | {help_text} |")
    lines += [
        "", "## Live spans", "",
        "The closed vocabulary of `Tracer.span` (`SPANS` in the same",
        "file).  Each is a `jax.profiler.TraceAnnotation`, so a profiler",
        "session finds it in the `.xplane.pb` beside the device",
        "operations, with its arguments as the event's stats; with the",
        "tracer's own sinks on it is also a Chrome event on its lane.",
        "`sinks`: `fleet` = flight-recorder ring, Chrome buffer and the",
        "fleet exporter; `local` = ring and buffer; `profiler` = the",
        "profiler's trace only.",
        "", "| span | lane | sinks | arguments | where |",
        "|---|---|---|---|---|"]
    for name, (_cat, lane, sinks, args, where) in SPANS.items():
        lines.append(f"| `{name}` | {lane} | {sinks} "
                     f"| {'`' + args + '`' if args else '—'} | {where} |")
    lines += [
        "", "## Time to ready by phase", "",
        "`GET /statusz` of a replica carries a `startup` block: `records`,",
        "one for each `startup.*` span that opened before the replica was",
        "ready (`name`, `args`, `start_age_s`, `dur_s`, `thread`, `depth`,",
        "`jit`), `sealed` (true once `/readyz` has flipped: nothing is",
        "appended afterwards), `ready_age_s` and `overflow` (records beyond",
        "the list's 256, counted and not kept).  `start_age_s` and",
        "`ready_age_s` are seconds since the PROCESS started, so the gap",
        "before the first record is the interpreter, jax and the launcher's",
        "own work.  Read a slow start from the top: `startup.import`, then",
        "`startup.model_init` (a random initialisation a checkpoint",
        "replaces), `startup.engine_build` with the stacked weights and",
        "the pool inside it, then under `startup.warm` one `startup.program`",
        "a step program, each split into `startup.lower` (Python, paid on",
        "every start) and `startup.compile`, whose `cache_hit` says whether",
        "the persistent cache held the program (`cache_read_s`) or the",
        "backend compiled it (`compile_s`); a record whose `dur_s` is null",
        "is still open, which is where a start that hangs is.  `jit` counts",
        "what jax reported while that phase was the innermost open one on",
        "its thread: `trace_n/_s`, `lower_n/_s`, `compile_n/_s` (every",
        "backend compile, the cache's read inside it) and `cache_read_n/_s`;",
        "a trace inside a trace is counted twice, so the seconds are a",
        "guide and the phases' own `dur_s` the measure."]
    return "\n".join(lines) + "\n"


def main() -> int:
    import pathlib
    out = pathlib.Path(__file__).resolve().parents[2] / "docs/metrics.md"
    out.write_text(generate_markdown())
    print(f"wrote {out} ({len(CATALOG)} families, {len(SPANS)} spans)")
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
