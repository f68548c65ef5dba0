"""Closed loop, as an offline batch job drives the engine: as many clients
as the engine has slots, each handing in its next document (from the mix's
fixed list) when its last is done, through ``engine.submit`` and
``engine.step`` directly.  No HTTP front end, so no SLO controller stands
in the path and nothing can be shed: an offline job has no latency limit.

The window closes at the first drain (the engine's own host-device sync)
at or after ``--seconds``; the rate is all the tokens worked through up to
that drain (prompt tokens prefilled + tokens generated, of finished and
unfinished documents alike) over the time it took."""

from __future__ import annotations

import importlib
import time

from chipbench.harness import registry, schedule, serving
from chipbench.harness.checks import emit
from chipbench.harness.core import process_age_s

REGISTRY_SERIES = ("serving.batch_occupancy",)


def _progress(eng, done_tokens: int) -> int:
    """Tokens worked through so far: finished documents plus, for those in
    a slot, the prompt tokens consumed and the tokens drained."""
    n = done_tokens
    for b, req in enumerate(eng.slot_req):
        if req is not None:
            n += int(eng.prompt_pos[b]) + len(req.output)
    return n


def run(r) -> None:
    prog = importlib.import_module(
        "chipbench.programs." + r.cell.config["family"])
    m, t = r.model, r.traffic
    docs = schedule.requests(t)
    emit(phase="schedule", **schedule.describe(t, r.seconds))
    eng, kw = prog.build_engine(m, t["engine"], r.seed)
    step_log = [] if r.args.trace else None
    serving.annotate_steps(eng, step_log)
    held = {"eng": eng}
    emit(phase="built", age_s=process_age_s(), engine={
        k: v for k, v in kw.items() if k != "gen"}, slo=None)
    vocab = m["vocab_size"]
    clients = int(t["clients"])

    def submit(it):
        ids = schedule.token_ids(r.seed, it.index, it.prompt_len, vocab)
        return eng.submit(ids, max_new_tokens=it.output_len)

    # warm-up: the two step programs on the schedule's shortest document
    warm = min(docs, key=lambda it: it.prompt_len)
    req = eng.submit(schedule.token_ids(r.seed, warm.index, warm.prompt_len,
                                        vocab), max_new_tokens=4)
    while not req.done:
        eng.step()
    eng.step()
    r.ready()

    series = registry.series_of(r.cell, REGISTRY_SERIES)
    before = {s: registry.snap(s) for s in series}
    r.watch.start()
    live, finished, nxt, done_tokens = {}, [], 0, 0
    drain_count = registry.counter("serving.drains")
    t0 = time.perf_counter()
    for _ in range(clients):
        rq = submit(docs[nxt])
        live[id(rq)] = (docs[nxt], rq)
        nxt += 1
    steps = 0
    closed = None
    while closed is None:
        d0 = drain_count.value
        retired = eng.step()
        steps += 1
        now = time.perf_counter()
        r.tracer.tick(now - t0)
        for rq in retired:
            it, _ = live.pop(id(rq))
            finished.append((it, list(rq.output), now - t0))
            done_tokens += it.prompt_len + len(rq.output)
            if nxt >= len(docs):
                raise RuntimeError(
                    f"the mix's {len(docs)} documents ran out before the "
                    "window closed: lengthen its list")
            new = submit(docs[nxt])
            live[id(new)] = (docs[nxt], new)
            nxt += 1
        if now - t0 >= r.seconds and drain_count.value > d0:
            closed = now
    window_s = closed - t0
    tokens = _progress(eng, done_tokens)
    r.note_compiles(t0)
    r.results["registry"] = {s: registry.delta(before[s], registry.snap(s))
                             for s in series}
    r.tracer.finish()
    r.note_memory()
    r.attempted = len(finished) + len(live)
    r.failed = sum(1 for it, toks, _ in finished
                   if len(toks) != it.output_len)
    r.results["step_log"] = step_log
    r.results["window"] = {
        "window_s": window_s, "steps": steps, "tokens": tokens,
        "documents_finished": len(finished), "in_flight": len(live),
        "documents_handed_in": nxt}
    emit(phase="window", **r.results["window"])
    r.results["end_to_end"] = {"serve_total_tok_s": tokens / window_s}
    del eng
    emit(phase="freed", bytes_in_use=serving.free_engine(held))
    ck = r.checks
    ck.add("compiles_in_window", r.results["compiles_in_window"], 0)
    ck.add("requests_failed", r.failed, 0)
    good = [(it, toks) for it, toks, _ in finished
            if len(toks) == it.output_len]
    if good:
        serving.check_served(r, good, vocab)
    else:
        ck.fail("documents_finished", "no document finished in the window")
