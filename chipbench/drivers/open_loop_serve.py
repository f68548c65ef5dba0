"""Open loop through the serving front end: the server built as the
launcher builds it (default SLO controller, warm-up), requests sent on the
mix's fixed schedule over real sockets, streamed, timed at the client.
Every request that falls due inside the window runs to its end; a request
that fails or is shed counts in ``failed``."""

from __future__ import annotations

import asyncio
import importlib
import time

from chipbench.harness import client, registry, schedule, serving
from chipbench.harness.checks import emit
from chipbench.harness.core import process_age_s

REGISTRY_SERIES = ("serving.queue_wait_ms", "serving.batch_occupancy")


def run(r) -> None:
    prog = importlib.import_module(
        "chipbench.programs." + r.cell.config["family"])
    m, t = r.model, r.traffic
    items = schedule.in_window(schedule.requests(t), r.seconds)
    emit(phase="schedule", **schedule.describe(t, r.seconds))
    eng, kw = prog.build_engine(m, t["engine"], r.seed)
    step_log = [] if r.args.trace else None
    serving.annotate_steps(eng, step_log)
    srv = prog.build_server(eng, r.cell.config_name)
    held = {"eng": eng, "srv": srv}
    emit(phase="built", age_s=process_age_s(), engine={
        k: v for k, v in kw.items() if k != "gen"},
        slo=None if srv.slo is None else "launcher default")
    vocab = m["vocab_size"]
    prompts = {it.index: schedule.token_ids(r.seed, it.index, it.prompt_len,
                                            vocab) for it in items}

    async def main():
        host, port = await srv.start_http("127.0.0.1", 0)
        while not srv.ready():
            if not srv.engine_alive():
                raise RuntimeError("engine thread died during warm-up")
            await asyncio.sleep(0.02)
        try:
            # replay the schedule's first shapes: a short and a long prompt
            # through the real path, so both step programs, the sockets and
            # the registry are warm before the window opens
            warm = sorted(items, key=lambda it: it.prompt_len)
            warm = [warm[0], warm[-1]] if len(warm) > 1 else warm
            t0 = time.perf_counter()
            await client.open_loop(
                host, port,
                [schedule.Item(it.index, 0.0, it.prompt_len, 8)
                 for it in warm], prompts, t0)
            if srv.slo is not None:
                srv.slo.forget()       # as after the server's own warm-up
            r.ready()
            series = registry.series_of(r.cell, REGISTRY_SERIES)
            before = {s: registry.snap(s) for s in series}
            r.watch.start()
            t0 = time.perf_counter()
            streams = await client.open_loop(host, port, items, prompts, t0,
                                             on_tick=r.tracer.tick)
            r.note_compiles(t0)
            r.results["registry"] = {
                s: registry.delta(before[s], registry.snap(s))
                for s in series}
            r.results["slo"] = None if srv.slo is None else srv.slo.state()
            return streams, t0
        finally:
            await srv.stop_http()
            srv.close()

    streams, t0 = asyncio.run(main())
    r.tracer.finish()
    r.note_memory()
    summary = client.summarize(streams, t0, r.seconds)
    emit(phase="client", **summary)
    r.results["client"] = summary
    r.results["step_log"] = step_log
    r.attempted = len(streams)
    r.failed = summary["failed"]
    r.results["end_to_end"] = {
        "itl_p99_ms": summary.get("gap_ms", {}).get("p99")}
    finished = [(it, s.tokens) for it, s in zip(items, streams)
                if s.ok and len(s.tokens) == it.output_len]
    del eng, srv
    emit(phase="freed", bytes_in_use=serving.free_engine(held))
    ck = r.checks
    ck.add("compiles_in_window", r.results["compiles_in_window"], 0)
    ck.add("requests_failed", r.failed, 0)
    ck.add("streams_complete", len(finished), len(streams), "==")
    if finished:
        serving.check_served(r, finished, vocab)
