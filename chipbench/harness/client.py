"""The open-loop client: streamed ``/v1/completions`` requests sent on a
schedule from ONE thread (an asyncio loop), each token stamped when its
bytes reach the client.  Times are taken from when a request was DUE, so a
stall charges the requests queued behind it; how late the generator ran is
reported beside them."""

from __future__ import annotations

import asyncio
import json
import time
from dataclasses import dataclass, field


@dataclass
class Stream:
    index: int
    due: float                     # perf_counter time it was due
    sent: float = 0.0
    status: int = 0
    token_times: list = field(default_factory=list)
    tokens: list = field(default_factory=list)
    finish: str | None = None
    error: str | None = None

    @property
    def ok(self) -> bool:
        return self.status == 200 and self.error is None \
            and self.finish in ("length", "stop")


async def _one(host: str, port: int, st: Stream, prompt: list,
               max_tokens: int) -> None:
    body = json.dumps({"prompt": prompt, "max_tokens": max_tokens,
                       "stream": True}).encode()
    head = (f"POST /v1/completions HTTP/1.1\r\nHost: {host}\r\n"
            "Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n")
    writer = None
    try:
        reader, writer = await asyncio.open_connection(host, port)
        st.sent = time.perf_counter()
        writer.write(head.encode() + body)
        await writer.drain()
        line = await reader.readline()
        st.status = int(line.split()[1])
        while (await reader.readline()).strip():
            pass                                   # headers
        if st.status != 200:
            st.error = (await reader.read()).decode(errors="replace")[:200]
            return
        while True:
            line = await reader.readline()
            if not line:
                break
            if not line.startswith(b"data: "):
                continue
            now = time.perf_counter()
            data = line[6:].strip()
            if data == b"[DONE]":
                break
            choice = json.loads(data)["choices"][0]
            ids = choice["token_ids"]
            st.tokens.extend(ids)
            st.token_times.extend([now] * len(ids))
            if choice.get("finish_reason"):
                st.finish = choice["finish_reason"]
    except (OSError, ValueError, KeyError, IndexError,
            asyncio.IncompleteReadError) as e:
        st.error = f"{type(e).__name__}: {e}"
    finally:
        if writer is not None:
            writer.close()


async def open_loop(host: str, port: int, items: list, prompts: dict,
                    t0: float, on_tick=None) -> list:
    """Send every item at ``t0 + item.due_s``; wait for all to end.
    ``on_tick(elapsed)`` is called about every 20 ms while waiting."""
    streams = [Stream(it.index, t0 + it.due_s) for it in items]
    tasks = []

    async def ticker():
        while True:
            on_tick(time.perf_counter() - t0)
            await asyncio.sleep(0.02)

    tick_task = asyncio.ensure_future(ticker()) if on_tick else None
    try:
        for it, st in zip(items, streams):
            delay = st.due - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            tasks.append(asyncio.ensure_future(
                _one(host, port, st, prompts[it.index], it.output_len)))
        await asyncio.gather(*tasks)
    finally:
        if tick_task is not None:
            tick_task.cancel()
    return streams


def summarize(streams: list, t0: float, seconds: float) -> dict:
    """Client-side numbers of a window: TTFT from due time, every gap
    between consecutive tokens of a stream (zeros from burst delivery
    count), generator lateness, backlog at the window's end."""
    from chipbench.harness.core import percentile
    good = [s for s in streams if s.ok and s.token_times]
    ttft = [(s.token_times[0] - s.due) * 1e3 for s in good]
    ends = [(b - a, b - t0) for s in good
            for a, b in zip(s.token_times, s.token_times[1:])]
    gaps = [g * 1e3 for g, _ in ends]
    late = [(s.sent - s.due) * 1e3 for s in streams if s.sent]
    end = t0 + seconds
    last = max((s.token_times[-1] for s in good), default=end)
    worst = max(ends, default=(0.0, 0.0))
    latest = max(((s.sent - s.due, s.due - t0) for s in streams if s.sent),
                 default=(0.0, 0.0))
    out = {"requests": len(streams), "failed": len(streams) - len(good),
           "widest_gap_ended_at_s": worst[1],
           "latest_send_was_due_at_s": latest[1],
           "shed": sum(s.status == 503 for s in streams),
           "tokens": sum(len(s.tokens) for s in good),
           "token_gaps": len(gaps),
           "lateness_ms": {"mean": sum(late) / max(len(late), 1),
                           "max": max(late, default=0.0)},
           "in_flight_at_window_end": sum(
               1 for s in good if s.token_times[-1] > end),
           "finished_after_window_s": max(0.0, last - end)}
    if ttft:
        out["ttft_ms"] = {"n": len(ttft), "p50": percentile(ttft, 0.5),
                          "p90": percentile(ttft, 0.9),
                          "p95": percentile(ttft, 0.95), "max": max(ttft)}
    if gaps:
        out["gap_ms"] = {"n": len(gaps), "p50": percentile(gaps, 0.5),
                         "p90": percentile(gaps, 0.9),
                         "p95": percentile(gaps, 0.95),
                         "p99": percentile(gaps, 0.99), "max": max(gaps),
                         "nonzero_share": sum(g > 0 for g in gaps) / len(gaps)}
    return out
