"""Window-bounded readings of the program's metrics registry
(``paddle_tpu.observability.metrics``): the registry's histograms are
process-wide, so a reading is the difference of two snapshots."""

from __future__ import annotations


def histogram(name: str):
    from paddle_tpu.observability import metrics
    return metrics.histogram(name)


def counter(name: str):
    from paddle_tpu.observability import metrics
    return metrics.counter(name)


def series_of(cell, always: tuple) -> tuple:
    """The histograms a driver snapshots around its window: its own
    (``always``) and those the cell's file names under
    ``reports.registry_series`` (absent: none more), so that a reader of a
    later configuration's series needs no edit to a driver."""
    more = cell.extras.get("reports", {}).get("registry_series", ())
    return tuple(dict.fromkeys(tuple(always) + tuple(more)))


def snap(name: str) -> dict:
    h = histogram(name)
    return {"bounds": tuple(h.bounds), "buckets": list(h.bucket_counts),
            "count": h.count, "sum": h.sum}


def delta(before: dict, after: dict) -> dict:
    return {"bounds": after["bounds"],
            "buckets": [a - b for a, b in zip(after["buckets"],
                                              before["buckets"])],
            "count": after["count"] - before["count"],
            "sum": after["sum"] - before["sum"]}


def mean(d: dict):
    return d["sum"] / d["count"] if d["count"] else None


def percentile(d: dict, q: float):
    """The q-quantile from bucket counts, linear inside the bucket (the
    registry's own estimate, on a window's difference)."""
    if not d["count"]:
        return None
    rank, seen = q * d["count"], 0
    bounds = d["bounds"]
    for i, c in enumerate(d["buckets"]):
        if not c:
            continue
        if seen + c >= rank:
            lo = bounds[i - 1] if i > 0 else 0.0
            hi = bounds[i] if i < len(bounds) else bounds[-1]
            return lo + (hi - lo) * (rank - seen) / c
        seen += c
    return bounds[-1]


class CompileWatch:
    """Every program that was compiled, or read from the persistent cache,
    since ``start()``: either means a program was first used after set-up.
    Listens to jax's own monitoring events (process-wide, any thread)."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.seen = []
        self._on = False
        self._registered = False

    def _listen(self, event, duration, **kw):
        if self._on and event in self.EVENTS:
            import time
            self.seen.append({"event": event.rsplit("/", 1)[1],
                              "seconds": duration,
                              "at": time.perf_counter()})

    def start(self) -> None:
        if not self._registered:
            import jax.monitoring
            jax.monitoring.register_event_duration_secs_listener(self._listen)
            self._registered = True
        self.seen = []
        self._on = True

    def stop(self) -> list:
        self._on = False
        return list(self.seen)
