"""Peaks, the least time a call could take, and the 105 % rule."""

from __future__ import annotations

import os

from chipbench.harness.spec import BENCH_DIR, load_json

SHARE_LIMIT_PCT = 105.0


class UnknownDevice(KeyError):
    """The device's kind has no row in peaks.json."""


class ShareTooHigh(ValueError):
    """A share of a peak read above 105 %: the operations or bytes are
    counted too high, or the time leaves out part of the work."""


def peaks(device_kind: str, path: str | None = None) -> dict:
    table = load_json(path or os.path.join(BENCH_DIR, "peaks.json"))
    row = table.get(device_kind.lower())
    if not isinstance(row, dict):
        raise UnknownDevice(
            f"no peaks known for device_kind {device_kind!r}; peaks.json "
            f"has {sorted(k for k in table if not k.startswith('_'))}")
    return row


def min_time_s(flops: float, nbytes: float, pk: dict) -> tuple:
    """(least seconds, which bound: 'compute' or 'memory')."""
    t_c = flops / pk["bf16_flops_per_s"]
    t_m = nbytes / pk["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def share_pct(name: str, least_s: float, measured_s: float, **made_of) -> float:
    """``least_s / measured_s`` in percent; never clipped.  Above 105 % it
    raises with the numbers that made it."""
    if measured_s <= 0:
        raise ShareTooHigh(f"{name}: measured time {measured_s} s")
    pct = 100.0 * least_s / measured_s
    if pct > SHARE_LIMIT_PCT:
        raise ShareTooHigh(
            f"{name} reads {pct:.2f} % (> {SHARE_LIMIT_PCT} %): least "
            f"{least_s} s over measured {measured_s} s, from {made_of}")
    return pct
