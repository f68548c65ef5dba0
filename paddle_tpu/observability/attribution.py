"""Per-phase engine step cost attribution (ISSUE 10 tentpole, part 1).

The serving engine's whole cost model is bucket-shaped — every dispatch is
one of a handful of program shapes (a prefill chunk at T=prefill_bucket, a
decode step at T=1, a speculative verify at T=K, a fused K-step decode, a
COW page copy, the gather's wait for the device) — but until now the
telemetry only answered "how fast is the engine" in aggregate.  This module
answers "which PHASE paid the latency": every dispatch is classified by its
program shape and its host-stamped wall time and token count fold into

- ``serving.step_ms{phase=...}``   — per-phase dispatch-to-dispatch wall
  time histograms (the StepTimer convention: converges to true step time
  in any steady loop whose caller eventually drains), and
- ``serving.tokens_per_sec{phase=...}`` — per-phase throughput gauges from
  the last drained window,

plus per-(phase, bucket) EWMA baselines (mean + absolute deviation) that
the regression sentinel and ``/statusz`` read — the host-side analog of a
per-dispatch-shape cost table.

Overhead contract (the PR 5 pattern, exactly): ``stamp()`` is one list
append on the hot step path; ALL arithmetic — durations, histogram
observes, EWMA folds — happens in ``fold()`` at the engine's gather (every
step: one stamp's worth).  Nothing here touches a device array, so warm
steps with attribution enabled stay telemetry-asserted at 0 compiles / 0
syncs.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

from .. import flags
from . import metrics as _metrics

__all__ = ["StepAttribution", "Ewma", "PHASES"]

# the closed phase vocabulary — every engine dispatch is exactly one of
# these program shapes (also the bounded label set of serving.step_ms)
PHASES = ("prefill", "decode", "spec_verify", "fused_k", "cow_copy",
          "drain")

# step_ms bucket ladder: finer than the default 1/2/5 ladder in the
# 0.1ms..1s band where engine dispatches actually live
_STEP_BOUNDS = (0.05, 0.1, 0.2, 0.5, 1.0, 2.0, 5.0, 10.0, 20.0, 50.0,
                100.0, 200.0, 500.0, 1000.0, 2000.0, 5000.0, 10000.0)


class Ewma:
    """EWMA mean + EWMA absolute deviation of one scalar series — THE
    baseline recurrence shared by the attribution cost table and the
    sentinel's drift detectors (one definition: a tweak to the seeding
    or the deviation form cannot diverge the two)."""

    __slots__ = ("mean", "dev", "n", "alpha")

    def __init__(self, alpha: float):
        self.mean = 0.0
        self.dev = 0.0
        self.n = 0
        self.alpha = alpha

    def update(self, v: float) -> None:
        if self.n == 0:
            self.mean = v
        else:
            a = self.alpha
            self.dev = (1 - a) * self.dev + a * abs(v - self.mean)
            self.mean = (1 - a) * self.mean + a * v
        self.n += 1


class StepAttribution:
    """Fold per-dispatch stamps into per-phase registry series.

    Engine wiring (``ContinuousBatchingEngine``)::

        attr.stamp(phase, bucket, t_dispatch, tokens)   # per step: append
        ...
        attr.credit_tokens("spec_verify", n_committed)  # at the drain
        attr.fold(t_drain_start)                        # at the drain
        attr.observe_host("drain", drain_seconds)       # host-timed block

    Durations are dispatch-to-dispatch: stamp ``i``'s cost is the gap to
    stamp ``i+1`` (the final stamp of a window closes against the drain's
    entry timestamp), so an async dispatch's cost lands where the host
    actually waited for it.  Token counts known only at the drain (the
    speculative lanes' device-computed commit counts) arrive via
    ``credit_tokens`` before the fold.
    """

    def __init__(self, registry=_metrics.REGISTRY,
                 alpha: Optional[float] = None):
        self._alpha = float(flags.flag("sentinel_alpha")
                            if alpha is None else alpha)
        self._step_ms = {}
        self._tps = {}
        for phase in PHASES:
            self._step_ms[phase] = registry.histogram(
                "serving.step_ms", bounds=_STEP_BOUNDS, phase=phase)
            self._tps[phase] = registry.gauge(
                "serving.tokens_per_sec", phase=phase)
        self._baselines: Dict[Tuple[str, int], Ewma] = {}
        # (phase, bucket, t_dispatch, tokens) stamps since the last fold
        self._pending: List[tuple] = []
        self._credits: Dict[str, int] = {}

    # ------------------------------------------------------------ hot path
    def stamp(self, phase: str, bucket: int, t: Optional[float] = None,
              tokens: int = 0) -> None:
        """Record one dispatch (one append; all math deferred to fold)."""
        self._pending.append(
            (phase, bucket, time.perf_counter() if t is None else t,
             tokens))

    # ------------------------------------------------------------- drain
    def credit_tokens(self, phase: str, tokens: int) -> None:
        """Attribute drain-resolved token counts (spec commit counts are
        device-computed and only materialize at the drain)."""
        if tokens:
            self._credits[phase] = self._credits.get(phase, 0) + tokens

    def fold(self, t_end: Optional[float] = None) -> None:
        """Fold the window: dispatch-to-dispatch durations into the
        per-phase histograms/baselines, window throughput into the
        per-phase gauges.  Called at the existing drain only."""
        pending = self._pending
        if not pending:
            self._credits.clear()
            return
        self._pending = []
        t_end = time.perf_counter() if t_end is None else t_end
        dur: Dict[str, float] = {}
        tok: Dict[str, int] = {}
        for i, (phase, bucket, t, tokens) in enumerate(pending):
            t_next = pending[i + 1][2] if i + 1 < len(pending) else t_end
            dt_ms = max(t_next - t, 0.0) * 1e3
            self._step_ms[phase].observe(dt_ms)
            base = self._baselines.get((phase, bucket))
            if base is None:
                base = self._baselines[(phase, bucket)] = \
                    Ewma(self._alpha)
            base.update(dt_ms)
            dur[phase] = dur.get(phase, 0.0) + dt_ms
            if tokens:
                tok[phase] = tok.get(phase, 0) + tokens
        for phase, n in self._credits.items():
            tok[phase] = tok.get(phase, 0) + n
        self._credits.clear()
        # every phase's gauge reflects THIS window: a phase that went
        # idle (prefill after the last chunk) drops to 0 instead of
        # advertising its last active window's rate forever
        for phase in PHASES:
            n = tok.get(phase, 0)
            ms = dur.get(phase, 0.0)
            self._tps[phase].set(n * 1e3 / ms if n and ms > 0 else 0.0)

    def observe_host(self, phase: str, dur_s: float,
                     tokens: int = 0) -> None:
        """Attribute a directly-timed host-side block (the drain's
        host<->device transfer is synchronous — its duration is known at
        the site, no dispatch chain involved)."""
        ms = max(dur_s, 0.0) * 1e3
        self._step_ms[phase].observe(ms)
        base = self._baselines.get((phase, 0))
        if base is None:
            base = self._baselines[(phase, 0)] = Ewma(self._alpha)
        base.update(ms)
        if tokens and ms > 0:
            self._tps[phase].set(tokens * 1e3 / ms)

    # ------------------------------------------------------------- export
    def baselines(self) -> Dict[str, dict]:
        """Per-(phase, bucket) EWMA cost table for /statusz and the
        sentinel: ``{"decode/T1": {"ewma_ms", "dev_ms", "n"}, ...}``."""
        return {f"{phase}/T{bucket}": {"ewma_ms": round(b.mean, 4),
                                       "dev_ms": round(b.dev, 4),
                                       "n": b.n}
                for (phase, bucket), b in
                sorted(dict(self._baselines).items())}
