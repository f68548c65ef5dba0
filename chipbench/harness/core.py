"""The run's context: device, clocks, the traced window, memory."""

from __future__ import annotations

import os
import shutil
import threading
import time

from chipbench.harness.checks import Checks, emit
from chipbench.harness.spec import Cell

OUT_DIR = ".chipbench_out"          # inside the checkout, git-ignored


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def process_age_s() -> float:
    """Seconds since this process was started (not since Python began
    running our code): set-up counts the interpreter's start and imports."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return up - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _IMPORTED


_IMPORTED = time.perf_counter()


def percentile(values, q: float) -> float:
    """Linear-interpolated q-quantile (q in [0, 1]) of all ``values``."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of nothing")
    pos = q * (len(v) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(chips: int, rehearse: bool) -> dict:
    info = device_info()
    if rehearse:
        if info["platform"] == "tpu":
            raise NoChip("--rehearse is the CPU rehearsal; this is a TPU")
    elif info["platform"] != "tpu":
        raise NoChip(f"no accelerator: jax found {info}")
    if info["count"] < chips:
        raise NoChip(f"the cell needs {chips} chip(s), jax found {info}")
    return info


def memory_peak_bytes(chips: int) -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


class Tracer:
    """The traced window: a few seconds of the profiler at a fixed offset
    of the fixed schedule (both in the mix's file).  Off for ``--trace 0``.
    The stop runs on a thread of its own, so the loop that offers the load
    never waits for the profiler to write its file."""

    def __init__(self, on: bool, traffic: dict, out_dir: str):
        t = traffic.get("trace", {})
        self.on = bool(on)
        self.offset_s = float(t.get("offset_s", 0.0))
        self.seconds = float(t.get("seconds", 3.0))
        self.dir = out_dir
        self.state = "idle"          # idle -> running -> stopping -> done
        self.t_started = None        # perf_counter when the trace began
        self._span = None
        self._thread = None

    def _start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation("bench.trace_window")
        self._span.__enter__()
        self.t_started = time.perf_counter()
        self.state = "running"

    def _stop(self):
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"

    def tick(self, elapsed_s: float) -> None:
        """Called by the driver's loop with the window's elapsed time."""
        if not self.on:
            return
        if self.state == "idle" and elapsed_s >= self.offset_s:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.state = "starting"
            self._start()
        elif self.state == "running" and \
                time.perf_counter() - self.t_started >= self.seconds:
            self.state = "stopping"
            self._thread = threading.Thread(target=self._stop,
                                            name="bench-trace-stop")
            self._thread.start()

    def finish(self) -> None:
        """After the window: the trace must be on disk (or never began)."""
        if not self.on:
            return
        if self.state == "running":
            self._stop()
        if self._thread is not None:
            self._thread.join()
        if self.state != "done":
            raise RuntimeError(
                f"the traced window never ran (state {self.state}): the "
                f"offset {self.offset_s} s lies outside the window")


class Run:
    """Everything a driver and a reader may ask for."""

    def __init__(self, cell: Cell, args, device: dict):
        self.cell = cell
        self.args = args
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.rehearse = bool(args.rehearse)
        self.control = bool(args.control)
        self.device = device
        self.checks = Checks()
        from chipbench.harness.registry import CompileWatch
        self.watch = CompileWatch()
        self.out_dir = os.path.join(cell.root, OUT_DIR, cell.name)
        self.tracer = Tracer(bool(args.trace), self.traffic,
                             os.path.join(self.out_dir, "trace"))
        self.results: dict = {}      # what the driver measured
        self.trace = None            # trace_reduce.Trace of a traced run
        self.attempted = 0
        self.failed = 0
        self.setup_s = None
        self.memory_peak = 0

    @property
    def model(self) -> dict:
        """The configuration's sizes with this traffic kind's depth.  For a
        configuration cut to one chip's share (``spec.py``'s docstring) the
        counts held here lie over the published ones, which stand beside
        them under ``published`` (the router's width, the whole vocabulary)
        with the place among the chips that share a layer under ``share``
        (and, where the file divides a key over fewer parts than chips, its
        ``over``): the program and the reference are handed the same share."""
        config = self.cell.config
        m = dict(config["model"])
        role = "train" if self.cell.kind == "train" else "serve"
        m["num_hidden_layers"] = int(config["depth"][role])
        share = config.get("share")
        if share is not None:
            m.update(share.get(role, {}))
            pattern = config.get("layer_pattern", {})
            if "leading_key" in pattern:        # leading dense layers, as run
                m[pattern["leading_key"]] = int(pattern["leading_dense"])
            m["published"] = {k: config["model"][k]
                              for k in config["reduced"]}
            m["share"] = {"chips": share["chips"], "index": share["index"]}
            if "over" in share:
                m["share"]["over"] = dict(share["over"])
        if self.rehearse:
            m.update(config.get("rehearsal_model", {}))
        return m

    @property
    def traffic(self) -> dict:
        t = dict(self.cell.traffic)
        if self.rehearse:
            for k, v in t.get("rehearsal", {}).items():
                t[k] = v
        return t

    def note_memory(self) -> None:
        """The program's peak, read before the reference runs."""
        self.memory_peak = memory_peak_bytes(self.cell.chips)

    def note_compiles(self, t0: float) -> None:
        """Programs compiled, or read from the persistent cache, inside the
        window (there must be none: set-up warms every shape)."""
        seen = self.watch.stop()
        self.results["compiles_in_window"] = len(seen)
        if seen:
            emit(phase="compiled_in_window", events=[
                dict(e, at=e["at"] - t0) for e in seen[:20]])

    def ready(self) -> None:
        """Set-up is over: everything warm, the window may open."""
        self.setup_s = process_age_s()
        emit(phase="ready", setup_s=self.setup_s)

    def peaks(self) -> dict:
        from chipbench.harness.roofline import peaks
        return peaks(self.device["kind"])
