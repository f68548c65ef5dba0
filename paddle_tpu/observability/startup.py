"""The start-up log: where a process's time to ready goes, by phase.

``phase(name, **args)`` is a context manager around one piece of set-up
(``catalog.SPANS``, lane ``startup``).  It opens ``TRACER.span(name,
**args)``, so a profiler session or the Chrome sinks see it like any span,
and, until the log is sealed, appends one record to a bounded list::

    {"name", "args", "start_age_s", "dur_s", "thread", "depth", "jit"}

``start_age_s`` is SECONDS SINCE THE PROCESS STARTED (``/proc/self/stat``'s
start time against ``/proc/uptime``; where ``/proc`` cannot be read, since
the package's import began): the clock an operator's "time to ``/readyz``"
and the benchmark's ``setup_s`` are read on, so a reader tells what ended
before ready from what came after.  A record is appended when its phase
opens (``dur_s`` is None while it is open: ``/statusz`` of a process that
hangs in set-up shows where); ``depth`` counts the phases open around it on
its thread.  ``jit`` is what jax's own monitoring events reported while the
phase was the INNERMOST open one on its thread: counts and summed seconds
(``trace_n``/``trace_s``, ``lower_*``, ``compile_*``, ``cache_read_*``) of
the four events of ``JIT_EVENTS``, fed by the one listener the package
registers (``observability/__init__.py``).  An event outside any open phase
lands in no record.

``program(name, **args)`` and ``compiling()`` are phases that say on exit
whether the persistent cache held what they asked for (``cache_hit``; the
backend's ``compile_s`` or the cache's ``cache_read_s``).  ``seal()`` closes
the log (``ServingServer`` seals when ``/readyz`` flips): later phases are
spans and nothing else.  None of the sites is reached on a warm step.
"""

from __future__ import annotations

import functools
import os
import threading
import time
from typing import Callable, List, Optional

from .tracing import TRACER

__all__ = ["JIT_EVENTS", "MAX_RECORDS", "StartupLog", "LOG", "phase",
           "program", "compiling", "around", "records", "seal", "status",
           "process_age_s"]

MAX_RECORDS = 256

# jax.monitoring duration events -> the key their count and seconds go by
JIT_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read",
}


def _import_span() -> tuple:
    """(began, ended) of ``paddle_tpu/__init__.py`` on ``perf_counter``."""
    import paddle_tpu
    began = paddle_tpu._IMPORT_BEGAN
    # (a module the package's own __init__ pulls in asks before its end)
    return began, getattr(paddle_tpu, "_IMPORT_ENDED", time.perf_counter())


def _age_offset() -> float:
    """What to add to a ``perf_counter`` reading for the process's age."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return up - ticks / os.sysconf("SC_CLK_TCK") - now
    except (OSError, ValueError, IndexError):
        return -_import_span()[0]


_AGE_OFFSET = _age_offset()


def process_age_s(t: Optional[float] = None) -> float:
    """The process's age at ``perf_counter`` reading ``t`` (default: now)."""
    return (time.perf_counter() if t is None else t) + _AGE_OFFSET


def _add(into: dict, more: dict) -> None:
    for k, v in more.items():
        into[k] = into.get(k, 0) + v


def _cache_outcome(jit: dict) -> dict:
    """What a phase that compiled says of the persistent cache: every
    backend compile wraps the cache's read, so a hit is a read for every
    compile; nothing compiled says nothing."""
    compiles, reads = jit.get("compile_n", 0), jit.get("cache_read_n", 0)
    if not compiles:
        return {}
    if reads >= compiles:
        return {"cache_hit": True, "cache_read_s": jit["cache_read_s"]}
    return {"cache_hit": False, "compile_s": jit["compile_s"]}


class _Phase:
    """One open phase: the tracer's span, and while the log is open its
    record.  ``set_metadata`` adds what is known only inside it to both."""

    __slots__ = ("_log", "_span", "_rec", "_jit", "_t0", "_says_cache")

    def __init__(self, log: "StartupLog", name: str, args: dict,
                 says_cache: bool = False):
        self._log = log
        self._span = TRACER.span(name, **args)
        self._rec = None if log.sealed else {
            "name": name, "args": args, "start_age_s": None, "dur_s": None,
            "thread": threading.current_thread().name, "depth": 0, "jit": {}}
        self._jit: dict = {}        # own events and the closed children's
        self._says_cache = says_cache

    def __enter__(self):
        self._span.__enter__()
        rec = self._rec
        if rec is not None:
            stack = self._log._stack()
            rec["depth"] = len(stack)
            stack.append(self)
            self._t0 = time.perf_counter()
            rec["start_age_s"] = process_age_s(self._t0)
            self._log._append(rec)
        return self

    def set_metadata(self, **args) -> None:
        self._span.set_metadata(**args)
        if self._rec is not None:
            self._rec["args"].update(args)

    def __exit__(self, exc_type, exc, tb):
        rec = self._rec
        if rec is not None:
            rec["dur_s"] = time.perf_counter() - self._t0
            stack = self._log._stack()
            stack.pop()
            _add(self._jit, rec["jit"])
            if self._says_cache:
                self.set_metadata(**_cache_outcome(self._jit))
            if stack:
                _add(stack[-1]._jit, self._jit)
        return self._span.__exit__(exc_type, exc, tb)


class StartupLog:
    """The bounded list of records and the phases open on each thread."""

    def __init__(self, max_records: int = MAX_RECORDS):
        self._max = max_records
        self._records: List[dict] = []
        self._lock = threading.Lock()
        self._local = threading.local()
        self.sealed = False
        self.ready_age_s: Optional[float] = None
        self.overflow = 0           # records the full list turned away

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _append(self, rec: dict) -> None:
        with self._lock:
            if len(self._records) >= self._max:
                self.overflow += 1
            else:
                self._records.append(rec)

    def phase(self, name: str, **args) -> _Phase:
        return _Phase(self, name, args)

    def program(self, program: str, **args) -> _Phase:
        """``startup.program``: one jitted program's first build or first
        call, whole.  ``program`` is the ``jit_<name>`` a trace shows."""
        return _Phase(self, "startup.program", dict(args, program=program),
                      says_cache=True)

    def compiling(self) -> _Phase:
        """``startup.compile``: a ``Lowered.compile()``."""
        return _Phase(self, "startup.compile", {}, says_cache=True)

    def note_import(self) -> None:
        """``startup.import`` after the fact: the package's ``__init__``
        ran before the tracer existed."""
        began, ended = _import_span()
        age = process_age_s(began)
        self._append({"name": "startup.import",
                      "args": {"began_age_s": age}, "start_age_s": age,
                      "dur_s": ended - began, "thread": "MainThread",
                      "depth": 0, "jit": {}})

    def on_jit_event(self, event: str, seconds) -> None:
        key = JIT_EVENTS.get(event)
        stack = getattr(self._local, "stack", None)
        if key is None or not stack:
            return
        jit = stack[-1]._rec["jit"]
        jit[key + "_n"] = jit.get(key + "_n", 0) + 1
        jit[key + "_s"] = jit.get(key + "_s", 0.0) + float(seconds)

    def records(self) -> List[dict]:
        """A copy of the records, in the order their phases opened."""
        with self._lock:
            return [dict(r, args=dict(r["args"]), jit=dict(r["jit"]))
                    for r in self._records]

    def seal(self) -> None:
        """Close the log: the process is ready.  Phases that are open keep
        filling their records; none is appended after this."""
        if not self.sealed:
            self.sealed = True
            self.ready_age_s = process_age_s()

    def status(self) -> dict:
        """The ``startup`` block of ``GET /statusz``."""
        return {"sealed": self.sealed, "ready_age_s": self.ready_age_s,
                "overflow": self.overflow, "records": self.records()}


# the process-wide log every subsystem's set-up writes into
LOG = StartupLog()
LOG.note_import()


def phase(name: str, **args) -> _Phase:
    return LOG.phase(name, **args)


def program(program: str, **args) -> _Phase:
    return LOG.program(program, **args)


def compiling() -> _Phase:
    return LOG.compiling()


def records() -> List[dict]:
    return LOG.records()


def seal() -> None:
    LOG.seal()


def status() -> dict:
    return LOG.status()


def around(name: str, describe: Optional[Callable] = None):
    """Decorator: the method's whole call is phase ``name``; ``describe(
    self)`` gives the arguments that are known once it has returned."""
    def wrap(fn):
        @functools.wraps(fn)
        def inside(self, *a, **kw):
            with LOG.phase(name) as ph:
                out = fn(self, *a, **kw)
                if describe is not None:
                    ph.set_metadata(**describe(self))
            return out
        return inside
    return wrap
