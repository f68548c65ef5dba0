"""Where ``setup_s`` went: the arithmetic the seven start-up readers share.

The program keeps a start-up log (``paddle_tpu.observability.startup``): one
record a phase of set-up, ``{name, args, start_age_s, dur_s, thread, depth,
jit}``, with ``start_age_s`` on the clock ``setup_s`` is read on (seconds
since the process started).  The records that ENDED at or before ``setup_s``
are laid over [0, ``setup_s``]: every instant belongs to the innermost record
over it (the deepest; among equals the latest begun), or to none, and the six
shares add up to ``setup_s``:

- ``import``: ``startup.import``;
- ``model_init``: ``startup.model_init``;
- ``lower``: ``startup.lower``;
- ``compile``: ``startup.compile``, and what a ``startup.program`` holds
  outside its two children (the dictionary's insert);
- ``build``: every other record (``startup.engine_build``, ``.train_build``,
  ``.warm`` and what lies inside them that is no program);
- ``outside_program``: under no record: the interpreter, jax, the backend's
  start, the benchmark's seeded weights and schedule.

A ``startup.program`` without children is the first CALL of a jitted
program: of its time the backend's own compile seconds (``jit.compile_s``,
which hold the cache's read) are ``compile`` and the rest, tracing and
lowering, is ``lower`` (the trace events nest and cannot be summed).

A program without the log (``ImportError``, or no record) gives None
everywhere.  ``run.startup`` stands in for the program's records where a
test or a builder's dump hands them in.
"""

from __future__ import annotations

SHARES = ("import", "model_init", "build", "lower", "compile",
          "outside_program")
_SHARE_OF = {"startup.import": "import", "startup.model_init": "model_init",
             "startup.lower": "lower", "startup.compile": "compile",
             "startup.program": "program"}


def records(run):
    """The program's records, or None where it keeps no log."""
    given = getattr(run, "startup", None)
    if given is not None:
        return list(given) or None
    try:
        from paddle_tpu.observability import startup
    except ImportError:
        return None
    return startup.records() or None


def before_ready(run):
    """The closed records that ended at or before ``setup_s``."""
    recs = records(run)
    if recs is None or getattr(run, "setup_s", None) is None:
        return None
    return [r for r in recs if r["dur_s"] is not None
            and r["start_age_s"] + r["dur_s"] <= run.setup_s]


def self_times(recs: list) -> list:
    """Seconds in which each record is the innermost one over the clock."""
    edges = sorted({r["start_age_s"] for r in recs}
                   | {r["start_age_s"] + r["dur_s"] for r in recs})
    own = [0.0] * len(recs)
    for a, b in zip(edges, edges[1:]):
        over = [i for i, r in enumerate(recs) if r["start_age_s"] <= a
                and r["start_age_s"] + r["dur_s"] >= b]
        if over:
            own[max(over, key=lambda i: (recs[i]["depth"],
                                         recs[i]["start_age_s"]))] += b - a
    return own


def _has_children(recs: list, prog: dict) -> bool:
    lo, hi = prog["start_age_s"], prog["start_age_s"] + prog["dur_s"]
    return any(r["name"] in ("startup.lower", "startup.compile")
               and r["thread"] == prog["thread"] and r["start_age_s"] >= lo
               and r["start_age_s"] + r["dur_s"] <= hi for r in recs)


def split(run):
    """{share: seconds} over ``SHARES``, adding up to ``setup_s``."""
    recs = before_ready(run)
    if recs is None:
        return None
    out = dict.fromkeys(SHARES, 0.0)
    for r, own in zip(recs, self_times(recs)):
        share = _SHARE_OF.get(r["name"], "build")
        if share != "program":
            out[share] += own
        elif _has_children(recs, r):
            out["compile"] += own
        else:
            compiled = min(own, r["jit"].get("compile_s", 0.0))
            out["compile"] += compiled
            out["lower"] += own - compiled
    out["outside_program"] = run.setup_s - sum(out.values())
    return out


def share(run, name: str):
    parts = split(run)
    return None if parts is None else parts[name]


def compiled_programs(run):
    """``program`` of every ``startup.program`` before ready whose
    ``cache_hit`` is false: none on a truly warm start."""
    recs = before_ready(run)
    if recs is None:
        return None
    return [r["args"].get("program", "?") for r in recs
            if r["name"] == "startup.program"
            and r["args"].get("cache_hit") is False]
