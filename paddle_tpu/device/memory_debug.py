"""Memory accounting + donation-audit tooling.

Reference: the allocator observability the reference builds into its own
allocator stack (paddle/fluid/memory/allocation + FLAGS_log_memory_stats,
stat_allocator cross-checks).  On TPU, XLA/PJRT owns allocation, so the
honest tooling surface is (a) XLA's own compiled-program memory accounting,
(b) a donation audit — did the buffers you donated actually alias the
outputs, or did XLA silently copy — and (c) a live-buffer census for
"what is still holding HBM" triage.
"""

from __future__ import annotations

import re
from collections import Counter
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import numpy as np

__all__ = ["memory_analysis", "compiled_memory_report", "donation_audit",
           "live_arrays_report"]


def _nbytes(x) -> int:
    x = getattr(x, "_data", x)
    return int(np.dtype(x.dtype).itemsize * int(np.prod(x.shape)))


def memory_analysis(fn: Callable, *example_args,
                    donate_argnums: Sequence[int] = (),
                    static_argnums: Sequence[int] = ()) -> Dict[str, Any]:
    """Compile ``fn`` on the example args and report XLA's memory
    accounting: argument/output/temp/alias bytes + code size.  ``temp``
    is the transient working set (the usual OOM driver under remat)."""
    args = [getattr(a, "_data", a) for a in example_args]
    # jaxlint: disable=JL003 -- debug wrapper forwards the caller's static spec verbatim; compiled once per explicit analysis call
    compiled = jax.jit(fn, donate_argnums=tuple(donate_argnums),
                       static_argnums=tuple(static_argnums)
                       ).lower(*args).compile()
    return compiled_memory_report(compiled)


def compiled_memory_report(compiled) -> Dict[str, Any]:
    """XLA's memory accounting of an already-compiled executable (the
    dict :func:`memory_analysis` returns)."""
    ms = compiled.memory_analysis()
    out = {"argument_bytes": getattr(ms, "argument_size_in_bytes", None),
           "output_bytes": getattr(ms, "output_size_in_bytes", None),
           "temp_bytes": getattr(ms, "temp_size_in_bytes", None),
           "alias_bytes": getattr(ms, "alias_size_in_bytes", None),
           "code_bytes": getattr(ms, "generated_code_size_in_bytes", None)}
    # aliased (donated) bytes appear in BOTH argument and output accounting;
    # subtract once so a fully-donated train step is not double-counted
    total = sum(v for k, v in out.items()
                if k != "alias_bytes" and isinstance(v, int))
    if isinstance(out["alias_bytes"], int):
        total -= out["alias_bytes"]
    out["peak_estimate_bytes"] = total
    return out


def donation_audit(fn: Callable, *example_args,
                   donate_argnums: Sequence[int],
                   static_argnums: Sequence[int] = ()) -> Dict[str, Any]:
    """Did each donated argument actually alias an output?

    XLA drops a donation silently (just a warning at dispatch) when no
    output matches the donated buffer's shape/layout — the donated memory
    is then briefly DOUBLE-allocated.  Reports per-donated-arg honored
    status (parsed from the compiled HLO's input_output_alias) plus the
    wasted bytes."""
    args = [getattr(a, "_data", a) for a in example_args]
    # keep_unused pins the arg->HLO-parameter numbering (jit otherwise DROPS
    # unused leaves from the executable and shifts every index after them)
    # jaxlint: disable=JL003 -- debug wrapper forwards the caller's static spec verbatim; compiled once per explicit audit call
    compiled = jax.jit(fn, donate_argnums=tuple(donate_argnums),
                       static_argnums=tuple(static_argnums),
                       keep_unused=True).lower(*args).compile()
    text = compiled.as_text()
    # header entries look like "{out_index}: (param, {param_index}, kind)";
    # the tuple form only occurs inside input_output_alias
    header = text.split("\n", 1)[0]
    aliased_params = {
        int(pm.group(1))
        for pm in re.finditer(
            r"\(\s*(\d+)\s*,\s*\{[^}]*\}\s*,\s*(?:may|must)-alias\)",
            header)}
    # map python argnums to FLAT HLO parameter indices: jax flattens the
    # non-static args' pytree leaves in order
    static = set(static_argnums)
    spans: Dict[int, range] = {}
    flat = 0
    for i, a in enumerate(args):
        if i in static:
            continue
        n = len(jax.tree_util.tree_leaves(a))
        spans[i] = range(flat, flat + n)
        flat += n
    per_arg = []
    wasted = 0
    for i in donate_argnums:
        leaves = jax.tree_util.tree_leaves(args[i])
        sizes = [_nbytes(l) for l in leaves]
        flat_idx = list(spans.get(i, []))
        honored_leaves = [j in aliased_params for j in flat_idx]
        missed = sum(s for s, h in zip(sizes, honored_leaves) if not h)
        wasted += missed
        per_arg.append({"argnum": i, "bytes": sum(sizes),
                        "honored": missed == 0,
                        "leaves": len(leaves),
                        "honored_leaves": sum(honored_leaves)})
    return {"donated": per_arg, "unhonored_bytes": wasted,
            "honored_all": wasted == 0}


def live_arrays_report(top: int = 20) -> Dict[str, Any]:
    """Census of live device arrays grouped by (shape, dtype) — the
    "what is still holding memory" triage view."""
    groups: Counter = Counter()
    bytes_by: Counter = Counter()
    total = 0
    for a in jax.live_arrays():
        key = (str(a.dtype), tuple(a.shape))
        n = _nbytes(a)
        groups[key] += 1
        bytes_by[key] += n
        total += n
    rows = [{"dtype": k[0], "shape": list(k[1]), "count": groups[k],
             "bytes": bytes_by[k]}
            for k, _ in bytes_by.most_common(top)]
    return {"total_bytes": total, "total_arrays": sum(groups.values()),
            "top": rows}
