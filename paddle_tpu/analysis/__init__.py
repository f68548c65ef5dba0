"""jaxlint: repo-native static analysis for the engine's hot-path
invariants (ISSUE 8).

The reference framework enforces its invariants at compile time
(enforce.h, the exported-flag registry, whole static-graph passes); this
reproduction's equivalents — zero warm recompiles, zero hidden
host<->device syncs, int32-only Pallas scalars, engine single-ownership,
bounded metric cardinality — were runtime-asserted only where telemetry
happened to exist, and several only manifest on hardware behind the
chip-capture queue.  ``paddle_tpu.analysis`` moves them to review time:
an AST pass over the package that runs as a tier-1 test gate.

Usage::

    python -m paddle_tpu.analysis paddle_tpu/        # or: paddle-tpu-lint
    paddle-tpu-lint --list-rules
    paddle-tpu-lint --format=json --baseline=lint_baseline.json src/

Rule catalog (full rationale in docs/jaxlint.md):

- **JL001** raw Python-int scalars in Pallas kernel bodies
- **JL002** sync-forcing calls on the serving/train hot path
- **JL003** warm-path recompile hazards
- **JL004** flag registry hygiene
- **JL005** blocking calls inside async handlers
- **JL006** metric labels fed from unbounded request data
- **JL007** direct engine calls from asyncio handler code

Suppressions require a reason: ``# jaxlint: disable=JL002 -- <why>``.
"""

from __future__ import annotations

from typing import Optional, Set

from .core import (ANALYZER_NAME, Finding, ModuleInfo, Rule, RunContext,
                   __version__, rule_catalog, run)
from .reporters import (apply_baseline, render_json, render_text,
                        write_baseline)

__all__ = ["ANALYZER_NAME", "__version__", "Finding", "ModuleInfo", "Rule",
           "RunContext", "rule_catalog", "run", "analyze_source",
           "render_text", "render_json", "write_baseline", "apply_baseline",
           "package_report"]


def analyze_source(source: str, rel: str = "paddle_tpu/example.py",
                   select: Optional[Set[str]] = None) -> RunContext:
    """Analyze one in-memory module (the fixture-test entry point).

    ``rel`` participates in path-scoped rules (JL002 hot-path modules,
    JL005/JL007 serving/router scope), so fixtures pick their scope by
    naming their virtual file.
    """
    from pathlib import Path

    from .core import analyze_modules, make_rules

    ctx = RunContext()
    ctx.files = 1
    mod = ModuleInfo(Path(rel), rel, source)
    return analyze_modules([mod], make_rules(select), ctx)


def package_report() -> dict:
    """Run the analyzer over the installed ``paddle_tpu`` package and
    return the JSON-shaped summary."""
    import json
    import os

    pkg_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ctx = run([pkg_dir])
    return json.loads(render_json(ctx, ctx.findings))
