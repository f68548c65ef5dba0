"""jaxlint (paddle_tpu.analysis) — per-rule fixture tests + the
whole-package tier-1 gate (ISSUE 8).

Every rule must BOTH fire on its positive fixture AND stay quiet on the
negative one; the package gate asserts `python -m paddle_tpu.analysis
paddle_tpu/` is clean, which is the invariant every future PR inherits.
All tier-1: no device, no sockets.
"""

from __future__ import annotations

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from paddle_tpu import analysis
from paddle_tpu.analysis.__main__ import main as lint_main


def lint(src: str, rel: str = "paddle_tpu/example.py", select=None):
    return analysis.analyze_source(textwrap.dedent(src), rel=rel,
                                   select=select)


def rules_fired(ctx):
    return sorted({f.rule for f in ctx.findings})


# ------------------------------------------------------------------ JL001 --

_KERNEL_POS = """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl

    def _k(x_ref, o_ref, sem):
        i = pl.program_id(0)
        slot = i // 2
        sem.at[slot, 1]
        jax.lax.fori_loop(0, i, lambda j, c: c, i)
        o_ref[...] = jnp.maximum(x_ref[...], 0)

    def entry(x):
        return pl.pallas_call(_k, out_shape=x)(x)
"""

_KERNEL_NEG = """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl

    _I0 = np.int32(0)

    def _k(x_ref, o_ref, sem):
        i = pl.program_id(0)
        slot = jax.lax.rem(i, np.int32(2))
        sem.at[slot, _I0]
        jax.lax.fori_loop(_I0, i, lambda j, c: c, i)
        o_ref[...] = jnp.maximum(x_ref[...], np.int32(0))
        pad = 8 // 2          # both operands literal: compile-time python

    def host_helper(n):
        return n // 2         # not a kernel body: out of scope

    def entry(x):
        return pl.pallas_call(_k, out_shape=x)(x)
"""


def test_jl001_fires_on_raw_ints_in_kernel():
    ctx = lint(_KERNEL_POS, select={"JL001"})
    assert len(ctx.findings) == 4          # //, .at[1], fori bound, max(,0)
    assert rules_fired(ctx) == ["JL001"]


def test_jl001_quiet_on_int32_discipline():
    ctx = lint(_KERNEL_NEG, select={"JL001"})
    assert ctx.findings == []


def test_jl001_alias_reuse_covers_every_kernel():
    # two builders reusing the local name `kernel` must BOTH be analyzed
    # (a last-wins alias dict silently dropped _gmm_kernel)
    src = """
        import functools
        from jax.experimental import pallas as pl

        def _a_kernel(x_ref, o_ref, *, n):
            v = n % 3

        def _b_kernel(x_ref, o_ref, *, n):
            v = n // 3

        def build_a(x):
            kernel = functools.partial(_a_kernel, n=4)
            return pl.pallas_call(kernel, out_shape=x)(x)

        def build_b(x):
            kernel = functools.partial(_b_kernel, n=4)
            return pl.pallas_call(kernel, out_shape=x)(x)
    """
    ctx = lint(src, select={"JL001"})
    assert len(ctx.findings) == 2


def test_jl001_scale_indexing_bug_shape():
    """ISSUE 13: the int8 dequant path indexes an SMEM scale row at a
    page id derived in-kernel — a bare python-int in that derivation is
    exactly the Mosaic i64 class JL001 exists for.  The fixture mirrors
    the bug shape (python-int divisor feeding the scale index, plus a
    raw int literal in the fallback index) and must fire; the np.int32
    discipline of the real kernel must stay quiet."""
    bad = """
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.experimental import pallas as pl

        def _dequant_kernel(sc_ref, bt_ref, x_ref, o_ref):
            b = pl.program_id(0)
            p = bt_ref[b, b // 2]            # JL001: python-int divisor
            s = sc_ref[b, p]
            o_ref[...] = x_ref[...].astype(jnp.float32) * s

        def entry(sc, bt, x):
            return pl.pallas_call(_dequant_kernel, out_shape=x)(sc, bt, x)
    """
    ctx = lint(bad, select={"JL001"})
    assert len(ctx.findings) == 1 and "//" in ctx.findings[0].message

    good = """
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.experimental import pallas as pl

        def _dequant_kernel(sc_ref, bt_ref, x_ref, o_ref):
            b = pl.program_id(0)
            p = bt_ref[b, jax.lax.div(b, np.int32(2))]
            s = sc_ref[b, p]
            o_ref[...] = x_ref[...].astype(jnp.float32) * s

        def entry(sc, bt, x):
            return pl.pallas_call(_dequant_kernel, out_shape=x)(sc, bt, x)
    """
    assert lint(good, select={"JL001"}).findings == []


def test_jl001_follows_helpers_called_from_kernel():
    """PR 21: grouped_matmul's _gather_rows — a module-level helper the
    kernels call — looped with python-int fori_loop bounds, and JL001
    never looked inside it because only pallas_call's own argument was a
    'kernel'.  Helpers reached from a kernel body are kernel code."""
    src = """
        import jax
        from jax.experimental import pallas as pl

        def _gather(rows_ref, bm):
            jax.lax.fori_loop(0, bm, lambda r, c: c, 0)

        def _unrelated(n):
            return n // 2                     # never reached from a kernel

        def _k(rows_ref, o_ref, *, bm):
            _gather(rows_ref, bm)

        def entry(x):
            return pl.pallas_call(_k, out_shape=x)(x)
    """
    ctx = lint(src, select={"JL001"})
    assert len(ctx.findings) == 2             # lower bound + init carry
    assert all("fori_loop" in f.message for f in ctx.findings)


def test_jl001_resolves_partial_alias():
    src = """
        import functools
        from jax.experimental import pallas as pl

        def _k(x_ref, o_ref, *, n):
            v = n % 3

        def entry(x):
            kernel = functools.partial(_k, n=4)
            return pl.pallas_call(kernel, out_shape=x)(x)
    """
    ctx = lint(src, select={"JL001"})
    assert len(ctx.findings) == 1 and "%" in ctx.findings[0].message


# ------------------------------------------------------------------ JL002 --

_SYNC_POS = """
    import jax.numpy as jnp
    import numpy as np

    def drain(vals):
        return np.asarray(jnp.stack(vals))

    def probe(x):
        return x.item()
"""

_SYNC_NEG_MARKED = """
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu import observability as _obs

    def drain(vals):
        _obs.count_sync()
        return np.asarray(jnp.stack(vals))

    def probe(x):
        _obs.count_sync()
        return x.item()
"""


def test_jl002_fires_on_hot_path_syncs():
    ctx = lint(_SYNC_POS, rel="paddle_tpu/inference/foo.py",
               select={"JL002"})
    assert len(ctx.findings) == 2


def test_jl002_quiet_when_marked_with_count_sync():
    ctx = lint(_SYNC_NEG_MARKED, rel="paddle_tpu/inference/foo.py",
               select={"JL002"})
    assert ctx.findings == []


def test_jl002_quiet_off_hot_path():
    # the eager Paddle-compat layer syncs on user request: out of scope
    ctx = lint(_SYNC_POS, rel="paddle_tpu/ops/foo.py", select={"JL002"})
    assert ctx.findings == []


def test_jl002_fires_inside_jitted_body_anywhere():
    src = """
        import jax

        def step(x):
            return x.block_until_ready()

        step_j = jax.jit(step)
    """
    ctx = lint(src, rel="paddle_tpu/misc/mod.py", select={"JL002"})
    assert len(ctx.findings) == 1
    assert "jitted" in ctx.findings[0].message


def test_jl002_quiet_on_host_only_asarray():
    src = """
        import numpy as np

        def prep(prompts):
            return np.asarray([len(p) for p in prompts], np.int32)
    """
    ctx = lint(src, rel="paddle_tpu/inference/foo.py", select={"JL002"})
    assert ctx.findings == []


# ------------------------------------------------------------------ JL003 --

def test_jl003_fires_on_jit_per_call():
    src = """
        import jax

        def f(fn, x):
            return jax.jit(fn)(x)
    """
    ctx = lint(src, select={"JL003"})
    assert len(ctx.findings) == 1
    assert "every call" in ctx.findings[0].message


def test_jl003_fires_on_computed_static_spec():
    src = """
        import jax

        def wrap(fn, statics):
            return jax.jit(fn, static_argnums=tuple(statics))
    """
    ctx = lint(src, select={"JL003"})
    assert len(ctx.findings) == 1
    assert "static_argnums" in ctx.findings[0].message


def test_jl003_fires_on_traced_branching():
    src = """
        import jax

        @jax.jit
        def f(x):
            if x > 0:
                return x
            return -x
    """
    ctx = lint(src, select={"JL003"})
    assert len(ctx.findings) == 1
    assert "traced parameter `x`" in ctx.findings[0].message


def test_jl003_fires_on_traced_membership():
    # `x in (1, 2)` with the PARAM as the member bool()s a tracer —
    # only container-side membership (`"k" in state`) is static
    src = """
        import jax

        @jax.jit
        def f(x):
            if x in (1, 2, 3):
                return x
            return -x
    """
    ctx = lint(src, select={"JL003"})
    assert len(ctx.findings) == 1


def test_jl003_quiet_on_safe_patterns():
    src = """
        from functools import partial

        import jax

        @jax.jit
        def f(x, state):
            if x is None:
                return state
            if "ef" in state:                  # pytree structure: static
                return state["ef"]
            if x.shape[0] > 2:                 # shapes are static
                return x
            if len(state) == 1:
                return x
            return x

        @partial(jax.jit, static_argnames=("mode",))
        def g(x, mode):
            if mode == "fast":                 # declared static
                return x
            return x + 1

        _cache = {}

        def cached(key, fn, x):
            if key not in _cache:
                _cache[key] = jax.jit(fn, static_argnums=(1,))
            return _cache[key](x)
    """
    ctx = lint(src, select={"JL003"})
    assert ctx.findings == []


# ------------------------------------------------------------------ JL004 --

_FLAGS_POS = """
    def define_flag(name, default, help_str=""):
        pass

    def flag(name):
        pass

    define_flag("alive", 1)
    define_flag("dead", 2)

    def use():
        flag("alive")
        return flag("missing")
"""


def test_jl004_fires_on_dead_and_unregistered():
    ctx = lint(_FLAGS_POS, select={"JL004"})
    msgs = " | ".join(f.message for f in ctx.findings)
    assert len(ctx.findings) == 2
    assert "`dead` is registered but never read" in msgs
    assert "`missing` is read but never registered" in msgs


def test_jl004_quiet_on_alias_and_enum_loop_reads():
    src = """
        import flags

        def define_flag(name, default, help_str=""):
            pass

        define_flag("a", 1)
        define_flag("b", 2)
        define_flag("c", 3)

        def use():
            f = flags.flag
            f("a")
            for name in ("b", "c"):
                flags.flag(name)
    """
    ctx = lint(src, select={"JL004"})
    assert ctx.findings == []


def test_jl004_quiet_on_registry_only_run():
    # linting flags.py alone (no reader modules in scope) must not
    # declare every flag dead
    src = """
        def define_flag(name, default, help_str=""):
            pass

        define_flag("a", 1)
        define_flag("b", 2)
    """
    ctx = lint(src, select={"JL004"})
    assert ctx.findings == []


def test_jl004_quiet_without_registry_in_scope():
    # a subtree run (registry module not analyzed) must not mislabel
    # reads as unregistered
    src = """
        import flags

        def use():
            return flags.flag("anything")
    """
    ctx = lint(src, select={"JL004"})
    assert ctx.findings == []


# ------------------------------------------------------------------ JL005 --

_ASYNC_POS = """
    import subprocess
    import time

    async def handler(reader, writer):
        time.sleep(0.5)
        data = open("/etc/hosts").read()
        subprocess.run(["ls"])
"""


def test_jl005_fires_on_blocking_in_async():
    ctx = lint(_ASYNC_POS, rel="paddle_tpu/serving/h.py", select={"JL005"})
    assert len(ctx.findings) == 3


def test_jl005_quiet_on_sync_defs_and_executor_closures():
    src = """
        import asyncio
        import time

        def engine_loop():
            time.sleep(0.5)                    # engine thread: fine

        async def handler(loop):
            def work():
                time.sleep(0.5)                # executor closure: the fix
            await loop.run_in_executor(None, work)
            await asyncio.sleep(0.5)
    """
    ctx = lint(src, rel="paddle_tpu/router/h.py", select={"JL005"})
    assert ctx.findings == []


def test_jl005_scoped_to_serving_and_router():
    ctx = lint(_ASYNC_POS, rel="paddle_tpu/io/h.py", select={"JL005"})
    assert ctx.findings == []


def test_jl005_covers_fleet_package():
    """ISSUE 12 satellite: the fleet supervisor/chaos modules run on the
    same event loop as the router — blocking calls in their async defs
    are the same head-of-line hazard."""
    ctx = lint(_ASYNC_POS, rel="paddle_tpu/fleet/chaos.py",
               select={"JL005"})
    assert len(ctx.findings) == 3
    # the supervisor's SYNC control loop (tick/run_forever on a side
    # thread) stays exempt: blocking there is the design
    src = """
        import time

        def run_forever(self, interval_s):
            time.sleep(interval_s)
    """
    ctx = lint(src, rel="paddle_tpu/fleet/supervisor.py", select={"JL005"})
    assert ctx.findings == []


def test_jl005_covers_migration_module():
    """ISSUE 14 satellite: the session-transfer module is part of the
    asyncio serving plane (its functions run under the /migratez
    handlers' executor seam) — an async def with blocking calls there
    is the same head-of-line hazard as one in serving/ proper."""
    ctx = lint(_ASYNC_POS, rel="paddle_tpu/inference/migration.py",
               select={"JL005"})
    assert len(ctx.findings) == 3
    # its sync control-path functions (export/import run on the engine
    # thread) stay exempt
    src = """
        import time

        def export_session(engine, req_id):
            time.sleep(0.01)
    """
    ctx = lint(src, rel="paddle_tpu/inference/migration.py",
               select={"JL005"})
    assert ctx.findings == []
    # other inference/ modules are NOT in the async plane
    ctx = lint(_ASYNC_POS, rel="paddle_tpu/inference/generation.py",
               select={"JL005"})
    assert ctx.findings == []


def test_jl005_covers_controlplane_package():
    """ISSUE 19 satellite: the control plane rides the router's event
    loop — a blocking store call in an async def there stalls every
    in-flight completion stream."""
    ctx = lint(_ASYNC_POS, rel="paddle_tpu/controlplane/store.py",
               select={"JL005"})
    assert len(ctx.findings) == 3
    # the SYNC faces (SyncStoreClient on the supervisor thread,
    # ProcessRouterHandle probes) stay exempt: blocking there is the
    # design
    src = """
        import time

        def _call(self, req):
            time.sleep(0.01)
    """
    ctx = lint(src, rel="paddle_tpu/controlplane/store.py",
               select={"JL005"})
    assert ctx.findings == []


def test_jl005_covers_trace_collector_module():
    """ISSUE 20 satellite: the trace collector's ingest/clock faces are
    called from the router's /collectz handler — an async def with
    blocking calls there stalls span assembly on the serving loop."""
    ctx = lint(_ASYNC_POS, rel="paddle_tpu/observability/collector.py",
               select={"JL005"})
    assert len(ctx.findings) == 3
    # its sync verbs (SpanExporter's flush thread, the supervisor-tick
    # poll_store) stay exempt: blocking there is the design
    src = """
        import time

        def flush(self):
            time.sleep(0.01)
    """
    ctx = lint(src, rel="paddle_tpu/observability/collector.py",
               select={"JL005"})
    assert ctx.findings == []
    # the rest of observability/ is NOT in the async plane
    ctx = lint(_ASYNC_POS, rel="paddle_tpu/observability/tracing.py",
               select={"JL005"})
    assert ctx.findings == []


# ------------------------------------------------------------------ JL006 --

def test_jl006_fires_on_request_data_labels():
    src = """
        def track(m, req):
            m.counter("serving.requests", user=req.user_id)
            m.histogram("serving.lat_ms", session=req.headers["sid"])
    """
    ctx = lint(src, select={"JL006"})
    assert len(ctx.findings) == 2


def test_jl006_quiet_on_bounded_labels():
    src = """
        PHASES = ("connect", "stream")

        def setup(m, code):
            m.counter("x.responses", code=str(code))
            m.counter("x.decision", decision="admit")
            by_phase = {p: m.counter("x.failover", phase=p)
                        for p in PHASES}
            for d in ("admit", "queue", "shed"):
                m.counter("x.slo", decision=d)
            m.histogram("x.lat_ms", bounds=[1.0, 2.0])
    """
    ctx = lint(src, select={"JL006"})
    assert ctx.findings == []


def test_jl006_fires_on_unbounded_family_name():
    src = """
        def track(m, req, name):
            m.counter(f"req.{req.request_id}")       # per-request family
            m.counter(f"{name}.steps")               # plain var: fine
    """
    ctx = lint(src, select={"JL006"})
    assert len(ctx.findings) == 1
    assert "FAMILY" in ctx.findings[0].message


def test_jl006_ignores_numpy_histogram():
    src = """
        import jax.numpy as jnp

        def h(arr, bins):
            hist, _ = jnp.histogram(arr, bins=bins, range=(0, 1))
            return hist
    """
    ctx = lint(src, select={"JL006"})
    assert ctx.findings == []


# ------------------------------------------------------------------ JL007 --

def test_jl007_fires_on_engine_calls_from_async():
    src = """
        async def completions(self, body):
            self.engine.submit(body)
            eng = self.engine
            eng.step()
    """
    ctx = lint(src, rel="paddle_tpu/serving/server.py", select={"JL007"})
    assert len(ctx.findings) == 2


def test_jl007_quiet_on_engine_thread_and_reads():
    src = """
        def _engine_loop(self):
            self.engine.step()                 # engine thread owns it

        async def statusz(self):
            eos = self.engine.gen_cfg.eos_token_id   # attribute READ
            cfg = self.engine.config           # read of a plain value...
            return cfg.get("timeout", eos)     # ...whose methods are fine

        async def route(self):
            if self.engine_alive():            # server method, not engine
                return 200
    """
    ctx = lint(src, rel="paddle_tpu/serving/server.py", select={"JL007"})
    assert ctx.findings == []


def test_jl007_covers_fleet_package():
    src = """
        async def drain(self):
            self.engine.step()
    """
    ctx = lint(src, rel="paddle_tpu/fleet/supervisor.py", select={"JL007"})
    assert len(ctx.findings) == 1
    ctx = lint(src, rel="paddle_tpu/io/h.py", select={"JL007"})
    assert ctx.findings == []


def test_jl007_covers_migration_module():
    """ISSUE 14 satellite: engine single-ownership applies to the
    transfer module too — imports/exports must ride the control-op
    seam, never call the engine from an async def."""
    src = """
        async def migrate(self):
            self.engine._drain()
    """
    ctx = lint(src, rel="paddle_tpu/inference/migration.py",
               select={"JL007"})
    assert len(ctx.findings) == 1


def test_jl007_covers_controlplane_package():
    """ISSUE 19 satellite: engine single-ownership applies on the
    control plane too — membership/ring code must never reach into an
    engine from its async defs."""
    src = """
        async def takeover(self):
            self.engine.step()
    """
    ctx = lint(src, rel="paddle_tpu/controlplane/plane.py",
               select={"JL007"})
    assert len(ctx.findings) == 1


def test_jl007_covers_trace_collector_module():
    """ISSUE 20 satellite: the collector assembles timelines FROM span
    exports — it must never reach into an engine from an async def."""
    src = """
        async def assemble(self, trace_id):
            self.engine.step()
    """
    ctx = lint(src, rel="paddle_tpu/observability/collector.py",
               select={"JL007"})
    assert len(ctx.findings) == 1


def test_jl008_fires_on_hardcoded_axis_in_shard_map_module():
    """ISSUE 18 satellite: a module that builds shard_map programs must
    pull collective axis names from the module-level mesh-axis constant
    — a literal repeated at the call site survives an axis rename and
    silently splits the axis_index/all_gather pair."""
    src = """
        import jax

        MP_AXIS = "mp"

        def build(mesh):
            def body(x):
                i = jax.lax.axis_index("mp")
                y = jax.lax.all_gather(x, "mp", axis=0, tiled=True)
                z = jax.lax.psum(x, axis_name="mp")
                return i, y, z
            return jax.shard_map(body, mesh=mesh,
                                 in_specs=None, out_specs=None)
    """
    ctx = lint(src, rel="paddle_tpu/inference/generation.py",
               select={"JL008"})
    assert len(ctx.findings) == 3


def test_jl008_quiet_on_constant_and_threaded_axis():
    src = """
        import jax

        MP_AXIS = "mp"

        def build(mesh, cache):
            axis = cache.axis
            def body(x):
                i = jax.lax.axis_index(MP_AXIS)
                y = jax.lax.all_gather(x, MP_AXIS, axis=0, tiled=True)
                z = jax.lax.psum(x, axis)          # threaded variable
                t = jax.lax.pmean(x, (MP_AXIS,))   # tuple of constants
                return i, y, z, t
            return jax.shard_map(body, mesh=mesh,
                                 in_specs=None, out_specs=None)
    """
    ctx = lint(src, rel="paddle_tpu/inference/generation.py",
               select={"JL008"})
    assert ctx.findings == []


def test_jl008_quiet_outside_shard_map_modules():
    """Modules that never mention shard_map trace their collectives
    under axis binders owned elsewhere — the constant-discipline
    contract does not reach them."""
    src = """
        import jax

        def loss(x):
            return jax.lax.psum(x, "dp")
    """
    ctx = lint(src, rel="paddle_tpu/models/other.py", select={"JL008"})
    assert ctx.findings == []


# ------------------------------------------------- suppressions (JL000) --

def test_suppression_with_reason_is_honored():
    src = """
        def probe(x):
            return x.item()  # jaxlint: disable=JL002 -- user-facing eager read, documented
    """
    ctx = lint(src, rel="paddle_tpu/inference/foo.py", select={"JL002"})
    assert ctx.findings == []
    assert ctx.suppressed == 1


def test_suppression_without_reason_is_a_finding_and_not_honored():
    src = """
        def probe(x):
            return x.item()  # jaxlint: disable=JL002
    """
    ctx = lint(src, rel="paddle_tpu/inference/foo.py", select={"JL002"})
    assert rules_fired(ctx) == ["JL000", "JL002"]


def test_standalone_suppression_covers_next_line():
    src = """
        def probe(x):
            # jaxlint: disable=JL002 -- drain-time read
            return x.item()
    """
    ctx = lint(src, rel="paddle_tpu/inference/foo.py", select={"JL002"})
    assert ctx.findings == []


def test_suppression_is_rule_scoped():
    src = """
        def probe(x):
            return x.item()  # jaxlint: disable=JL001 -- wrong rule id on purpose
    """
    ctx = lint(src, rel="paddle_tpu/inference/foo.py", select={"JL002"})
    assert rules_fired(ctx) == ["JL002"]


def test_disable_file_suppression():
    src = """
        # jaxlint: disable-file=JL002 -- synthetic fixture, syncs are the point
        def probe(x):
            return x.item()

        def probe2(x):
            return x.item()
    """
    ctx = lint(src, rel="paddle_tpu/inference/foo.py", select={"JL002"})
    assert ctx.findings == []
    assert ctx.suppressed == 2


def test_suppression_covers_multiline_statement():
    # a trailing comment on ANY physical line of a black-wrapped call
    # covers the whole statement (findings anchor to its first line)
    src = """
        import time

        async def handler():
            time.sleep(
                1)  # jaxlint: disable=JL005 -- test shim, loop is idle here
    """
    ctx = lint(src, rel="paddle_tpu/serving/h.py", select={"JL005"})
    assert ctx.findings == []
    assert ctx.suppressed == 1


def test_prose_mentioning_jaxlint_is_not_a_directive():
    src = """
        # see docs/jaxlint.md for how to disable rules
        X = 1
    """
    ctx = lint(src)
    assert ctx.findings == []


def test_directive_shaped_but_malformed_comment_is_jl000():
    src = """
        # jaxlint: disable JL002 -- missing the equals sign
        X = 1
    """
    ctx = lint(src)
    assert rules_fired(ctx) == ["JL000"]


def test_jl005_urllib_parse_is_not_blocking():
    src = """
        import urllib.parse
        import urllib.request

        async def handler(q):
            ok = urllib.parse.quote(q)
            return urllib.request.urlopen("http://x/" + ok)
    """
    ctx = lint(src, rel="paddle_tpu/router/h.py", select={"JL005"})
    assert len(ctx.findings) == 1
    assert "urlopen" in ctx.findings[0].message


# ------------------------------------------------------- CLI + baseline --

@pytest.fixture
def bad_tree(tmp_path):
    d = tmp_path / "serving"
    d.mkdir()
    (d / "h.py").write_text(textwrap.dedent("""
        import time

        async def handler():
            time.sleep(1)
    """))
    return d


def test_cli_exit_codes_and_json(bad_tree, capsys):
    assert lint_main([str(bad_tree)]) == 1
    assert lint_main([str(bad_tree), "--select=JL001"]) == 0
    assert lint_main([str(bad_tree), "--format=json"]) == 1
    out = capsys.readouterr().out
    doc = json.loads(out[out.rindex('{"analyzer"'):]
                     if '{"analyzer"' in out else out[out.index("{"):])
    assert doc["counts"] == {"JL005": 1}
    assert doc["findings"][0]["rule"] == "JL005"


def test_cli_baseline_roundtrip(bad_tree, tmp_path, capsys):
    base = tmp_path / "base.json"
    assert lint_main([str(bad_tree), "--write-baseline", str(base)]) == 0
    assert lint_main([str(bad_tree), "--baseline", str(base)]) == 0
    # a NEW finding still fails past the baseline
    (bad_tree / "h2.py").write_text(textwrap.dedent("""
        import time

        async def handler2():
            time.sleep(1)
    """))
    assert lint_main([str(bad_tree), "--baseline", str(base)]) == 1


def test_cli_rejects_unknown_rule_ids(bad_tree, capsys):
    # a typo'd selector must not run zero rules and exit 0
    assert lint_main([str(bad_tree), "--select=JL05"]) == 2
    assert lint_main([str(bad_tree), "--ignore=JL999"]) == 2


def test_cli_rejects_missing_and_empty_paths(tmp_path, capsys):
    # a typo'd path must not analyze 0 files and exit 0
    assert lint_main([str(tmp_path / "no_such_dir")]) == 2
    empty = tmp_path / "empty"
    empty.mkdir()
    assert lint_main([str(empty)]) == 2


def test_cli_list_rules(capsys):
    assert lint_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in ("JL001", "JL002", "JL003", "JL004", "JL005", "JL006",
                "JL007"):
        assert rid in out


def test_rule_catalog_complete():
    cat = analysis.rule_catalog()
    assert sorted(cat) == ["JL001", "JL002", "JL003", "JL004", "JL005",
                           "JL006", "JL007", "JL008"]
    for cls in cat.values():
        assert cls.title and cls.rationale


# ------------------------------------------------- whole-package gate --

def _package_dir() -> Path:
    import paddle_tpu
    return Path(paddle_tpu.__file__).resolve().parent


def test_package_is_clean():
    """THE tier-1 gate: zero unsuppressed findings over paddle_tpu/,
    and every suppression carries a reason (a reasonless one surfaces
    as JL000 right here)."""
    ctx = analysis.run([str(_package_dir())])
    assert ctx.findings == [], "\n" + "\n".join(
        f.render() for f in ctx.findings)
    assert ctx.files > 150          # the whole package was actually seen


def test_cli_module_invocation_matches_gate():
    """`python -m paddle_tpu.analysis paddle_tpu/` — the acceptance
    invocation — exits 0 on the clean tree."""
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.analysis", str(_package_dir())],
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 finding(s)" in proc.stdout


def test_package_report_shape():
    rep = analysis.package_report()
    assert rep["analyzer"] == "jaxlint"
    assert rep["version"] == analysis.__version__
    assert rep["counts"] == {} and rep["findings"] == []


def test_jl005_jl007_cover_issue15_modules():
    """ISSUE 15 satellite: the quarantine (router/) and cascade-breaker
    (fleet/) modules live on the router's event-loop plane — JL005
    (blocking calls in async defs) and JL007 (engine single-ownership)
    scope to them exactly like the rest of their packages."""
    for rel in ("paddle_tpu/router/quarantine.py",
                "paddle_tpu/fleet/breaker.py"):
        ctx = lint(_ASYNC_POS, rel=rel, select={"JL005"})
        assert len(ctx.findings) == 3, rel
        ctx = lint("""
            async def probe(self):
                self.engine.step()
        """, rel=rel, select={"JL007"})
        assert len(ctx.findings) == 1, rel
    # their sync verbs (supervisor-thread callers) stay exempt
    src = """
        import time

        def record_death(self, now=None):
            time.sleep(0.0)
    """
    ctx = lint(src, rel="paddle_tpu/fleet/breaker.py", select={"JL005"})
    assert ctx.findings == []
