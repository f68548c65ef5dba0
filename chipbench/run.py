"""``python -m chipbench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell.

Earlier lines of stdout carry phases, counts and every number compared
beside its limit; the LAST line is the result (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, ``breakdown`` with ``--trace 1``, and
last ``checks``: each number compared beside its limit, which are also the
last lines of stderr).
Without a TPU (or with fewer chips than the cell asks for) it exits with
code 3 and prints no result.  ``--rehearse 1`` is the builder's CPU
rehearsal at tiny sizes: it prints ``"rehearsal": true`` and no metric.
``--control 1`` also reads the lower-precision control (builder's runs).
"""

from __future__ import annotations

import argparse
import os
import sys
import traceback


def parse(argv=None):
    ap = argparse.ArgumentParser(prog="chipbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--set", action="append", default=[],
                    metavar="traffic.key=value",
                    help="builder's sweeps only: override one number of "
                         "the mix (e.g. arrivals.rate_rps=3); the result "
                         "line then says \"overridden\"")
    return ap.parse_args(argv)


def _override(traffic: dict, pairs: list) -> None:
    for pair in pairs:
        key, value = pair.split("=", 1)
        node = traffic
        *path, last = key.split(".")
        for k in path:
            node = node[k]
        node[last] = type(node[last])(float(value))


def main(argv=None) -> int:
    args = parse(argv)
    from chipbench.harness import spec
    from chipbench.harness.checks import emit, last_line
    root = spec.ROOT
    os.chdir(root)
    if not os.path.isdir(os.path.join(root, "paddle_tpu")):
        print("chipbench: the system under test (paddle_tpu/) is not in "
              f"{root}; nothing to measure", file=sys.stderr)
        return 3
    try:
        cell = spec.load_cell(args.workload, root)
    except spec.SpecError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    _override(cell.traffic, args.set)

    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        if cell.chips > 1:
            flag = f"--xla_force_host_platform_device_count={cell.chips}"
            if flag not in os.environ.get("XLA_FLAGS", ""):
                os.environ["XLA_FLAGS"] = (
                    os.environ.get("XLA_FLAGS", "") + " " + flag).strip()
    import jax
    from chipbench.harness import core
    try:
        device = core.require_chips(cell.chips, bool(args.rehearse))
    except (core.NoChip, RuntimeError) as e:
        print(f"chipbench: {e}; this benchmark measures the chip and does "
              "not run without it", file=sys.stderr)
        return 3
    # every program, however quickly it compiled, goes to the persistent
    # cache (the program fixes the directory: JAX_COMPILATION_CACHE_DIR, or
    # .paddle_tpu_cache/xla inside the checkout)
    import paddle_tpu  # noqa: F401  (sets the cache directory)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from paddle_tpu import flags
    flags.set_flags(dict(cell.config.get("program_flags", {})))
    if args.rehearse:
        from paddle_tpu.kernels import (flash_attention,  # noqa: F401
                                        paged_attention)  # noqa: F401
        flags.set_flags({"flash_attention_interpret": True,
                         "paged_attention_interpret": True})

    run = core.Run(cell, args, device)
    emit(phase="start", workload=cell.name, config=cell.config_name,
         traffic=cell.traffic_name, kind=cell.kind, seed=run.seed,
         seconds=run.seconds, trace=args.trace, rehearsal=run.rehearse,
         overridden=args.set or None, device=device,
         compile_cache=jax.config.jax_compilation_cache_dir)
    driver = spec.load_module(root, "drivers", cell.kind)
    try:
        driver.run(run)
    except Exception:
        traceback.print_exc()
        print("chipbench: the run failed; no result", file=sys.stderr)
        return 1

    metrics, breakdown = {}, None
    dev = dict(device, memory_peak_bytes=run.memory_peak)
    if args.trace:
        from chipbench.harness import metrics_out
        try:
            metrics, breakdown, extra = metrics_out.per_layer(run)
        except Exception:
            traceback.print_exc()
            print("chipbench: the trace could not be reduced; no result",
                  file=sys.stderr)
            return 1
        dev.update(extra)
    else:
        e2e = dict(run.results.get("end_to_end", {}), setup_s=run.setup_s)
        for entry in cell.end_to_end:
            value = e2e.get(entry["name"])
            if value is None:
                run.checks.fail(entry["name"], "the run produced no value")
                continue
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    if run.rehearse:
        metrics = {}
    last_line(run.checks, run.attempted, run.failed, metrics, dev,
              breakdown, rehearsal=run.rehearse)
    return 0


if __name__ == "__main__":
    sys.exit(main())
