"""chipbench: the on-chip benchmark of paddle_tpu (BENCHMARK.json's harness).

Everything that belongs to one configuration, one traffic mix, one cell, one
way of offering traffic, one kernel or one per-layer metric is a file of its
own, found by the name in ``BENCHMARK.json``:

    configs/<config>.json        sizes, source, what was cut, what is assumed
    traffic/<mix>.json           kind, parameters, schedule_seed, trace offset
    workloads/<cell>.json        config + traffic + chips + why + limits
    drivers/<kind>.py            open_loop_serve, closed_loop_serve, train
    layer_metrics/<metric>.py    one reader: trace + counters -> one number
    kernels/<kernel>.py          operations and bytes of one kernel
    references/<family>.py       plain float32 jax.numpy forward / loss / grads
    harness/                     schedule, weights, client, trace reduction,
                                 checks, the last line

No name of a cell, a configuration or a mix appears in any ``.py`` here.
"""
