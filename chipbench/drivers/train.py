"""Training: ``PretrainStep.train_step`` on the mix's fixed batch shapes,
steps back to back, each ended by ``block_until_ready``, for the window.

Set-up builds ONE object (the compiled step with its state), drives it from
the seed through its first steps (through the window's own call and feed,
on rows that all differ) and hands that same object to the window.  The
reference follows those first steps in float32 once the window has closed
and the state is freed."""

from __future__ import annotations

import gc
import importlib
import statistics
import time

import numpy as np

from chipbench.harness import schedule, weights
from chipbench.harness.checks import emit
from chipbench.harness.core import process_age_s


def _leaf_sq(tree) -> dict:
    """{'embed': .., 'blocks.<name>': ..}: sum of squares of every leaf."""
    import jax.numpy as jnp
    out = {k: jnp.sum(jnp.square(v.astype(jnp.float32)))
           for k, v in tree.items() if k != "blocks"}
    out.update({f"blocks.{k}": jnp.sum(jnp.square(v.astype(jnp.float32)))
                for k, v in tree["blocks"].items()})
    return out


def worst_leaf_gap(got: dict, want: dict) -> tuple:
    """The largest |got - want| over the leaves, each measured against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero).  (gap, leaf)."""
    med = statistics.median(want.values())
    gaps = {k: abs(got[k] - want[k]) / max(want[k], med) for k in want}
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


def compare(run, got: dict, want: dict, prefix: str = "") -> dict:
    """The numbers that decide ``correct`` for a training cell."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(got["losses"], want["losses"]))
    g_gap, g_leaf = worst_leaf_gap(got["grad_norms"], want["grad_norms"])
    c_gap, c_leaf = worst_leaf_gap(got["change_norms"], want["change_norms"])
    out = {"loss_rel_gap": loss_gap, "first_grad_norm_gap": g_gap,
           "first_grad_norm_leaf": g_leaf, "param_change_norm_gap": c_gap,
           "param_change_norm_leaf": c_leaf, "losses": got["losses"],
           "reference_losses": want["losses"]}
    emit(phase=prefix + "compare", **out)
    return out


def run(r) -> None:
    import jax
    import jax.numpy as jnp

    cfg = r.cell.config
    prog = importlib.import_module("chipbench.programs." + cfg["family"])
    ref = importlib.import_module("chipbench.references." + cfg["family"])
    m, t = r.model, r.traffic
    layout, hyper = cfg.get("layout", {}), cfg["optimizer"]
    chips = r.cell.chips
    if layout.get("dp", 1) * layout.get("mp", 1) != chips:
        raise ValueError(f"layout {layout} does not fill {chips} chip(s)")
    emit(phase="schedule", **schedule.describe(t, r.seconds))
    if not r.rehearse:
        emit(phase="flash_pins",
             pins=prog.pin_flash_tiles(cfg.get("flash_pins", [])))
    ps, state, made_sh = prog.build_train(m, layout, hyper, r.seed)
    emit(phase="built", age_s=process_age_s(), layout=layout,
         params=ref.count_params(m, m["num_hidden_layers"]))
    host = schedule.train_batches(t, r.seed, m["vocab_size"],
                                  layout.get("dp", 1))
    feed = [ps.shard_batch(b[:, :-1], b[:, 1:]) for b in host]
    tokens_per_step = int(host.shape[1] * (host.shape[2] - 1))
    n_ref = int(r.cell.extras.get("reference_steps", 3))
    leaves = ref.leaf_specs(m)
    dt = m["torch_dtype"]
    count = [0]

    def step(state):
        ids, labels = feed[count[0] % len(feed)]
        with jax.profiler.TraceAnnotation("bench.train_step", step=count[0]):
            state, loss = ps.train_step(state, ids, labels)
            loss.block_until_ready()
        count[0] += 1
        return state, loss

    # the first steps, followed by the reference
    sq = jax.jit(_leaf_sq)
    got = {"losses": []}
    for i in range(n_ref):
        state, loss = step(state)
        got["losses"].append(float(loss))
        if i == 0:
            # the first gradient as the optimizer got it: m = (1 - b1) g
            got["grad_norms"] = {
                k: float(jnp.sqrt(v)) / (1.0 - hyper["beta1"])
                for k, v in sq(state["m"]).items()}
    p0 = weights.make(r.seed, leaves, m["num_hidden_layers"], dt,
                      shardings=made_sh)
    diff = jax.jit(lambda p, z: _leaf_sq({
        "embed": p["embed"].astype(jnp.float32) - z["embed"],
        "head": p["head"].astype(jnp.float32) - z["head"],
        "norm": p["norm"].astype(jnp.float32) - z["norm"],
        "blocks": {k: v[0].astype(jnp.float32) - z[k]
                   for k, v in p["blocks"].items()}}))
    got["change_norms"] = {k: float(jnp.sqrt(v))
                           for k, v in diff(state["params"], p0).items()}
    del p0
    state, _ = step(state)              # one more: the warm, steady call
    r.ready()

    r.watch.start()
    s0 = count[0]
    t0 = time.perf_counter()
    elapsed = 0.0
    while elapsed < r.seconds:
        state, loss = step(state)
        elapsed = time.perf_counter() - t0
        r.tracer.tick(elapsed)
    steps = count[0] - s0
    r.note_compiles(t0)
    r.tracer.finish()
    r.note_memory()
    last_loss = float(loss)
    r.attempted, r.failed = steps, 0
    r.results["window"] = {"window_s": elapsed, "steps": steps,
                           "tokens_per_step": tokens_per_step,
                           "step_s": elapsed / steps, "last_loss": last_loss}
    emit(phase="window", **r.results["window"])
    r.results["end_to_end"] = {
        "train_tok_s_chip": steps * tokens_per_step / elapsed / chips}
    del state, feed, ps
    gc.collect()

    # the reference, after the program's state is freed
    devices = jax.devices()[:chips]
    flat0 = weights.make_flat(r.seed, leaves, dt)
    seed_layer = lambda l: weights.make_layer(r.seed, leaves, l, dt)  # noqa: E731
    t1 = time.perf_counter()
    want = ref.train_steps(seed_layer, flat0, m["num_hidden_layers"], m,
                           host, hyper, n_ref, devices=devices)
    emit(phase="reference", reference_seconds=time.perf_counter() - t1,
         steps_followed=n_ref)
    out = compare(r, got, want)
    r.results["reference"] = out
    if r.control:
        low = ref.train_steps(seed_layer, flat0, m["num_hidden_layers"], m,
                              host, hyper, n_ref, precision="int8",
                              devices=devices)
        r.results["control"] = compare(r, low, want, prefix="control_int8_")
    ck = r.checks
    ck.add("compiles_in_window", r.results["compiles_in_window"], 0)
    ck.add("loss_finite", float(np.isfinite(last_loss)), 1, "==")
    for name in ("loss_rel_gap", "first_grad_norm_gap",
                 "param_change_norm_gap"):
        ck.add(name, out[name], r.cell.limit(name))
