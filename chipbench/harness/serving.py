"""What the two serving drivers share: the annotated engine step, the log
of what each step worked on, and the comparison of served tokens with the
plain reference."""

from __future__ import annotations

import gc
import importlib
import time

import numpy as np

from chipbench.harness import schedule, weights
from chipbench.harness.checks import emit


def annotate_steps(eng, log: list | None) -> None:
    """Wrap ``engine.step`` in the benchmark's own ``bench.engine_step``
    span.  With ``log`` (traced runs only) each step also records which
    program ran (T) and every slot's (query length, context before it),
    read from the engine's host-side mirrors after the dispatch: what the
    paged kernel's operation count needs and shapes cannot give."""
    import jax
    inner = eng.step
    bucket = eng.g.prefill_bucket
    count = [0]

    def step():
        with jax.profiler.TraceAnnotation("bench.engine_step",
                                          step=count[0]):
            out = inner()
        count[0] += 1
        if log is not None:
            rows, prefill = [], False
            for b, req in enumerate(eng.slot_req):
                if req is None:
                    continue
                n = int(eng.host_lens[b])
                if n == int(eng.prompt_pos[b]) and n > 0:
                    q = n - bucket * ((n - 1) // bucket)       # last chunk
                    prefill = True
                else:
                    q = 1
                rows.append((q, n - q))
            log.append({"T": bucket if prefill else 1, "rows": rows,
                        "t": time.perf_counter()})
        return out

    eng.step = step


def free_engine(holder: dict) -> int:
    """Drop the engine, server and model; the bytes still in use."""
    import jax
    holder.clear()
    gc.collect()
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("bytes_in_use", 0))


def _gaps(logits: list, chosen: list) -> np.ndarray:
    """How far each chosen token's logit lies below the reference's best,
    over every position of every sequence."""
    return np.concatenate([
        lg.max(-1) - np.take_along_axis(lg, np.asarray(c)[:, None], -1)[:, 0]
        for lg, c in zip(logits, chosen)])


def _margins(lg: np.ndarray) -> np.ndarray:
    """How far the reference's best logit lies above its second, a position:
    a served token can differ from the best only where this is small."""
    top = np.partition(lg, -2, axis=-1)[:, -2:]
    return top[:, 1] - top[:, 0]


def readings(gaps: np.ndarray, margins: np.ndarray, flip: dict | None) -> dict:
    """What one set of chosen tokens reads against the reference: the gaps'
    widest, mean and 99th percentile and the share that are the reference's
    own first choice.  With ``flip`` (a cell whose file limits
    ``served_flip_share`` states ``gap_over``, ``margin_under``, ``plus``
    beside the limit) also ``flips``, the tokens that lie more than
    ``gap_over`` below the reference's best, ``near``, the positions where
    the reference's own best leads its second by under ``margin_under``,
    and ``flip_share`` = flips / (near + plus): a token can only differ
    where the reference's choice is close, and how many such positions a
    run's text has swings from seed to seed (seeded weights often repeat one
    token at wide margins), so the flips are held against them and not
    against all tokens; ``plus`` keeps a text with few close calls from
    reading a ratio of two small counts."""
    out = {"gap_max": float(gaps.max()), "gap_mean": float(gaps.mean()),
           "gap_p99": float(np.quantile(gaps, 0.99)),
           "greedy_agree_share": float((gaps == 0).mean())}
    if flip:
        out["flips"] = int((gaps > flip["gap_over"]).sum())
        out["near"] = int((margins < flip["margin_under"]).sum())
        out["flip_share"] = out["flips"] / (out["near"] + flip["plus"])
    return out


def check_served(run, finished: list, vocab: int) -> None:
    """Once the window has closed and the engine is freed: a sample of the
    finished requests, drawn from ``--seed`` with the longest in it; the
    reference runs once over each prompt with its served tokens; the number
    compared is how far a served token's logit lies below the reference's
    best, at every served position (``readings``; every position's numbers
    go out on the ``reference_detail`` line, so that a refused seed can be
    read again token by token).  ``finished``: [(item, served ids)].
    With ``--control 1`` the int8 reference's own first choices are read
    at the same positions (the comparison that must fail)."""
    cell, m = run.cell, run.model
    ref = importlib.import_module(
        "chipbench.references." + cell.config["family"])
    k = int(run.traffic["reference_sample"])
    by_index = {it.index: (it, toks) for it, toks in finished}
    longest = max(by_index, key=lambda i: by_index[i][0].prompt_len
                  + len(by_index[i][1]))
    picked = schedule.sample(run.seed, sorted(by_index), k, must=[longest])
    seqs, positions, served = [], [], []
    for i in picked:
        it, toks = by_index[i]
        prompt = schedule.token_ids(run.seed, it.index, it.prompt_len, vocab)
        seqs.append(prompt + list(toks[:-1]))
        positions.append(list(range(len(prompt) - 1,
                                    len(prompt) - 1 + len(toks))))
        served.append(np.asarray(toks))
    leaves = ref.leaf_specs(m)
    dt = m["torch_dtype"]
    flat = weights.make_flat(run.seed, leaves, dt)
    L = m["num_hidden_layers"]
    t0 = time.perf_counter()
    logits = ref.sequence_logits(
        lambda l: weights.make_layer(run.seed, leaves, l, dt), flat, L, m,
        seqs, positions)
    gaps = _gaps(logits, served)
    took = time.perf_counter() - t0
    margins = np.concatenate([_margins(lg) for lg in logits])
    flip = cell.extras["limits"].get("served_flip_share")
    detail = [{"index": i, "prompt_len": by_index[i][0].prompt_len,
               "served": [int(t) for t in c],
               "best": [int(t) for t in lg.argmax(-1)],
               "gaps": [float(g) for g in _gaps([lg], [c])],
               "margins": [float(g) for g in _margins(lg)]}
              for i, lg, c in zip(picked, logits, served)]
    out = {"reference_seconds": took, "requests_compared": len(picked),
           "sample": picked, "tokens": int(gaps.size),
           **readings(gaps, margins, flip)}
    if run.control:
        low = ref.sequence_logits(
            lambda l: weights.make_layer(run.seed, leaves, l, dt), flat, L,
            m, seqs, positions, precision="int8")
        cg = _gaps(logits, [lo.argmax(-1) for lo in low])
        for d, lg, lo in zip(detail, logits, low):
            d["control_gaps"] = [float(g)
                                 for g in _gaps([lg], [lo.argmax(-1)])]
        out["control_int8"] = readings(cg, margins, flip)
    emit(phase="reference", **out)
    emit(phase="reference_detail", requests=detail)
    run.results["reference"] = out
    ck = run.checks
    ck.add("served_tokens_compared", gaps.size,
           1 if run.rehearse else cell.limit("served_tokens_compared"), ">=")
    out["disagree_share"] = 1.0 - out["greedy_agree_share"]
    for name, key in (("served_logit_gap_max", "gap_max"),
                      ("served_logit_gap_p99", "gap_p99"),
                      ("served_logit_gap_mean", "gap_mean"),
                      ("served_disagree_share", "disagree_share"),
                      ("served_flip_share", "flip_share")):
        if name in cell.extras["limits"]:        # those the cell's file limits
            ck.add(name, out[key], cell.limit(name))
