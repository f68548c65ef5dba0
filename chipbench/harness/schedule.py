"""The one traffic generator.  A mix is a data file; this reads it.

The WORK of a cell (arrival offsets, prompt and output lengths, the list of
documents, batch shapes, the traced window) is drawn once from the
``schedule_seed`` written in the mix's file and is the same for every
``--seed``.  ``--seed`` picks only the token ids (and the weights, and which
requests are compared with the reference): see ``token_ids``.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Item:
    """One request (serving) — ``due_s`` is None in a closed loop."""
    index: int
    due_s: float | None
    prompt_len: int
    output_len: int


def _draw(rng, spec: dict, n: int) -> np.ndarray:
    """``n`` whole numbers from a length distribution of a mix's file."""
    dist = spec["dist"]
    if dist == "fixed":
        out = np.full(n, spec["value"], np.float64)
    elif dist == "uniform":
        out = rng.integers(spec["min"], spec["max"] + 1, n).astype(np.float64)
    elif dist == "lognormal":
        out = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], n))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    lo, hi = spec.get("min"), spec.get("max")
    if lo is not None or hi is not None:
        out = np.clip(out, lo, hi)
    return np.rint(out).astype(np.int64)


def _rng(traffic: dict, stream: int):
    return np.random.default_rng([int(traffic["schedule_seed"]), stream])


def requests(traffic: dict) -> list:
    """The whole fixed list of a serving mix, in order.  Open loop
    (``arrivals`` given): due times from exponential or fixed gaps at
    ``rate_rps`` up to ``horizon_s``.  Closed loop: ``documents`` items with
    no due time; a client takes the next when its last is done."""
    arr = traffic.get("arrivals")
    if arr is not None:
        rate, horizon = float(arr["rate_rps"]), float(arr["horizon_s"])
        n_max = int(rate * horizon * 2) + 16
        if arr["process"] == "exponential":
            # unit-rate gaps scaled by the rate: another rate is the same
            # draw compressed, which keeps a sweep's points comparable
            gaps = _rng(traffic, 0).exponential(1.0, n_max) / rate
        elif arr["process"] == "fixed":
            gaps = np.full(n_max, 1.0 / rate)
        else:
            raise ValueError(f"unknown arrival process {arr['process']!r}")
        due = np.cumsum(gaps)
        due = due[due < horizon]
        n = len(due)
    else:
        n = int(traffic["documents"])
        due = [None] * n
    p = _draw(_rng(traffic, 1), traffic["prompt_len"], n)
    o = _draw(_rng(traffic, 2), traffic["output_len"], n)
    return [Item(i, None if due[i] is None else float(due[i]),
                 int(p[i]), int(o[i])) for i in range(n)]


def in_window(items: list, seconds: float) -> list:
    """The open-loop requests that fall due inside a window of this length."""
    return [it for it in items if it.due_s is not None and it.due_s < seconds]


def token_ids(seed: int, index: int, n: int, vocab: int) -> list:
    """The ``n`` token ids of item ``index`` under ``--seed``: content only,
    never a length.  Id 0 is left out (the engine pads with it)."""
    rng = np.random.default_rng([int(seed), 7, int(index)])
    return rng.integers(1, vocab, n).tolist()


def train_batches(traffic: dict, seed: int, vocab: int, replicas: int) -> np.ndarray:
    """``[n_batches, rows, seq_len + 1]`` int32 token ids: ``rows`` is the
    mix's rows per data-parallel replica times the replicas.  The shapes are
    the mix's; the ids are ``--seed``'s, every row different."""
    rows = int(traffic["rows_per_replica"]) * replicas
    rng = np.random.default_rng([int(seed), 11])
    return rng.integers(
        0, vocab, (int(traffic["batches"]), rows, int(traffic["seq_len"]) + 1)
    ).astype(np.int32)


def sample(seed: int, candidates: list, k: int, must: list = ()) -> list:
    """``k`` of ``candidates`` drawn from ``--seed``, with ``must`` in it."""
    keep = list(dict.fromkeys(must))
    rest = [c for c in candidates if c not in keep]
    rng = np.random.default_rng([int(seed), 13])
    rng.shuffle(rest)
    return keep + rest[:max(0, k - len(keep))]


def describe(traffic: dict, seconds: float) -> dict:
    """What a window of this mix holds: counts that must not depend on
    ``--seed`` (printed on an early line of every run)."""
    if traffic["kind"] == "train":
        return {"rows_per_replica": traffic["rows_per_replica"],
                "seq_len": traffic["seq_len"], "batches": traffic["batches"]}
    items = requests(traffic)
    if traffic.get("arrivals") is not None:
        items = in_window(items, seconds)
    return {"requests": len(items),
            "prompt_tokens": sum(i.prompt_len for i in items),
            "output_tokens": sum(i.output_len for i in items),
            "token_gaps": sum(i.output_len - 1 for i in items),
            "first_due_s": items[0].due_s if items else None,
            "last_due_s": items[-1].due_s if items else None}
