"""The work of a cell is a property of the cell: the same for every
``--seed``, drawn once from the mix's ``schedule_seed``."""

import glob
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench.harness import schedule  # noqa: E402

MIXES = sorted(glob.glob(os.path.join(ROOT, "chipbench", "traffic",
                                      "*.json")))


def _load(path):
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_two_seeds_get_the_same_work(path):
    t = _load(path)
    a, b = schedule.describe(t, 51.0), schedule.describe(t, 51.0)
    assert a == b
    if t["kind"] == "train":
        x = schedule.train_batches(t, 11, 32768, 2)
        y = schedule.train_batches(t, 2**31 + 5, 32768, 2)
        assert x.shape == y.shape == (t["batches"],
                                      2 * t["rows_per_replica"],
                                      t["seq_len"] + 1)
        assert not np.array_equal(x, y)          # the ids are the seed's
        rows = x.reshape(-1, x.shape[-1])
        assert len({r.tobytes() for r in rows}) == len(rows)  # all differ
        return
    items = schedule.requests(t)
    again = schedule.requests(json.loads(json.dumps(t)))
    assert items == again and len(items) > 50
    # --seed reaches token ids only, never a length or an arrival
    it = items[0]
    p1 = schedule.token_ids(1, it.index, it.prompt_len, 32000)
    p2 = schedule.token_ids(2**31 + 7, it.index, it.prompt_len, 32000)
    assert len(p1) == len(p2) == it.prompt_len and p1 != p2
    assert min(p1) >= 1 and max(p1) < 32000
    assert p1 == schedule.token_ids(1, it.index, it.prompt_len, 32000)


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_the_schedule_changes_with_schedule_seed(path):
    t = _load(path)
    if t["kind"] == "train":
        pytest.skip("a training mix has shapes only, no drawn schedule")
    other = dict(t, schedule_seed=t["schedule_seed"] + 1)
    assert schedule.requests(t) != schedule.requests(other)


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_the_traced_window_is_pinned_in_the_mix(path):
    t = _load(path)
    assert t["trace"]["offset_s"] + t["trace"]["seconds"] < 51
    assert t["trace"]["seconds"] >= 1.0


def test_lengths_follow_the_mix_and_its_clips():
    t = _load(os.path.join(ROOT, "chipbench/traffic/chat-steady.json"))
    items = schedule.requests(t)
    p = np.array([i.prompt_len for i in items])
    o = np.array([i.output_len for i in items])
    assert p.min() >= 64 and p.max() <= 2048 and 350 < np.median(p) < 750
    assert o.min() >= 16 and o.max() <= 256 and 45 < np.median(o) < 90
    due = np.array([i.due_s for i in items])
    assert np.all(np.diff(due) > 0)
    rate = t["arrivals"]["rate_rps"]
    assert abs(len(items) / t["arrivals"]["horizon_s"] - rate) < 0.25 * rate


def test_another_rate_is_the_same_draw_compressed():
    t = _load(os.path.join(ROOT, "chipbench/traffic/chat-steady.json"))
    fast = json.loads(json.dumps(t))
    fast["arrivals"]["rate_rps"] = 2 * t["arrivals"]["rate_rps"]
    a, b = schedule.requests(t), schedule.requests(fast)
    n = len(a)
    assert [i.prompt_len for i in b[:n]] == [i.prompt_len for i in a]
    assert np.allclose([i.due_s * 2 for i in b[:n]], [i.due_s for i in a])


def test_the_sample_is_the_seeds_and_keeps_the_longest():
    got = schedule.sample(5, list(range(40)), 8, must=[17])
    assert got[0] == 17 and len(got) == len(set(got)) == 8
    assert got == schedule.sample(5, list(range(40)), 8, must=[17])
    assert got != schedule.sample(6, list(range(40)), 8, must=[17])
