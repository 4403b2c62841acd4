"""The traffic generator: deterministic per seed, the same work for every
seed, and the stated distributions."""

import json
import math
from pathlib import Path

import numpy as np
import pytest

from chipbench.loadgen import n_requests, output_lengths, schedule

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"


def chat():
    return json.loads((TRAFFIC / "ds7b-chat-mixed.json").read_text())


def batch():
    return json.loads((TRAFFIC / "ds7b-batch-q16.json").read_text())


def test_same_seed_same_schedule():
    a = schedule(chat(), 2**31 + 12345, 51, 102400)
    b = schedule(chat(), 2**31 + 12345, 51, 102400)
    assert [(p.due, p.prompt, p.max_new, p.level) for p in a] == \
           [(p.due, p.prompt, p.max_new, p.level) for p in b]


def test_seeds_change_order_not_work():
    a = schedule(chat(), 1, 51, 102400)
    b = schedule(chat(), 2, 51, 102400)
    assert [p.prompt for p in a] != [p.prompt for p in b]
    for key in (lambda p: len(p.prompt), lambda p: p.max_new, lambda p: p.level):
        assert sorted(map(key, a)) == sorted(map(key, b))
    assert len(a) == len(b) == n_requests(chat(), 51)
    assert abs(a[-1].due - b[-1].due) < 60 / chat()["rate_rps"]


def test_every_prefix_holds_nearly_the_same_work():
    # a window serves only the first part of a backlog: that part's work
    # must not depend on the seed
    loads = []
    for seed in range(2**31, 2**31 + 6):
        plan = schedule(batch(), seed, 51, 102400)[:85]
        loads.append((sum(p.max_new for p in plan), sum(len(p.prompt) for p in plan)))
    for i in (0, 1):
        vals = [x[i] for x in loads]
        assert max(vals) / min(vals) < 1.08, vals
    plan = schedule(chat(), 5, 51, 102400)
    for k in range(4, len(plan) + 1, 4):   # f32 keeps its share in every prefix
        assert abs(sum(p.level == "f32" for p in plan[:k]) - k / 4) <= 1


def test_distributions():
    t = chat()
    plan = schedule(t, 7, 51, 102400)
    plens = np.array([len(p.prompt) for p in plan])
    outs = np.array([p.max_new for p in plan])
    assert plens.min() >= t["prompt"]["min"] and plens.max() <= t["prompt"]["max"]
    assert outs.min() >= t["output"]["min"] and outs.max() <= t["output"]["max"]
    assert abs(np.median(plens) - t["prompt"]["median"]) <= 0.05 * t["prompt"]["median"]
    assert abs(np.median(outs) - t["output"]["median"]) <= 0.05 * t["output"]["median"]
    # log-lengths spread as stated (before clipping, most of the mass)
    assert np.std(np.log(plens)) == pytest.approx(t["prompt"]["sigma"], rel=0.25)
    share = np.mean([p.level == "f32" for p in plan])
    assert share == pytest.approx(t["levels"]["f32"], abs=1.0 / len(plan))
    dues = np.array([p.due for p in plan])
    assert dues[0] >= 0.0 and dues[-1] < 51 and np.all(np.diff(dues) >= 0)
    # the window holds the stated rate's count
    assert len(plan) == round(t["rate_rps"] * 51)
    assert all(1 <= tok < 102400 for p in plan for tok in p.prompt)


def test_arrivals_are_poisson():
    # a Poisson process given its count: due times uniform over the window,
    # gaps as irregular as exponential ones (coefficient of variation near
    # 1), and a short gap as likely next to a short gap as anywhere
    # (consecutive gaps uncorrelated, up to the -1/n of a fixed count)
    t = chat()
    dues, gaps, pairs = [], [], []
    for seed in range(2**31 + 100, 2**31 + 400):
        d = np.array([p.due for p in schedule(t, seed, 51, 102400)])
        dues.extend(d / 51)
        g = np.diff(d)
        gaps.extend(g)
        pairs.extend(zip(g[:-1], g[1:]))
    assert np.mean(dues) == pytest.approx(0.5, abs=0.01)
    assert np.mean(np.asarray(dues) < 0.25) == pytest.approx(0.25, abs=0.01)
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, abs=0.1)
    a, b = np.asarray(pairs).T
    assert abs(np.corrcoef(a, b)[0, 1]) < 0.1


def test_backlog_and_output_lengths():
    t = batch()
    plan = schedule(t, 3, 51, 102400)
    assert len(plan) == t["backlog"] and all(p.due == 0.0 for p in plan)
    assert {p.level for p in plan} == {"q16_16"}
    assert sorted({p.max_new for p in plan}) == output_lengths(t, 51)
    assert math.isclose(sum(p.max_new for p in plan),
                        sum(p.max_new for p in schedule(t, 4, 51, 102400)))
