"""The traffic generator: seeded, the same work for every seed, within
its clips and the cache length."""

import numpy as np
import pytest

from bench import arrivals, spec
from bench_helpers import BENCHMARK


def _cells():
    return [spec.load_cell(w["name"]) for w in BENCHMARK["workloads"]]


def _sizes(sched):
    return sorted((r.prompt_len, r.max_new) for r in sched)


@pytest.mark.parametrize("cell", _cells(), ids=lambda c: c.name)
def test_same_seed_same_schedule(cell):
    kv, vocab = cell.config["serve"]["kv_len"], cell.config["vocab_size"]
    a = arrivals.schedule(cell.traffic, 2**31 + 17, 45, vocab, kv)
    b = arrivals.schedule(cell.traffic, 2**31 + 17, 45, vocab, kv)
    assert [(r.due, r.max_new, r.prompt.tolist()) for r in a] == \
        [(r.due, r.max_new, r.prompt.tolist()) for r in b]


@pytest.mark.parametrize("cell", _cells(), ids=lambda c: c.name)
def test_every_seed_gets_the_same_work(cell):
    kv, vocab = cell.config["serve"]["kv_len"], cell.config["vocab_size"]
    a = arrivals.schedule(cell.traffic, 3, 45, vocab, kv)
    b = arrivals.schedule(cell.traffic, 2**40 + 3, 45, vocab, kv)
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in b]
    # the sizes and due times, in their order, are the seed's neither
    assert [(r.due, r.prompt_len, r.max_new) for r in a] == \
        [(r.due, r.prompt_len, r.max_new) for r in b]


@pytest.mark.parametrize("cell", _cells(), ids=lambda c: c.name)
def test_lengths_within_clips_and_cache(cell):
    t, kv = cell.traffic, cell.config["serve"]["kv_len"]
    vocab = cell.config["vocab_size"]
    sched = arrivals.schedule(t, 99, 45, vocab, kv)
    for r in sched:
        assert t["prompt"]["min"] <= r.prompt_len <= t["prompt"]["max"]
        assert t["output"]["min"] <= r.max_new <= t["output"]["max"]
        assert r.prompt_len + r.max_new <= kv
        assert 0 <= r.prompt.min() and r.prompt.max() < vocab
    assert set(arrivals.prompt_lengths(t, 45)) == {r.prompt_len
                                                   for r in sched}


def test_open_loop_due_times_fill_the_window():
    t = {"loop": "open", "arrivals": {"process": "gamma", "cv": 2.0,
                                      "rate_per_s": 4.0},
         "prompt": {"median": 10, "sigma": 0.5, "min": 2, "max": 20},
         "output": {"median": 4, "sigma": 0.5, "min": 1, "max": 8}}
    sched = arrivals.schedule(t, 5, 30, 100, 64)
    dues = [r.due for r in sched]
    assert len(sched) == 120
    assert dues[0] == 0.0 and dues == sorted(dues) and dues[-1] < 30


def test_gaps_are_the_arrival_process():
    rng = np.random.default_rng(0)
    pois = arrivals.gaps({"process": "poisson"}, 4000, 1000.0, rng)
    burst = arrivals.gaps({"process": "gamma", "cv": 2.0}, 4000, 1000.0, rng)
    for g in (pois, burst):
        assert np.isclose(g.sum(), 1000.0)
    cv = lambda g: g.std() / g.mean()
    assert abs(cv(pois) - 1.0) < 0.1
    assert abs(cv(burst) - 2.0) < 0.3


def test_lengths_are_lognormal_quantiles():
    spec_ = {"median": 100, "sigma": 0.5, "min": 1, "max": 10**6}
    q = arrivals.length_quantiles(spec_, 101)
    assert q[50] == 100 and list(q) == sorted(q)


def test_traffic_that_cannot_fit_is_refused():
    t = {"loop": "closed", "clients": 1, "pool": 2,
         "prompt": {"median": 10, "sigma": 0.1, "min": 10, "max": 40},
         "output": {"median": 10, "sigma": 0.1, "min": 10, "max": 30}}
    with pytest.raises(ValueError):
        arrivals.schedule(t, 1, 10, 100, 64)
