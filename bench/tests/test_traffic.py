"""The traffic files: deterministic per seed, one prompt length per wave,
every request within its lane, the same work from every seed."""

import itertools
from collections import Counter

import pytest

from bench import spec, traffic

BENCH_JSON = spec.load_json(spec.CHECKOUT / "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH_JSON["workloads"]]
SEEDS = (0, 1, 2**31 + 5, 2**40 + 3)


def first(cell, seed, n=6):
    c = spec.load_cell(cell)
    e = c.config["engine"]
    return list(itertools.islice(traffic.waves(
        c.traffic, e["n_slots"], c.config["dims"]["vocab"], e["max_seq"], seed), n))


@pytest.mark.parametrize("cell", CELLS)
def test_deterministic_per_seed(cell):
    a, b = first(cell, 2**31 + 5), first(cell, 2**31 + 5)
    for x, y in zip(a, b):
        assert x.plen == y.plen and x.max_new == y.max_new
        assert (x.prompts == y.prompts).all()
    c = first(cell, 2**31 + 6)
    assert any((x.prompts != y.prompts).any() for x, y in zip(a, c))


@pytest.mark.parametrize("cell", CELLS)
def test_one_prompt_length_per_wave_within_the_lanes(cell):
    c = spec.load_cell(cell)
    e = c.config["engine"]
    for w in first(cell, 3):
        assert w.prompts.shape == (e["n_slots"], w.plen)
        assert len(w.max_new) == e["n_slots"]
        assert w.plen + max(w.max_new) <= e["max_seq"] - 1
        assert w.prompts.min() >= 0 and w.prompts.max() < c.config["dims"]["vocab"]


@pytest.mark.parametrize("cell", CELLS)
def test_every_seed_does_the_same_work(cell):
    """Same prompt lengths in the same order, same max_new multiset."""
    runs = [first(cell, s) for s in SEEDS]
    for waves in zip(*runs):
        assert len({w.plen for w in waves}) == 1
        assert len({tuple(sorted(w.max_new)) for w in waves}) == 1


def test_max_new_sets():
    u = traffic.max_new_set({"dist": "uniform", "min": 4, "max": 16}, 16)
    # quantile (i + 1/2) / 16 of the 13 values 4..16
    assert u == [4 + int((i + 0.5) / 16 * 13) for i in range(16)]
    assert u[0] == 4 and u[-1] == 16 and u == sorted(u)
    s = traffic.max_new_set({"dist": "lognormal", "median": 96, "sigma": 0.8,
                             "min": 32, "max": 384}, 32)
    assert s == sorted(s) and s[0] == 32 and s[-1] == 384
    assert Counter(s)[384] == 1


def test_a_traffic_that_overflows_the_lanes_is_refused():
    tr = spec.load_json(spec.BENCH / "traffic" / "prefill_heavy.json")
    with pytest.raises(ValueError):
        traffic.validate(tr, 1024)
