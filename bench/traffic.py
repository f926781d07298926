"""One general generator for closed-wave traffic, driven by a data file.

A traffic file (`bench/traffic/<name>.json`) holds only parameters:

- `prompt_len_cycle`: the prompt length of each wave, in a fixed cycle;
- `max_new`: how many tokens each request asks for, as a distribution
  (`lognormal` with `median` and `sigma`, or integer `uniform`), clipped to
  [`min`, `max`];
- `close_on`: `wave` or `cycle`, the unit after which the window may close;
- `trace_waves`: how many waves a traced run records, from the second on;
- `check_requests`: how many finished requests the check compares.

A wave is `n_slots` requests of one prompt length, submitted together.  Each
wave's `max_new` values are the same stratified set, the distribution's
quantiles at (i + 1/2) / n_slots, so every seed does the same work: the seed
only shuffles those values over the requests and draws the token ids,
uniform over the vocabulary.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Iterator

import numpy as np


@dataclasses.dataclass(frozen=True)
class Wave:
    index: int
    plen: int
    prompts: np.ndarray      # [n_slots, plen] int32
    max_new: tuple[int, ...]


def max_new_set(dist: dict, n: int) -> list[int]:
    """The stratified set of `max_new` values of one wave, ascending."""
    lo, hi = int(dist["min"]), int(dist["max"])
    qs = [(i + 0.5) / n for i in range(n)]
    if dist["dist"] == "lognormal":
        nd = NormalDist()
        vals = [round(dist["median"] * math.exp(dist["sigma"] * nd.inv_cdf(q)))
                for q in qs]
    elif dist["dist"] == "uniform":
        vals = [lo + int(q * (hi - lo + 1)) for q in qs]
    else:
        raise ValueError(f"unknown max_new distribution {dist['dist']!r}")
    return [min(hi, max(lo, v)) for v in vals]


def validate(traffic: dict, max_seq: int) -> None:
    """Every request fits its lane: prompt + max_new <= max_seq - 1."""
    longest = max(traffic["prompt_len_cycle"]) + int(traffic["max_new"]["max"])
    if longest > max_seq - 1:
        raise ValueError(f"traffic {traffic['name']!r} needs {longest} "
                         f"positions; the lanes hold {max_seq - 1}")
    if traffic["close_on"] not in ("wave", "cycle"):
        raise ValueError(f"close_on must be wave or cycle, not "
                         f"{traffic['close_on']!r}")


def waves(traffic: dict, n_slots: int, vocab: int, max_seq: int,
          seed: int) -> Iterator[Wave]:
    """The endless sequence of waves that `seed` gives."""
    validate(traffic, max_seq)
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    base = np.asarray(max_new_set(traffic["max_new"], n_slots))
    cycle = traffic["prompt_len_cycle"]
    k = 0
    while True:
        plen = int(cycle[k % len(cycle)])
        prompts = rng.integers(0, vocab, size=(n_slots, plen), dtype=np.int32)
        yield Wave(k, plen, prompts, tuple(int(x) for x in rng.permutation(base)))
        k += 1
