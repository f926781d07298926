"""Smoke-width cells for the benchmark's tests."""

from __future__ import annotations

import time

from bench import harness, spec
from bench.peaks import PEAKS

DATA = spec.BENCH / "tests" / "data"
SMOKE_CONFIGS = ("smoke-full-rotary", "smoke-half-rotary")


def smoke_cell(config: str, name: str = "smoke.decode") -> spec.Cell:
    """A cell at smoke width with every metric of a decode cell."""
    decode = spec.load_cell("smollm-360m.decode")
    return spec.Cell(name, 1, spec.load_json(DATA / f"{config}.json"),
                     spec.load_json(DATA / "traffic-smoke.json"),
                     decode.end_to_end, decode.per_layer)


def run_smoke(config: str, seed: int = 2**31 + 7, seconds: float = 0.3) -> dict:
    return harness.run_cell(smoke_cell(config), seed, seconds, False,
                            time.perf_counter(), require_chip=False,
                            peaks=PEAKS["TPU v5 lite"], log=lambda m: None)
