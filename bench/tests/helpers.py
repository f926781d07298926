"""Smoke-width cells for the benchmark's tests."""

from __future__ import annotations

import json
import time

from bench import client, harness, spec, trace_reduce
from bench.peaks import PEAKS

DATA = spec.BENCH / "tests" / "data"
SMOKE_CONFIGS = ("smoke-full-rotary", "smoke-half-rotary")


def smoke_cell(config: str, name: str = "smoke.decode") -> spec.Cell:
    """A cell at smoke width with every metric of a decode cell."""
    decode = spec.load_cell("smollm-360m.decode")
    return spec.Cell(name, 1, spec.load_json(DATA / f"{config}.json"),
                     spec.load_json(DATA / "traffic-smoke.json"),
                     decode.end_to_end, decode.per_layer)


def run_smoke(config: str | spec.Cell, seed: int = 2**31 + 7,
              seconds: float = 0.3) -> dict:
    """A `--trace 0` run of a smoke configuration's cell, or of `config`
    where it is a cell already."""
    cell = config if isinstance(config, spec.Cell) else smoke_cell(config)
    return harness.run_cell(cell, seed, seconds, False,
                            time.perf_counter(), require_chip=False,
                            peaks=PEAKS["TPU v5 lite"], log=lambda m: None)


def recorded_run(config: dict,
                 summary: trace_reduce.Summary | None = None) -> harness.Run:
    """The decode wave recorded on the chip (`smoke_decode.xplane.pb`, with
    the client's record of it beside it) as a `Run` of `config`'s family."""
    if summary is None:
        summary = trace_reduce.summarize(str(DATA / "smoke_decode.xplane.pb"))
    rec = json.loads((DATA / "smoke_decode.json").read_text())
    ticks = [client.Tick(p, a, True) for p, a in zip(rec["positions"], rec["active"])]
    prefills = [client.Prefill(p, True) for p in rec["plens"]]
    window = client.Window([], ticks, prefills, 0.0, 1.0, 1)
    return harness.Run(spec.family_module(config), config["dims"],
                       config["engine"]["n_slots"], 1, PEAKS["TPU v5 lite"],
                       1.0, window, summary)
