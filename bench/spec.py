"""Find a cell and everything it names, by name, in files of their own.

- `BENCHMARK.json` at the checkout's root lists configurations, cells and
  metrics;
- a configuration is the JSON file its entry names, under `bench/configs/`;
  its `family` names `bench/family/<family>.py`, everything the benchmark
  knows of that family's shapes (`bench/family/__init__.py` lists it), and
  its `reference` names `bench/reference/<reference>.py`;
- a traffic mix is `bench/traffic/<traffic>.json`;
- a metric is `bench/metrics/<name>.py`, whose `read(run)` returns the value
  or None when the run holds nothing to read.

Adding a configuration, of a family already here or of a new one, a mix or
a metric is adding files and entries; no code here names one.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent

@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple      # metric entries this cell reports with --trace 0
    per_layer: tuple       # metric entries this cell reports with --trace 1


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = CHECKOUT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(root / cfg_entry["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)),
    )


def _load_module(path: Path, tag: str):
    if not path.is_file():
        raise FileNotFoundError(f"{path} is missing")
    spec = importlib.util.spec_from_file_location(tag, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench: Path = BENCH):
    """The `read(run)` function of metric `name`."""
    return _load_module(bench / "metrics" / f"{name}.py",
                        f"bench_metric_{name}").read


def reference_module(config: dict, bench: Path = BENCH):
    """The plain reference that the configuration names."""
    return _load_module(bench / "reference" / f"{config['reference']}.py",
                        f"bench_reference_{config['reference']}")


def family_module(config: dict):
    """The model family that the configuration names, imported as
    `bench.family.<family>`: one module for the process, however often the
    harness, the check and the readers ask for it."""
    return importlib.import_module(f"bench.family.{config['family']}")
