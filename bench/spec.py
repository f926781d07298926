"""Find a cell and everything it names, by name, in files of their own.

- `BENCHMARK.json` at the checkout's root lists configurations, cells and
  metrics;
- a configuration is the JSON file its entry names, under `bench/configs/`;
  its `reference` names `bench/reference/<reference>.py`;
- a traffic mix is `bench/traffic/<traffic>.json`;
- a metric is `bench/metrics/<name>.py`, whose `read(run)` returns the value
  or None when the run holds nothing to read.

Adding a configuration, a mix or a metric is adding files and entries; no
code here names one.
"""

from __future__ import annotations

import dataclasses
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


def check_widths(config: dict, arch_cfg) -> None:
    """Refuse a configuration whose stated widths the program's own
    `repro.configs` entry does not share."""
    d = config["dims"]
    have = {
        "n_layers": arch_cfg.n_layers, "d_model": arch_cfg.d_model,
        "n_heads": arch_cfg.n_heads, "n_kv_heads": arch_cfg.n_kv_heads,
        "head_dim": arch_cfg.hd, "d_ff": arch_cfg.d_ff,
        "vocab": arch_cfg.vocab_size,
        "tie_embeddings": arch_cfg.tie_embeddings,
        "qkv_bias": arch_cfg.qkv_bias,
        "rotary_dims": arch_cfg.hd // 2 if arch_cfg.rope_style == "2d" else arch_cfg.hd,
        "norm_eps": arch_cfg.norm_eps,
        "family": arch_cfg.family,
    }
    want = {k: d[k] for k in have if k in d}
    want["family"] = config["family"]
    bad = {k: (want[k], have[k]) for k in have if want.get(k) != have[k]}
    if bad:
        raise ValueError(f"configuration {config['name']!r} disagrees with "
                         f"repro.configs {config['arch']!r} (stated, program): {bad}")
