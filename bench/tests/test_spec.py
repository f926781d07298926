"""Every configuration, mix and metric is found by name in its own file."""

import json
import shutil

import pytest

from bench import spec

BENCH_JSON = spec.load_json(spec.CHECKOUT / "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH_JSON["workloads"]]
METRICS = [m["name"] for m in BENCH_JSON["end_to_end"] + BENCH_JSON["per_layer"]]


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_loads_its_files(cell):
    c = spec.load_cell(cell)
    w = {x["name"]: x for x in BENCH_JSON["workloads"]}[cell]
    assert c.config["name"] == w["config"]
    assert c.traffic["name"] == w["traffic"]
    assert {m["name"] for m in c.end_to_end} >= {"setup_s", "output_tok_s"}
    assert c.per_layer
    assert spec.reference_module(c.config).logits_at


@pytest.mark.parametrize("name", METRICS)
def test_each_metric_has_a_reader(name):
    assert callable(spec.metric_reader(name))


@pytest.mark.parametrize("name", [c["name"] for c in BENCH_JSON["configs"]])
def test_config_widths_agree_with_the_program(name):
    from repro.configs import get_config

    entry = {c["name"]: c for c in BENCH_JSON["configs"]}[name]
    config = spec.load_json(spec.CHECKOUT / entry["file"])
    spec.family_module(config).check_widths(config, get_config(config["arch"]))
    assert sorted(config["reduced"]) == sorted(entry["reduced"])


def test_a_wrong_width_is_refused():
    from repro.configs import get_config

    config = spec.load_cell(CELLS[0]).config
    bad = dict(config, dims=dict(config["dims"], d_ff=config["dims"]["d_ff"] + 1))
    with pytest.raises(ValueError, match="d_ff"):
        spec.family_module(bad).check_widths(bad, get_config(config["arch"]))


def test_new_files_are_found_by_name(tmp_path):
    """A new configuration, mix and metric are new files and entries only."""
    bench = tmp_path / "bench"
    shutil.copytree(spec.BENCH / "configs", bench / "configs")
    shutil.copytree(spec.BENCH / "traffic", bench / "traffic")
    (bench / "metrics").mkdir()
    (bench / "metrics" / "answer.py").write_text("def read(run):\n    return 42.0\n")
    cfg = spec.load_json(bench / "configs" / "smollm-360m.json")
    cfg["name"] = "smollm-copy"
    (bench / "configs" / "smollm-copy.json").write_text(json.dumps(cfg))
    tr = spec.load_json(bench / "traffic" / "decode_heavy.json")
    tr["name"] = "decode_copy"
    (bench / "traffic" / "decode_copy.json").write_text(json.dumps(tr))
    doc = dict(BENCH_JSON)
    doc["configs"] = [{"name": "smollm-copy", "source": "x",
                       "file": "bench/configs/smollm-copy.json",
                       "reduced": [], "why": "x"}]
    doc["workloads"] = [{"name": "copy.cell", "config": "smollm-copy",
                         "traffic": "decode_copy", "chips": 1, "why": "x"}]
    doc["per_layer"] = [{"name": "answer", "unit": "%", "better": "higher",
                         "source": "program_counter", "layer": "x",
                         "moves": "output_tok_s"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    cell = spec.load_cell("copy.cell", root=tmp_path)
    assert cell.config["name"] == "smollm-copy"
    assert cell.traffic["name"] == "decode_copy"
    assert [m["name"] for m in cell.per_layer] == ["answer"]
    assert spec.metric_reader("answer", bench)(None) == 42.0
