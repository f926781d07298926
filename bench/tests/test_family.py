"""The harness, the check and the readers reach a model's shapes only
through the family module that its configuration names: a probe family,
which delegates to the dense one and records each call, is reached for
every function of a family's interface."""

import dataclasses
import sys

import pytest

from bench import spec

from helpers import DATA, SMOKE_CONFIGS, recorded_run, run_smoke, smoke_cell


@pytest.fixture
def probe(monkeypatch):
    mod = spec._load_module(DATA / "probe_family.py", "bench.family.probe")
    monkeypatch.setitem(sys.modules, "bench.family.probe", mod)
    return mod


READERS = {"decode_roofline": {"decode_work"}, "prefill_roofline": {"prefill_work"},
           "mfu": {"decode_work", "prefill_work"}}


def test_a_run_reaches_every_family_function_through_the_config(probe):
    cell = smoke_cell(SMOKE_CONFIGS[0])
    cfg = dict(cell.config, family="probe")
    res = run_smoke(dataclasses.replace(cell, config=cfg))
    assert res["correct"] is True
    reached = set(probe.CALLED)
    assert reached == set(probe.INTERFACE) - {"decode_work", "prefill_work"}
    run = recorded_run(cfg)
    assert run.family is probe
    for name, needs in READERS.items():
        probe.CALLED.clear()
        value = spec.metric_reader(name)(run)
        assert value is not None and 0 < value <= 100, name
        assert probe.CALLED == needs, name
        reached |= probe.CALLED
    assert reached == set(probe.INTERFACE)


def test_a_family_without_a_dense_width_loads(probe):
    """A width the configuration's family does not have is not asked for;
    the dense family still asks for it."""
    from repro.configs import get_config

    cfg = spec.load_json(DATA / f"{SMOKE_CONFIGS[0]}.json")
    arch = get_config(cfg["arch"], smoke=True)
    dims = {k: v for k, v in cfg["dims"].items() if k != "d_ff"}
    no_ff = dict(cfg, family="probe", dims=dims)
    spec.family_module(no_ff).check_widths(no_ff, arch)
    with pytest.raises(ValueError, match="d_ff"):
        spec.family_module(cfg).check_widths(no_ff, arch)
