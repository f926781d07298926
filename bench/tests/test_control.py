"""The control fails the check: the reference computed with float8
operands, put in the program's place, reads a worst gap above the limit on
every seed, where the program reads one below it.  The same code, at the
cells' own sizes on the chip, gave the readings in PERF.md."""

import pytest

from bench import calibrate, harness, spec

from helpers import DATA, SMOKE_CONFIGS, smoke_cell


@pytest.mark.parametrize("name", SMOKE_CONFIGS)
def test_control_fails_where_the_program_passes(name):
    cell = smoke_cell(name)
    session = harness.Session(cell)
    session.load(0)
    session.warm_up()
    reference = spec.reference_module(cell.config)
    limit = cell.config["check"]["max_gap"]
    for seed in (1, 2, 2**31 + 3):
        r = calibrate.readings(session, seed, 0.1, True, reference)
        assert r["failed"] == 0 and r["tokens_compared"] >= 20
        assert r["program_max_gap"] <= limit < r["control_max_gap"], r


def test_calibrate_prints_one_line_per_seed(capsys):
    rc = calibrate.main(["--workload", "smollm-360m.decode", "--cpu",
                         "--config", str(DATA / "smoke-full-rotary.json"),
                         "--traffic", str(DATA / "traffic-smoke.json"),
                         "--seeds", "4,5", "--control-seeds", "5",
                         "--seconds", "0.1"])
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 2 and "control_max_gap" in lines[1]
