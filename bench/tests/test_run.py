"""The run's last line at smoke width, and the refusal off the chip."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import spec

from helpers import SMOKE_CONFIGS, run_smoke

ENTRY = spec.BENCH / "run.py"


@pytest.mark.parametrize("name", SMOKE_CONFIGS)
def test_last_line_schema(name):
    res = run_smoke(name)
    assert list(res)[-1] == "checks"
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"output_tok_s", "itl_p95_ms", "setup_s"}
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    dev = res["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    assert dev["count"] == 1
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"}
    assert res["compiles_in_window"] == 0
    json.dumps(res, allow_nan=False)


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "smollm-360m.decode",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    lines = out.strip().splitlines()
    return not lines or not lines[-1].lstrip().startswith("{")


def test_exits_nonzero_on_the_cpu():
    p = _run(spec.CHECKOUT)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "accelerator" in p.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(spec.CHECKOUT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)
