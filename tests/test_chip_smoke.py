"""`chip_smoke.py` on the CPU: it refuses to run without a TPU, and its
serve-and-check path passes its own token check at smoke width."""

from __future__ import annotations

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get_config

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_no_tpu_exits_nonzero_without_result(where, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    script = SCRIPT
    if where == "alone":          # a directory holding the script and nothing else
        script = tmp_path / SCRIPT.name
        shutil.copy(SCRIPT, script)
        env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                          env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


def test_serve_and_check_passes_at_smoke_width():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    lines = []
    rep = smoke.serve_and_check(get_config("smollm-360m", smoke=True), seed=0,
                                log=lines.append)
    n = smoke.WAVES * smoke.N_SLOTS
    assert rep["requests"] == n and len(lines) == n
    assert rep["tokens"] == n * smoke.MAX_NEW
    assert rep["worst_gap"] <= smoke.GAP_TOL
    assert len(rep["wave_seconds"]) == smoke.WAVES
