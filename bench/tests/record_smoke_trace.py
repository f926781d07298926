#!/usr/bin/env python3
"""Record the smoke-width trace that `test_serve_trace.py` reads.

    python bench/tests/record_smoke_trace.py <out_dir>

On one accelerator chip: the smoke-width decode cell of the tests, with the
profiler over its second wave as a `--trace 1` run sets it, writes
`<out_dir>/smoke_serve.xplane.pb` and, beside it, `smoke_serve.json`, the
client's record of the traced wave (decode ticks with their positions and
active slots, prefill lengths).  It refuses to record on the CPU, whose
trace holds no device ops.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(CHECKOUT), str(CHECKOUT / "src")]

SEED = 2**31 + 7
SECONDS = 0.3
NAME = "smoke_serve"


def record(out_dir: Path, require_chip: bool = True) -> dict:
    import jax

    from bench import harness
    from helpers import SMOKE_CONFIGS, smoke_cell

    cell = smoke_cell(SMOKE_CONFIGS[0])
    harness.devices_for(cell, require_chip)
    s = harness.Session(cell)
    s.load(SEED)
    s.warm_up()
    tracer = harness.Tracer(1, int(s.traffic["trace_waves"]))
    try:
        window = s.window(SEED, SECONDS, trace_hook=tracer,
                          min_waves=1 + int(s.traffic["trace_waves"]))
    finally:
        tracer.stop()
    found = sorted(glob.glob(os.path.join(tracer.dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise RuntimeError("the profiler wrote no trace")
    out_dir.mkdir(parents=True, exist_ok=True)
    shutil.copy(found[-1], out_dir / f"{NAME}.xplane.pb")
    shutil.rmtree(tracer.dir, ignore_errors=True)
    ticks = [t for t in window.ticks if t.traced]
    rec = {"device": jax.devices()[0].device_kind,
           "traced_ticks": len(ticks),
           "traced_prefills": sum(p.traced for p in window.prefills),
           "positions": [t.position for t in ticks],
           "active": [t.active for t in ticks],
           "plens": [p.plen for p in window.prefills if p.traced]}
    (out_dir / f"{NAME}.json").write_text(json.dumps(rec) + "\n")
    return rec


if __name__ == "__main__":
    if len(sys.argv) != 2:
        sys.exit(__doc__)
    print(json.dumps(record(Path(sys.argv[1]))))
