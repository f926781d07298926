#!/usr/bin/env python3
"""Readings that the `max_gap` limits are set from; not part of a run.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--seconds 1]

In one process, for each seed: make the seed's weights, serve a short
window of the cell's own traffic (at least one wave, which holds the mix's
longest request) through the cell's engine, free the weights, and compare
the same sample of served tokens that a run compares with the float32
reference: the program's worst gap.  For each control seed, the reference
computed with float8 operands (`quant="fp8"`) stands in the program's
place: at each position of the same prompts and served tokens, the gap of
the token that it puts first.  One JSON line per seed on stdout.

`--config` and `--traffic` take files in place of the cell's own, so the
tests run the same code on the CPU at smoke width (`--cpu`).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parent.parent


def readings(session, seed: int, seconds: float, control: bool, reference) -> dict:
    """The program's worst gap on one seed and, with `control`, the
    control's worst gap on the same prompts and tokens."""
    import numpy as np

    from bench import check
    from bench.family import root_key

    session.load(seed)
    window = session.window(seed, seconds)
    session.release()
    done = [r for r in window.requests if len(r.output) == r.max_new]
    reqs = check.sample(done, int(session.traffic["check_requests"]), seed)
    tokens, idx, served, valid = check.batch(reqs)
    root = root_key(seed)
    ref = np.asarray(reference.logits_at(session.family, session.dims, root,
                                         tokens, idx))
    out = {"seed": seed, "requests": len(window.requests),
           "failed": len(window.requests) - len(done),
           "tokens_compared": int(valid.sum()),
           "program_max_gap": float(check.gaps(ref, served)[valid].max())}
    if control:
        try:
            ctl = np.asarray(reference.logits_at(session.family, session.dims, root,
                                                 tokens, idx, quant="fp8"))
            out["control_max_gap"] = float(
                check.gaps(ref, ctl.argmax(-1))[valid].max())
        except Exception as e:  # a control that crashes has failed
            out["control_error"] = repr(e)[:300]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--config")
    ap.add_argument("--traffic")
    ap.add_argument("--cpu", action="store_true",
                    help="run on the CPU (smoke widths only)")
    args = ap.parse_args(argv)

    if not args.cpu:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CHECKOUT / ".jax_cache")
    sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
    import dataclasses

    from bench import harness, spec

    cell = spec.load_cell(args.workload)
    if args.config:
        cell = dataclasses.replace(cell, config=spec.load_json(Path(args.config)))
    if args.traffic:
        cell = dataclasses.replace(cell, traffic=spec.load_json(Path(args.traffic)))
    harness.devices_for(cell, require_chip=not args.cpu)
    session = harness.Session(cell)
    session.load(0)
    session.warm_up()
    reference = spec.reference_module(cell.config)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in seeds + sorted(controls - set(seeds)):
        print(json.dumps(readings(session, seed, args.seconds, seed in controls,
                                  reference)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
