#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in `BENCHMARK.json`, makes the weights on the device from the
seed, builds `ServeEngine`, warms up exactly the cell's shapes, drives closed
waves of requests for `--seconds`, then checks a sample of the served tokens
against the configuration's float32 reference.  The last line of stdout is
one JSON object: `correct`, `attempted`, `failed`, `metrics` (the cell's
end-to-end metrics, or with `--trace 1` its per-layer metrics read from a
profiler trace), `device`, and `checks`, each number compared beside its
limit; the same numbers end stderr.

With no accelerator, or fewer chips than the cell asks for, it exits 1 and
prints no result.  It never falls back to the CPU.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parent.parent
CACHE_DIR = CHECKOUT / ".jax_cache"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")

    # The compile cache lives at a fixed path inside the checkout, whatever
    # the environment names: a path that moves never hits, and a shared one
    # would carry programs between checkouts.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # No eviction: with a size limit set in the environment, JAX reads an
    # access-time file beside every entry before each write, and one entry
    # copied into the directory without it makes every write fail.
    jax.config.update("jax_compilation_cache_max_size", -1)

    from bench import harness, spec

    cell = spec.load_cell(args.workload)
    try:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), T_PROCESS)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
