"""Benchmark harness: one module per paper table/figure (see DESIGN.md §7).

Each bench runs in its own subprocess with forced host devices (the main
process keeps 1 CPU device).  Output: ``name,us_per_call,derived`` CSV.

The harness also emits ``BENCH_rma_plan.json`` — eager vs coalesced message
counts (traced through `OpCounter`) plus the §8 model's latency for both
paths and the aggregation crossover — ``BENCH_serve_flow.json`` —
reject/retry vs credit-based enqueue counts and modeled/measured message
rates for the serving path (§9, written by `bench_serve_flow`) — and
``BENCH_rmem.json`` — page-pool alloc throughput and the paged KV-cache's
prefix-sharing bytes_wire savings (§10, written by `bench_rmem`).  Every
run then folds ALL ``BENCH_*.json`` files into ``BENCH_trajectory.json``,
one entry per commit — the per-PR perf series.  ``--smoke`` runs the JSON
emissions plus the message-rate bench (the `make bench-smoke` target).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

BENCHES = [
    # (module, devices, paper figure)
    ("benchmarks.bench_latency", 8, "Fig 4a-c latency/bandwidth"),
    ("benchmarks.bench_overlap", 8, "Fig 5a overlap"),
    ("benchmarks.bench_message_rate", 8, "Fig 5b-c message rate"),
    ("benchmarks.bench_atomics", 8, "Fig 6a atomics"),
    ("benchmarks.bench_sync", 16, "Fig 6b-c + lock/flush constants"),
    ("benchmarks.bench_hashtable", 8, "Fig 7a hashtable"),
    ("benchmarks.bench_dsde", 8, "Fig 7b DSDE"),
    ("benchmarks.bench_rmaq", 8, "rmaq queues (DESIGN.md §6.8)"),
    ("benchmarks.bench_serve_flow", 8, "serve flow control (DESIGN.md §9)"),
    ("benchmarks.bench_rmem", 8, "page pool + paged KV (DESIGN.md §10)"),
    ("benchmarks.bench_fft", 8, "Fig 7c 3D FFT"),
    ("benchmarks.bench_milc", 8, "Fig 8 MILC stencil"),
    ("benchmarks.bench_roofline", 1, "roofline from dry-run"),
]

SMOKE_BENCHES = [
    ("benchmarks.bench_message_rate", 4, "Fig 5b-c message rate (smoke)"),
    ("benchmarks.bench_serve_flow", 4, "serve flow control (smoke, "
                                       "emits BENCH_serve_flow.json)"),
    ("benchmarks.bench_rmem", 4, "page pool + paged KV (smoke, "
                                 "emits BENCH_rmem.json)"),
]


def emit_rma_plan_json(path: str = "BENCH_rma_plan.json", k: int = 32,
                       msg_bytes: int = 8) -> dict:
    """Trace a k-put epoch eagerly and as one coalesced plan; write counts
    and the §8 model's latency for both paths (the perf-trajectory seed)."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from repro.core import plan as plan_mod, rma
    from repro.core.perfmodel import DEFAULT_MODEL
    from repro.core.rma import OpCounter

    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("x",))
    sm = functools.partial(shard_map, mesh=mesh, check_vma=False)
    words = max(1, msg_bytes // 4)
    x = jnp.zeros((n, k, words), jnp.float32)

    def eager(v):
        return jnp.stack([rma.put_shift(v[0, i], 1, "x") for i in range(k)])[None]

    def coalesced(v):
        pl = plan_mod.RmaPlan("x")
        hs = [pl.put_shift(v[0, i], 1) for i in range(k)]
        pl.flush(aggregate=True)
        return jnp.stack([h.result() for h in hs])[None]

    spec = P("x", None, None)
    counts = {}
    for name, fn in (("eager", eager), ("coalesced", coalesced)):
        with OpCounter() as c:
            jax.eval_shape(sm(fn, in_specs=spec, out_specs=spec), x)
        counts[name] = c

    m = DEFAULT_MODEL
    out = {
        "k_msgs": k,
        "msg_bytes": msg_bytes,
        "eager": {
            "raw_msgs": counts["eager"].raw_msgs,
            "wire_transfers": counts["eager"].coalesced_msgs,
            "modeled_us": m.p_direct_transfers(k, msg_bytes) * 1e6,
        },
        "coalesced": {
            "raw_msgs": counts["coalesced"].raw_msgs,
            "wire_transfers": counts["coalesced"].coalesced_msgs,
            "modeled_us": m.p_packed_transfer(k, msg_bytes) * 1e6,
        },
        "aggregation_factor": counts["coalesced"].aggregation_factor,
        "modeled_speedup": (
            m.p_direct_transfers(k, msg_bytes) / m.p_packed_transfer(k, msg_bytes)
        ),
        "crossover_bytes_n16": m.aggregation_crossover_bytes(16),
    }
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"# wrote {path}: raw={out['eager']['raw_msgs']} -> "
          f"wire={out['coalesced']['wire_transfers']} "
          f"(modeled {out['modeled_speedup']:.1f}x on {msg_bytes}B msgs)",
          flush=True)
    return out


def emit_trajectory(root: str, path: str = "BENCH_trajectory.json") -> dict:
    """Aggregate every BENCH_*.json into one per-PR series file.

    Each entry is (commit, benches); re-running on the same commit replaces
    its entry instead of appending, so the series stays one point per PR —
    the perf trajectory a future regression gate can diff against.
    """
    import glob

    benches = {}
    for f in sorted(glob.glob(os.path.join(root, "BENCH_*.json"))):
        name = os.path.splitext(os.path.basename(f))[0]
        if name == "BENCH_trajectory":
            continue
        try:
            with open(f) as fh:
                benches[name] = json.load(fh)
        except (OSError, json.JSONDecodeError) as e:
            print(f"# trajectory: skipping {name}: {e}", flush=True)
    try:
        commit = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, cwd=root, timeout=30,
        ).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        commit = "unknown"

    out_path = os.path.join(root, path)
    series: list = []
    if os.path.exists(out_path):
        try:
            with open(out_path) as fh:
                series = json.load(fh).get("series", [])
        except (OSError, json.JSONDecodeError):
            series = []
    series = [e for e in series if e.get("commit") != commit]
    entry = {"commit": commit, "benches": benches}
    # latency trajectory (§12): roll the serve benches' TTFT/TBT summaries
    # up to a flat per-commit metrics block so p50/p99 diffs across PRs
    # don't require digging through nested bench JSON
    metrics = {}
    sf = benches.get("BENCH_serve_flow") or {}
    for mode, e in (sf.get("serve_engine") or {}).items():
        for hist, summ in (e.get("metrics") or {}).items():
            if isinstance(summ, dict):
                for q in ("p50", "p99"):
                    if q in summ:
                        metrics[f"serve.{mode}.{hist}.{q}"] = summ[q]
    # §15 causal slice: per-segment TTFT attribution in virtual ticks
    # (deterministic, so these series are exact across commits)
    ss = sf.get("sim_serve") or {}
    for seg, summ in (ss.get("segments_vt") or {}).items():
        for q in ("p50", "p99"):
            if q in summ:
                metrics[f"serve.sim.seg.{seg}.{q}_vt"] = summ[q]
    if "ttft_vt" in ss:
        for q in ("p50", "p99"):
            metrics[f"serve.sim.ttft.{q}_vt"] = ss["ttft_vt"][q]
    if "sync_ledger" in ss:
        metrics["serve.sim.sync_wait_vt"] = ss["sync_ledger"]["total_wait"]
    # §16 transport slice: eager-vs-rendezvous wire footprint per workload
    # shape, the modeled crossover, and the 64-rank rendezvous sim TTFT
    tp = sf.get("transport") or {}
    for size, ab in tp.items():
        if size == "crossover":
            metrics["serve.transport.crossover_bytes"] = ab["crossover_bytes"]
            continue
        for proto in ("eager", "rendezvous"):
            for k in ("ring_window_nbytes", "bytes_wire_per_req",
                      "wire_msgs_per_step"):
                metrics[f"serve.transport.{size}.{proto}.{k}"] = ab[proto][k]
    sr = sf.get("sim_rendezvous") or {}
    for seg, summ in (sr.get("segments_vt") or {}).items():
        for q in ("p50", "p99"):
            if q in summ:
                metrics[f"serve.rdv.seg.{seg}.{q}_vt"] = summ[q]
    if "ttft_vt" in sr:
        for q in ("p50", "p99"):
            metrics[f"serve.rdv.ttft.{q}_vt"] = sr["ttft_vt"][q]
    if metrics:
        entry["metrics"] = metrics
    series.append(entry)
    out = {"series": series}
    with open(out_path, "w") as fh:
        json.dump(out, fh, indent=2)
    print(f"# wrote {path}: {len(series)} commits x {len(benches)} bench files",
          flush=True)
    return out


def main() -> None:
    smoke = "--smoke" in sys.argv[1:]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    print("name,us_per_call,derived")
    failures = 0
    for mod, devices, fig in (SMOKE_BENCHES if smoke else BENCHES):
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
        env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + root + os.pathsep + env.get("PYTHONPATH", "")
        print(f"# {mod} [{fig}] ({devices} devices)", flush=True)
        proc = subprocess.run([sys.executable, "-m", mod], capture_output=True,
                              text=True, env=env, cwd=root, timeout=1800)
        if proc.returncode != 0:
            failures += 1
            print(f"# FAILED {mod}: {proc.stderr.strip().splitlines()[-1] if proc.stderr else '?'}",
                  flush=True)
        sys.stdout.write(proc.stdout)
    emit_rma_plan_json(os.path.join(root, "BENCH_rma_plan.json"))
    if failures:
        # do NOT fold stale JSON into the trajectory under this commit
        raise SystemExit(f"{failures} benchmarks failed")
    # model-vs-measured drift gate (§12): every deterministic wire-transfer
    # count the PerfModel predicts must match what the benchmarks measured
    from repro.obs import drift
    drift.gate(root, json_path=os.path.join(root, "BENCH_drift.json"))
    emit_trajectory(root)


if __name__ == "__main__":
    main()
