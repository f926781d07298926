"""rmem benchmarks (DESIGN.md §10): page-pool alloc throughput + the paged
KV-cache's prefix-sharing wire savings — writes ``BENCH_rmem.json``.

The acceptance evidence rides here: on a workload with >= 50% shared prompt
prefix, paged mode moves measurably fewer bytes_wire per admitted request
than inline-payload mode, at the SAME 2 fused wire transfers per channel
append (the scatter of novel pages is a separate, prefix-shrinkable
transfer).  Alloc throughput covers both the host CAS free-list (real
threads) and the SPMD rank-ordered alloc epoch, next to the §10 model.

The ``decode`` series is the §13 evidence: the same workload decoded by
the fused paged-attention kernel (2-page staging window) vs the
gather-then-attend baseline (full packed block), with the modeled
fused-vs-gather crossover alongside.
"""
import functools
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from benchmarks.common import emit, time_fn
from jax import shard_map
from repro.core.perfmodel import DEFAULT_MODEL
from repro.rmem import heap
from repro.serve.disagg import DisaggConfig, DisaggEngine


# ------------------------------------------------------------ alloc speed
def host_alloc_throughput(n_pages: int = 256, iters: int = 2000,
                          n_threads: int = 4) -> dict:
    """Alloc/release pairs per second on the literal CAS free-list."""
    import time

    pool = heap.HostPagePool(n_pages)
    t0 = time.perf_counter()
    for _ in range(iters):
        pool.release(pool.alloc())
    single = iters / (time.perf_counter() - t0)

    pool = heap.HostPagePool(n_pages)
    errs: list = []

    def worker(seed: int) -> None:
        rng = np.random.RandomState(seed)
        held: list = []
        try:
            for _ in range(iters // n_threads):
                if held and rng.rand() < 0.5:
                    pool.release(held.pop())
                else:
                    pid = pool.alloc()
                    if pid is not None:
                        held.append(pid)
            while held:
                pool.release(held.pop())
        except Exception as e:  # surface thread failures to the main thread
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(s,)) for s in range(n_threads)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    threaded = iters / (time.perf_counter() - t0)
    if errs:
        raise errs[0]
    cons = pool.conservation()
    assert cons["free_plus_live"] == cons["capacity"], cons
    return {
        "single_thread_ops_per_s": single,
        f"threaded_{n_threads}_ops_per_s": threaded,
        "amos_per_op": pool.total_amos / max(pool.allocs + pool.frees, 1),
        "conservation_ok": True,
    }


def spmd_alloc_epoch_us(n: int, n_pages: int = 64, kmax: int = 4) -> float:
    """One fused alloc+release round across all ranks (the §10 SPMD path)."""
    mesh = jax.make_mesh((n,), ("x",))
    sm = functools.partial(shard_map, mesh=mesh, check_vma=False)
    desc, state = heap.pool_allocate(mesh, "x", n_pages, (2,))
    specs = heap.state_specs("x", 1)

    def step(s, want):
        s = heap.to_local(s)
        s, ids, _ = heap.alloc(desc, s, want[0], kmax=kmax)
        owner = jnp.repeat(jnp.arange(n, dtype=jnp.int32)[:, None], kmax,
                           axis=1).reshape(-1)
        flat = ids.reshape(-1)
        s, _ = heap.release(desc, s, flat, jnp.where(flat >= 0, owner, -1))
        return heap.to_global(s), ids[None]

    f = jax.jit(sm(step, in_specs=(specs, P("x", None)),
                   out_specs=(specs, P("x", None, None))))
    want = jnp.full((n, n), 1, jnp.int32)
    return time_fn(lambda s: f(s, want)[1], state)


# ----------------------------------------------------- prefix-hit savings
def run_engine(n: int, paged: bool, n_req: int = 12,
               shared_frac: float = 0.5, seed: int = 5,
               attend: str = "fused") -> dict:
    """One mode on the shared-prefix workload: every request's first
    `shared_frac` of the prompt is identical (>= 50% page-level reuse for
    all but the first request routed to each decoder)."""
    mesh = jax.make_mesh((n,), ("serve",))
    cfg = DisaggConfig(
        n_prefill=max(1, n // 2), block_tokens=16, d_model=32, vocab=61,
        queue_capacity=16, max_recv_per_step=4, n_lanes=2, flow=True,
        paged=paged, page_tokens=4, novel_slots=2, pool_pages=48,
        attend=attend,
    )
    eng = DisaggEngine(mesh, "serve", cfg, seed=0)
    rng = np.random.RandomState(seed)
    n_shared = int(cfg.block_tokens * shared_frac)
    prefix = rng.randint(0, cfg.vocab, size=n_shared)
    prompts = {
        rid: np.concatenate(
            [prefix, rng.randint(0, cfg.vocab, size=cfg.block_tokens - n_shared)])
        for rid in range(n_req)
    }
    for rid, toks in prompts.items():
        eng.submit(rid, toks)
    res = eng.run_until_drained()
    correct = sum(res[rid] == eng.reference(toks)
                  for rid, toks in prompts.items())
    assert correct == n_req, f"only {correct}/{n_req} tokens correct"

    plans = eng.msg_stats["plans"]
    if paged:
        # program order: plan 0 is the novel-page scatter; the channel
        # append is the remaining reserve + payload pair
        append_transfers = sum(pl["coalesced"] for pl in plans[1:])
        ps = eng.paged_stats()
        assert ps["pool_conservation_ok"], ps
        extra = {
            "novel_pages_shipped": ps["novel_pages_shipped"],
            "prefix_hits": ps["prefix_hits"],
            "prefix_hit_rate": ps["prefix_hit_rate"],
            "effective_payload_bytes_per_req":
                ps["effective_payload_bytes"] / n_req,
            "attend_path": ps["attend_path"],
            "pages_per_block": ps["pages_per_block"],
            "staging_pages_resident": ps["staging_pages_resident"],
            "staging_bytes_per_decode": ps["staging_bytes_per_decode"],
            "attend_us": eng.serve_metrics()["attend_us"],
        }
    else:
        append_transfers = eng.msg_stats["wire_msgs_per_step"]
        extra = {
            "effective_payload_bytes_per_req":
                float(cfg.block_nbytes),   # the whole block, every request
        }
    assert eng.flow_stats()["conservation_ok"]
    return {
        "served": len(res),
        "steps": eng.steps_run,
        "wire_transfers_per_append": int(append_transfers),
        "bytes_wire_per_step": eng.msg_stats["bytes_wire_per_step"],
        "bytes_wire_per_req":
            eng.msg_stats["bytes_wire_per_step"] * eng.steps_run / n_req,
        "retries": eng.retries,
        **extra,
    }


# --------------------------------------------------- shadow-mode overhead
def shadow_overhead(p: int = 8, rounds: int = 400) -> dict:
    """Events/sec through `LocalFabric` with the §14 race checker attached
    vs detached — the cost of running every protocol under the shadow.

    The loop is the conformance access mix: cross-rank puts and accs, a
    get, a flush and a notification per rank per round, a fence per round.
    """
    import time

    from repro.core.fabric import LocalFabric

    def drive(attach: bool) -> tuple[float, int]:
        fab = LocalFabric(p=p)
        fab.register("win", np.zeros((p, 8), np.int64))
        chk = None
        if attach:
            from repro.analysis.races import RaceChecker
            chk = fab.attach_shadow(RaceChecker(p))
        # disjoint cells per op kind: clean under the checker by
        # construction (put=0, acc=1, get reads untouched 2, notify ctr=3)
        n_ops = 0
        t0 = time.perf_counter()
        for _ in range(rounds):
            for r in range(p):
                dst = (r + 1) % p
                fab.put(r, dst, "win", (0,), 1)
                fab.add(r, dst, "win", (1,), 1)
                fab.flush_remote(r)
                fab.get(r, dst, "win", (2,))
                fab.fence_add(dst, "win", (3,), 1)
                n_ops += 5
            fab.fence()
            n_ops += 1
        dt = time.perf_counter() - t0
        if chk is not None:
            assert chk.violations == [], chk.violations[:3]
        return n_ops / dt, (chk.events if chk is not None else 0)

    off, _ = drive(False)
    on, seen = drive(True)
    return {
        "events_per_s_off": off,
        "events_per_s_on": on,
        "overhead_x": off / on,
        "shadow_events_observed": seen,
    }


# ------------------------------------------------- fused-vs-gather decode
def decode_series(n: int, paged_fused: dict) -> dict:
    """The DESIGN.md §13 A/B: the same shared-prefix workload decoded by
    the fused paged-attention kernel vs the gather-then-attend baseline.
    The structural win is the staging bound — O(page·2) resident bytes vs
    the gather's O(block) packed copy — at identical wire fingerprints and
    identical emitted tokens (both runs assert correctness inside
    `run_engine`)."""
    m = DEFAULT_MODEL
    gather = run_engine(n, paged=True, attend="gather")
    ppb = paged_fused["pages_per_block"]
    page_nbytes = int(paged_fused["staging_bytes_per_decode"]
                      / paged_fused["staging_pages_resident"])
    series = {
        "pages_per_block": ppb,
        "page_nbytes": page_nbytes,
        "fused": {k: paged_fused[k] for k in (
            "attend_path", "staging_pages_resident",
            "staging_bytes_per_decode", "wire_transfers_per_append",
            "attend_us")},
        "gather": {k: gather[k] for k in (
            "attend_path", "staging_pages_resident",
            "staging_bytes_per_decode", "wire_transfers_per_append",
            "attend_us")},
        "staging_bytes_reduction":
            gather["staging_bytes_per_decode"]
            / paged_fused["staging_bytes_per_decode"],
        "model": {
            "p_paged_attention_us":
                m.p_paged_attention(ppb, page_nbytes) * 1e6,
            "p_paged_gather_attend_us":
                m.p_paged_gather_attend(ppb, page_nbytes) * 1e6,
            "select_paged_attend_toy":
                m.select_paged_attend(ppb, page_nbytes),
            "select_paged_attend_64KB_pages":
                m.select_paged_attend(ppb, 64 * 1024),
            "crossover_page_bytes": m.paged_attend_crossover_bytes(ppb),
        },
    }
    # the staging-window bound, asserted where the evidence is produced
    assert series["fused"]["staging_pages_resident"] == min(2, ppb)
    assert series["gather"]["staging_pages_resident"] == ppb
    assert series["fused"]["wire_transfers_per_append"] == \
        series["gather"]["wire_transfers_per_append"]
    return series


def main() -> None:
    n = len(jax.devices())
    m = DEFAULT_MODEL

    alloc = host_alloc_throughput()
    spmd_us = spmd_alloc_epoch_us(n)
    inline = run_engine(n, paged=False)
    paged = run_engine(n, paged=True)
    decode = decode_series(n, paged)
    shadow = shadow_overhead()

    cfg_block, cfg_ppb = 16 * 2 * 32 * 4.0, 4
    model = {
        "p_page_alloc_fused_us": m.p_page_alloc(True) * 1e6,
        "p_page_alloc_standalone_us": m.p_page_alloc(False) * 1e6,
        "paged_crossover_reuse_toy_block": m.paged_crossover_reuse(
            cfg_block, cfg_ppb),
        "paged_crossover_reuse_2MB_block": m.paged_crossover_reuse(
            2048 * 2 * 128 * 4.0, 16),
        "inline_append_us": m.p_append_inline(cfg_block) * 1e6,
        "paged_append_us_by_reuse": {
            str(f): m.p_append_paged(cfg_block, cfg_ppb, f) * 1e6
            for f in (0.0, 0.25, 0.5, 0.75, 1.0)
        },
    }
    out = {
        "devices": n,
        "alloc": {**alloc, "spmd_epoch_us": spmd_us},
        "inline": inline,
        "paged": paged,
        "decode": decode,
        "savings": {
            "effective_payload_per_req":
                1.0 - paged["effective_payload_bytes_per_req"]
                / inline["effective_payload_bytes_per_req"],
            "bytes_wire_per_req":
                1.0 - paged["bytes_wire_per_req"] / inline["bytes_wire_per_req"],
        },
        "model": model,
        "shadow": shadow,
    }
    with open("BENCH_rmem.json", "w") as f:
        json.dump(out, f, indent=2, default=float)

    emit("rmem_host_alloc", 1e6 / alloc["single_thread_ops_per_s"],
         f"threaded_ops_per_s={alloc['threaded_4_ops_per_s']:.0f};"
         f"amos_per_op={alloc['amos_per_op']:.2f}")
    emit("rmem_spmd_alloc_epoch", spmd_us, "fused_gather=1_wire_transfer")
    for name, r in (("inline", inline), ("paged", paged)):
        emit(f"rmem_serve_{name}", 0.0,
             f"bytes_wire_per_req={r['bytes_wire_per_req']:.0f};"
             f"payload_per_req={r['effective_payload_bytes_per_req']:.0f};"
             f"wire_per_append={r['wire_transfers_per_append']}")
    emit("rmem_shadow_overhead", shadow["overhead_x"],
         f"events_per_s_off={shadow['events_per_s_off']:.0f};"
         f"events_per_s_on={shadow['events_per_s_on']:.0f};"
         f"events={shadow['shadow_events_observed']}")
    for path in ("fused", "gather"):
        d = decode[path]
        emit(f"rmem_decode_{path}", d["attend_us"]["p50"],
             f"staging_pages={d['staging_pages_resident']};"
             f"staging_bytes={d['staging_bytes_per_decode']}")
    print(f"# wrote BENCH_rmem.json: bytes_wire/req "
          f"{inline['bytes_wire_per_req']:.0f} (inline) -> "
          f"{paged['bytes_wire_per_req']:.0f} (paged, "
          f"hit_rate={paged['prefix_hit_rate']:.2f}) at "
          f"{paged['wire_transfers_per_append']} wire transfers per append",
          flush=True)
    print(f"# decode staging: gather {decode['gather']['staging_bytes_per_decode']}B"
          f" -> fused {decode['fused']['staging_bytes_per_decode']}B"
          f" ({decode['staging_bytes_reduction']:.1f}x; modeled crossover at "
          f"{decode['model']['crossover_page_bytes']:.0f}B pages)", flush=True)

    # the acceptance criteria, asserted where the evidence is produced
    assert paged["wire_transfers_per_append"] == \
        inline["wire_transfers_per_append"] == 2
    assert paged["effective_payload_bytes_per_req"] < \
        inline["effective_payload_bytes_per_req"]
    assert paged["bytes_wire_per_req"] < inline["bytes_wire_per_req"]
    assert paged["prefix_hit_rate"] > 0.0
    assert paged["retries"] == inline["retries"] == 0


if __name__ == "__main__":
    main()
