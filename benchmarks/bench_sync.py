"""Paper Fig. 6b/c + §3.2 constants: fence scaling, PSCW ring, locks, flush.

Fence is measured at growing process counts (dissemination psum); PSCW on a
ring (k=2) should be ~constant in p — the paper's headline scalability plot.
Lock/unlock/flush constants come from the faithful host-protocol simulation.
"""
import time

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from benchmarks.common import emit, time_fn
from repro.core import collectives, locks_sim, rma
from repro.core.epoch import FenceEpoch, PSCWEpoch, choose_sync
from repro.core.perfmodel import DEFAULT_MODEL


def main() -> None:
    n_all = len(jax.devices())
    sizes = [p for p in (2, 4, 8, 16) if p <= n_all]
    for p in sizes:
        mesh = jax.make_mesh((p,), ("x",), devices=jax.devices()[:p])
        x = jnp.zeros((p, 8), jnp.float32)

        def fence_body(v):
            ep = FenceEpoch("x", p)
            v = ep.open(v)
            v = rma.put_shift(v, 1, "x")
            v = ep.close(v)
            return jax.lax.psum(v, "x")  # the barrier carrier

        f = jax.jit(shard_map(fence_body, mesh=mesh, in_specs=P("x", None),
                              out_specs=P("x", None), check_vma=False))
        emit(f"fence_p{p}", time_fn(f, x),
             f"tpu_model_us={DEFAULT_MODEL.p_fence(p)*1e6:.2f}")

        def pscw_body(v):
            ep = PSCWEpoch("x", group=[0, 1])
            v = ep.post(v)
            v = collectives.halo_exchange_1d(v, 1, "x", dim=0)[:v.shape[0]]
            v = ep.complete(v)
            return v

        g = jax.jit(shard_map(pscw_body, mesh=mesh, in_specs=P("x", None),
                              out_specs=P("x", None), check_vma=False))
        emit(f"pscw_ring_p{p}", time_fn(g, x),
             f"tpu_model_us={DEFAULT_MODEL.p_pscw(2)*1e6:.2f};mode={choose_sync(2, p)}")

    # lock constants (host protocol, measured ns -> us)
    win = locks_sim.LockWindow(p=4)
    o = locks_sim.LockOrigin(win, 0)
    for name, acquire, release, model_us in (
        ("lock_shared", lambda: o.lock_shared(1), lambda: o.unlock_shared(1),
         DEFAULT_MODEL.p_lock_shared() * 1e6),
        ("lock_exclusive", lambda: o.lock_exclusive(1), lambda: o.unlock_exclusive(1),
         DEFAULT_MODEL.p_lock_excl() * 1e6),
        ("lock_all", o.lock_all, o.unlock_all, DEFAULT_MODEL.p_lock_shared() * 1e6),
    ):
        t0 = time.perf_counter()
        for _ in range(1000):
            acquire()
            release()
        us = (time.perf_counter() - t0) / 1000 * 1e6
        emit(name, us, f"tpu_model_us={model_us:.2f}")

    # flush: XLA-path scheduling barrier cost
    mesh = jax.make_mesh((min(4, n_all),), ("x",))
    x = jnp.zeros((min(4, n_all), 64), jnp.float32)
    from repro.core.epoch import flush as rma_flush

    def flushed(v):
        v = rma.put_shift(v, 1, "x")
        return rma_flush(v)

    f = jax.jit(shard_map(flushed, mesh=mesh, in_specs=P("x", None),
                          out_specs=P("x", None), check_vma=False))
    base = jax.jit(shard_map(lambda v: rma.put_shift(v, 1, "x"), mesh=mesh,
                             in_specs=P("x", None), out_specs=P("x", None), check_vma=False))
    emit("flush_overhead", max(time_fn(f, x) - time_fn(base, x), 0.0),
         f"tpu_model_us={DEFAULT_MODEL.p_flush()*1e6:.3f};paper_cray_ns=76")


if __name__ == "__main__":
    main()
