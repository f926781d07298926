"""Paper Fig. 8 / §4.4: MILC-style 4D stencil — one-sided halo exchange +
overlapped compute vs bulk-synchronous message-passing formulation."""
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from benchmarks.common import emit, time_fn
from repro.core import collectives
from repro.core.epoch import PSCWEpoch


def main() -> None:
    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("x",))
    # local lattice 4^3 x 8 per rank (the paper's weak-scaling local volume),
    # 3-component complex vectors -> real [T, X, Y, Z, 6]
    T, X, Y, Z, C = 8 * n, 4, 4, 4, 6
    lat = jax.random.normal(jax.random.PRNGKey(0), (T, X, Y, Z, C))

    def stencil_rma(v):
        # one-sided halo exchange on the distributed T axis (PSCW epoch,
        # k=2 neighbors), local periodic shifts in X/Y/Z
        ep = PSCWEpoch("x", group=[0, 1])
        v = ep.post(v)
        padded = collectives.halo_exchange_1d(v, 1, "x", dim=0)
        v = ep.complete(v)
        acc = padded[2:] + padded[:-2]                      # T+1 / T-1
        for d in (1, 2, 3):
            acc = acc + jnp.roll(v, 1, axis=d) + jnp.roll(v, -1, axis=d)
        return acc - 8.0 * v

    def stencil_msg(v):
        # message-passing formulation: full all-gather of the T axis
        # (receiver-side buffering), then the same stencil
        full = jax.lax.all_gather(v, "x", tiled=True)       # [T*n, ...]
        me = jax.lax.axis_index("x")
        Tl = v.shape[0]
        up = jax.lax.dynamic_slice_in_dim(full, ((me + 1) % n) * Tl, Tl, 0)
        dn = jax.lax.dynamic_slice_in_dim(full, ((me - 1) % n) * Tl, Tl, 0)
        padded = jnp.concatenate([dn[-1:], v, up[:1]], axis=0)
        acc = padded[2:] + padded[:-2]
        for d in (1, 2, 3):
            acc = acc + jnp.roll(v, 1, axis=d) + jnp.roll(v, -1, axis=d)
        return acc - 8.0 * v

    fr = jax.jit(shard_map(stencil_rma, mesh=mesh, in_specs=P("x", None, None, None, None),
                           out_specs=P("x", None, None, None, None), check_vma=False))
    fm = jax.jit(shard_map(stencil_msg, mesh=mesh, in_specs=P("x", None, None, None, None),
                           out_specs=P("x", None, None, None, None), check_vma=False))
    us_r = time_fn(fr, lat)
    us_m = time_fn(fm, lat)
    emit("milc_stencil_rma", us_r, f"bytes_moved_ratio={2/(2*n):.3f}_of_msg")
    emit("milc_stencil_msg", us_m, f"rma_speedup={us_m/us_r:.2f}x;paper_gain=13.8pct")


if __name__ == "__main__":
    main()
