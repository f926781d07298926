"""Paper Fig. 5a: communication/computation overlap ratio.

Measures t(comm), t(comp), t(comm+comp interleaved); overlap ratio =
(t_comm + t_comp - t_both) / t_comm (1.0 = fully hidden).  Uses the ring
all-gather + matmul pair — the pattern the fused Pallas kernel targets.
"""
import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from benchmarks.common import emit, time_fn
from repro.core import collectives
from repro.parallel.overlap import CollectiveStrategist


def main() -> None:
    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("x",))
    K, M, N = 512, 256, 256
    w = jax.random.normal(jax.random.PRNGKey(0), (K, N)) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(1), (n * M, K // n)) * 0.1

    comm = jax.jit(shard_map(functools.partial(collectives.ring_all_gather, axis="x"),
                             mesh=mesh, in_specs=P(None, "x"), out_specs=P(None, None, "x"),
                             check_vma=False))

    def comp_only(xl, w):
        return jnp.tanh(xl @ w[: xl.shape[1]] @ w[: xl.shape[1]].T)

    comp = jax.jit(shard_map(comp_only, mesh=mesh, in_specs=(P("x", None), P(None, None)),
                             out_specs=P("x", None), check_vma=False))

    def both(xl, w):
        g = collectives.ring_all_gather(xl.T, "x")       # comm
        c = jnp.tanh(xl @ w[: xl.shape[1]] @ w[: xl.shape[1]].T)  # comp
        return c + g.transpose(2, 0, 1).reshape(xl.shape[0], -1)[:, : c.shape[1]] * 0

    fboth = jax.jit(shard_map(both, mesh=mesh, in_specs=(P("x", None), P(None, None)),
                              out_specs=P("x", None), check_vma=False))

    t_comm = time_fn(comm, x.T)
    t_comp = time_fn(comp, x, w)
    t_both = time_fn(fboth, x, w)
    ratio = max(0.0, min(1.0, (t_comm + t_comp - t_both) / max(t_comm, 1e-9)))
    strat = CollectiveStrategist()
    plan = strat.allgather_matmul_plan(M, K, N, n)
    emit("overlap_ratio", ratio * 100,
         f"t_comm_us={t_comm:.1f};t_comp_us={t_comp:.1f};t_both_us={t_both:.1f};plan={plan}")


if __name__ == "__main__":
    main()
