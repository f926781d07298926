"""Paper Fig. 7b: dynamic sparse data exchange — accumulate protocol vs
alltoall / reduce-scatter baselines, k=6 random neighbors per process."""
import jax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from benchmarks.common import emit, time_fn
from repro.core import dsde


def main() -> None:
    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("x",))
    k = 6
    items = k
    key = jax.random.PRNGKey(0)
    data = jax.random.normal(key, (n * items, 2))
    targets = jax.random.randint(jax.random.fold_in(key, 1), (n * items,), 0, n)
    cap = 4 * k

    protos = {
        "dsde_accumulate": dsde.exchange_accumulate,          # the paper's winner
        "dsde_alltoall": dsde.exchange_alltoall_baseline,
        "dsde_reduce_scatter": dsde.exchange_reduce_scatter_baseline,
        "dsde_queue": dsde.exchange_queue,                    # rmaq MPSC rings
    }
    results = {}
    for name, proto in protos.items():
        def body(d, t, proto=proto):
            r = proto(d, t, "x", cap)
            return r.recv_data, r.recv_valid
        f = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("x", None), P("x")),
                              out_specs=(P("x", None), P("x")), check_vma=False))
        results[name] = time_fn(f, data, targets)
    base = results["dsde_accumulate"]
    for name, us in results.items():
        emit(name, us, f"k={k};vs_accumulate={us/base:.2f}x")


if __name__ == "__main__":
    main()
