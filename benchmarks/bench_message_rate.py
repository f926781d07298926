"""Paper Fig. 5b/c: message rate — issue a batch of small puts in one epoch.

The paper injects 1000 8-byte messages without sync; here one jitted epoch
carries k puts (XLA pipelines the ppermutes), measuring per-message cost.
Two series (DESIGN.md §8):

  * **eager**     — every put lowers to its own ppermute at call time;
  * **coalesced** — the same puts recorded into one `RmaPlan` and flushed
    as a single fused transfer (epoch-scoped aggregation).

The derived column carries the §3/§8 model's per-message cost for both
paths; on the modeled small-message rate the coalesced path must win — the
paper's UPC comparison hinges on exactly this aggregation.
"""
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from benchmarks.common import emit, time_fn
from jax import shard_map
from repro.core import plan as plan_mod, rma
from repro.core.perfmodel import DEFAULT_MODEL
from repro.core.rma import OpCounter


def main() -> None:
    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("x",))
    k = 256
    waves = 8
    x = jnp.zeros((n, k, 2), jnp.float32)  # k 8-byte messages per rank

    def burst_eager(v):
        outs = []
        for i in range(waves):  # 8 distinct wavefronts of k/8 messages
            outs.append(rma.put_shift(v[:, i::waves], 1, "x"))
        return jnp.concatenate(outs, axis=1)

    def burst_coalesced(v):
        # the same wavefronts recorded in one plan -> ONE fused ppermute
        pl = plan_mod.RmaPlan("x")
        hs = [pl.put_shift(v[:, i::waves], 1) for i in range(waves)]
        pl.flush(aggregate=True)
        return jnp.concatenate([h.result() for h in hs], axis=1)

    sm = functools.partial(
        shard_map, mesh=mesh, in_specs=P("x", None, None),
        out_specs=P("x", None, None), check_vma=False,
    )
    model = DEFAULT_MODEL
    modeled_eager_us = model.p_direct_transfers(k, 8) * 1e6 / k
    modeled_coal_us = model.p_packed_transfer(k, 8) * 1e6 / k

    with OpCounter() as c_e:
        f_eager = jax.jit(sm(burst_eager))
        us = time_fn(f_eager, x)
    emit("message_rate_8B_eager", us / k,
         f"tpu_model_us={modeled_eager_us:.3f};wire_transfers={c_e.coalesced_msgs};"
         f"paper_cray_ns=416")

    with OpCounter() as c_c:
        f_coal = jax.jit(sm(burst_coalesced))
        us_c = time_fn(f_coal, x)
    emit("message_rate_8B_coalesced", us_c / k,
         f"tpu_model_us={modeled_coal_us:.3f};wire_transfers={c_c.coalesced_msgs};"
         f"raw_msgs={c_c.raw_msgs};aggregation={c_c.aggregation_factor:.0f}x")

    assert modeled_coal_us < modeled_eager_us, (
        "coalesced path must beat eager on modeled small-message rate"
    )
    emit("message_rate_modeled_speedup", 0.0,
         f"eager_us_per_msg={modeled_eager_us:.3f};"
         f"coalesced_us_per_msg={modeled_coal_us:.3f};"
         f"speedup={modeled_eager_us / modeled_coal_us:.1f}x;"
         f"crossover_bytes={model.aggregation_crossover_bytes(k):.0f}")


if __name__ == "__main__":
    main()
