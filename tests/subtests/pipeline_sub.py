"""Multi-device: GPipe pipeline forward == sequential stage application."""
import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.parallel.pipeline import PipelineConfig, pipeline_forward

S = 4  # stages
mesh = jax.make_mesh((S,), ("pod",))
cfg = PipelineConfig(n_stages=S, n_micro=6, axis="pod")
mb, d = 3, 8

# stage s multiplies by W_s (stacked [S, d, d], sharded by stage)
W = jax.random.normal(jax.random.PRNGKey(0), (S, d, d)) * 0.5
x = jax.random.normal(jax.random.PRNGKey(1), (cfg.n_micro, mb, d))


def stage_fn(w, v):
    return jnp.tanh(v @ w[0])


f = jax.jit(shard_map(
    functools.partial(pipeline_forward, stage_fn, cfg=cfg),
    mesh=mesh, in_specs=(P("pod", None, None), P(None, None, None)),
    out_specs=P(None, None, None), check_vma=False,
))
out = f(W, x)

ref = x
for s in range(S):
    ref = jnp.tanh(ref @ W[s])
err = float(jnp.max(jnp.abs(out - ref)))
print(f"pipeline err={err:.2e}, bubble={cfg.bubble_fraction:.2f}")
assert err < 1e-5
print("PASS pipeline")
