"""Multi-device: overlapped hierarchical gradient sync + int8 DCN compression.

Checks: (1) `overlapped_grad_sync` over a (pod, data) mesh equals a flat
psum; (2) with error-feedback int8 on the cross-pod hop, the running
average converges to the true gradient (unbiasedness).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.parallel.compression import compress_decompress, init_compression_state
from repro.parallel.overlap import bucket_grads, overlapped_grad_sync

N = len(jax.devices())
mesh = jax.make_mesh((2, N // 2), ("pod", "data"))

grads = {
    "w1": jax.random.normal(jax.random.PRNGKey(0), (N * 4, 8)),
    "w2": {"b": jax.random.normal(jax.random.PRNGKey(1), (N * 2, 3))},
}
specs = jax.tree.map(lambda g: P(("pod", "data"), None), grads)

f = jax.jit(shard_map(
    functools.partial(overlapped_grad_sync, inner_axis="data", outer_axis="pod",
                      bucket_bytes=64),
    mesh=mesh, in_specs=(specs,), out_specs=specs, check_vma=False))
ref = jax.jit(shard_map(
    lambda g: jax.tree.map(lambda x: jax.lax.psum(x, ("pod", "data")), g),
    mesh=mesh, in_specs=(specs,), out_specs=specs, check_vma=False))

out, want = f(grads), ref(grads)
for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(want)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)
print("PASS hierarchical grad sync == flat psum")

# bucketing covers every leaf exactly once
buckets = bucket_grads(grads, bucket_bytes=64)
flat_idx = sorted(i for b in buckets for i in b)
assert flat_idx == list(range(len(jax.tree.leaves(grads)))), buckets
print("PASS bucketing partition")

# error-feedback int8 on the DCN hop: mean of compressed rounds -> truth
g = {"w": jax.random.normal(jax.random.PRNGKey(2), (512,)) * 1e-2}
state = init_compression_state(g)
acc = jnp.zeros((512,))
for _ in range(40):
    comp, state, _ = compress_decompress(g, state)
    acc = acc + comp["w"]
err = float(jnp.abs(acc / 40 - g["w"]).max() / jnp.abs(g["w"]).max())
assert err < 0.05, err
print("PASS error-feedback convergence", err)
