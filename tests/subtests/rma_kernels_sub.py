"""Multi-device: RMA Pallas kernels (interpret mode) vs lax refs."""
import functools
import jax, jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro.kernels.rma import ops, ref

mesh = jax.make_mesh((4,), ("x",))
x = jnp.arange(4 * 8 * 128, dtype=jnp.float32).reshape(4 * 8, 128)

# put
y = ops.put_shift(x, 1, mesh, "x")
yr = jax.jit(shard_map(functools.partial(ref.put_shift_ref, shift=1, axis="x"),
             mesh=mesh, in_specs=P("x", None), out_specs=P("x", None), check_vma=False))(x)
assert jnp.allclose(y, yr), "put"; print("PASS put")
# get
y = ops.get_shift(x, 1, mesh, "x")
yr = jax.jit(shard_map(functools.partial(ref.get_shift_ref, src_shift=1, axis="x"),
             mesh=mesh, in_specs=P("x", None), out_specs=P("x", None), check_vma=False))(x)
assert jnp.allclose(y, yr), "get"; print("PASS get")
# accumulate
acc = jnp.ones_like(x)
y = ops.accumulate_shift(x, acc, 1, mesh, "x")
yr = jax.jit(shard_map(functools.partial(ref.accumulate_shift_ref, shift=1, axis="x"),
             mesh=mesh, in_specs=(P("x", None), P("x", None)), out_specs=P("x", None), check_vma=False))(x, acc)
assert jnp.allclose(y, yr), "acc"; print("PASS acc")
# ring all-gather
y = ops.ring_all_gather(x, mesh, "x")
assert jnp.allclose(y.reshape(-1, 128), x), "ring_ag"; print("PASS ring_ag")

