"""Multi-device: cross-rank streamed paged attention (interpret mode).

Rank r attends over pages ids[r] of rank (r+shift)'s pool, streamed
page-at-a-time through the 2-slot staging window — checked against the
shift oracle (gather_ref + attention_ref) for every shift 1..n-1 with
masked ids and causal masking, and against the actual paged_gather kernel
+ local fused attention (the materialize-then-attend baseline the fused
path replaces)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.kernels.paged_attention import ops, ref
from repro.kernels.paged_gather import ops as pg_ops

n = len(jax.devices())
mesh = jax.make_mesh((n,), ("x",))
n_pages, pt, hd, Sq, k = 8, 4, 64, 8, 4

key = jax.random.PRNGKey(0)
kv = jax.random.normal(key, (n, n_pages, pt, 2, hd), jnp.float32)
q = jax.random.normal(jax.random.fold_in(key, 1), (n, Sq, hd), jnp.float32)
ids = jax.random.randint(jax.random.fold_in(key, 2), (n, k), 0, n_pages,
                         jnp.int32)
ids_masked = ids.at[0, 1].set(-1).at[2, 3].set(-1)   # per-rank holes


def oracle(qv, pages, idv, shift, causal):
    fn = functools.partial(ref.paged_attention_shift_ref, shift=shift,
                           axis="x", causal=causal)
    return jax.jit(shard_map(
        lambda qq, b, i: fn(qq[0], b[0], i[0])[None],
        mesh=mesh,
        in_specs=(P("x", None, None), P("x", None, None, None, None),
                  P("x", None)),
        out_specs=P("x", None, None), check_vma=False))(qv, pages, idv)


for shift in range(1, n):
    for causal in (False, True):
        y = ops.paged_attention_shift(q, kv, ids_masked, shift, mesh, "x",
                                      causal=causal)
        yr = oracle(q, kv, ids_masked, shift, causal)
        err = float(jnp.max(jnp.abs(y - yr)))
        assert err < 1e-4, f"shift={shift} causal={causal} err={err}"
    print(f"PASS paged_attention shift={shift} (masked ids, +/- causal)")

# streamed kernel == paged_gather kernel + local fused kernel (all-valid ids)
shift = 2
w = pt * 2 * hd
y = ops.paged_attention_shift(q, kv, ids, shift, mesh, "x")
rows = pg_ops.paged_gather(kv.reshape(n, n_pages, w), ids, shift, mesh, "x")
rows = rows.reshape(n, k, pt, 2, hd)
local_ids = jnp.arange(k, dtype=jnp.int32)[None]
# per-rank rows of the mesh-sharded results are read back on the host
q_h, rows_h, y_h = (np.asarray(a) for a in (q, rows, y))
for r in range(n):
    yb = ops.paged_attention(jnp.asarray(q_h[r][None]), jnp.asarray(rows_h[r]),
                             local_ids)[0]
    err = float(np.max(np.abs(y_h[r] - np.asarray(yb))))
    assert err < 1e-4, f"rank={r} err={err}"
print(f"PASS streamed == paged_gather + local fused (shift={shift}, {n} ranks)")
