"""Paper Fig. 7c / §4.3: distributed 3D FFT — slab decomposition with
one-sided exchange and overlap vs bulk-synchronous baseline.

2D-decomposed pencil FFT: local FFT over two axes, one-sided all-to-all
transpose, FFT over the third.  The overlap variant starts each slab's
exchange as soon as that slab's local FFT finishes (paper: "communicate the
data of a plane as soon as it is available").
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from benchmarks.common import emit, time_fn
from repro.core import collectives


def main() -> None:
    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("x",))
    N = 64  # N^3 grid

    def fft3d_bulk(v):  # [N/n, N, N] complex on each rank
        v = jnp.fft.fftn(v, axes=(1, 2))              # local 2D FFTs
        # bulk-synchronous transpose: one big all-to-all, then z-FFT
        blocks = v.reshape(v.shape[0], n, N // n, N).transpose(1, 0, 2, 3)
        blocks = collectives.all_to_all(blocks, "x")  # [n, N/n, N/n, N]
        w = blocks.transpose(1, 2, 0, 3).reshape(v.shape[0], N // n, n * N)
        w = w[..., :N]
        return jnp.fft.fft(w, axis=1)

    def fft3d_overlap(v):
        # slab-by-slab: FFT one x-slab, immediately exchange it (XLA can
        # overlap the next slab's FFT with the previous slab's all-to-all)
        outs = []
        S = v.shape[0]
        for s in range(S):
            slab = jnp.fft.fftn(v[s], axes=(0, 1))    # [N, N]
            blk = slab.reshape(n, N // n, N)
            blk = collectives.all_to_all(blk, "x")
            outs.append(blk)
        w = jnp.stack(outs, axis=1)                   # [n, S, N/n, N]
        w = w.transpose(1, 2, 0, 3).reshape(S, N // n, n * N)[..., :N]
        return jnp.fft.fft(w, axis=1)

    x = (jax.random.normal(jax.random.PRNGKey(0), (N, N, N))
         + 1j * jax.random.normal(jax.random.PRNGKey(1), (N, N, N))).astype(jnp.complex64)

    fb = jax.jit(shard_map(fft3d_bulk, mesh=mesh, in_specs=P("x", None, None),
                           out_specs=P("x", None, None), check_vma=False))
    fo = jax.jit(shard_map(fft3d_overlap, mesh=mesh, in_specs=P("x", None, None),
                           out_specs=P("x", None, None), check_vma=False))
    us_b = time_fn(fb, x, iters=10)
    us_o = time_fn(fo, x, iters=10)
    flops = 5 * N**3 * np.log2(N**3)  # standard FFT flop count
    emit("fft3d_bulk", us_b, f"gflops={flops/(us_b*1e-6)/1e9:.2f}")
    emit("fft3d_overlap", us_o, f"gflops={flops/(us_o*1e-6)/1e9:.2f};speedup={us_b/us_o:.2f}x")


if __name__ == "__main__":
    main()
