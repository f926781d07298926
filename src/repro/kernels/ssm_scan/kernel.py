"""Selective-state-space scan (Mamba recurrence) as a Pallas TPU kernel.

    h_t = decay_t * h_{t-1} + drive_t          h, decay, drive: [d, N]
    y_t = h_t . C_t                            C_t: [N]

The XLA path (`models.mamba`) uses `lax.associative_scan`, which is O(S log S)
work and materializes [B, S, d, N] twice; this kernel streams time through
VMEM in blocks with the state held in scratch — O(S) work, O(block) memory,
and the channel grid dimension is embarrassingly parallel across cores.

Grid: (B, d/bd, S/bt), time innermost (arbitrary); state scratch [bd, N]
persists across time blocks.  Each time block is an in-register sequential
loop over bt steps of [bd, N] elementwise FMA — VPU-shaped work.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssm_kernel(block_t: int, decay_ref, drive_ref, c_ref, y_ref, h_ref):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    def step(t, h):
        d = decay_ref[0, t].astype(jnp.float32)      # [bd, N]
        u = drive_ref[0, t].astype(jnp.float32)      # [bd, N]
        c = c_ref[0, t].astype(jnp.float32)          # [N]
        h = d * h + u
        y_ref[0, t] = (h @ c).astype(y_ref.dtype)    # [bd]
        return h

    h_ref[...] = jax.lax.fori_loop(0, block_t, step, h_ref[...])


def ssm_scan_pallas(
    decay: jax.Array,    # [B, S, d, N]
    drive: jax.Array,    # [B, S, d, N]
    c: jax.Array,        # [B, S, N]
    block_d: int = 256,
    block_t: int = 128,
    *, interpret: bool,
) -> jax.Array:
    """Returns y [B, S, d] = sum_N C_t * h_t."""
    B, S, d, N = decay.shape
    block_d = min(block_d, d)
    block_t = min(block_t, S)
    assert d % block_d == 0 and S % block_t == 0, (d, block_d, S, block_t)

    grid = (B, d // block_d, S // block_t)
    return pl.pallas_call(
        functools.partial(_ssm_kernel, block_t),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_t, block_d, N), lambda b, id_, it: (b, it, id_, 0)),
            pl.BlockSpec((1, block_t, block_d, N), lambda b, id_, it: (b, it, id_, 0)),
            pl.BlockSpec((1, block_t, N), lambda b, id_, it: (b, it, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_t, block_d), lambda b, id_, it: (b, it, id_)),
        out_shape=jax.ShapeDtypeStruct((B, S, d), decay.dtype),
        scratch_shapes=[pltpu.VMEM((block_d, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(decay, drive, c)
