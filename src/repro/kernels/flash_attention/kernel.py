"""Flash attention (GQA, causal) as a Pallas TPU kernel.

Grid (B, Hq, nq, nk) with the KV dimension innermost/arbitrary; online
softmax state (m, l, acc) lives in VMEM scratch and is carried across the
nk steps; the output block is written once at the last KV step.  Causal
blocks strictly above the diagonal are skipped (`pl.when`), halving the
work.  GQA is pure indexing: the k/v BlockSpecs map query head h to kv head
h // group.

Block shapes are MXU-aligned ((bq, hd) x (hd, bk), hd in {64, 128}); VMEM
footprint per step = q + k + v + acc blocks ≈ 4·bq·hd·4B — far under the
128 MiB/core budget at bq = bk = 512.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(causal: bool, block_q: int, block_k: int, seq_k: int,
                  q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: skip blocks strictly above the diagonal
    first_q = iq * block_q
    last_q = first_q + block_q - 1
    first_k = ik * block_k
    run = (first_k <= last_q) if causal else (ik >= 0)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                 # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)                 # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [bq, bk]

        q_pos = first_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = first_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = k_pos < seq_k
        if causal:
            mask &= q_pos >= k_pos
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + p @ v
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,        # [B, Hq, Sq, hd]
    k: jax.Array,        # [B, Hkv, Sk, hd]
    v: jax.Array,
    causal: bool = True,
    block_q: int = 512,
    block_k: int = 512,
    *, interpret: bool,
) -> jax.Array:
    B, Hq, Sq, hd = q.shape
    _, Hkv, Sk, _ = k.shape
    g = Hq // Hkv
    scale = 1.0 / (hd ** 0.5)
    q = q * jnp.asarray(scale, q.dtype)

    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    pq = (-Sq) % block_q
    pk = (-Sk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    nq = (Sq + pq) // block_q
    nk = (Sk + pk) // block_k

    grid = (B, Hq, nq, nk)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, causal, block_q, block_k, Sk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, iq, ik, g=g: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, iq, ik, g=g: (b, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq + pq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(qp, kp, vp)
    return out[:, :, :Sq]
