"""Shared neural building blocks (pure JAX, pytree params).

Conventions:
  * activations [B, S, D]; attention heads [B, S, H, hd];
  * params are nested dicts of jnp arrays; stacked-layer weights carry a
    leading [L, ...] axis consumed by ``lax.scan``;
  * compute dtype bf16, params bf16, reductions fp32.

Attention is *blockwise* (online-softmax over KV chunks, same math as the
flash kernel's oracle in `kernels/flash_attention/ref.py`) so that 32k-seq
prefill never materializes an S x S score matrix even on the XLA path.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint

from repro.parallel.sharding import shard

Array = jax.Array

DEFAULT_BLOCK = 512

# attention backend: "xla" (blockwise scan, default — compiles everywhere) or
# "pallas" (fused flash kernel, kernels/flash_attention — TPU deployments /
# interpret-mode tests).  Set via set_attention_backend().
_ATTN_BACKEND: list[str] = ["xla"]


def set_attention_backend(name: str) -> None:
    assert name in ("xla", "pallas"), name
    _ATTN_BACKEND[0] = name


# ---------------------------------------------------------- gradient dtype
@jax.custom_vjp
def grad_cast_bf16(x: Array) -> Array:
    """Identity forward; casts the incoming cotangent to bf16.

    Without this, the f32 loss cotangent propagates f32 gradients through
    the entire residual stream (f32 TP all-reduces, f32 remat-saved hiddens
    — 2x HBM and 2x ICI on the backward; measured on qwen1.5-110b train_4k,
    see EXPERIMENTS.md §Perf).  Numerically this matches standard bf16
    mixed-precision training: master weights/optimizer stay f32.
    """
    return x


def _gc_fwd(x):
    return x, None


def _gc_bwd(_, g):
    return (g.astype(jnp.bfloat16).astype(g.dtype) if g.dtype == jnp.bfloat16
            else g.astype(jnp.bfloat16),)


grad_cast_bf16.defvjp(_gc_fwd, _gc_bwd)


# ------------------------------------------------------------------- norms
def rmsnorm(x: Array, scale: Array, eps: float = 1e-5) -> Array:
    """RMS norm with f32 *reduction* but bf16 large-tensor math.

    Casting the whole input to f32 (the textbook form) lets XLA's
    excess-precision pass hoist the convert through the preceding residual
    add AND the TP all-reduce, silently doubling HBM+ICI traffic on the
    residual stream (measured: +100% AR bytes on qwen1.5-110b train_4k).
    Keeping the elementwise path in bf16 pins the collective to bf16; the
    variance is still accumulated in f32.
    """
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    inv = lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * scale


def init_rmsnorm(d: int, dtype=jnp.bfloat16) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


# -------------------------------------------------------------------- RoPE
def rope_freqs(head_dim: int, theta: float = 10_000.0) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: Array, positions: Array, style: str = "full", theta: float = 10_000.0) -> Array:
    """x [B, S, H, hd]; positions [B, S] or [S].

    style='full': rotate all pairs.  style='2d' (ChatGLM): rotate only the
    first half of head_dim, pass the second half through unchanged.
    """
    if style == "none":
        return x
    hd = x.shape[-1]
    rot_dim = hd // 2 if style == "2d" else hd
    x_rot, x_pass = x[..., :rot_dim], x[..., rot_dim:]

    freqs = rope_freqs(rot_dim, theta)                      # [rot_dim/2]
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [B, S, rd/2]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]

    x1, x2 = jnp.split(x_rot.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), x_pass], axis=-1)


# --------------------------------------------------------------- attention
def init_attention(rng, d_model: int, n_heads: int, n_kv: int, head_dim: int,
                   bias: bool = False, dtype=jnp.bfloat16) -> dict:
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    s = 1.0 / math.sqrt(d_model)
    p = {
        "wq": (jax.random.normal(k1, (d_model, n_heads, head_dim)) * s).astype(dtype),
        "wk": (jax.random.normal(k2, (d_model, n_kv, head_dim)) * s).astype(dtype),
        "wv": (jax.random.normal(k3, (d_model, n_kv, head_dim)) * s).astype(dtype),
        "wo": (jax.random.normal(k4, (n_heads, head_dim, d_model)) * s).astype(dtype),
    }
    if bias:
        p["bq"] = jnp.zeros((n_heads, head_dim), dtype)
        p["bk"] = jnp.zeros((n_kv, head_dim), dtype)
        p["bv"] = jnp.zeros((n_kv, head_dim), dtype)
    return p


def blockwise_attention(
    q: Array,           # [B, Sq, H, hd]
    k: Array,           # [B, Sk, Hkv, hd]
    v: Array,           # [B, Sk, Hkv, hd]
    causal: bool = True,
    q_offset: int | Array = 0,   # absolute position of q[0] (for caches)
    block_size: int = DEFAULT_BLOCK,
    kv_valid_len: Optional[Array] = None,  # mask out cache slots >= this
    block_q: Optional[int] = None,
    scale: Optional[float] = None,         # default 1/sqrt(q's width)
) -> Array:
    """Flash-structured attention on the XLA path: outer scan over Q chunks,
    inner online-softmax scan over KV blocks.

    Never materializes S x S; the inner-scan carry is one Q chunk's (m, l,
    acc) — O(bq * hd) — so HBM traffic scales with S * hd, not S^2 (the
    ungrouped variant carried full-S state through every KV step and was
    the dominant memory-roofline term at 32k; see EXPERIMENTS.md §Perf).
    Scores are computed in f32; probabilities travel to the p@v matmul in
    bf16 (standard flash practice); accumulation stays f32.

    GQA: H must be a multiple of Hkv; kv heads are broadcast per group.
    `v` may be narrower or wider than `q` and `k` (latent attention).
    """
    B, Sq, H, hd = q.shape
    Sk, Hkv, hv = k.shape[1], k.shape[2], v.shape[-1]
    g = H // Hkv
    scale = 1.0 / math.sqrt(hd) if scale is None else scale
    bq = min(block_q or block_size, Sq)
    bk = min(block_size, Sk)

    nq = max(1, (Sq + bq - 1) // bq)
    pq = nq * bq - Sq
    nk = max(1, (Sk + bk - 1) // bk)
    pk = nk * bk - Sk

    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    # [nq, B, Hkv, g, bq, hd] / [nk, B, Hkv, bk, hd]
    qb = qp.reshape(B, nq, bq, Hkv, g, hd).transpose(1, 0, 3, 4, 2, 5)
    kb = kp.reshape(B, nk, bk, Hkv, hd).transpose(1, 0, 3, 2, 4)
    vb = vp.reshape(B, nk, bk, Hkv, hv).transpose(1, 0, 3, 2, 4)

    def q_chunk(carry, xs):
        iq, qblk = xs                                    # qblk [B,Hkv,g,bq,hd]
        q_pos = q_offset + iq * bq + jnp.arange(bq)

        def kv_step(inner, ys):
            m, l, acc = inner
            ik, kblk, vblk = ys
            kv_pos = ik * bk + jnp.arange(bk)
            sc = jnp.einsum(
                "bhgqd,bhkd->bhgqk", qblk, kblk,
                preferred_element_type=jnp.float32,
            ) * scale
            mask = jnp.ones((bq, bk), bool)
            if causal:
                mask &= q_pos[:, None] >= kv_pos[None, :]
            mask &= (kv_pos < Sk)[None, :]
            if kv_valid_len is not None:
                mask &= (kv_pos < kv_valid_len)[None, :]
            sc = jnp.where(mask[None, None, None], sc, -jnp.inf)
            m_new = jnp.maximum(m, sc.max(-1))
            m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
            pr = jnp.exp(sc - m_safe[..., None])
            pr = jnp.where(mask[None, None, None], pr, 0.0)
            corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
            l = l * corr + pr.sum(-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", pr.astype(v.dtype), vblk,
                preferred_element_type=jnp.float32,
            )
            return (m_new, l, acc), None

        m0 = jnp.full((B, Hkv, g, bq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, Hkv, g, bq), jnp.float32)
        a0 = jnp.zeros((B, Hkv, g, bq, hv), jnp.float32)
        # checkpoint per KV block too: the backward otherwise stacks every
        # block's score matrix (a full S x S residual per layer)
        (m, l, acc), _ = lax.scan(
            jax.checkpoint(kv_step, prevent_cse=False), (m0, l0, a0),
            (jnp.arange(nk), kb, vb)
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return carry, out.astype(q.dtype)

    # remat per Q chunk: backward recomputes the inner KV scan blockwise
    _, outs = lax.scan(
        jax.checkpoint(q_chunk, prevent_cse=False), 0, (jnp.arange(nq), qb)
    )
    # [nq, B, Hkv, g, bq, hv] -> [B, Sq, H, hv]
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, nq * bq, H, hv)
    return out[:, :Sq]


def cached_decode_attention(q: Array, k: Array, v: Array, valid_len: Array,
                            scale: Optional[float] = None) -> Array:
    """One query position against one layer's cache rows, where they lie.

    q [B, 1, H, hd]; k [B, Hkv, hd, Smax], v [B, Hkv, hv, Smax], the stored
    cache layout: the scores are a plain matmul over k's rows and the values
    one against v's minor dim, so the rows are read once, with no pad,
    reshape or transpose.  `v` may be `k` itself (the absorbed latent
    attention reads the latent rows as both).  Rows at or past
    `valid_len` are masked.  Scores are f32 (scaled by `scale`, default
    1/sqrt(hd)), probabilities travel to the p@v matmul in bf16 and the sum
    stays f32, as in `blockwise_attention`.
    """
    B, _, H, hd = q.shape
    Hkv, Smax, hv = k.shape[1], k.shape[3], v.shape[2]
    qg = q.reshape(B, Hkv, H // Hkv, hd)
    sc = jnp.einsum(
        "bhgd,bhds->bhgs", qg, k, preferred_element_type=jnp.float32,
    ) * (1.0 / math.sqrt(hd) if scale is None else scale)
    sc = jnp.where(jnp.arange(Smax) < valid_len, sc, -jnp.inf)
    pr = jnp.exp(sc - sc.max(-1, keepdims=True))
    out = jnp.einsum(
        "bhgs,bhds->bhgd", pr.astype(v.dtype), v, preferred_element_type=jnp.float32,
    ) / pr.sum(-1)[..., None]
    return out.reshape(B, 1, H, hv).astype(q.dtype)


def cache_write(stack: Array, rows: Array, layer: Array, start: Array) -> Array:
    """Write one layer's new rows [B, S, Hkv, hd] into the stacked cache
    [L, B, Hkv, hd, Smax] at positions [start, start + S): one update of
    the buffer the caller carries, in place when that buffer is donated."""
    rows = rows.astype(stack.dtype).transpose(0, 2, 3, 1)[None]
    out = lax.dynamic_update_slice(stack, rows, (layer, 0, 0, 0, start))
    # Left free, XLA lays the carried stack out for this row write (position
    # major) and relayouts it around every read and at the program's edges;
    # row-major is the layout the decode matmuls and the program's arguments
    # already use, so nothing is copied.
    return with_layout_constraint(out, Layout(major_to_minor=tuple(range(out.ndim))))


def attention(
    params: dict,
    x: Array,                       # [B, S, D]
    positions: Array,               # [B, S] or [S]
    rope_style: str = "full",
    causal: bool = True,
    cache: Optional[dict] = None,   # {"k": [L,B,Hkv,hd,Smax], "v":..., "len": [], "layer": []}
    cross_kv: Optional[tuple] = None,   # precomputed (k, v) for cross-attn
    block_size: int = DEFAULT_BLOCK,
    theta: float = 10_000.0,
) -> tuple[Array, Optional[dict]]:
    """GQA attention, optionally with a decode cache or cross-attention KV.

    With a cache, `cache["k"]`/`["v"]` are the whole layer stack and
    `cache["layer"]` says which layer this is: the new rows are written
    into the stack and the layer's rows read back from it, and the updated
    stack is returned as `{"k", "v"}`.
    """
    B, S, D = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    if "bq" in params:
        q = q + params["bq"]
    q = shard(q, "act_bthd")

    if cross_kv is not None:
        k, v = cross_kv
        out = blockwise_attention(q, k, v, causal=False, block_size=block_size)
        new_cache = cache
    else:
        k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
        if "bk" in params:
            k, v = k + params["bk"], v + params["bv"]
        q = apply_rope(q, positions, rope_style, theta)
        k = apply_rope(k, positions, rope_style, theta)
        if cache is None:
            if _ATTN_BACKEND[0] == "pallas":
                from repro.kernels.flash_attention.ops import flash_attention

                out = flash_attention(
                    q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                    v.transpose(0, 2, 1, 3), causal=causal,
                ).transpose(0, 2, 1, 3)
            else:
                out = blockwise_attention(q, k, v, causal=causal, block_size=block_size)
            new_cache = None
        else:
            # decode / chunked prefill: append to the layer's rows, attend over them
            start, layer = cache["len"], cache["layer"]
            ck = cache_write(cache["k"], k, layer, start)
            cv = cache_write(cache["v"], v, layer, start)
            new_cache = {"k": ck, "v": cv}
            lk = lax.dynamic_index_in_dim(ck, layer, 0, keepdims=False)
            lv = lax.dynamic_index_in_dim(cv, layer, 0, keepdims=False)
            if S == 1:
                out = cached_decode_attention(q, lk, lv, start + 1)
            else:
                out = blockwise_attention(
                    q, lk.transpose(0, 3, 1, 2), lv.transpose(0, 3, 1, 2),
                    causal=True, q_offset=start, block_size=block_size,
                    kv_valid_len=start + S,
                )

    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return shard(y, "act_btd"), new_cache


# ------------------------------------------------ latent attention (MLA)
# DeepSeek-V3 builds its latent RMSNorm without an eps, so the norm's own
# default holds there, not the model's rms_norm_eps.
MLA_NORM_EPS = 1e-6


def init_mla(rng, d_model: int, n_heads: int, kv_lora: int, nope: int, rope: int,
             v_dim: int, dtype=jnp.bfloat16) -> dict:
    """Multi-head latent attention with one q projection (no q latent)."""
    ks = jax.random.split(rng, 5)
    s, s_l = 1.0 / math.sqrt(d_model), 1.0 / math.sqrt(kv_lora)

    def normal(k, shape, std):
        return (jax.random.normal(k, shape) * std).astype(dtype)

    return {
        "wq": normal(ks[0], (d_model, n_heads, nope + rope), s),
        "wkv_a": normal(ks[1], (d_model, kv_lora + rope), s),
        "kv_norm": init_rmsnorm(kv_lora, dtype),
        "wk_b": normal(ks[2], (n_heads, nope, kv_lora), s_l),       # W_UK
        "wv_b": normal(ks[3], (n_heads, kv_lora, v_dim), s_l),      # W_UV
        "wo": normal(ks[4], (n_heads, v_dim, d_model), 1.0 / math.sqrt(n_heads * v_dim)),
    }


def mla_attention(
    params: dict,
    x: Array,                       # [B, S, D]
    positions: Array,               # [B, S] or [S]
    cache: Optional[dict] = None,   # {"lat": [L,B,1,lora+rope,Smax], "len": [], "layer": []}
    theta: float = 10_000.0,
    block_size: int = DEFAULT_BLOCK,
) -> tuple[Array, Optional[dict]]:
    """Multi-head latent attention (DeepSeek-V2/V3).

    Each position keeps one row of `lora + rope` values: the normed latent
    c and the rotary key k_pe, which all heads share.  Per head, the key is
    [c @ W_UK, k_pe] and the value c @ W_UV; scores are scaled by
    1/sqrt(nope + rope).

    Without a cache (training, the full forward) the keys and values are
    expanded per head.  With a cache the attention is *absorbed*: the new
    rows are written into the latent stack (`cache_write`, as K/V rows are),
    the query becomes [q_nope @ W_UK^T, q_pe] and scores the latent rows
    directly, and the output leaves the latent through W_UV.  No per-head
    key or value is built from the cache, in decode or in prefill.
    """
    B, S, _ = x.shape
    H, nope, lora = params["wk_b"].shape
    rope = params["wq"].shape[-1] - nope
    scale = 1.0 / math.sqrt(nope + rope)
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    q_nope = q[..., :nope]
    q_pe = apply_rope(q[..., nope:], positions, "full", theta)
    kv = x @ params["wkv_a"]                                        # [B, S, lora + rope]
    c = rmsnorm(kv[..., :lora], params["kv_norm"]["scale"], MLA_NORM_EPS)
    k_pe = apply_rope(kv[..., None, lora:], positions, "full", theta)  # [B, S, 1, rope]

    if cache is None:
        k_nope = jnp.einsum("bsl,hnl->bshn", c, params["wk_b"])
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (B, S, H, rope))], axis=-1)
        v = jnp.einsum("bsl,hlv->bshv", c, params["wv_b"])
        out = blockwise_attention(jnp.concatenate([q_nope, q_pe], axis=-1), k, v,
                                  causal=True, block_size=block_size, scale=scale)
        new_cache = None
    else:
        start, layer = cache["len"], cache["layer"]
        rows = jnp.concatenate([c, k_pe[:, :, 0]], axis=-1)[:, :, None]
        lat = cache_write(cache["lat"], rows, layer, start)
        new_cache = {"lat": lat}
        rows = lax.dynamic_index_in_dim(lat, layer, 0, keepdims=False)  # [B, 1, lora+rope, Smax]
        qa = jnp.concatenate(
            [jnp.einsum("bshn,hnl->bshl", q_nope, params["wk_b"]), q_pe], axis=-1)
        # the values are the rows' first `lora` values; they are read as the
        # whole rows and the output sliced, since a slice of the rows as the
        # value operand makes XLA copy the layer's rows out of the stack
        if S == 1:
            o_lat = cached_decode_attention(qa, rows, rows, start + 1, scale)
        else:
            rows = rows.transpose(0, 3, 1, 2)
            o_lat = blockwise_attention(
                qa, rows, rows, causal=True, q_offset=start, block_size=block_size,
                kv_valid_len=start + S, scale=scale,
            )
        out = jnp.einsum("bshl,hlv->bshv", o_lat[..., :lora], params["wv_b"])

    y = jnp.einsum("bshv,hvd->bsd", out, params["wo"])
    return shard(y, "act_btd"), new_cache


# --------------------------------------------------------------------- MLP
def init_mlp(rng, d_model: int, d_ff: int, mlp_type: str = "swiglu", dtype=jnp.bfloat16) -> dict:
    k1, k2, k3 = jax.random.split(rng, 3)
    s_in, s_out = 1.0 / math.sqrt(d_model), 1.0 / math.sqrt(d_ff)
    p = {
        "w_in": (jax.random.normal(k1, (d_model, d_ff)) * s_in).astype(dtype),
        "w_out": (jax.random.normal(k2, (d_ff, d_model)) * s_out).astype(dtype),
    }
    if mlp_type == "swiglu":
        p["w_gate"] = (jax.random.normal(k3, (d_model, d_ff)) * s_in).astype(dtype)
    return p


def mlp(params: dict, x: Array, mlp_type: str = "swiglu") -> Array:
    h = x @ params["w_in"]
    if mlp_type == "swiglu":
        h = jax.nn.silu(x @ params["w_gate"]) * h
    else:
        h = jax.nn.gelu(h)
    h = shard(h, "act_btf")
    return shard(h @ params["w_out"], "act_btd")


def sinusoidal_pos(positions: Array, d_model: int) -> Array:
    """Classic sin/cos positional embedding for arbitrary positions [S]."""
    half = d_model // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32) * (math.log(10000.0) / max(half - 1, 1)))
    ang = positions[:, None].astype(jnp.float32) * freqs[None]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# --------------------------------------------------------------- embedding
def init_embed(rng, vocab: int, d_model: int, tie: bool, dtype=jnp.bfloat16) -> dict:
    k1, k2 = jax.random.split(rng)
    p = {"embed": (jax.random.normal(k1, (vocab, d_model)) * 0.02).astype(dtype)}
    if not tie:
        p["lm_head"] = (jax.random.normal(k2, (d_model, vocab)) * 0.02).astype(dtype)
    return p


def embed(params: dict, tokens: Array) -> Array:
    return shard(params["embed"][tokens], "act_btd")


def unembed(params: dict, x: Array) -> Array:
    w = params.get("lm_head")
    if w is None:
        w = params["embed"].T
    x = grad_cast_bf16(x)  # keep the backward residual stream in bf16
    return shard(jnp.einsum("bsd,dv->bsv", x, w), "logits")
