"""Plain float32 reference of the dense decoder family.

A decoder-only transformer as the published models describe it: token
embedding; per layer an RMSNorm, grouped-query attention with rotary
position embedding on the first `rotary_dims` of each head, a residual add,
an RMSNorm, a SwiGLU MLP and a residual add; a final RMSNorm and the output
head (the transposed embedding where tied).  Full causal attention with an
explicit softmax, no cache, no batching tricks, every matmul in float32 at
`highest` precision.

Departure, for ChatGLM3-6B: the rotary pairs are the two halves of the
rotated dims, as in Llama, where ChatGLM pairs neighbouring dims.  With
random weights this is a fixed permutation of the `wq`/`wk` columns and
changes no cost.

It imports nothing of the program under test and never reads its weights:
it makes the same bf16 values from the seed with the configuration's family
module (`make_globals`, `make_layer`), one layer at a time, and upcasts
them, so it never holds a float32 copy of the whole model.  `quant="fp8"`
computes every linear layer with float8 (e4m3) operands instead, scaled per
row of activations and per output channel of weights: the precision below
the bf16 that the configurations state, which is the control that the
comparison must fail.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0  # largest finite float8_e4m3fn


def _fq(x, axes, quant):
    """Round `x` to float8 with one scale per slice over `axes`."""
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown quant {quant!r}")
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, rot, theta):
    """x [S, H, hd]: rotate the first `rot` dims in two halves."""
    freqs = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], axis=-1)


def _block(dims, w, h, quant):
    """One layer over one sequence h [S, d]."""
    eps, hd = dims["norm_eps"], dims["head_dim"]
    g = dims["n_heads"] // dims["n_kv_heads"]
    S = h.shape[0]
    pos = jnp.arange(S)

    x = _fq(_rmsnorm(h, w["ln1"], eps), (-1,), quant)
    q = jnp.einsum("sd,dhk->shk", x, _fq(w["wq"], (0,), quant))
    k = jnp.einsum("sd,dhk->shk", x, _fq(w["wk"], (0,), quant))
    v = jnp.einsum("sd,dhk->shk", x, _fq(w["wv"], (0,), quant))
    if dims["qkv_bias"]:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = _rope(q, pos, dims["rotary_dims"], dims["rope_theta"])
    k = _rope(k, pos, dims["rotary_dims"], dims["rope_theta"])
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    sc = jnp.einsum("qhk,shk->hqs", q, k) / math.sqrt(hd)
    sc = jnp.where(pos[:, None] >= pos[None, :], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("hqs,shk->qhk", p, v)
    o = _fq(o.reshape(S, -1), (-1,), quant).reshape(o.shape)
    h = h + jnp.einsum("qhk,hkd->qd", o, _fq(w["wo"], (0, 1), quant))

    x = _fq(_rmsnorm(h, w["ln2"], eps), (-1,), quant)
    a = jax.nn.silu(x @ _fq(w["w_gate"], (0,), quant)) * (x @ _fq(w["w_in"], (0,), quant))
    return h + _fq(a, (-1,), quant) @ _fq(w["w_out"], (0,), quant)


@functools.lru_cache(maxsize=None)
def _programs(family, dims_json: str, quant):
    dims = json.loads(dims_json)

    def f32(tree):
        return jax.tree.map(lambda a: a.astype(jnp.float32), tree)

    @jax.jit
    def embed(root, tokens):
        return f32(family.make_globals(root, dims))["embed"][tokens]

    @jax.jit
    def layer(root, i, h):
        w = f32(family.make_layer(root, dims, i))
        return jax.lax.map(lambda hs: _block(dims, w, hs, quant), h)

    @jax.jit
    def head(root, h, idx):
        g = f32(family.make_globals(root, dims))
        x = jnp.take_along_axis(h, idx[..., None], axis=1)
        x = _fq(_rmsnorm(x, g["final_norm"], dims["norm_eps"]), (-1,), quant)
        wh = g["lm_head"] if "lm_head" in g else g["embed"].T
        return x @ _fq(wh, (0,), quant)

    return embed, layer, head


def logits_at(family, dims: dict, root, tokens: np.ndarray, idx: np.ndarray,
              quant=None) -> jax.Array:
    """Float32 logits [B, n, vocab] at positions `idx` [B, n] of the
    sequences `tokens` [B, S], with the weights that `family`'s makers give
    `root`.  Padding after a sequence's end changes none of its positions,
    since attention is causal."""
    embed, layer, head = _programs(family, json.dumps(dims, sort_keys=True), quant)
    with jax.default_matmul_precision("highest"):
        h = embed(root, jnp.asarray(tokens, jnp.int32))
        for i in range(dims["n_layers"]):
            h = layer(root, jnp.int32(i), h)
        return head(root, h, jnp.asarray(idx, jnp.int32))
