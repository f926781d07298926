"""Plain float32 reference of the DeepSeek-V3 family (Moonlight-16B-A3B).

The decoder as DeepSeek-V3's published modelling code describes it: token
embedding; per layer an RMSNorm, multi-head latent attention, a residual
add, an RMSNorm, a channel mixer and a residual add; a final RMSNorm and
the output head.  The first `first_k_dense` layers mix channels with a
SwiGLU MLP; the others with an expert layer:

- `MoEGate` (`topk_method` noaux_tc): f32 logits, sigmoid scores, the group
  step (`n_group` groups scored by their two best biased scores, the best
  `topk_group` kept; with one group it keeps every expert), the top_k
  experts by score + `e_score_correction_bias`, and their weights the
  chosen scores without the bias, normalised to sum 1 (`norm_topk_prob`)
  and multiplied by `routed_scaling_factor`;
- the routed experts, each a SwiGLU, computed one expert at a time over
  every token, weighted by that expert's gate (0 where it was not chosen);
- the shared experts, one SwiGLU of width `shared_d_ff`, for every token.

Attention is written out expanded: q from one projection (no q latent); the
latent c = RMSNorm(x @ W_DKV) and one rotary key k_pe shared by all heads;
per head k = [c @ W_UK, k_pe], v = c @ W_UV; causal softmax at scale
1/sqrt(qk_nope + qk_rope), explicit; no cache, no absorption, every matmul
in float32 at `highest` precision.

Departures, each with no change in cost:

- the router's input is rounded to bf16, the dtype the configuration
  states, before its f32 logits are taken, as the published `MoEGate` takes
  them from the model's bf16 hidden state; everything else stays f32.  An
  f32 router input flips the 6th and 7th expert against a bf16 one wherever
  their biased scores nearly tie;
- the rotary pairs are the two halves of the 64 rotary dims, where the
  published code interleaves them and then permutes: with random weights a
  fixed permutation of the rotary columns of `wq` and `wkv_a`;
- only the routed experts that the configuration holds (`experts_held`,
  global ids 0 .. held - 1) are computed: the part that experts held on
  other chips would add is left out, as the program leaves it out.  The
  router still scores, and chooses among, all `n_experts`.

It imports nothing of the program under test and never reads its weights:
it makes the same bf16 values from the seed with the configuration's family
module, one layer at a time, and upcasts them.  `quant="fp8"` computes every
linear layer, the router too, with float8 (e4m3) operands, scaled per row
of activations and per output channel of weights: the precision below the
bf16 that the configuration states, which is the control that the
comparison must fail.  Where the program holds a tensor in bf16, the
control holds it in float8 too: besides every linear layer's operands, the
residual stream after each add, the embedded tokens, and both operands of
attention's scores and of its weighted sum of values.  Even so, routing
flips between bf16 and float32 cascade, and put a few of the program's
positions nearly as far off as the control's worst (PERF.md §6, question
22).
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.dense import _fq, _rmsnorm, _rope


def _swiglu(x, w_gate, w_in, w_out, quant):
    a = jax.nn.silu(x @ _fq(w_gate, (0,), quant)) * (x @ _fq(w_in, (0,), quant))
    return _fq(a, (-1,), quant) @ _fq(w_out, (0,), quant)


def _attention(dims, w, x, quant):
    """Expanded MLA over one normed sequence x [S, d]."""
    S = x.shape[0]
    pos = jnp.arange(S)
    H, lora = dims["n_heads"], dims["kv_lora_rank"]
    nope, rope = dims["qk_nope_head_dim"], dims["qk_rope_head_dim"]
    theta = dims["rope_theta"]

    q = jnp.einsum("sd,dhk->shk", x, _fq(w["wq"], (0,), quant))
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, rope, theta)], axis=-1)
    kv = x @ _fq(w["wkv_a"], (0,), quant)
    c = _fq(_rmsnorm(kv[:, :lora], w["kv_norm"], dims["kv_norm_eps"]), (-1,), quant)
    k_pe = _rope(kv[:, None, lora:], pos, rope, theta)
    k_nope = jnp.einsum("sl,hnl->shn", c, _fq(w["wk_b"], (2,), quant))
    v = jnp.einsum("sl,hlv->shv", c, _fq(w["wv_b"], (1,), quant))
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (S, H, rope))], axis=-1)

    sc = jnp.einsum("qhk,shk->hqs", _fq(q, (-1,), quant), _fq(k, (-1,), quant))
    sc = sc / math.sqrt(nope + rope)
    sc = jnp.where(pos[:, None] >= pos[None, :], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("hqs,shv->qhv", _fq(p, (-1,), quant), _fq(v, (0,), quant))
    o = _fq(o.reshape(S, -1), (-1,), quant).reshape(o.shape)
    return jnp.einsum("qhv,hvd->qd", o, _fq(w["wo"], (0, 1), quant))


def _gate(dims, w, x, quant):
    """The published MoEGate: (expert ids [T, top_k], weights [T, top_k])."""
    T, E = x.shape[0], dims["n_experts"]
    n_group, topk_group = dims["n_group"], dims["topk_group"]
    # the router's input in the configuration's bf16, its logits in f32
    xr = x.astype(jnp.bfloat16).astype(jnp.float32)
    scores = jax.nn.sigmoid(_fq(xr, (-1,), quant) @ _fq(w["router"], (0,), quant))
    choice = scores + w["router_bias"]
    groups = choice.reshape(T, n_group, E // n_group)
    group_scores = jax.lax.top_k(groups, 2)[0].sum(-1)
    group_idx = jax.lax.top_k(group_scores, topk_group)[1]
    group_mask = jnp.zeros((T, n_group)).at[jnp.arange(T)[:, None], group_idx].set(1.0)
    score_mask = jnp.repeat(group_mask, E // n_group, axis=-1)
    _, idx = jax.lax.top_k(jnp.where(score_mask > 0, choice, -jnp.inf), dims["top_k"])
    weight = jnp.take_along_axis(scores, idx, axis=-1)
    weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
    return idx, weight * dims["routed_scaling_factor"]


def _experts(dims, w, x, quant):
    """Held routed experts, one at a time, plus the shared experts."""
    idx, weight = _gate(dims, w, x, quant)
    xq = _fq(x, (-1,), quant)
    y = _swiglu(xq, w["s_gate"], w["s_in"], w["s_out"], quant)
    for e in range(dims["experts_held"]):
        gate = jnp.where(idx == e, weight, 0.0).sum(-1)
        y = y + gate[:, None] * _swiglu(xq, w["e_gate"][e], w["e_in"][e], w["e_out"][e], quant)
    return y


def _block(dims, w, h, quant, dense):
    """One layer over one sequence h [S, d]."""
    eps = dims["norm_eps"]
    h = h + _attention(dims, w, _fq(_rmsnorm(h, w["ln1"], eps), (-1,), quant), quant)
    h = _fq(h, (-1,), quant)
    x = _rmsnorm(h, w["ln2"], eps)
    if dense:
        y = _swiglu(_fq(x, (-1,), quant), w["w_gate"], w["w_in"], w["w_out"], quant)
    else:
        y = _experts(dims, w, x, quant)
    return _fq(h + y, (-1,), quant)


@functools.lru_cache(maxsize=None)
def _programs(family, dims_json: str, quant):
    dims = json.loads(dims_json)

    def f32(tree):
        return jax.tree.map(lambda a: a.astype(jnp.float32), tree)

    @jax.jit
    def embed(root, tokens):
        return _fq(f32(family.make_globals(root, dims))["embed"][tokens], (-1,), quant)

    def layer_program(dense):
        @jax.jit
        def layer(root, i, h):
            w = f32(family.make_layer(root, dims, i, dense))
            return jax.lax.map(lambda hs: _block(dims, w, hs, quant, dense), h)
        return layer

    @jax.jit
    def head(root, h, idx):
        g = f32(family.make_globals(root, dims))
        x = jnp.take_along_axis(h, idx[..., None], axis=1)
        x = _fq(_rmsnorm(x, g["final_norm"], dims["norm_eps"]), (-1,), quant)
        wh = g["lm_head"] if "lm_head" in g else g["embed"].T
        return x @ _fq(wh, (0,), quant)

    return embed, layer_program(True), layer_program(False), head


def logits_at(family, dims: dict, root, tokens: np.ndarray, idx: np.ndarray,
              quant=None) -> jax.Array:
    """Float32 logits [B, n, vocab] at positions `idx` [B, n] of the
    sequences `tokens` [B, S], with the weights that `family`'s makers give
    `root`.  Padding after a sequence's end changes none of its positions,
    since attention is causal and each token is routed alone."""
    embed, dense_layer, moe_layer, head = _programs(
        family, json.dumps(dims, sort_keys=True), quant)
    with jax.default_matmul_precision("highest"):
        h = embed(root, jnp.asarray(tokens, jnp.int32))
        for i in range(dims["n_layers"]):
            layer = dense_layer if i < dims["first_k_dense"] else moe_layer
            h = layer(root, jnp.int32(i), h)
        return head(root, h, jnp.asarray(idx, jnp.int32))
