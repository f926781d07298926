"""The DeepSeek-V3 family: latent attention (MLA), leading dense layers, and
expert layers of routed experts (sigmoid `noaux_tc` routing) beside shared
experts.  Moonlight-16B-A3B is one.

Weights.  As in the dense family, every leaf has its own key,
`fold_in(fold_in(root, leaf id), layer)`, and each routed expert one more
fold, by its global id, so the held experts 0 .. held - 1 are the same
values whatever share is held.  The served weights are made on the device
in one jitted call (`program_params`), in bf16; the router and its bias
are made in bf16 and upcast, since the program routes in f32.  The
reference makes the same values one layer at a time (`make_layer`).
`check_layout` refuses a program whose own init lays them out otherwise.

Work.  `prefill_work` and `decode_work` count, from the shapes alone:

- attention in the absorbed form the program runs: per token the q
  projection, the latent projection, q_nope through W_UK, the output
  through W_UV and the out projection; per (query, key) pair, scores over
  the lora + rope latent row and values over its lora part, every head;
- the held experts' part of each expert layer for the pairs expected on
  them, top_k x held / experts a token, and the shared experts for every
  token;
- bytes: every weight outside the routed experts once a pass, and the
  routed experts that the pass is expected to hit: `a` tokens, each
  choosing top_k of the n_experts, leave a given expert unchosen with
  probability (1 - top_k / n_experts)^a, so they hit
  held x (1 - (1 - top_k / n_experts)^a) of the held experts; the latent
  rows at or below each slot's position read, one row per token written.

`dims` is the `dims` block of a configuration file: n_layers,
first_k_dense, d_model, n_heads, kv_lora_rank, qk_nope_head_dim,
qk_rope_head_dim, v_head_dim, d_ff (the dense layers), n_experts,
experts_held, top_k, n_group, topk_group, moe_d_ff, shared_d_ff,
routed_scaling_factor, vocab, tie_embeddings, rope_theta, norm_eps,
kv_norm_eps.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.family import BYTES, DTYPE, compare_widths, root_key

# Stable ids: a leaf's values depend on its id, never on dict order.
LEAF_IDS = {
    "embed": 1, "lm_head": 2, "final_norm": 3,
    "ln1": 10, "wq": 11, "wkv_a": 12, "kv_norm": 13, "wk_b": 14, "wv_b": 15, "wo": 16,
    "ln2": 20, "w_gate": 21, "w_in": 22, "w_out": 23,
    "router": 30, "router_bias": 31,
    "e_gate": 32, "e_in": 33, "e_out": 34,
    "s_gate": 35, "s_in": 36, "s_out": 37,
}
ROUTED = ("e_gate", "e_in", "e_out")
BIAS_STD = 0.05                          # e_score_correction_bias, drawn


def widths(arch_cfg) -> dict:
    """The program's values of the widths a configuration of this family
    states."""
    return {
        "n_layers": arch_cfg.n_layers, "first_k_dense": arch_cfg.first_k_dense,
        "d_model": arch_cfg.d_model, "n_heads": arch_cfg.n_heads,
        "kv_lora_rank": arch_cfg.kv_lora_rank,
        "qk_nope_head_dim": arch_cfg.qk_nope_head_dim,
        "qk_rope_head_dim": arch_cfg.qk_rope_head_dim,
        "v_head_dim": arch_cfg.v_head_dim, "d_ff": arch_cfg.d_ff,
        "n_experts": arch_cfg.moe_experts,
        "top_k": arch_cfg.moe_top_k, "moe_d_ff": arch_cfg.moe_d_ff,
        "shared_d_ff": arch_cfg.moe_shared_ff,
        "routed_scaling_factor": arch_cfg.moe_route_scale,
        "vocab": arch_cfg.vocab_size, "tie_embeddings": arch_cfg.tie_embeddings,
        "rope_theta": arch_cfg.rope_theta, "norm_eps": arch_cfg.norm_eps,
        # the program routes without the group step: one group, kept whole
        "n_group": 1, "topk_group": 1,
    }


def check_widths(config: dict, arch_cfg) -> None:
    """Refuse a configuration whose stated widths the program's own
    `repro.configs` entry does not share, whose entry is not a latent-
    attention expert model with sigmoid routing, or whose held share is not
    a share of the routed experts.  The share is the deployment's: the
    entry's own init may hold every expert (`check_layout`)."""
    if arch_cfg.family != "moe" or not arch_cfg.kv_lora_rank or arch_cfg.moe_router != "sigmoid":
        raise ValueError(f"repro.configs {config['arch']!r} is not of the "
                         f"deepseek_v3 family (MLA, sigmoid-routed experts)")
    compare_widths(config, widths(arch_cfg))
    held = config["dims"]["experts_held"]
    if not 0 < held <= arch_cfg.moe_experts:
        raise ValueError(f"configuration {config['name']!r}: experts_held {held} is "
                         f"not a share of {arch_cfg.moe_experts} routed experts")


def global_specs(dims: dict) -> dict:
    """name -> (shape, std, mean) of the leaves outside the layers."""
    d, v = dims["d_model"], dims["vocab"]
    out = {"embed": ((v, d), 0.02, 0.0), "final_norm": ((d,), 0.05, 1.0)}
    if not dims["tie_embeddings"]:
        out["lm_head"] = ((d, v), 0.02, 0.0)
    return out


def layer_specs(dims: dict, dense: bool) -> dict:
    """name -> (shape, std, mean) of one layer's leaves; routed experts give
    the shape of one expert."""
    d, h, lora = dims["d_model"], dims["n_heads"], dims["kv_lora_rank"]
    nope, rope, vd = dims["qk_nope_head_dim"], dims["qk_rope_head_dim"], dims["v_head_dim"]
    s_d = 1.0 / math.sqrt(d)
    out = {
        "ln1": ((d,), 0.05, 1.0),
        "wq": ((d, h, nope + rope), s_d, 0.0),
        "wkv_a": ((d, lora + rope), s_d, 0.0),
        "kv_norm": ((lora,), 0.05, 1.0),
        "wk_b": ((h, nope, lora), 1.0 / math.sqrt(lora), 0.0),
        "wv_b": ((h, lora, vd), 1.0 / math.sqrt(lora), 0.0),
        "wo": ((h, vd, d), 1.0 / math.sqrt(h * vd), 0.0),
        "ln2": ((d,), 0.05, 1.0),
    }
    if dense:
        f = dims["d_ff"]
        out.update({"w_gate": ((d, f), s_d, 0.0), "w_in": ((d, f), s_d, 0.0),
                    "w_out": ((f, d), 1.0 / math.sqrt(f), 0.0)})
        return out
    e, fe, fs = dims["n_experts"], dims["moe_d_ff"], dims["shared_d_ff"]
    out.update({
        "router": ((d, e), s_d, 0.0),
        "router_bias": ((e,), BIAS_STD, 0.0),
        "e_gate": ((d, fe), s_d, 0.0), "e_in": ((d, fe), s_d, 0.0),
        "e_out": ((fe, d), 1.0 / math.sqrt(fe), 0.0),
        "s_gate": ((d, fs), s_d, 0.0), "s_in": ((d, fs), s_d, 0.0),
        "s_out": ((fs, d), 1.0 / math.sqrt(fs), 0.0),
    })
    return out


def _leaf(key, spec):
    shape, std, mean = spec
    return (jax.random.normal(key, shape, jnp.float32) * std + mean).astype(DTYPE)


def _key(root, name, layer):
    return jax.random.fold_in(jax.random.fold_in(root, LEAF_IDS[name]), layer)


def make_globals(root, dims: dict) -> dict:
    return {n: _leaf(_key(root, n, 0), s) for n, s in global_specs(dims).items()}


def make_layer(root, dims: dict, layer, dense: bool | None = None) -> dict:
    """One layer's leaves in bf16, the held routed experts stacked
    [experts_held, ...].  `layer` may be traced where `dense` is given."""
    if dense is None:
        dense = layer < dims["first_k_dense"]
    out = {}
    for n, s in layer_specs(dims, dense).items():
        key = _key(root, n, layer)
        if n in ROUTED:
            out[n] = jax.vmap(lambda e, key=key, s=s: _leaf(jax.random.fold_in(key, e), s))(
                jnp.arange(dims["experts_held"]))
        else:
            out[n] = _leaf(key, s)
    return out


def _program_layer(w: dict) -> dict:
    """One layer's leaves, as `repro.models.transformer.init_lm` lays out a
    block of the `moe` family with latent attention."""
    out = {
        "ln1": {"scale": w["ln1"]},
        "attn": {"wq": w["wq"], "wkv_a": w["wkv_a"], "kv_norm": {"scale": w["kv_norm"]},
                 "wk_b": w["wk_b"], "wv_b": w["wv_b"], "wo": w["wo"]},
        "ln2": {"scale": w["ln2"]},
    }
    if "w_in" in w:
        out["mlp"] = {k: w[k] for k in ("w_gate", "w_in", "w_out")}
    else:
        out["moe"] = {
            "router": w["router"].astype(jnp.float32),
            "router_bias": w["router_bias"].astype(jnp.float32),
            "experts": {"w_gate": w["e_gate"], "w_in": w["e_in"], "w_out": w["e_out"]},
            "shared": {"w_gate": w["s_gate"], "w_in": w["s_in"], "w_out": w["s_out"]},
        }
    return out


def program_params(root, dims: dict) -> dict:
    """The whole model in the program's pytree layout."""
    g = make_globals(root, dims)
    k, n = dims["first_k_dense"], dims["n_layers"]

    def stack(first, count, dense):
        return jax.vmap(lambda i: _program_layer(make_layer(root, dims, i, dense)))(
            first + jnp.arange(count))

    tok = {"embed": g["embed"]}
    if "lm_head" in g:
        tok["lm_head"] = g["lm_head"]
    out = {"tok": tok, "final_norm": {"scale": g["final_norm"]},
           "blocks": stack(k, n - k, False)}
    if k:
        out["dense_blocks"] = stack(0, k, True)
    return out


def check_layout(program_shapes, dims: dict) -> None:
    """Raise unless the program's own init makes the same tree of shapes and
    dtypes as `program_params` would for the experts that init holds, and
    that is at least the configuration's held share: the share served is
    then the first `experts_held` of the program's routed experts."""
    init_held = program_shapes["blocks"]["moe"]["experts"]["w_in"].shape[1]
    if dims["experts_held"] > init_held:
        raise ValueError(f"the program's init holds {init_held} routed experts, "
                         f"fewer than experts_held {dims['experts_held']}")
    ours = jax.eval_shape(lambda: program_params(root_key(0), dict(dims, experts_held=init_held)))

    def flat(tree):
        return {jax.tree_util.keystr(p): (tuple(x.shape), jnp.dtype(x.dtype))
                for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}

    a, b = flat(ours), flat(program_shapes)
    if a != b:
        diff = sorted(set(a.items()) ^ set(b.items()))
        raise ValueError(f"the program's parameter layout differs from the "
                         f"benchmark's: {diff[:6]}")


def param_counts(dims: dict) -> dict:
    """Parameters by part.  `attn` holds one layer's attention matmuls,
    `norms` its three norm scales (two of d_model, the latent's), `expert`
    one routed expert, `moe_rest` an expert layer's router, bias and
    shared experts."""
    d, h, lora = dims["d_model"], dims["n_heads"], dims["kv_lora_rank"]
    nope, rope, vd = dims["qk_nope_head_dim"], dims["qk_rope_head_dim"], dims["v_head_dim"]
    e = dims["n_experts"]
    return {
        "embed": dims["vocab"] * d,
        "lm_head": 0 if dims["tie_embeddings"] else d * dims["vocab"],
        "attn": (d * h * (nope + rope) + d * (lora + rope)
                 + h * nope * lora + h * lora * vd + h * vd * d),
        "norms": 2 * d + lora,
        "dense_mlp": 3 * d * dims["d_ff"],
        "expert": 3 * d * dims["moe_d_ff"],
        "moe_rest": d * e + e + 3 * d * dims["shared_d_ff"],
        "final_norm": d,
    }


def n_params(dims: dict) -> int:
    """What this program holds: embedding, output head and every layer with
    its held experts; the final norm left out, as `ArchConfig.n_params()`
    leaves it out."""
    c = param_counts(dims)
    k, n = dims["first_k_dense"], dims["n_layers"]
    return (c["embed"] + c["lm_head"] + n * (c["attn"] + c["norms"])
            + k * c["dense_mlp"]
            + (n - k) * (dims["experts_held"] * c["expert"] + c["moe_rest"]))


def held_experts_hit(dims: dict, tokens: int) -> float:
    """Expected distinct held experts that `tokens` tokens choose, in one
    expert layer: held x (1 - (1 - top_k / n_experts)^tokens)."""
    miss = (1.0 - dims["top_k"] / dims["n_experts"]) ** tokens
    return dims["experts_held"] * (1.0 - miss)


def weight_bytes_per_pass(dims: dict, tokens: int) -> float:
    """Weight bytes one pass of `tokens` tokens has to read: everything
    outside the routed experts, and the held experts they are expected to
    hit in each expert layer."""
    c = param_counts(dims)
    k, n = dims["first_k_dense"], dims["n_layers"]
    head = c["lm_head"] or c["embed"]
    fixed = (n * (c["attn"] + c["norms"]) + k * c["dense_mlp"]
             + (n - k) * c["moe_rest"] + c["final_norm"] + head)
    routed = (n - k) * held_experts_hit(dims, tokens) * c["expert"]
    return BYTES * (fixed + routed)


def _lat_row_bytes(dims: dict) -> int:
    """Bytes of one position's latent rows over all layers."""
    return dims["n_layers"] * (dims["kv_lora_rank"] + dims["qk_rope_head_dim"]) * BYTES


def _token_matmul_flops(dims: dict) -> float:
    """One token through every layer's matmuls: absorbed-MLA projections,
    the dense MLPs, and in each expert layer the router, the shared
    experts and the expected top_k x held / n_experts held-expert pairs."""
    c = param_counts(dims)
    k, n = dims["first_k_dense"], dims["n_layers"]
    pairs = dims["top_k"] * dims["experts_held"] / dims["n_experts"]
    moe = c["moe_rest"] - dims["n_experts"] + pairs * c["expert"]   # the bias is no matmul
    return 2 * (n * c["attn"] + k * c["dense_mlp"] + (n - k) * moe)


def _attn_flops(dims: dict, keys: int) -> int:
    """Absorbed scores over lora + rope and values over lora, every head and
    layer, for `keys` (query, key) pairs."""
    lora, rope = dims["kv_lora_rank"], dims["qk_rope_head_dim"]
    return 2 * dims["n_layers"] * dims["n_heads"] * (2 * lora + rope) * keys


def _logit_row_flops(dims: dict) -> int:
    return 2 * dims["d_model"] * dims["vocab"]


def prefill_work(dims: dict, plen: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one prompt of `plen` tokens into an empty lane:
    every token through every matmul, causal attention, one row of logits;
    weights read once, the prompt's embedding rows read, its latent rows
    written."""
    flops = (plen * _token_matmul_flops(dims)
             + _attn_flops(dims, plen * (plen + 1) // 2)
             + _logit_row_flops(dims))
    nbytes = (weight_bytes_per_pass(dims, plen)
              + plen * dims["d_model"] * BYTES
              + plen * _lat_row_bytes(dims)
              + dims["vocab"] * BYTES)
    return flops, nbytes


def decode_work(dims: dict, positions: list[int]) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode tick whose active slots hold their new
    token at `positions`: each token attends to the latent rows at or below
    its position; weights are read once per tick (the routed experts that
    the active tokens are expected to hit), each slot's rows at or below its
    position are read and one row is written."""
    a = len(positions)
    keys = sum(p + 1 for p in positions)
    flops = (a * (_token_matmul_flops(dims) + _logit_row_flops(dims))
             + _attn_flops(dims, keys))
    nbytes = (weight_bytes_per_pass(dims, a)
              + a * dims["d_model"] * BYTES
              + (keys + a) * _lat_row_bytes(dims)
              + a * dims["vocab"] * BYTES)
    return flops, nbytes
