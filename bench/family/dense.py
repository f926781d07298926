"""The dense decoder family: GQA attention with rotary, SwiGLU MLP, RMSNorm.

Weights.  Every leaf of every layer has its own key, `fold_in(fold_in(root,
leaf id), layer)`, so one layer can be made alone, bit for bit as it sits in
the stacked whole.  The served weights are made on the device in one jitted
call (`program_params`), in bf16, the type they are served in.  The
reference makes the same bf16 values one layer at a time (`make_layer`) and
upcasts them; it never reads what the program holds.  `program_params` lays
the leaves out as `repro.models.transformer.init_lm` lays out a dense model;
`check_layout` refuses a program whose layout differs, rather than serving
misplaced weights.

Work.  `prefill_work` and `decode_work` count the operations and bytes the
algorithm needs, from the shapes alone; nothing here reads the compiled
program, so a roofline share built on them counts wasted work (cache
copies, padded slots, finished slots still decoded) as lost time.  Weights
and the K/V cache are bf16.

`dims` is the `dims` block of a configuration file: n_layers, d_model,
n_heads, n_kv_heads, head_dim, d_ff, vocab, tie_embeddings, qkv_bias,
rotary_dims, rope_theta, norm_eps.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.family import BYTES, DTYPE, compare_widths, root_key

# Stable ids: a leaf's values depend on its id, never on dict order.
LEAF_IDS = {
    "embed": 1, "lm_head": 2, "final_norm": 3,
    "ln1": 10, "wq": 11, "wk": 12, "wv": 13, "wo": 14,
    "bq": 15, "bk": 16, "bv": 17,
    "ln2": 20, "w_gate": 21, "w_in": 22, "w_out": 23,
}


def widths(arch_cfg) -> dict:
    """The program's values of the widths a dense configuration states."""
    return {
        "n_layers": arch_cfg.n_layers, "d_model": arch_cfg.d_model,
        "n_heads": arch_cfg.n_heads, "n_kv_heads": arch_cfg.n_kv_heads,
        "head_dim": arch_cfg.hd, "d_ff": arch_cfg.d_ff,
        "vocab": arch_cfg.vocab_size,
        "tie_embeddings": arch_cfg.tie_embeddings,
        "qkv_bias": arch_cfg.qkv_bias,
        "rotary_dims": arch_cfg.hd // 2 if arch_cfg.rope_style == "2d" else arch_cfg.hd,
        "norm_eps": arch_cfg.norm_eps,
    }


def check_widths(config: dict, arch_cfg) -> None:
    """Refuse a configuration whose stated widths the program's own
    `repro.configs` entry does not share, or whose entry is not dense."""
    if arch_cfg.family != "dense":
        raise ValueError(f"repro.configs {config['arch']!r} is of family "
                         f"{arch_cfg.family!r}, not dense")
    compare_widths(config, widths(arch_cfg))


def global_specs(dims: dict) -> dict:
    """name -> (shape, std, mean) of the leaves outside the layers."""
    d, v = dims["d_model"], dims["vocab"]
    out = {"embed": ((v, d), 0.02, 0.0), "final_norm": ((d,), 0.05, 1.0)}
    if not dims["tie_embeddings"]:
        out["lm_head"] = ((d, v), 0.02, 0.0)
    return out


def layer_specs(dims: dict) -> dict:
    """name -> (shape, std, mean) of one layer's leaves."""
    d, h, kv, hd, f = (dims["d_model"], dims["n_heads"], dims["n_kv_heads"],
                       dims["head_dim"], dims["d_ff"])
    s_d = 1.0 / math.sqrt(d)
    out = {
        "ln1": ((d,), 0.05, 1.0),
        "wq": ((d, h, hd), s_d, 0.0),
        "wk": ((d, kv, hd), s_d, 0.0),
        "wv": ((d, kv, hd), s_d, 0.0),
        "wo": ((h, hd, d), 1.0 / math.sqrt(h * hd), 0.0),
        "ln2": ((d,), 0.05, 1.0),
        "w_gate": ((d, f), s_d, 0.0),
        "w_in": ((d, f), s_d, 0.0),
        "w_out": ((f, d), 1.0 / math.sqrt(f), 0.0),
    }
    if dims["qkv_bias"]:
        out.update({"bq": ((h, hd), 0.1, 0.0), "bk": ((kv, hd), 0.1, 0.0),
                    "bv": ((kv, hd), 0.1, 0.0)})
    return out


def _leaf(root, name, layer, spec):
    shape, std, mean = spec
    key = jax.random.fold_in(jax.random.fold_in(root, LEAF_IDS[name]), layer)
    x = jax.random.normal(key, shape, jnp.float32) * std + mean
    return x.astype(DTYPE)


def make_globals(root, dims: dict) -> dict:
    return {n: _leaf(root, n, 0, s) for n, s in global_specs(dims).items()}


def make_layer(root, dims: dict, layer) -> dict:
    """One layer's leaves in bf16; `layer` may be traced."""
    return {n: _leaf(root, n, layer, s) for n, s in layer_specs(dims).items()}


def program_params(root, dims: dict) -> dict:
    """The whole model in the program's pytree layout (dense family)."""
    g = make_globals(root, dims)
    layers = jax.vmap(lambda i: make_layer(root, dims, i))(
        jnp.arange(dims["n_layers"]))
    attn = {k: layers[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
            if k in layers}
    tok = {"embed": g["embed"]}
    if "lm_head" in g:
        tok["lm_head"] = g["lm_head"]
    return {
        "tok": tok,
        "final_norm": {"scale": g["final_norm"]},
        "blocks": {
            "ln1": {"scale": layers["ln1"]},
            "attn": attn,
            "ln2": {"scale": layers["ln2"]},
            "mlp": {k: layers[k] for k in ("w_gate", "w_in", "w_out")},
        },
    }


def check_layout(program_shapes, dims: dict) -> None:
    """Raise unless the program's own init makes the same tree of shapes and
    dtypes as `program_params`."""
    ours = jax.eval_shape(lambda: program_params(root_key(0), dims))

    def flat(tree):
        return {jax.tree_util.keystr(p): (tuple(x.shape), jnp.dtype(x.dtype))
                for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}

    a, b = flat(ours), flat(program_shapes)
    if a != b:
        diff = sorted(set(a.items()) ^ set(b.items()))
        raise ValueError(f"the program's parameter layout differs from the "
                         f"benchmark's: {diff[:6]}")


def param_counts(dims: dict) -> dict:
    """Parameters by part.  `layer` holds one layer's matmul weights,
    biases and its two norm scales."""
    d, h, kv, hd, f, v = (dims["d_model"], dims["n_heads"], dims["n_kv_heads"],
                          dims["head_dim"], dims["d_ff"], dims["vocab"])
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    bias = (h + 2 * kv) * hd if dims["qkv_bias"] else 0
    mlp = 3 * d * f
    return {
        "embed": v * d,
        "lm_head": 0 if dims["tie_embeddings"] else d * v,
        "layer_matmul": attn + mlp,
        "layer": attn + bias + mlp + 2 * d,
        "final_norm": d,
    }


def n_params(dims: dict) -> int:
    """Embedding, output head and layers; the final norm's `d_model` scales
    are left out, as `ArchConfig.n_params()` leaves them out."""
    c = param_counts(dims)
    return c["embed"] + c["lm_head"] + dims["n_layers"] * c["layer"]


def weight_bytes_per_pass(dims: dict) -> int:
    """Weight bytes one forward pass has to read: every layer, the final norm
    and the output head (the tied embedding read whole as the head)."""
    c = param_counts(dims)
    head = c["lm_head"] or c["embed"]
    return BYTES * (dims["n_layers"] * c["layer"] + c["final_norm"] + head)


def _attn_flops(dims: dict, keys: int) -> int:
    """Score and value products of one query over `keys` keys, all layers."""
    return 4 * dims["n_layers"] * dims["n_heads"] * dims["head_dim"] * keys


def _kv_row_bytes(dims: dict) -> int:
    """Bytes of one position's K and V rows over all layers."""
    return 2 * dims["n_layers"] * dims["n_kv_heads"] * dims["head_dim"] * BYTES


def _token_matmul_flops(dims: dict) -> int:
    return 2 * dims["n_layers"] * param_counts(dims)["layer_matmul"]


def _logit_row_flops(dims: dict) -> int:
    return 2 * dims["d_model"] * dims["vocab"]


def prefill_work(dims: dict, plen: int) -> tuple[int, int]:
    """(FLOPs, bytes) of one prompt of `plen` tokens into an empty lane: every
    token through every matmul, causal attention, one row of logits; weights
    read once, the prompt's embedding rows read, its K/V rows written."""
    flops = (plen * _token_matmul_flops(dims)
             + _attn_flops(dims, plen * (plen + 1) // 2)
             + _logit_row_flops(dims))
    nbytes = (weight_bytes_per_pass(dims)
              + plen * dims["d_model"] * BYTES
              + plen * _kv_row_bytes(dims)
              + dims["vocab"] * BYTES)
    return flops, nbytes


def decode_work(dims: dict, positions: list[int]) -> tuple[int, int]:
    """(FLOPs, bytes) of one decode tick whose active slots hold their new
    token at `positions`: each token attends to the rows at or below its
    position; weights are read once per tick, each slot's K/V rows at or
    below its position are read and one row is written."""
    a = len(positions)
    keys = sum(p + 1 for p in positions)
    flops = (a * (_token_matmul_flops(dims) + _logit_row_flops(dims))
             + _attn_flops(dims, keys))
    nbytes = (weight_bytes_per_pass(dims)
              + a * dims["d_model"] * BYTES
              + (keys + a) * _kv_row_bytes(dims)
              + a * dims["vocab"] * BYTES)
    return flops, nbytes
