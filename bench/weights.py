"""Seeded random weights of the dense family, made by the benchmark.

Every leaf of every layer has its own key, `fold_in(fold_in(root, leaf id),
layer)`, so one layer can be made alone, bit for bit as it sits in the
stacked whole.  The served weights are made on the device in one jitted call
(`program_params`), in bf16, the type they are served in.  The reference
makes the same bf16 values one layer at a time (`layer_weights`) and
upcasts them; it never reads what the program holds.

`program_params` lays the leaves out as `repro.models.transformer.init_lm`
lays out a dense model; `check_layout` refuses a program whose layout
differs, rather than serving misplaced weights.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

DTYPE = jnp.bfloat16

# Stable ids: a leaf's values depend on its id, never on dict order.
LEAF_IDS = {
    "embed": 1, "lm_head": 2, "final_norm": 3,
    "ln1": 10, "wq": 11, "wk": 12, "wv": 13, "wo": 14,
    "bq": 15, "bk": 16, "bv": 17,
    "ln2": 20, "w_gate": 21, "w_in": 22, "w_out": 23,
}


def root_key(seed: int) -> jax.Array:
    """A key from any whole number, also one past 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    words = np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)
    key = jax.random.PRNGKey(0)
    return jax.random.fold_in(jax.random.fold_in(key, words[0]), words[1])


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
