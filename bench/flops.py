"""The work a dense decoder needs, computed from its shapes alone.

Nothing here reads the compiled program: these are the operations and bytes
the algorithm needs, so a roofline share built on them counts wasted work
(cache copies, padded slots, finished slots still decoded) as lost time.

`dims` is the `dims` block of a configuration file: n_layers, d_model,
n_heads, n_kv_heads, head_dim, d_ff, vocab, tie_embeddings, qkv_bias.
Weights and the K/V cache are bf16 (2 bytes).
"""

from __future__ import annotations

BYTES = 2  # bf16 weights, cache rows and logits


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


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline: the larger of compute time and memory time at peak."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
