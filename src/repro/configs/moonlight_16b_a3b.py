"""Moonlight-16B-A3B [moe] — DeepSeek-V3 block: MLA, 1 dense + 26 expert layers,
64 routed experts top-6 (sigmoid, noaux_tc), 2 shared.
[hf:moonshotai/Moonlight-16B-A3B config.json]

Every width is the published one, and `CONFIG` holds all 64 routed experts
(15.96 B parameters).  A deployment that serves the model expert-parallel
gives each chip's program only that chip's share of the experts' weights
(`models.moe.moe_serve` holds what its weights hold); the router still
scores all 64.  `SMOKE` keeps the structure at small widths and holds such a
share, for the CPU tests of the serving path: 1 dense + 2 expert layers,
MLA, 8 experts top-2, 2 shared, 4 held.
"""
from dataclasses import replace

from .base import ArchConfig

CONFIG = ArchConfig(
    name="moonlight-16b-a3b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=11264, vocab_size=163840,
    rope_style="full", mlp_type="swiglu", norm_eps=1e-5,
    moe_experts=64, moe_top_k=6, moe_d_ff=1408, moe_every=1, moe_shared_ff=2 * 1408,
    moe_router="sigmoid", moe_route_scale=2.446, first_k_dense=1,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    rope_theta=50_000.0,
    source="hf:moonshotai/Moonlight-16B-A3B",
)

SMOKE = replace(
    CONFIG, name="moonlight-16b-a3b-smoke",
    n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=256,
    moe_experts=8, moe_top_k=2, moe_d_ff=32, moe_shared_ff=2 * 32, moe_held=4,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
)
