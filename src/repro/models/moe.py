"""Mixture-of-Experts FFN with sort-based, capacity-bounded dispatch.

Expert parallelism is the paper's **DSDE motif** (§4.2): tokens are items,
experts are targets, and no rank knows its receive volume in advance.  The
dispatch below is the SPMD formulation of `repro.core.dsde`: tokens are
bucketed into per-expert slot ranges (the slotted one-sided accumulate) and a
sharding constraint moves the expert dimension onto the `model` axis — GSPMD
lowers that reshard to exactly the all-to-all of one-sided puts that the
DSDE protocol issues.  `examples/moe_dsde.py` runs the explicit shard_map
version over `core.dsde` to show they agree.

**Grouped dispatch** (perf-critical, see EXPERIMENTS.md §Perf/qwen3): tokens
are first reshaped to [G, T/G, D] where G matches the data-parallel shard
count, and every scatter/gather carries the group dimension.  Each group's
slot buffer is then built entirely inside one data shard, so GSPMD lowers
the expert reshard to an all-to-all of the slot ranges (~84 MB/device for
qwen3 train_4k) instead of an all-reduce of the *entire* dispatch buffer
(~43 GB/layer — the ungrouped formulation measured 23 TB/device/step of
all-reduce traffic).

Capacity drops (`pos_in_expert >= capacity`) are the paper's bounded-buffer
semantics; dropped tokens fall through on the residual path (standard
GShard/Switch behavior).  That is the *training* dispatch (`moe_ffn`).

Serving uses `moe_serve`, which drops no token: a served token's output may
not depend on what else shares its batch.  So do weights that hold only a
share of the routed experts, with or without a cache: `moe_ffn` dispatches
over every expert.  It routes over every expert,
computes only the experts this program holds (`params["experts"]` holds
`n_held` of them, global ids `first .. first + n_held - 1`), for the
(token, expert) pairs routed to them: the pairs are sorted by expert and
run through `jax.lax.ragged_dot`, which reads each held expert's weights
once.  The TPU compiler lowers it to a grouped matmul that computes whole
row tiles per group (256 rows on a v5e at Moonlight's widths), so below a
tile of pairs an expert the work is fixed per held expert, not per pair:
at 12 pairs an expert a decode tick it computes 16 x 256 rows where a
dense product of every token against every held expert would compute
16 x 128 (PERF.md, Open questions).  What experts held elsewhere would add
is left out: on one chip the layer runs without its exchange (ROADMAP
Reach 5).

Routers (`route`): `softmax` (top-k of the softmax, renormalised) and
`sigmoid`, DeepSeek-V3's `noaux_tc`: f32 logits, sigmoid scores, top-k of
score + `router_bias` (`e_score_correction_bias`), weights the chosen
scores without the bias, normalised to sum 1 and scaled.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import _dp, current_policy, shard_spec

Array = jax.Array


class MoEMetrics(NamedTuple):
    aux_loss: Array        # load-balance loss (Switch-style)
    router_z_loss: Array
    drop_fraction: Array


def init_moe(rng, d_model: int, n_experts: int, d_ff: int, mlp_type: str = "swiglu",
             shared_ff: int = 0, dtype=jnp.bfloat16, router: str = "softmax",
             n_held: int = 0) -> dict:
    """Router over all `n_experts`; weights for the first `n_held` (0: all)."""
    ks = jax.random.split(rng, 5)
    s_in, s_out = 1.0 / math.sqrt(d_model), 1.0 / math.sqrt(d_ff)
    h = n_held or n_experts
    p = {
        "router": (jax.random.normal(ks[0], (d_model, n_experts)) * s_in).astype(jnp.float32),
        "experts": {
            "w_in": (jax.random.normal(ks[1], (h, d_model, d_ff)) * s_in).astype(dtype),
            "w_out": (jax.random.normal(ks[2], (h, d_ff, d_model)) * s_out).astype(dtype),
        },
    }
    if mlp_type == "swiglu":
        p["experts"]["w_gate"] = (
            jax.random.normal(ks[3], (h, d_model, d_ff)) * s_in
        ).astype(dtype)
    if router == "sigmoid":
        p["router_bias"] = jnp.zeros((n_experts,), jnp.float32)
    if shared_ff:
        from .layers import init_mlp

        p["shared"] = init_mlp(ks[4], d_model, shared_ff, mlp_type, dtype)
    return p


def route(params: dict, x: Array, top_k: int, router: str = "softmax",
          scale: float = 1.0) -> tuple[Array, Array, Array]:
    """Route tokens x [..., D] over all experts: (weights [..., k] f32,
    expert ids [..., k], per-expert scores [..., E] summing to 1, for the
    load-balance loss)."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32), params["router"])
    if router == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(probs, top_k)
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    elif router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(scores + params["router_bias"], top_k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
        probs = scores / scores.sum(-1, keepdims=True)
    else:
        raise ValueError(f"unknown router {router!r}")
    return w * scale, idx, probs


def _n_groups(B: int) -> int:
    """Dispatch groups = data shards when a policy is active (else 1)."""
    pol = current_policy()
    if pol is None:
        return 1
    g = 1
    for ax in ("pod", "data"):
        g *= pol.mesh.shape.get(ax, 1)
    while g > 1 and B % g:
        g //= 2
    return max(g, 1)


def moe_ffn(
    params: dict,
    x: Array,                 # [B, S, D]
    top_k: int,
    capacity_factor: float = 1.25,
    mlp_type: str = "swiglu",
    router: str = "softmax",
    scale: float = 1.0,
) -> tuple[Array, MoEMetrics]:
    """Capacity-bounded dispatch (training), over every expert."""
    B, S, D = x.shape
    E = params["router"].shape[1]
    G = _n_groups(B)
    Tg = (B // G) * S          # tokens per group
    pol = current_policy()
    dp = _dp(pol.mesh) if pol is not None else None
    xt = x.reshape(G, Tg, D)
    xt = shard_spec(xt, P(dp, None, None))

    # ---- routing (grouped)
    gate_vals, expert_idx, probs = route(params, xt, top_k, router, scale)  # [G, Tg, k]

    # ---- aux losses (global)
    me = probs.mean((0, 1))
    ce = jnp.zeros((E,)).at[expert_idx.reshape(-1)].add(1.0) / (G * Tg * top_k)
    aux = E * jnp.sum(me * ce)
    logits = jnp.einsum("gtd,de->gte", xt.astype(jnp.float32), params["router"])
    zloss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)

    # ---- sort-based dispatch per group (DSDE packing, §4.2)
    # floor of min(Tg, 16) keeps short sequences dropless: with a
    # length-dependent cap, appending a token changes capacity and can
    # (un)drop an earlier token — a causality artifact at smoke scale.
    # Production shapes have int(cf*Tg*k/E) >> 16, so they are unaffected.
    cap = max(int(capacity_factor * Tg * top_k / E), 4, min(Tg, 16))
    n_slots = E * cap

    def pack(xt_g, eidx_g, gate_g):
        flat_e = eidx_g.reshape(-1)                               # [Tg*k]
        flat_g = gate_g.reshape(-1)
        flat_src = jnp.repeat(jnp.arange(Tg), top_k)
        order = jnp.argsort(flat_e, stable=True)
        s_e, s_g, s_src = flat_e[order], flat_g[order], flat_src[order]
        pos = jnp.arange(Tg * top_k) - jnp.searchsorted(s_e, s_e, side="left")
        ok = pos < cap
        slot = jnp.where(ok, s_e * cap + pos, n_slots)            # overflow -> drop
        disp = jnp.zeros((n_slots, D), xt_g.dtype).at[slot].set(xt_g[s_src], mode="drop")
        meta = {
            "slot": slot, "src": s_src, "gate": s_g, "ok": ok,
        }
        return disp.reshape(E, cap, D), meta

    disp, meta = jax.vmap(pack)(xt, expert_idx, gate_vals)        # [G, E, cap, D]
    drop = 1.0 - jnp.mean(meta["ok"])
    # EP reshard: experts onto `model` (GSPMD -> all-to-all of slot ranges)
    disp = shard_spec(disp, P(dp, "model", None, None))

    # ---- expert FFN (E over model; groups over data)
    h = jnp.einsum("gecd,edf->gecf", disp, params["experts"]["w_in"])
    if mlp_type == "swiglu":
        g = jnp.einsum("gecd,edf->gecf", disp, params["experts"]["w_gate"])
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    out = jnp.einsum("gecf,efd->gecd", h, params["experts"]["w_out"])
    out = shard_spec(out, P(dp, "model", None, None))

    # ---- combine per group (return trip + gate-weighted scatter-add)
    def combine(out_g, meta_g):
        flat = out_g.reshape(n_slots, D)
        got = flat[jnp.minimum(meta_g["slot"], n_slots - 1)]
        contrib = jnp.zeros((Tg, D), jnp.float32).at[
            jnp.where(meta_g["ok"], meta_g["src"], Tg)
        ].add(
            jnp.where(meta_g["ok"][:, None],
                      got.astype(jnp.float32) * meta_g["gate"][:, None], 0.0),
            mode="drop",
        )
        return contrib

    y = jax.vmap(combine)(out, meta)                              # [G, Tg, D]
    y = shard_spec(y, P(dp, None, None))
    y = y.astype(x.dtype).reshape(B, S, D)

    if "shared" in params:
        from .layers import mlp

        y = y + mlp(params["shared"], x, mlp_type)

    return y, MoEMetrics(aux, zloss, drop)


def moe_serve(
    params: dict,
    x: Array,                 # [B, S, D]
    top_k: int,
    router: str = "softmax",
    scale: float = 1.0,
    mlp_type: str = "swiglu",
    first: int = 0,
    lanes: Array | None = None,
) -> tuple[Array, Array]:
    """Dropless expert layer over the held experts (serving).

    Routes every token over all experts; computes the experts held here,
    global ids `first .. first + n_held - 1`, for exactly the pairs routed
    to them, and the shared experts for every token.  Returns (y, counts):
    counts uint32 [4] = pairs routed to held experts, all routed pairs, the
    busiest held expert's pairs, and 1 (this layer, for the count of layers
    counted).  `lanes` (bool [B], default all) says whose pairs the counts
    count: a serving engine's decode computes every lane, free ones too,
    and counts only the lanes that carry a request.
    """
    B, S, D = x.shape
    T = B * S
    ex = params["experts"]
    n_held = ex["w_in"].shape[0]
    xt = x.reshape(T, D)
    w, idx, _ = route(params, xt, top_k, router, scale)           # [T, k]

    local = idx.reshape(-1) - first
    held = (local >= 0) & (local < n_held)
    key = jnp.where(held, local, n_held)                          # not held -> last
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((n_held + 1,), jnp.int32).at[key].add(1)[:n_held]
    xs = xt[order // top_k]                                       # [T*k, D], by expert
    # rows past sum(sizes) are the pairs of experts held elsewhere: no group
    # covers them, and their outputs are masked out below
    h = jax.lax.ragged_dot(xs, ex["w_in"], sizes)
    if mlp_type == "swiglu":
        h = jax.nn.silu(jax.lax.ragged_dot(xs, ex["w_gate"], sizes)) * h
    else:
        h = jax.nn.gelu(h)
    out = jax.lax.ragged_dot(h, ex["w_out"], sizes)
    out = out[jnp.argsort(order)].reshape(T, top_k, D)            # back to (token, k)
    out = jnp.where(held.reshape(T, top_k, 1), out.astype(jnp.float32), 0.0)
    y = jnp.einsum("tk,tkd->td", w, out).astype(x.dtype).reshape(B, S, D)

    if "shared" in params:
        from .layers import mlp

        y = y + mlp(params["shared"], x, mlp_type)
    counted = jnp.ones((T * top_k,), bool) if lanes is None else \
        jnp.repeat(jnp.broadcast_to(lanes[:, None], (B, S)).reshape(T), top_k)
    csizes = jnp.zeros((n_held + 1,), jnp.int32).at[
        jnp.where(counted, key, n_held)].add(1)[:n_held]
    counts = jnp.stack([csizes.sum(), counted.sum(dtype=jnp.int32), csizes.max(),
                        jnp.int32(1)]).astype(jnp.uint32)
    return y, counts
