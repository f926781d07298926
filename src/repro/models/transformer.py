"""Unified LM: one scan-based decoder covering all assigned families.

Families map to a *period* structure consumed by ``lax.scan`` (HLO size is
independent of depth — essential for compiling 80-layer configs on CPU):

  dense / vlm / moe : period = 1 layer, stacked [L, ...]
  hybrid (jamba)    : period = `attn_period` layers (1 attn + rest mamba,
                      channel mixer alternating dense/MoE per `moe_every`)
  ssm (xlstm)       : period = `slstm_period` blocks (period-1 mLSTM + 1 sLSTM)
  audio (whisper)   : encoder stack + decoder stack with cross-attention

`forward(..., cache=None)` is training; passing a cache makes the same code
path do prefill (S tokens into an empty cache) and decode (S=1).  The K/V
cache is one stacked buffer per tensor, [L, B, Hkv, hd, Smax], carried
through the layer scan: each layer writes its new rows into it and reads its
rows from it (`_attn_block`), so a caller that donates the cache gets it
updated in place.  A latent-attention model (`kv_lora_rank`) keeps one
stack of latent rows instead, [L, B, 1, lora + rope, Smax], under the same
contract (DESIGN.md §9.5).

The `moe` family may lead with `first_k_dense` dense layers
(`params["dense_blocks"]`); they run through the same layer body, in a scan
of their own, before the expert layers' scan, and write the same cache
stack at layers 0 .. first_k_dense - 1.  With a cache, expert layers serve
dropless over the experts held here (`moe.moe_serve`), and each decode step
adds its routed-pair counts to `cache["experts"]` (uint32 [4]: pairs on held
experts, all routed pairs, the busiest held expert's pairs, expert layers),
summed over the expert layers and over the lanes that an optional
`cache["lanes"]` (bool [B]) marks, which the serving engine reads outside its
decode ticks.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig

from . import layers as L
from . import mamba as M
from . import moe as X
from . import xlstm as XL

Array = jax.Array

# when True, per-layer scan bodies are rematerialized (activation checkpointing)
_REMAT: list[bool] = [False]


def set_remat(flag: bool) -> None:
    _REMAT[0] = flag


def _maybe_remat(body):
    if _REMAT[0]:
        return jax.checkpoint(body, prevent_cse=False)
    return body


class ForwardOut(NamedTuple):
    logits: Array
    cache: Any
    aux_loss: Array
    z_loss: Array


# ============================================================ init
def _init_attn_layer(rng, cfg: ArchConfig) -> dict:
    k1, k2 = jax.random.split(rng)
    if cfg.kv_lora_rank:
        attn = L.init_mla(k1, cfg.d_model, cfg.n_heads, cfg.kv_lora_rank,
                          cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim)
    else:
        attn = L.init_attention(k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.qkv_bias)
    return {"ln1": L.init_rmsnorm(cfg.d_model), "attn": attn}


def _init_ffn(rng, cfg: ArchConfig, is_moe: bool) -> dict:
    if is_moe:
        return {
            "ln2": L.init_rmsnorm(cfg.d_model),
            "moe": X.init_moe(rng, cfg.d_model, cfg.moe_experts, cfg.moe_d_ff,
                              cfg.mlp_type, cfg.moe_shared_ff, router=cfg.moe_router,
                              n_held=cfg.moe_held),
        }
    return {"ln2": L.init_rmsnorm(cfg.d_model), "mlp": L.init_mlp(rng, cfg.d_model, cfg.d_ff, cfg.mlp_type)}


def _stack(rngs, init_fn):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *[init_fn(r) for r in rngs])


def init_lm(rng, cfg: ArchConfig) -> dict:
    ks = jax.random.split(rng, 8)
    params: dict = {"tok": L.init_embed(ks[0], cfg.vocab_size, cfg.d_model, cfg.tie_embeddings)}
    params["final_norm"] = L.init_rmsnorm(cfg.d_model)

    if cfg.family in ("dense", "vlm"):
        rngs = jax.random.split(ks[1], cfg.n_layers)
        params["blocks"] = _stack(
            rngs, lambda r: {**_init_attn_layer(r, cfg), **_init_ffn(jax.random.fold_in(r, 1), cfg, False)}
        )
    elif cfg.family == "moe":
        k = cfg.first_k_dense
        rngs = jax.random.split(ks[1], cfg.n_layers - k)
        params["blocks"] = _stack(
            rngs, lambda r: {**_init_attn_layer(r, cfg), **_init_ffn(jax.random.fold_in(r, 1), cfg, True)}
        )
        if k:
            params["dense_blocks"] = _stack(
                jax.random.split(ks[2], k),
                lambda r: {**_init_attn_layer(r, cfg), **_init_ffn(jax.random.fold_in(r, 1), cfg, False)},
            )
    elif cfg.family == "hybrid":
        period = cfg.attn_period
        n_p = cfg.n_layers // period
        n_mamba = period - 1
        n_moe = sum(1 for j in range(period) if j % cfg.moe_every == cfg.moe_every - 1)

        def init_period(r):
            rs = jax.random.split(r, 4)
            mamba_rngs = jax.random.split(rs[0], n_mamba)
            moe_rngs = jax.random.split(rs[1], n_moe)
            mlp_rngs = jax.random.split(rs[2], period - n_moe)
            return {
                "attn": _init_attn_layer(rs[3], cfg),
                "mamba": _stack(mamba_rngs, lambda q: {
                    "ln1": L.init_rmsnorm(cfg.d_model),
                    "mix": M.init_mamba(q, cfg.d_model, cfg.ssm_expand, cfg.ssm_state_dim, cfg.ssm_conv_width),
                }),
                "moe": _stack(moe_rngs, lambda q: _init_ffn(q, cfg, True)),
                "mlp": _stack(mlp_rngs, lambda q: _init_ffn(q, cfg, False)),
            }

        params["periods"] = _stack(jax.random.split(ks[1], n_p), init_period)
    elif cfg.family == "ssm":  # xlstm
        period = cfg.slstm_period
        n_p = cfg.n_layers // period

        def init_period(r):
            rs = jax.random.split(r, 2)
            m_rngs = jax.random.split(rs[0], period - 1)
            return {
                "mlstm": _stack(m_rngs, lambda q: {
                    "ln1": L.init_rmsnorm(cfg.d_model),
                    "mix": XL.init_mlstm(q, cfg.d_model, cfg.n_heads),
                }),
                "slstm": {
                    "ln1": L.init_rmsnorm(cfg.d_model),
                    "mix": XL.init_slstm(rs[1], cfg.d_model, cfg.n_heads),
                },
            }

        params["periods"] = _stack(jax.random.split(ks[1], n_p), init_period)
    elif cfg.family == "audio":  # whisper enc-dec
        enc_rngs = jax.random.split(ks[1], cfg.encoder_layers)
        dec_rngs = jax.random.split(ks[2], cfg.n_layers)
        params["enc_blocks"] = _stack(
            enc_rngs, lambda r: {**_init_attn_layer(r, cfg), **_init_ffn(jax.random.fold_in(r, 1), cfg, False)}
        )
        params["enc_norm"] = L.init_rmsnorm(cfg.d_model)
        params["enc_pos"] = (jax.random.normal(ks[3], (cfg.encoder_seq, cfg.d_model)) * 0.01).astype(jnp.bfloat16)

        def init_dec(r):
            r1, r2, r3 = jax.random.split(r, 3)
            return {
                **_init_attn_layer(r1, cfg),
                "ln_x": L.init_rmsnorm(cfg.d_model),
                "xattn": L.init_attention(r2, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd),
                **_init_ffn(r3, cfg, False),
            }

        params["blocks"] = _stack(dec_rngs, init_dec)
    else:
        raise ValueError(f"unknown family {cfg.family}")
    return params


# ============================================================ caches
def init_cache(cfg: ArchConfig, batch: int, max_seq: int) -> dict:
    """Decode cache pytree (stacked per scan period).  K/V rows are stored
    [L, B, Hkv, hd, Smax], latent rows [L, B, 1, lora + rope, Smax]: the
    layouts decode attention reads in place."""
    def kv(n_layers):
        if cfg.kv_lora_rank:
            width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
            return {"lat": jnp.zeros((n_layers, batch, 1, width, max_seq), jnp.bfloat16)}
        return {
            "k": jnp.zeros((n_layers, batch, cfg.n_kv_heads, cfg.hd, max_seq), jnp.bfloat16),
            "v": jnp.zeros((n_layers, batch, cfg.n_kv_heads, cfg.hd, max_seq), jnp.bfloat16),
        }

    if cfg.family == "moe":
        return {"kv": kv(cfg.n_layers), "len": jnp.zeros((), jnp.int32),
                "experts": jnp.zeros((4,), jnp.uint32)}
    if cfg.family in ("dense", "vlm"):
        return {"kv": kv(cfg.n_layers), "len": jnp.zeros((), jnp.int32)}
    if cfg.family == "hybrid":
        n_p = cfg.n_layers // cfg.attn_period
        n_m = cfg.attn_period - 1
        st = M.init_mamba_state(batch, cfg.d_model, cfg.ssm_expand, cfg.ssm_state_dim, cfg.ssm_conv_width)
        return {
            "kv": kv(n_p),
            "mamba": jax.tree.map(lambda x: jnp.broadcast_to(x, (n_p, n_m) + x.shape), st),
            "len": jnp.zeros((), jnp.int32),
        }
    if cfg.family == "ssm":
        n_p = cfg.n_layers // cfg.slstm_period
        ms = XL.init_mlstm_state(batch, cfg.d_model, cfg.n_heads)
        ss = XL.init_slstm_state(batch, cfg.d_model)
        return {
            "mlstm": jax.tree.map(lambda x: jnp.broadcast_to(x, (n_p, cfg.slstm_period - 1) + x.shape), ms),
            "slstm": jax.tree.map(lambda x: jnp.broadcast_to(x, (n_p,) + x.shape), ss),
            "len": jnp.zeros((), jnp.int32),
        }
    if cfg.family == "audio":
        return {
            "kv": kv(cfg.n_layers),
            "enc_out": jnp.zeros((batch, cfg.encoder_seq, cfg.d_model), jnp.bfloat16),
            "len": jnp.zeros((), jnp.int32),
        }
    raise ValueError(cfg.family)


# ============================================================ forward
def _attn_block(cfg, blk, h, positions, kv, layer, cache_len, cross_kv=None):
    """One attention (or cross-attention) residual branch.

    `kv` is the stacked cache {"k", "v"} that the layer scan carries (None
    without a cache): the branch writes layer `layer`'s new rows into it and
    attends over that layer's rows.  Every family's cached scan body goes
    through here.  Returns (h, kv)."""
    cache = None if kv is None else {**kv, "len": cache_len, "layer": layer}
    xn = L.rmsnorm(h, blk["ln1"]["scale"], cfg.norm_eps)
    if cfg.kv_lora_rank:
        y, new_cache = L.mla_attention(blk["attn"], xn, positions, cache, cfg.rope_theta)
    else:
        y, new_cache = L.attention(blk["attn"], xn, positions, cfg.rope_style,
                                   causal=True, cache=cache, theta=cfg.rope_theta)
    h = h + y
    if cross_kv is not None:
        yx, _ = L.attention(
            blk["xattn"], L.rmsnorm(h, blk["ln_x"]["scale"], cfg.norm_eps),
            positions, "none", causal=False, cross_kv=cross_kv,
        )
        h = h + yx
    return h, new_cache


def _ffn_block(cfg, blk, h, serve=False, lanes=None):
    """Channel mixer; returns (h, aux, z, counts).  `serve` (a cache is
    present) takes expert layers through the dropless `moe_serve`, whose
    routed-pair counts, of the lanes `lanes` marks, come back as `counts`;
    otherwise counts is None.  Weights that hold a share of the routed
    experts are a serving deployment's, and serve without a cache too."""
    xn = L.rmsnorm(h, blk["ln2"]["scale"], cfg.norm_eps)
    zero = jnp.zeros(())
    if "moe" in blk and (serve or blk["moe"]["experts"]["w_in"].shape[0] < cfg.moe_experts):
        y, counts = X.moe_serve(blk["moe"], xn, cfg.moe_top_k, cfg.moe_router,
                                cfg.moe_route_scale, cfg.mlp_type, lanes=lanes)
        return h + y, zero, zero, (counts if serve else None)
    if "moe" in blk:
        y, met = X.moe_ffn(blk["moe"], xn, cfg.moe_top_k, mlp_type=cfg.mlp_type,
                           router=cfg.moe_router, scale=cfg.moe_route_scale)
        return h + y, met.aux_loss, met.router_z_loss, None
    return h + L.mlp(blk["mlp"], xn, cfg.mlp_type), zero, zero, None


def forward(
    params: dict,
    cfg: ArchConfig,
    tokens: Array,                    # [B, S]
    cache: Optional[dict] = None,
    prefix_embeds: Optional[Array] = None,   # vlm patches / audio frames [B, P, D]
) -> ForwardOut:
    B, S = tokens.shape
    h = L.embed(params["tok"], tokens)
    if prefix_embeds is not None and cfg.family == "vlm":
        h = jnp.concatenate([prefix_embeds.astype(h.dtype), h], axis=1)
        S = h.shape[1]
    start = cache["len"] if cache is not None else jnp.int32(0)
    positions = start + jnp.arange(S)[None, :] + jnp.zeros((B, 1), jnp.int32)

    aux = jnp.zeros(())
    zl = jnp.zeros(())

    if cfg.family in ("dense", "vlm", "moe"):
        kv = cache["kv"] if cache is not None else None
        # routed-pair counters: summed over the expert layers of decode
        # steps, over the lanes `cache["lanes"]` marks (default: all)
        ex = cache.get("experts") if cache is not None else None
        lanes = cache.get("lanes") if cache is not None else None

        def body(carry, xs):
            h, aux, zl, kv, ex = carry
            blk, i = xs
            h, kv = _attn_block(cfg, blk, h, positions, kv, i, start)
            h, a, z, counts = _ffn_block(cfg, blk, h, serve=cache is not None, lanes=lanes)
            if counts is not None and ex is not None and S == 1:
                ex = ex + counts
            return (h, aux + a, zl + z, kv, ex), None

        carry = (h, aux, zl, kv, ex)
        k = cfg.first_k_dense if "dense_blocks" in params else 0
        if k:
            carry, _ = lax.scan(_maybe_remat(body), carry,
                                (params["dense_blocks"], jnp.arange(k)))
        (h, aux, zl, kv, ex), _ = lax.scan(
            _maybe_remat(body), carry, (params["blocks"], jnp.arange(k, cfg.n_layers))
        )
        new_cache = None if cache is None else {"kv": kv, "len": start + S}
        if ex is not None:
            new_cache["experts"] = ex

    elif cfg.family == "hybrid":
        period = cfg.attn_period
        attn_pos = period // 2
        mamba_in = cache["mamba"] if cache is not None else None
        decode = cache is not None and S == 1

        def body(carry, xs):
            h, aux, zl, kv = carry
            per, i, mst = xs
            m_i = 0
            ffn_i = {"moe": 0, "mlp": 0}
            mst_out = mst
            for j in range(period):
                if j == attn_pos:
                    h, kv = _attn_block(cfg, per["attn"], h, positions, kv, i, start)
                else:
                    mp = jax.tree.map(lambda x, i=m_i: x[i], per["mamba"])
                    xn = L.rmsnorm(h, mp["ln1"]["scale"], cfg.norm_eps)
                    st = jax.tree.map(lambda x, i=m_i: x[i], mst)
                    if decode:
                        y, st2 = M.mamba_decode(mp["mix"], xn, st)
                    else:  # cached prefill: parallel scan seeded by state
                        y, st2 = M.mamba_prefill(mp["mix"], xn, st)
                    mst_out = jax.tree.map(
                        lambda full, new, i=m_i: full.at[i].set(new), mst_out, st2
                    )
                    h = h + y
                    m_i += 1
                is_moe = j % cfg.moe_every == cfg.moe_every - 1
                key = "moe" if is_moe else "mlp"
                fp = jax.tree.map(lambda x, i=ffn_i[key]: x[i], per[key])
                h, a, z, _ = _ffn_block(cfg, fp, h, serve=True)
                ffn_i[key] += 1
                aux, zl = aux + a, zl + z
            return (h, aux, zl, kv), mst_out

        n_p = cfg.n_layers // period
        if cache is None:
            # training: mamba_forward handles state-free path; attention w/o cache
            def body_nocache(carry, per):
                h, aux, zl = carry
                m_i = 0
                ffn_i = {"moe": 0, "mlp": 0}
                for j in range(period):
                    if j == attn_pos:
                        h, _ = _attn_block(cfg, per["attn"], h, positions, None, None, start)
                    else:
                        mp = jax.tree.map(lambda x, i=m_i: x[i], per["mamba"])
                        xn = L.rmsnorm(h, mp["ln1"]["scale"], cfg.norm_eps)
                        h = h + M.mamba_forward(mp["mix"], xn)
                        m_i += 1
                    is_moe = j % cfg.moe_every == cfg.moe_every - 1
                    key = "moe" if is_moe else "mlp"
                    fp = jax.tree.map(lambda x, i=ffn_i[key]: x[i], per[key])
                    h, a, z, _ = _ffn_block(cfg, fp, h)
                    ffn_i[key] += 1
                    aux, zl = aux + a, zl + z
                return (h, aux, zl), None

            (h, aux, zl), _ = lax.scan(_maybe_remat(body_nocache), (h, aux, zl), params["periods"])
            new_cache = None
        else:
            (h, aux, zl, kv), mst_out = lax.scan(
                body, (h, aux, zl, cache["kv"]), (params["periods"], jnp.arange(n_p), mamba_in)
            )
            new_cache = {"kv": kv, "mamba": mst_out, "len": start + S}

    elif cfg.family == "ssm":
        period = cfg.slstm_period
        n_p = cfg.n_layers // period
        decode = cache is not None and S == 1

        stateful = cache is not None

        def body(carry, xs):
            h, aux, zl = carry
            per, mst, sst = xs
            mst_out = mst
            for j in range(period - 1):
                mp = jax.tree.map(lambda x, i=j: x[i], per["mlstm"])
                xn = L.rmsnorm(h, mp["ln1"]["scale"], cfg.norm_eps)
                st = jax.tree.map(lambda x, i=j: x[i], mst)
                if decode:
                    y, st2 = XL.mlstm_decode(mp["mix"], xn, st)
                elif stateful:
                    y, st2 = XL.mlstm_prefill(mp["mix"], xn, st)
                else:
                    y, st2 = XL.mlstm_prefill(mp["mix"], xn, None)[0], st
                mst_out = jax.tree.map(lambda full, new, i=j: full.at[i].set(new), mst_out, st2)
                h = h + y
            sp = per["slstm"]
            xn = L.rmsnorm(h, sp["ln1"]["scale"], cfg.norm_eps)
            if decode:
                y, sst = XL.slstm_decode(sp["mix"], xn, sst, cfg.n_heads)
            elif stateful:
                y, sst = XL.slstm_prefill(sp["mix"], xn, sst, cfg.n_heads)
            else:
                y = XL.slstm_prefill(sp["mix"], xn, None, cfg.n_heads)[0]
            h = h + y
            return (h, aux, zl), (mst_out, sst)

        if cache is None:
            ms = XL.init_mlstm_state(B, cfg.d_model, cfg.n_heads)
            ss = XL.init_slstm_state(B, cfg.d_model)
            mst_in = jax.tree.map(lambda x: jnp.broadcast_to(x, (n_p, period - 1) + x.shape), ms)
            sst_in = jax.tree.map(lambda x: jnp.broadcast_to(x, (n_p,) + x.shape), ss)
        else:
            mst_in, sst_in = cache["mlstm"], cache["slstm"]
        (h, aux, zl), (mst_out, sst_out) = lax.scan(
            body, (h, aux, zl), (params["periods"], mst_in, sst_in)
        )
        new_cache = (
            None if cache is None
            else {"mlstm": mst_out, "slstm": sst_out, "len": start + S}
        )

    elif cfg.family == "audio":
        # decoder over tokens with cross-attention to cached encoder output
        if cache is None:
            raise ValueError("whisper forward requires a cache carrying enc_out; use encode() + forward")
        enc_out = cache["enc_out"]
        # sinusoidal decoder positions, computed functionally so any context
        # length lowers (adaptation of whisper's learned table; DESIGN.md §5)
        h = h + L.sinusoidal_pos(positions[0], cfg.d_model).astype(h.dtype)[None]

        def body(carry, xs):
            h, aux, zl, kv = carry
            blk, i = xs
            # cross KV computed from encoder output per layer
            xk = jnp.einsum("bsd,dhk->bshk", enc_out, blk["xattn"]["wk"])
            xv = jnp.einsum("bsd,dhk->bshk", enc_out, blk["xattn"]["wv"])
            h, kv = _attn_block(cfg, blk, h, positions, kv, i, start, cross_kv=(xk, xv))
            h, a, z, _ = _ffn_block(cfg, blk, h)
            return (h, aux + a, zl + z, kv), None

        (h, aux, zl, kv), _ = lax.scan(
            _maybe_remat(body), (h, aux, zl, cache["kv"]), (params["blocks"], jnp.arange(cfg.n_layers))
        )
        new_cache = {"kv": kv, "enc_out": enc_out, "len": start + S}
    else:
        raise ValueError(cfg.family)

    h = L.rmsnorm(h, params["final_norm"]["scale"], cfg.norm_eps)
    logits = L.unembed(params["tok"], h)
    return ForwardOut(logits, new_cache, aux, zl)


def encode(params: dict, cfg: ArchConfig, frames: Array) -> Array:
    """Whisper encoder over precomputed frame embeddings (conv frontend stub)."""
    h = frames.astype(jnp.bfloat16) + params["enc_pos"][None, : frames.shape[1]]
    positions = jnp.arange(frames.shape[1])[None] + jnp.zeros((frames.shape[0], 1), jnp.int32)

    def body(h, blk):
        y, _ = L.attention(
            blk["attn"], L.rmsnorm(h, blk["ln1"]["scale"], cfg.norm_eps),
            positions, "none", causal=False,
        )
        h = h + y
        h, _, _, _ = _ffn_block(cfg, blk, h)
        return h, None

    h, _ = lax.scan(_maybe_remat(body), h, params["enc_blocks"])
    return L.rmsnorm(h, params["enc_norm"]["scale"], cfg.norm_eps)
