"""Latent attention and the held-share expert layer (Moonlight-16B-A3B's
DeepSeek-V3 block) at smoke width on the CPU: the serving engine's programs
against the plain float32 reference, absorbed decode against expanded
attention, dropless routing, and shares that add up to the uncut layer."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.models import layers as L
from repro.models import moe as X
from repro.obs.metrics import MetricsRegistry
from repro.serve.engine import Request, ServeEngine

CHECKOUT = Path(__file__).resolve().parents[1]
if str(CHECKOUT) not in sys.path:
    sys.path.insert(0, str(CHECKOUT))

RNG = jax.random.PRNGKey(0)
ARCH = "moonlight-16b-a3b"


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def test_published_parameter_count():
    cfg = get_config(ARCH)
    assert cfg.n_params() == 15_960_108_160          # all 64 experts, as published
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (512, 128, 64, 128)
    assert (cfg.moe_experts, cfg.moe_top_k, cfg.moe_held, cfg.moe_shared_ff,
            cfg.first_k_dense, cfg.d_ff) == (64, 6, 0, 2816, 1, 11264)
    # the catalog's entry builds every expert; its smoke form holds a share
    assert get_config(ARCH, smoke=True).moe_held == 4
    assert (cfg.moe_router, cfg.moe_route_scale, cfg.rope_theta) == ("sigmoid", 2.446, 50_000.0)


def test_engine_programs_match_the_reference():
    """Prefill then decode through `ServeEngine`'s jitted programs, with the
    dense layer, latent attention and held-share experts all on, against
    the plain reference's full forward (`bench/reference/deepseek_v3.py`).
    The weights are the family's bf16 values upcast to f32, so routing sees
    no bf16 rounding that could flip a near tie between the 2nd and 3rd
    expert; the latent cache still rounds rows to bf16, which moves these
    logits (|logit| up to about 0.5) by at most about 0.011 over 20 seeds.
    A wrong row, position, mask or expert moves them by 0.1 or more."""
    from bench import spec
    from bench.family import deepseek_v3 as fam
    from bench.family import root_key

    config = spec.load_json(CHECKOUT / "bench" / "tests" / "data" / "smoke-deepseek-v3.json")
    dims = config["dims"]
    ref = spec.reference_module(config)
    model = build_model(get_config(ARCH, smoke=True))
    root = root_key(11)
    params = _f32(fam.program_params(root, dims))
    eng = ServeEngine(model, params, n_slots=2, max_seq=32, metrics=MetricsRegistry())
    slot, plen, steps = 1, 9, 6
    prompt = np.random.default_rng(0).integers(0, dims["vocab"], plen).astype(np.int32)
    tokens = jnp.zeros((eng.max_seq,), jnp.int32).at[:plen].set(prompt)
    logits, cache = eng._prefill(params, eng.cache, tokens, slot, plen=plen)
    got, toks = [np.asarray(logits, np.float32)], []
    for i in range(steps):
        toks.append(int(jnp.argmax(logits)))
        cache = dict(cache, len=jnp.int32(plen + i))
        lanes = jnp.zeros((eng.n_slots,), jnp.int32).at[slot].set(toks[-1])
        logits, cache = eng._decode(params, lanes, cache)
        logits = logits[slot]
        got.append(np.asarray(logits, np.float32))
    seq = np.concatenate([prompt, toks]).astype(np.int32)[None]
    idx = np.arange(plen - 1, plen + steps)[None]
    want = np.asarray(ref.logits_at(fam, dims, root, seq, idx))[0]
    assert np.abs(want - np.stack(got)).max() < 0.02
    assert np.abs(want).max() > 0.2                  # the logits are not all ~0


def test_absorbed_decode_matches_expanded_attention():
    """Prefill (S tokens) then decode (1) over the latent cache, absorbed,
    against the expanded attention over all S + 1 positions, in f32."""
    B, S, D, H, lora, nope, rope, vd = 2, 7, 32, 4, 16, 8, 4, 8
    p = _f32(L.init_mla(RNG, D, H, lora, nope, rope, vd))
    x = jax.random.normal(jax.random.fold_in(RNG, 1), (B, S + 1, D), jnp.float32)
    pos = jnp.arange(S + 1)
    with jax.default_matmul_precision("highest"):
        full, _ = L.mla_attention(p, x, pos, theta=50_000.0)
        cache = {"lat": jnp.zeros((3, B, 1, lora + rope, 16), jnp.float32),
                 "len": jnp.int32(0), "layer": jnp.int32(1)}
        pre, new = L.mla_attention(p, x[:, :S], pos[:S], cache, theta=50_000.0)
        cache = {**new, "len": jnp.int32(S), "layer": jnp.int32(1)}
        dec, new = L.mla_attention(p, x[:, S:], pos[S:], cache, theta=50_000.0)
    np.testing.assert_allclose(np.asarray(pre), np.asarray(full[:, :S]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full[:, S:]), atol=1e-5)
    # one row per position, in layer 1 only: the latent and the rotary key
    lat = np.asarray(new["lat"])
    assert not lat[0].any() and not lat[2].any()
    assert lat[1, :, :, :, :S + 1].any(axis=(1, 2)).all() and not lat[1, :, :, :, S + 1:].any()


def test_cached_decode_attention_takes_a_value_width_and_scale():
    """The absorbed read: keys of width 12 and values the first 8 of them."""
    B, H, W, V, Smax = 2, 3, 12, 8, 10
    q = jax.random.normal(RNG, (B, 1, H, W))
    rows = jax.random.normal(jax.random.fold_in(RNG, 2), (B, 1, W, Smax))
    out = L.cached_decode_attention(q, rows, rows[:, :, :V], jnp.int32(6), scale=0.3)
    k = np.asarray(rows[:, 0, :, :6])                       # [B, W, 6]
    sc = np.einsum("bhw,bws->bhs", np.asarray(q[:, 0]), k) * 0.3
    pr = np.exp(sc - sc.max(-1, keepdims=True))
    want = np.einsum("bhs,bvs->bhv", pr / pr.sum(-1, keepdims=True), k[:, :V])
    np.testing.assert_allclose(np.asarray(out[:, 0]), want, atol=1e-5)


def _moe(n_held=0, d=16, e=8, f=12, shared=24):
    p = _f32(X.init_moe(RNG, d, e, f, shared_ff=shared, router="sigmoid", n_held=n_held))
    p["router_bias"] = 0.05 * jax.random.normal(jax.random.fold_in(RNG, 3), (e,))
    return p


def _swiglu(x, w_gate, w_in, w_out):
    g = x @ w_gate
    return (g / (1 + np.exp(-g)) * (x @ w_in)) @ w_out


def _expert_loop(p, x, top_k, scale, first=0):
    """Written out: the published gate, then each token's chosen experts that
    are held, one at a time, and the shared experts."""
    x = np.asarray(x, np.float64)
    scores = 1 / (1 + np.exp(-(x @ np.asarray(p["router"], np.float64))))
    ex = {k: np.asarray(v, np.float64) for k, v in p["experts"].items()}
    sh = {k: np.asarray(v, np.float64) for k, v in p["shared"].items()}
    y = _swiglu(x, sh["w_gate"], sh["w_in"], sh["w_out"])
    for t in range(x.shape[0]):
        chosen = np.argsort(-(scores[t] + np.asarray(p["router_bias"])), kind="stable")[:top_k]
        w = scores[t, chosen] / scores[t, chosen].sum() * scale
        for e, we in zip(chosen, w):
            if first <= e < first + ex["w_in"].shape[0]:
                i = e - first
                y[t] += we * _swiglu(x[t], ex["w_gate"][i], ex["w_in"][i], ex["w_out"][i])
    return y


def test_route_is_the_published_noaux_gate():
    p = _moe()
    x = jax.random.normal(jax.random.fold_in(RNG, 4), (10, 16))
    w, idx, _ = X.route(p, x, 3, "sigmoid", 2.446)
    scores = jax.nn.sigmoid(x @ p["router"])
    want = np.argsort(-np.asarray(scores + p["router_bias"]), axis=-1)[:, :3]
    np.testing.assert_array_equal(np.sort(np.asarray(idx), -1), np.sort(want, -1))
    chosen = np.take_along_axis(np.asarray(scores), np.asarray(idx), -1)
    np.testing.assert_allclose(np.asarray(w), chosen / chosen.sum(-1, keepdims=True) * 2.446,
                               rtol=1e-6)


def test_dropless_when_every_token_picks_the_same_experts():
    """Every token routed to experts 1 and 2 (both held): the serving layer
    computes every pair, where the capacity-bounded training dispatch has
    to drop most of them."""
    whole = _moe()
    whole["router_bias"] = whole["router_bias"].at[jnp.array([1, 2])].add(100.0)
    p = dict(whole, experts=jax.tree.map(lambda a: a[:4], whole["experts"]))
    x = jax.random.normal(jax.random.fold_in(RNG, 5), (2, 32, 16))
    with jax.default_matmul_precision("highest"):
        y, counts = X.moe_serve(p, x, 2, "sigmoid", 2.446)
        _, met = X.moe_ffn(whole, x, 2, router="sigmoid", scale=2.446)
    want = _expert_loop(p, x.reshape(-1, 16), 2, 2.446)
    np.testing.assert_allclose(np.asarray(y).reshape(-1, 16), want, rtol=2e-4, atol=2e-4)
    assert counts.tolist() == [128, 128, 64, 1]           # held, routed, busiest, layer
    assert float(met.drop_fraction) > 0.5


def test_serve_matches_a_loop_over_experts_with_pairs_held_elsewhere():
    p = _moe(n_held=3)
    x = jax.random.normal(jax.random.fold_in(RNG, 6), (3, 5, 16))
    with jax.default_matmul_precision("highest"):
        y, counts = X.moe_serve(p, x, 3, "sigmoid", 2.446)
    want = _expert_loop(p, x.reshape(-1, 16), 3, 2.446)
    np.testing.assert_allclose(np.asarray(y).reshape(-1, 16), want, rtol=2e-4, atol=2e-4)
    held, routed, busiest, layers = counts.tolist()
    assert 0 < held < routed == 45 and busiest <= held and layers == 1


@pytest.mark.parametrize("held", [2, 4])
def test_shares_add_up_to_the_uncut_layer(held):
    """The partial outputs of all n_experts / held shares, with the shared
    experts (which every chip computes alike) counted once, equal the
    layer that holds every expert."""
    p = _moe()
    x = jax.random.normal(jax.random.fold_in(RNG, 7), (2, 6, 16))
    n_shares = 8 // held
    with jax.default_matmul_precision("highest"):
        whole, _ = X.moe_serve(p, x, 2, "sigmoid", 2.446)
        shared = L.mlp(p["shared"], x)
        parts = []
        for s in range(n_shares):
            share = dict(p, experts=jax.tree.map(lambda a: a[s * held:(s + 1) * held], p["experts"]))
            parts.append(X.moe_serve(share, x, 2, "sigmoid", 2.446, first=s * held)[0])
    total = sum(parts) - (n_shares - 1) * shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=2e-5)


def test_engine_counts_experts_on_the_device_and_reads_them_when_scraped():
    cfg = get_config(ARCH, smoke=True)
    model = build_model(cfg)
    reg = MetricsRegistry()
    eng = ServeEngine(model, model.init(RNG), n_slots=3, max_seq=32, metrics=reg)
    reqs = [Request(rid=i, prompt=[5, 6, 7, i], max_new=4) for i in range(3)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    # nothing was read back while serving: the host counters are still 0
    assert reg.counter("serve.experts.layer_steps").value == 0
    c = reg.flat()
    layers = cfg.n_layers - cfg.first_k_dense
    ticks = c["serve.experts.layer_steps"] // layers
    assert ticks == 3 and c["serve.experts.layer_steps"] == ticks * layers
    assert c["serve.experts.routed_pairs"] == ticks * layers * eng.n_slots * cfg.moe_top_k
    assert 0 < c["serve.experts.held_pairs"] <= c["serve.experts.routed_pairs"]
    assert c["serve.experts.held"] == cfg.moe_held == 4
    # the busiest held expert carries at least the mean and at most all
    assert cfg.moe_held * c["serve.experts.peak_pairs"] >= c["serve.experts.held_pairs"]
    assert c["serve.experts.peak_pairs"] <= c["serve.experts.held_pairs"]
    assert reg.flat()["serve.experts.held_pairs"] == c["serve.experts.held_pairs"]  # no double count


def test_engine_counts_only_the_lanes_that_carry_a_request():
    """One request in four slots: the decode program computes all four
    lanes, and counts the pairs of the one that carries the request."""
    cfg = get_config(ARCH, smoke=True)
    model = build_model(cfg)
    reg = MetricsRegistry()
    eng = ServeEngine(model, model.init(RNG), n_slots=4, max_seq=32, metrics=reg)
    eng.submit(Request(rid=0, prompt=[5, 6, 7], max_new=5))
    eng.run_until_drained()
    c = reg.flat()
    layers = cfg.n_layers - cfg.first_k_dense
    ticks = c["serve.experts.layer_steps"] // layers
    assert ticks == 4
    assert c["serve.experts.routed_pairs"] == ticks * layers * 1 * cfg.moe_top_k
    assert c["serve.experts.held_pairs"] <= c["serve.experts.routed_pairs"]
    # unmasked, the same layer counts every lane
    x = jax.random.normal(RNG, (4, 1, cfg.d_model), jnp.bfloat16)
    blk = jax.tree.map(lambda a: a[0], model.init(RNG)["blocks"])["moe"]
    _, every = X.moe_serve(blk, x, cfg.moe_top_k, cfg.moe_router, cfg.moe_route_scale)
    _, one = X.moe_serve(blk, x, cfg.moe_top_k, cfg.moe_router, cfg.moe_route_scale,
                         lanes=jnp.array([False, True, False, False]))
    assert int(every[1]) == 4 * cfg.moe_top_k and int(one[1]) == cfg.moe_top_k
    assert int(one[0]) <= int(every[0]) and int(one[2]) <= int(every[2])
