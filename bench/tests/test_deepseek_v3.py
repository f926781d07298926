"""The deepseek_v3 family (Moonlight-16B-A3B): its weights, its reference
against the program, its work counts by hand, its width check, the probe
that every call site reaches it, and the expert counters' readers."""

import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec
from bench.family import deepseek_v3 as fam
from bench.family import root_key

from helpers import DATA, recorded_run, run_smoke

SMOKE = "smoke-deepseek-v3"
# The program's logits against float32, with the family's bf16 weights upcast
# to f32 so that routing sees no rounding that could flip a near tie: the
# latent cache still rounds rows to bf16, which moves these logits (up to
# about 0.5) by at most about 0.011 over 20 seeds; a wrong row, position,
# mask or expert moves them by 0.1 or more.
LOGIT_TOL = 0.02
TINY = {"n_layers": 3, "first_k_dense": 1, "d_model": 4, "n_heads": 2,
        "kv_lora_rank": 2, "qk_nope_head_dim": 2, "qk_rope_head_dim": 2,
        "v_head_dim": 2, "d_ff": 8, "n_experts": 4, "experts_held": 2, "top_k": 2,
        "moe_d_ff": 3, "shared_d_ff": 6, "vocab": 10, "tie_embeddings": False}


def config():
    return spec.load_json(DATA / f"{SMOKE}.json")


def cell():
    base = spec.load_cell("smollm-360m.decode")
    return spec.Cell("smoke.decode", 1, config(), spec.load_json(DATA / "traffic-smoke.json"),
                     base.end_to_end, base.per_layer)


def test_one_layer_alone_equals_the_stacked_layer():
    cfg = config()
    dims = cfg["dims"]
    root = root_key(2**33 + 1)
    stacked = fam.program_params(root, dims)
    for block, layer, dense in (("dense_blocks", 0, True), ("blocks", 2, False)):
        alone = fam._program_layer(fam.make_layer(root, dims, layer, dense))
        row = layer - (0 if dense else dims["first_k_dense"])
        got = jax.tree.map(lambda a, row=row: a[row], stacked[block])
        assert jax.tree.all(jax.tree.map(lambda a, b: bool((a == b).all()), alone, got))
    assert stacked["blocks"]["moe"]["experts"]["w_in"].shape[:2] == (2, dims["experts_held"])
    assert stacked["blocks"]["moe"]["router"].dtype == jnp.float32


def test_held_experts_do_not_depend_on_the_share_held():
    dims = config()["dims"]
    root = root_key(7)
    some = fam.make_layer(root, dims, 1, False)["e_in"]
    more = fam.make_layer(root, dict(dims, experts_held=dims["n_experts"]), 1, False)["e_in"]
    assert np.array_equal(np.asarray(some), np.asarray(more[:dims["experts_held"]]))


def test_reference_matches_prefill_then_decode():
    from repro.configs import get_config
    from repro.models import build_model

    cfg = config()
    dims, ref = cfg["dims"], spec.reference_module(cfg)
    model = build_model(get_config(cfg["arch"], smoke=True))
    root = root_key(12)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), fam.program_params(root, dims))
    plen, steps = 9, 6
    prompt = np.random.default_rng(1).integers(0, dims["vocab"], plen).astype(np.int32)
    cache = model.init_cache(1, 32)
    logits, cache = model.prefill(params, jnp.asarray(prompt)[None], cache)
    got, toks = [np.asarray(logits[0], np.float32)], []
    for _ in range(steps):
        toks.append(int(jnp.argmax(logits[0])))
        logits, cache = model.decode_step(params, jnp.asarray([toks[-1]], jnp.int32), cache)
        got.append(np.asarray(logits[0], np.float32))
    seq = np.concatenate([prompt, toks]).astype(np.int32)[None]
    idx = np.arange(plen - 1, plen + steps)[None]
    want = np.asarray(ref.logits_at(fam, dims, root, seq, idx))[0]
    assert np.abs(want - np.stack(got)).max() < LOGIT_TOL
    assert np.abs(want).max() > 10 * LOGIT_TOL


def test_fp8_control_departs_from_float32():
    cfg = config()
    dims, ref = cfg["dims"], spec.reference_module(cfg)
    root = root_key(5)
    seq = np.arange(24, dtype=np.int32)[None] % dims["vocab"]
    idx = np.arange(24, dtype=np.int32)[None]
    want = np.asarray(ref.logits_at(fam, dims, root, seq, idx))
    ctl = np.asarray(ref.logits_at(fam, dims, root, seq, idx, quant="fp8"))
    assert np.abs(want - ctl).max() > 2 * LOGIT_TOL


def test_padding_after_a_sequence_changes_none_of_its_logits():
    cfg = config()
    dims, ref = cfg["dims"], spec.reference_module(cfg)
    root = root_key(3)
    seq = (np.arange(40, dtype=np.int32) * 7 % dims["vocab"])[None]
    idx = np.arange(10, dtype=np.int32)[None]
    short = np.asarray(ref.logits_at(fam, dims, root, seq[:, :10], idx))
    padded = np.asarray(ref.logits_at(fam, dims, root, seq, idx))
    np.testing.assert_allclose(short, padded, atol=1e-5)


def test_counts_by_hand():
    c = fam.param_counts(TINY)
    # q 4*2*4, latent 4*4, W_UK 2*2*2, W_UV 2*2*2, out 2*2*4
    assert c["attn"] == 32 + 16 + 8 + 8 + 16
    assert c["norms"] == 2 * 4 + 2
    assert c["expert"] == 3 * 4 * 3 and c["dense_mlp"] == 3 * 4 * 8
    assert c["moe_rest"] == 4 * 4 + 4 + 3 * 4 * 6
    # embed + head, 3 layers of attention, 1 dense MLP, 2 expert layers of 2 held
    assert fam.n_params(TINY) == 80 + 3 * (80 + 10) + 96 + 2 * (2 * 36 + 92)
    # one token chooses 2 of 4: it hits a held expert with chance 1 - (1/2)^1
    assert fam.held_experts_hit(TINY, 1) == pytest.approx(2 * 0.5)
    assert fam.held_experts_hit(TINY, 3) == pytest.approx(2 * (1 - 0.5 ** 3))
    fixed = 3 * 90 + 96 + 2 * 92 + 4 + 40
    assert fam.weight_bytes_per_pass(TINY, 1) == pytest.approx(2 * (fixed + 2 * 1.0 * 36))


def test_decode_and_prefill_work_by_hand():
    fixed = 3 * 90 + 96 + 2 * 92 + 4 + 40
    # per token: attention 3 * 80, dense MLP 96, per expert layer the router
    # 16 and shared experts 72, and 2 * 2 / 4 = 1 expected held pair of 36
    token = 2 * (3 * 80 + 96 + 2 * (16 + 72 + 1 * 36))
    row = 3 * (2 + 2) * 2                        # 3 layers of lora + rope, bf16
    per_key = 2 * 3 * 2 * (2 * 2 + 2)            # scores and values, 2 heads
    f, b = fam.decode_work(TINY, [3, 5])
    assert f == pytest.approx(2 * (token + 2 * 4 * 10) + per_key * (4 + 6))
    hit = 2 * (1 - 0.5 ** 2)
    assert b == pytest.approx(2 * (fixed + 2 * hit * 36) + 2 * 4 * 2 + (10 + 2) * row + 2 * 10 * 2)
    f, b = fam.prefill_work(TINY, 3)
    assert f == pytest.approx(3 * token + per_key * 6 + 2 * 4 * 10)
    hit = 2 * (1 - 0.5 ** 3)
    assert b == pytest.approx(2 * (fixed + 2 * hit * 36) + 3 * 4 * 2 + 3 * row + 10 * 2)


def test_the_cut_holds_5_164_billion_parameters():
    from repro.configs import get_config

    cfg = spec.load_json(spec.BENCH / "configs" / "moonlight-16b-a3b.json")
    assert fam.n_params(cfg["dims"]) == 5_163_969_664
    # holding all 64, it is the published model's count
    assert fam.n_params(dict(cfg["dims"], experts_held=64)) == \
        get_config("moonlight-16b-a3b").n_params() == 15_960_108_160
    # a decode tick at 128 active tokens hits all but 5e-5 of the 16 held experts
    assert fam.held_experts_hit(cfg["dims"], 128) == pytest.approx(16, abs=1e-4)
    assert fam.weight_bytes_per_pass(cfg["dims"], 128) == pytest.approx(9.657e9, rel=1e-3)


@pytest.mark.parametrize("key", ["kv_lora_rank", "experts_held", "moe_d_ff"])
def test_a_wrong_width_is_refused(key):
    from repro.configs import get_config

    cfg = spec.load_json(spec.BENCH / "configs" / "moonlight-16b-a3b.json")
    # a held share may be any share of the experts, and no more than all
    wrong = cfg["dims"]["n_experts"] + 1 if key == "experts_held" else cfg["dims"][key] + 1
    bad = dict(cfg, dims=dict(cfg["dims"], **{key: wrong}))
    with pytest.raises(ValueError, match=key):
        fam.check_widths(bad, get_config(cfg["arch"]))
    with pytest.raises(ValueError, match="deepseek_v3"):
        fam.check_widths(cfg, get_config("smollm-360m"))


def test_layout_check_holds_the_share_to_what_the_program_init_holds():
    """The catalog's entry builds all 64 experts and the cell serves 16 of
    them: the layout is checked whole, and a share larger than the
    program's init holds is refused."""
    from repro.configs import get_config
    from repro.models import build_model

    dims = spec.load_json(DATA / f"{SMOKE}.json")["dims"]
    shapes = build_model(get_config("moonlight-16b-a3b", smoke=True)).init_shapes()
    fam.check_layout(shapes, dims)                            # 4 held of 4 built
    fam.check_layout(shapes, dict(dims, experts_held=2))      # a smaller share
    with pytest.raises(ValueError, match="experts_held"):
        fam.check_layout(shapes, dict(dims, experts_held=5))
    with pytest.raises(ValueError, match="layout"):
        fam.check_layout(shapes, dict(dims, moe_d_ff=dims["moe_d_ff"] + 1))


@pytest.fixture
def probe(monkeypatch):
    mod = spec._load_module(DATA / "probe_deepseek_v3.py", "bench.family.probe_deepseek_v3")
    monkeypatch.setitem(sys.modules, "bench.family.probe_deepseek_v3", mod)
    return mod


def test_a_run_reaches_every_family_function_through_the_config(probe):
    c = cell()
    cfg = dict(c.config, family="probe_deepseek_v3")
    res = run_smoke(dataclasses.replace(c, config=cfg))
    assert res["failed"] == 0 and res["checks"]["max_gap"]["value"] is not None
    reached = set(probe.CALLED)
    assert reached == set(probe.INTERFACE) - {"decode_work", "prefill_work"}
    run = recorded_run(cfg)
    assert run.family is probe
    for name, needs in {"decode_roofline": {"decode_work"},
                        "prefill_roofline": {"prefill_work"},
                        "mfu": {"decode_work", "prefill_work"}}.items():
        probe.CALLED.clear()
        value = spec.metric_reader(name)(run)
        assert value is not None and value > 0, name
        assert probe.CALLED == needs, name
        reached |= probe.CALLED
    assert reached == set(probe.INTERFACE)


READERS = ("expert_tokens_per_expert", "expert_load_peak")


def _registry():
    from repro.obs.metrics import MetricsRegistry

    return MetricsRegistry()


@pytest.mark.parametrize("name", READERS)
def test_empty_registry_reads_nothing(name):
    assert spec.metric_reader(name)(None, _registry()) is None


def test_filled_registry():
    reg = _registry()
    reg.gauge("serve.experts.held").set(16)
    reg.counter("serve.experts.layer_steps").inc(26 * 10)
    reg.counter("serve.experts.held_pairs").inc(26 * 10 * 192)
    reg.counter("serve.experts.peak_pairs").inc(26 * 10 * 18)
    reg.counter("serve.experts.routed_pairs").inc(26 * 10 * 768)
    assert spec.metric_reader("expert_tokens_per_expert")(None, reg) == pytest.approx(12.0)
    assert spec.metric_reader("expert_load_peak")(None, reg) == pytest.approx(1.5)


def test_readers_read_an_engine_run():
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serve.engine import Request, ServeEngine

    arch = get_config("moonlight-16b-a3b", smoke=True)
    model = build_model(arch)
    reg = _registry()
    eng = ServeEngine(model, model.init(jax.random.PRNGKey(0)), n_slots=4, max_seq=32,
                      metrics=reg)
    for i in range(4):
        eng.submit(Request(rid=i, prompt=[1, 2, 3, i], max_new=5))
    eng.run_until_drained()
    per_expert = spec.metric_reader("expert_tokens_per_expert")(None, reg)
    c = reg.flat()
    # 4 decode ticks of 4 lanes x top-2 over 2 expert layers, 4 of 8 held
    assert c["serve.experts.routed_pairs"] == 4 * 4 * 2 * 2
    assert per_expert == pytest.approx(c["serve.experts.held_pairs"] / (4 * 2 * 4))
    assert spec.metric_reader("expert_load_peak")(None, reg) >= 1.0

