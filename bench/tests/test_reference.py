"""The float32 reference against the program, at smoke widths on the CPU."""

import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, spec, weights
from bench.reference import dense

from helpers import DATA, SMOKE_CONFIGS

# Served logits in bf16 against float32: at smoke width the logits stay
# below about 0.5, where one bf16 step is 2**-9 and the K/V cache rounds to
# bf16; a wrong position, row or mask moves them by 0.05 or more.
LOGIT_TOL = 0.02


def config(name):
    return spec.load_json(DATA / f"{name}.json")


@pytest.mark.parametrize("name", SMOKE_CONFIGS)
def test_one_layer_alone_equals_the_stacked_layer(name):
    dims = config(name)["dims"]
    root = weights.root_key(2**33 + 1)
    stacked = weights.program_params(root, dims)
    alone = weights.make_layer(root, dims, 1)
    assert np.array_equal(np.asarray(alone["wq"]),
                          np.asarray(stacked["blocks"]["attn"]["wq"][1]))
    assert np.array_equal(np.asarray(alone["w_out"]),
                          np.asarray(stacked["blocks"]["mlp"]["w_out"][1]))
    assert stacked["tok"]["embed"].dtype == jnp.bfloat16


@pytest.mark.parametrize("name", SMOKE_CONFIGS)
def test_reference_matches_prefill_then_decode(name):
    from repro.configs import get_config
    from repro.models import build_model

    cfg = config(name)
    dims = cfg["dims"]
    model = build_model(get_config(cfg["arch"], smoke=True))
    root = weights.root_key(11)
    params = weights.program_params(root, dims)
    plen, steps, max_seq = 9, 6, 32
    prompt = np.random.default_rng(0).integers(0, dims["vocab"], plen).astype(np.int32)
    cache = model.init_cache(1, max_seq)
    logits, cache = model.prefill(params, jnp.asarray(prompt)[None], cache)
    got, toks = [np.asarray(logits[0], np.float32)], []
    for _ in range(steps):
        toks.append(int(jnp.argmax(logits[0])))
        logits, cache = model.decode_step(params, jnp.asarray([toks[-1]], jnp.int32), cache)
        got.append(np.asarray(logits[0], np.float32))
    seq = np.concatenate([prompt, toks]).astype(np.int32)[None]
    idx = np.arange(plen - 1, plen + steps)[None]
    ref = np.asarray(dense.logits_at(dims, root, seq, idx))[0]
    err = np.abs(ref - np.stack(got)).max()
    assert err < LOGIT_TOL, err
    assert np.abs(ref).max() > 10 * LOGIT_TOL     # the logits are not all ~0
    # the served (greedy) tokens sit at the reference's best, within rounding
    assert check.gaps(ref[None, :-1], np.asarray(toks)[None]).max() < LOGIT_TOL


def test_fp8_control_departs_from_float32():
    cfg = config(SMOKE_CONFIGS[0])
    dims = cfg["dims"]
    root = weights.root_key(5)
    seq = np.arange(24, dtype=np.int32)[None] % dims["vocab"]
    idx = np.arange(24, dtype=np.int32)[None]
    ref = np.asarray(dense.logits_at(dims, root, seq, idx))
    ctl = np.asarray(dense.logits_at(dims, root, seq, idx, quant="fp8"))
    assert np.abs(ref - ctl).max() > 2 * LOGIT_TOL


def test_padding_after_a_sequence_changes_none_of_its_logits():
    dims = config(SMOKE_CONFIGS[1])["dims"]
    root = weights.root_key(3)
    seq = (np.arange(40, dtype=np.int32) * 7 % dims["vocab"])[None]
    idx = np.arange(10, dtype=np.int32)[None]
    short = np.asarray(dense.logits_at(dims, root, seq[:, :10], idx))
    padded = np.asarray(dense.logits_at(dims, root, seq, idx))
    np.testing.assert_allclose(short, padded, atol=1e-5)
