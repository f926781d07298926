"""The float32 reference against the program, at smoke widths on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, spec
from bench.family import root_key
from bench.reference import dense

from helpers import DATA, SMOKE_CONFIGS

# Served logits in bf16 against float32: at smoke width the logits stay
# below about 0.5, where one bf16 step is 2**-9 and the K/V cache rounds to
# bf16; a wrong position, row or mask moves them by 0.05 or more.
LOGIT_TOL = 0.02


def config(name):
    return spec.load_json(DATA / f"{name}.json")


def family(cfg):
    return spec.family_module(cfg)


@pytest.mark.parametrize("name", SMOKE_CONFIGS)
def test_one_layer_alone_equals_the_stacked_layer(name):
    """Each leaf that `make_layer` makes alone is, bit for bit, layer 1 of
    the stacked leaf whose path names it."""
    cfg = config(name)
    fam, dims = family(cfg), cfg["dims"]
    root = root_key(2**33 + 1)
    stacked = fam.program_params(root, dims)
    alone = fam.make_layer(root, dims, 1)
    paths = {tuple(getattr(k, "key", None) for k in p): x
             for p, x in jax.tree_util.tree_flatten_with_path(stacked)[0]}
    for leaf, x in alone.items():
        found = [v for p, v in paths.items() if p[0] == "blocks" and leaf in p]
        assert len(found) == 1, leaf
        assert np.array_equal(np.asarray(x), np.asarray(found[0][1])), leaf
    assert stacked["tok"]["embed"].dtype == jnp.bfloat16


@pytest.mark.parametrize("name", SMOKE_CONFIGS)
def test_reference_matches_prefill_then_decode(name):
    from repro.configs import get_config
    from repro.models import build_model

    cfg = config(name)
    fam, dims = family(cfg), cfg["dims"]
    model = build_model(get_config(cfg["arch"], smoke=True))
    root = root_key(11)
    params = fam.program_params(root, dims)
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
    ref = np.asarray(dense.logits_at(fam, dims, root, seq, idx))[0]
    err = np.abs(ref - np.stack(got)).max()
    assert err < LOGIT_TOL, err
    assert np.abs(ref).max() > 10 * LOGIT_TOL     # the logits are not all ~0
    # the served (greedy) tokens sit at the reference's best, within rounding
    assert check.gaps(ref[None, :-1], np.asarray(toks)[None]).max() < LOGIT_TOL


def test_fp8_control_departs_from_float32():
    cfg = config(SMOKE_CONFIGS[0])
    fam, dims = family(cfg), cfg["dims"]
    root = root_key(5)
    seq = np.arange(24, dtype=np.int32)[None] % dims["vocab"]
    idx = np.arange(24, dtype=np.int32)[None]
    ref = np.asarray(dense.logits_at(fam, dims, root, seq, idx))
    ctl = np.asarray(dense.logits_at(fam, dims, root, seq, idx, quant="fp8"))
    assert np.abs(ref - ctl).max() > 2 * LOGIT_TOL


def test_padding_after_a_sequence_changes_none_of_its_logits():
    cfg = config(SMOKE_CONFIGS[1])
    fam, dims = family(cfg), cfg["dims"]
    root = root_key(3)
    seq = (np.arange(40, dtype=np.int32) * 7 % dims["vocab"])[None]
    idx = np.arange(10, dtype=np.int32)[None]
    short = np.asarray(dense.logits_at(fam, dims, root, seq[:, :10], idx))
    padded = np.asarray(dense.logits_at(fam, dims, root, seq, idx))
    np.testing.assert_allclose(short, padded, atol=1e-5)
