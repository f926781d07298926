"""Per-architecture smoke tests (deliverable f): every assigned arch, reduced
config, one forward/train step + one prefill/decode cycle on CPU — shapes
asserted, no NaNs.  Also decode-vs-full-forward consistency where exact.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import build_model

RNG = jax.random.PRNGKey(0)


def _batch(cfg, B=2, S=16):
    b = {
        "tokens": jax.random.randint(RNG, (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(jax.random.fold_in(RNG, 1), (B, S), 0, cfg.vocab_size),
    }
    if cfg.frontend == "audio_frames":
        b["frames"] = jax.random.normal(RNG, (B, cfg.encoder_seq, cfg.d_model), jnp.float32)
    if cfg.frontend == "vision_patches":
        b["patches"] = jax.random.normal(RNG, (B, cfg.frontend_tokens, cfg.d_model), jnp.float32)
    return b


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_smoke(arch):
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init(RNG)
    batch = _batch(cfg)

    def loss_fn(p):
        return model.loss(p, batch)[0]

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    assert np.isfinite(float(loss)), f"{arch}: loss not finite"
    # a reasonable starting NLL for random init: ~ln(vocab)
    assert 0.5 * np.log(cfg.vocab_size) < float(loss) < 3 * np.log(cfg.vocab_size) + 1
    gn = sum(float(jnp.sum(jnp.abs(g.astype(jnp.float32)))) for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0, f"{arch}: degenerate grads"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes(arch):
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init(RNG)
    B, S = 2, 16
    batch = _batch(cfg, B, S)
    out = jax.jit(model.forward_logits)(params, batch)
    assert out.logits.shape == (B, S, cfg.vocab_size), arch
    assert not bool(jnp.isnan(out.logits).any()), arch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_smoke(arch):
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init(RNG)
    B, S, MAX = 2, 8, 32
    cache = model.init_cache(B, MAX)
    tokens = jax.random.randint(RNG, (B, S), 0, cfg.vocab_size)
    extra = {k: v for k, v in _batch(cfg, B, S).items() if k in ("frames", "patches")} or None
    logits, cache = jax.jit(model.prefill)(params, tokens, cache, extra)
    assert logits.shape == (B, cfg.vocab_size)
    for _ in range(3):
        tok = jnp.argmax(logits, -1)
        logits, cache = jax.jit(model.decode_step)(params, tok, cache)
        assert not bool(jnp.isnan(logits).any()), arch
    prefix = cfg.frontend_tokens if cfg.family == "vlm" else 0
    assert int(cache["len"]) == S + 3 + prefix


@pytest.mark.parametrize("arch", ["qwen1.5-110b", "jamba-v0.1-52b", "xlstm-1.3b", "whisper-small",
                                  "smollm-360m", "chatglm3-6b", "moonlight-16b-a3b"])
def test_decode_matches_full_forward(arch):
    """Prefill(S) + decode(1) logits == forward over S+1 tokens at position S.

    Exact-cache families only need numerical tolerance; SSM families test the
    recurrent-vs-parallel equivalence — the sharpest correctness check in the
    suite.  For the K/V families the decode step writes its row into the
    carried cache and reads the layer's rows in place (`cache_write`,
    `cached_decode_attention`); smollm-360m (three query heads to a kv head
    in its smoke form) and chatglm3-6b (qkv bias, rotary on half the head)
    are the two configurations the chip benchmark serves.  moonlight-16b-a3b
    decodes in the absorbed form over its latent cache, against the full
    forward's expanded attention, with a leading dense layer and dropless
    experts over a held share of 4 of 8.
    """
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init(RNG)
    B, S = 1, 12
    toks = jax.random.randint(RNG, (B, S + 1), 0, cfg.vocab_size)
    extra = None
    batch = {"tokens": toks, "labels": toks}
    if cfg.frontend == "audio_frames":
        frames = jax.random.normal(RNG, (B, cfg.encoder_seq, cfg.d_model), jnp.float32)
        batch["frames"] = frames
        extra = {"frames": frames}

    full = model.forward_logits(params, batch).logits
    # incremental: prefill S tokens (logits for position S-1), decode token S
    cache = model.init_cache(B, S + 4)
    prefill_logits, cache = model.prefill(params, toks[:, :S], cache, extra)
    err = float(jnp.max(jnp.abs(prefill_logits - full[:, S - 1])))
    assert err < 0.05, f"{arch}: prefill/forward mismatch {err}"
    logits, cache = model.decode_step(params, toks[:, S], cache)
    err = float(jnp.max(jnp.abs(logits - full[:, S])))
    assert err < 0.05, f"{arch}: decode/forward mismatch {err}"
    assert int(cache["len"]) == S + 1
