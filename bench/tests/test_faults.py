"""The check catches a broken timed path: each fault a serving cell can have
is planted under the engine, the rest of a run goes on as usual, and
`correct` must come out false.  (A mean over half a batch and an exchange
between chips are faults of training and of cells on several chips; a
one-chip serving cell has neither.)"""

import jax
import jax.numpy as jnp
import pytest

from repro.serve import engine as engine_mod

from helpers import SMOKE_CONFIGS, run_smoke


def _patch_decode(monkeypatch, wrap):
    orig = engine_mod.ServeEngine.__init__

    def init(self, *a, **k):
        orig(self, *a, **k)
        self._decode = wrap(self._decode)

    monkeypatch.setattr(engine_mod.ServeEngine, "__init__", init)


def state_unchanged(decode):
    """The decode step returns the cache as it was given: no K/V row lands.
    The engine donates the cache to its decode, so the fault keeps a copy
    taken before the call and returns that."""
    def f(params, tokens, cache):
        kept = jax.tree.map(jnp.copy, cache)
        logits, _ = decode(params, tokens, cache)
        return logits, kept
    return f


def token_altered(decode):
    """Each decoded token is replaced, where it is produced, by its
    neighbour in the vocabulary: a sampler off by one."""
    def f(params, tokens, cache):
        logits, new = decode(params, tokens, cache)
        rows = jnp.arange(logits.shape[0])
        nxt = (jnp.argmax(logits, -1) + 1) % logits.shape[-1]
        return logits.at[rows, nxt].add(1e3), new
    return f


@pytest.mark.parametrize("name", SMOKE_CONFIGS)
@pytest.mark.parametrize("fault", [state_unchanged, token_altered])
def test_fault_is_caught(monkeypatch, name, fault):
    _patch_decode(monkeypatch, fault)
    res = run_smoke(name)
    assert res["correct"] is False
    gap = res["checks"]["max_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("name", SMOKE_CONFIGS)
def test_sound_run_is_correct(name):
    assert run_smoke(name)["correct"] is True
