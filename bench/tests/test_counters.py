"""The readers of the program's own counters: nothing on an empty registry,
the counted mean or sum on a filled one, and what a stub-model engine
counted when it is handed the registry."""

import pytest

from bench import spec

READERS = ("decode_host_gap_ms", "prefill_host_gap_ms", "engine_compile_s")


def _registry():
    from repro.obs.metrics import MetricsRegistry

    return MetricsRegistry()


@pytest.mark.parametrize("name", READERS)
def test_empty_registry_reads_nothing(name):
    assert spec.metric_reader(name)(None, _registry()) is None


def test_filled_registry():
    reg = _registry()
    reg.counter("serve.decode.host_gaps").inc(4)
    reg.counter("serve.decode.host_gap_s").inc(0.006)
    reg.counter("serve.prefill.host_gaps").inc(3)
    reg.counter("serve.prefill.host_gap_s").inc(0.012)
    reg.counter("serve.compiles", program="decode").inc(1)
    reg.counter("serve.compile_s", program="decode").inc(0.5)
    reg.counter("serve.compiles", program="prefill").inc(3)
    reg.counter("serve.compile_s", program="prefill").inc(1.25)
    read = {n: spec.metric_reader(n) for n in READERS}
    assert read["decode_host_gap_ms"](None, reg) == pytest.approx(1.5)
    assert read["prefill_host_gap_ms"](None, reg) == pytest.approx(4.0)
    assert read["engine_compile_s"](None, reg) == pytest.approx(1.75)


class _Echo:
    """A model whose next token is the last one plus one."""

    vocab = 17

    def init_cache(self, b, max_seq):
        import jax.numpy as jnp

        return {"k": jnp.zeros((b, max_seq, 4)), "len": jnp.zeros((), jnp.int32)}

    def prefill(self, params, tokens, cache, _):
        import jax

        return jax.nn.one_hot((tokens[:, -1] + 1) % self.vocab, self.vocab), cache

    def decode_step(self, params, tokens, cache):
        import jax

        return jax.nn.one_hot((tokens + 1) % self.vocab, self.vocab), cache


def test_readers_read_an_engine_run():
    from repro.serve.engine import Request, ServeEngine

    reg = _registry()
    eng = ServeEngine(_Echo(), {}, n_slots=2, max_seq=32, metrics=reg)
    for i in range(3):
        eng.submit(Request(rid=i, prompt=[1, 2], max_new=4))
    eng.run_until_drained()
    c = reg.flat()
    assert spec.metric_reader("decode_host_gap_ms")(None, reg) == pytest.approx(
        1e3 * c["serve.decode.host_gap_s"] / c["serve.decode.host_gaps"])
    assert spec.metric_reader("prefill_host_gap_ms")(None, reg) > 0
    assert spec.metric_reader("engine_compile_s")(None, reg) == pytest.approx(
        c["serve.compile_s{program=decode}"] + c["serve.compile_s{program=prefill}"])
