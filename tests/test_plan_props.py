"""Property-based tests for the §8 uint32 word codec and plan coalescing
(hypothesis via the tests/helpers shim: degrades to seeded example pools
when hypothesis is absent).

Two families:

  * **codec round-trip** — `_encode`/`_decode` are lossless for every
    supported payload dtype (f32/i32/u32/bool and the widened bf16/f16/i8
    sub-word dtypes) over randomized shapes and leading dims, and for the
    64-bit payloads (f64/i64/u64) that split into two words.
  * **coalescing preserves order** — a randomized sequence of recorded ops
    flushed with ``aggregate=True`` resolves every handle to exactly the
    value its own op produced: the fused transfer's segment offsets never
    mix payloads up, whatever the mix of dtypes, shapes, and signatures.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.core import plan as plan_mod
from repro.core.plan import RmaPlan
from repro.core.rma import OpCounter

from .helpers import given, settings, st

DTYPES_32 = ["float32", "int32", "uint32", "bool", "bfloat16", "float16", "int8"]
DTYPES_64 = ["float64", "int64", "uint64"]


def _sample(rng: np.random.RandomState, dtype_name: str, shape):
    dt = jnp.dtype(dtype_name)
    if dt == jnp.dtype(jnp.bool_):
        return jnp.asarray(rng.rand(*shape) > 0.5)
    if dt in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16)):
        # exactly representable values: the widen-cast must be value-exact
        return jnp.asarray(rng.randint(-128, 128, size=shape), dt)
    if dt.kind in "iu":
        info = jnp.iinfo(dt)
        lo = max(int(info.min), -(2 ** 62))
        hi = min(int(info.max), 2 ** 62)
        return jnp.asarray(rng.randint(lo, hi, size=shape).astype(dt))
    return jnp.asarray(rng.randn(*shape), dt)


class TestCodecRoundTrip:
    @settings(max_examples=40, deadline=None)
    @given(st.integers(0, 10_000), st.sampled_from(DTYPES_32),
           st.integers(1, 7), st.integers(1, 9), st.integers(0, 2))
    def test_roundtrip_randomized(self, seed, dtype_name, d0, d1, lead):
        rng = np.random.RandomState(seed)
        x = _sample(rng, dtype_name, (d0, d1))
        w = plan_mod._encode(x, lead)
        assert w.dtype == jnp.uint32
        assert w.shape[:lead] == x.shape[:lead]
        y = plan_mod._decode(w, x.shape, x.dtype)
        assert y.dtype == x.dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    @settings(max_examples=20, deadline=None)
    @given(st.integers(0, 10_000), st.sampled_from(DTYPES_64))
    def test_roundtrip_64bit_payloads(self, seed, dtype_name):
        """64-bit payloads split into two words losslessly (x64 scope)."""
        with jax.enable_x64(True):
            rng = np.random.RandomState(seed)
            x = _sample(rng, dtype_name, (3, 4))
            assert jnp.dtype(x.dtype).itemsize == 8
            assert plan_mod._words_per_elt(x.dtype) == 2
            w = plan_mod._encode(x, 1)
            assert w.shape == (3, 8)               # two words per element
            y = plan_mod._decode(w, x.shape, x.dtype)
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_widen_covers_exactly_the_supported_set(self):
        for name in DTYPES_32 + DTYPES_64:
            plan_mod._widen(jnp.dtype(name))
        with pytest.raises(plan_mod.PlanError):
            plan_mod._widen(np.complex128)       # 16-byte payloads: unsupported


# ---------------------------------------------------------------- coalescing
def _mesh():
    return jax.make_mesh((1,), ("w",))


OP_KINDS = ("put", "acc", "a2a", "gather")


def _random_program(seed: int, k: int):
    """[(op_kind, dtype_name, width)] — the op sequence under test."""
    rng = np.random.RandomState(seed)
    return [
        (OP_KINDS[rng.randint(len(OP_KINDS))],
         DTYPES_32[rng.randint(len(DTYPES_32))],
         int(rng.randint(1, 5)))
        for _ in range(k)
    ]


class TestCoalescingPreservesOrder:
    @settings(max_examples=12, deadline=None)
    @given(st.integers(0, 10_000), st.integers(2, 8))
    def test_randomized_op_sequence(self, seed, k):
        """Every handle of a fused flush resolves to its own op's value."""
        program = _random_program(seed, k)
        rng = np.random.RandomState(seed + 1)
        payloads = [_sample(rng, dt, (1, w)) for (_, dt, w) in program]

        def body(_token):
            pl = RmaPlan("w")
            handles = []
            for (op, _dt, _w), x in zip(program, payloads):
                if op == "put":
                    handles.append((pl.put_shift(x, 0), x))
                elif op == "acc":
                    acc = jnp.zeros_like(x)
                    handles.append((pl.accumulate_shift(x, acc, 0), x))
                elif op == "a2a":
                    handles.append((pl.put_all_to_all(x), x))
                else:
                    handles.append((pl.all_gather(x), x[None]))
                    # gather result gains the leading p=1 dim
            stats = pl.flush(aggregate=True)
            outs = [h.result().astype(jnp.float32).reshape(-1)
                    for h, _ in handles]
            return jnp.concatenate(outs)[None], jnp.int32(stats.coalesced)[None]

        f = jax.jit(shard_map(body, mesh=_mesh(), in_specs=P("w"),
                              out_specs=(P("w", None), P("w")),
                              check_vma=False))
        with OpCounter() as c:
            out, coalesced = f(jnp.zeros((1,), jnp.float32))
        out = np.asarray(out)[0]

        # order preservation: each segment decodes back to its own payload
        expected = []
        for (op, _dt, _w), x in zip(program, payloads):
            want = x[None] if op == "gather" else x
            expected.append(np.asarray(want.astype(jnp.float32)).reshape(-1))
        np.testing.assert_array_equal(out, np.concatenate(expected))

        # aggregation accounting: raw == k, one wire transfer per signature
        n_sigs = len({op if op != "acc" else "put" for (op, _, _) in program})
        assert c.raw_msgs == k
        assert c.coalesced_msgs == int(np.asarray(coalesced)[0]) <= n_sigs

    def test_interleaved_signatures_keep_per_signature_fifo(self):
        """Ops alternating between two signatures: within each fused group
        the recorded order is the decode order."""
        xs = [jnp.full((1, 2), float(i), jnp.float32) for i in range(6)]

        def body(_token):
            pl = RmaPlan("w")
            hs = []
            for i, x in enumerate(xs):
                hs.append(pl.put_shift(x, 0) if i % 2 == 0
                          else pl.put_all_to_all(x))
            pl.flush(aggregate=True)
            return jnp.stack([h.result() for h in hs])[None]

        f = jax.jit(shard_map(body, mesh=_mesh(), in_specs=P("w"),
                              out_specs=P("w", None, None, None),
                              check_vma=False))
        with OpCounter() as c:
            out = np.asarray(f(jnp.zeros((1,), jnp.float32)))[0]
        for i in range(6):
            np.testing.assert_array_equal(out[i], np.asarray(xs[i]))
        assert c.raw_msgs == 6 and c.coalesced_msgs == 2
