"""Deferred-substrate tests (DESIGN.md §8): plan recording under all three
epoch families, op coalescing with raw-vs-coalesced accounting, the
aggregation-crossover model, and the sync-ledger flush accounting."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.core import plan as plan_mod
from repro.core import rma
from repro.core.epoch import SyncStats, flush, flush_local
from repro.core.perfmodel import DEFAULT_MODEL
from repro.core.plan import AccessEpoch, PlanError, RmaPlan
from repro.core.rma import OpCounter

K = 4  # ops per epoch in the recording tests


def _mesh():
    return jax.make_mesh((1,), ("w",))


def _sm(fn, in_specs, out_specs):
    return jax.jit(
        shard_map(fn, mesh=_mesh(), in_specs=in_specs, out_specs=out_specs,
                  check_vma=False)
    )


# ------------------------------------------------------------ plan recording
class TestPlanRecording:
    def test_k_same_perm_puts_flush_as_one_transfer(self):
        """The acceptance property: k same-permutation puts -> raw=k,
        coalesced=1, attributed to `puts` (not one fused ppermute-as-put)."""
        x = jnp.arange(3, dtype=jnp.float32)[None]

        def body(v):
            pl = RmaPlan("w")
            hs = [pl.put_shift(v[0] + i, 1) for i in range(K)]
            pl.flush(aggregate=True)
            return jnp.stack([h.result() for h in hs])[None]

        f = _sm(body, P("w", None), P("w", None, None))
        with OpCounter() as c:
            out = np.asarray(f(x))
        for i in range(K):
            np.testing.assert_allclose(out[0, i], np.asarray(x)[0] + i)
        assert c.raw_msgs == K and c.coalesced_msgs == 1
        assert c.puts == K  # attributed to the originating kind
        assert c.aggregation_factor == K

    def test_model_guided_aggregation_packs_small_messages(self):
        x = jnp.arange(2, dtype=jnp.float32)[None]

        def body(v):
            pl = RmaPlan("w")
            hs = [pl.put_shift(v[0], 1) for _ in range(8)]
            st = pl.flush()  # aggregate=None -> model decides; 8B msgs pack
            assert st.packed_groups == 1 and st.coalesced == 1
            return hs[0].result()[None]

        f = _sm(body, P("w", None), P("w", None))
        with OpCounter() as c:
            f(x)
        assert c.coalesced_msgs == 1 and c.raw_msgs == 8

    def test_distinct_signatures_stay_separate_transfers(self):
        # a shift put and an all-gather cannot share a wire transfer (their
        # collective signatures differ); multi-device distinct-permutation
        # coverage lives in tests/subtests/plan_sub.py
        x = jnp.arange(3, dtype=jnp.float32)[None]

        def body(v):
            pl = RmaPlan("w")
            h1 = pl.put_shift(v[0], 1)
            h2 = pl.all_gather(v[0])
            st = pl.flush(aggregate=True)
            assert st.groups == 2 and st.coalesced == 2
            return (h1.result() + h2.result()[0])[None]

        f = _sm(body, P("w", None), P("w", None))
        with OpCounter() as c:
            f(x)
        assert c.raw_msgs == 2 and c.coalesced_msgs == 2
        assert c.puts == 1 and c.gets == 1

    def test_fetch_and_op_records_and_resolves(self):
        def body(v):
            pl = RmaPlan("w")
            h = pl.fetch_and_op(v[0], jnp.float32(4.0))
            pl.flush()
            old, new = h.result()
            return jnp.stack([old, new])[None]

        f = _sm(body, P("w"), P("w", None))
        with OpCounter() as c:
            out = np.asarray(f(jnp.asarray([3.0])))
        assert out[0, 0] == 4.0 and out[0, 1] == 7.0
        assert c.accs == 1

    def test_double_flush_and_late_record_raise(self):
        def body(v):
            pl = RmaPlan("w")
            pl.put_shift(v[0], 1)
            pl.flush()
            with pytest.raises(PlanError):
                pl.flush()
            with pytest.raises(PlanError):
                pl.put_shift(v[0], 1)
            return v

        f = _sm(body, P("w", None), P("w", None))
        f(jnp.zeros((1, 2), jnp.float32))

    def test_unresolved_handle_raises(self):
        def body(v):
            pl = RmaPlan("w")
            h = pl.put_shift(v[0], 1)
            with pytest.raises(PlanError):
                h.result()
            pl.flush()
            return h.result()[None]

        f = _sm(body, P("w", None), P("w", None))
        f(jnp.zeros((1, 2), jnp.float32))

    def test_eager_wrappers_count_one_to_one(self):
        """Backward compat: eager rma ops are single-op plans (raw == wire)."""
        f = _sm(lambda v: rma.put_shift(v, 1, "w"), P("w", None), P("w", None))
        with OpCounter() as c:
            f(jnp.zeros((1, 2), jnp.float32))
        assert c.puts == 1 and c.raw_msgs == 1 and c.coalesced_msgs == 1


# ------------------------------------------------------------- epoch familes
class TestAccessEpochFamilies:
    @pytest.mark.parametrize("family,kwargs", [
        ("fence", {"p": 1}),
        ("pscw", {"group": [0]}),
        ("lock", {}),
    ])
    def test_plan_recording_under_each_family(self, family, kwargs):
        x = jnp.arange(3, dtype=jnp.float32)[None]
        eps = {}

        def body(v):
            ep = AccessEpoch("w", family=family, **kwargs)
            t = ep.open(v)
            hs = [ep.put_shift(t[0] + i, 1) for i in range(K)]
            t = ep.close(t, aggregate=True)
            eps["ep"] = ep
            return t + jnp.stack([h.result() for h in hs]).sum(0)[None]

        f = _sm(body, P("w", None), P("w", None))
        with OpCounter() as c:
            f(x)
        ep = eps["ep"]
        # the epoch counted both raw and coalesced messages
        assert ep.sync.stats.raw_msgs == K
        assert ep.sync.stats.coalesced_msgs == 1
        assert ep.plan_stats.aggregation_factor == K
        assert c.raw_msgs >= K and c.coalesced_msgs >= 1
        if family == "pscw":
            assert ep.sync.stats.post_msgs == 1  # k=1 access group
        if family == "fence":
            assert ep.sync.stats.barrier_stages >= 1

    def test_fence_family_requires_p(self):
        with pytest.raises(PlanError):
            AccessEpoch("w", family="fence")

    def test_epoch_begin_plan_flushes_at_close(self):
        """The rewired epoch classes are plan scopes themselves."""
        from repro.core.epoch import FenceEpoch

        x = jnp.arange(3, dtype=jnp.float32)[None]
        stats = {}

        def body(v):
            ep = FenceEpoch("w", p=1)
            t = ep.open(v)
            pl = ep.begin_plan()
            hs = [pl.put_shift(t[0], 1) for _ in range(3)]
            t = ep.close(t)  # flushes the pending plan
            stats["s"] = ep.stats
            return t + jnp.stack([h.result() for h in hs]).sum(0)[None]

        f = _sm(body, P("w", None), P("w", None))
        f(x)
        assert stats["s"].raw_msgs == 3 and stats["s"].coalesced_msgs == 1


# ----------------------------------------------------------- sync accounting
class TestSyncLedger:
    def test_flush_records_into_active_stats(self):
        x = jnp.ones((2,), jnp.float32)
        with SyncStats() as s:
            flush(x)
            flush(x)
            flush_local(x)
        assert s.flush_msgs == 2 and s.flush_local_msgs == 1

    def test_flush_records_into_explicit_stats(self):
        s = SyncStats()
        flush(jnp.ones((2,)), stats=s)
        assert s.flush_msgs == 1

    def test_explicit_stats_also_counted_inside_equal_valued_scope(self):
        """Identity, not value, equality: a fresh all-zero stats object must
        still receive the flush even while another all-zero scope is active."""
        x = jnp.ones((2,), jnp.float32)
        with SyncStats() as outer:
            s = SyncStats()
            flush(x, stats=s)
        assert s.flush_msgs == 1 and outer.flush_msgs == 1

    def test_nested_zero_valued_scopes_exit_cleanly(self):
        x = jnp.ones((2,), jnp.float32)
        outer = SyncStats()
        inner = SyncStats()
        with outer:
            with inner:
                pass
            flush(x)  # inner already exited: only outer must count
        assert outer.flush_msgs == 1 and inner.flush_msgs == 0

    def test_grad_sync_counts_one_flush_per_bucket(self):
        from repro.parallel.overlap import overlapped_grad_sync

        grads = {"a": jnp.ones((8,), jnp.float32), "b": jnp.ones((8,), jnp.float32)}

        def body(g):
            s = SyncStats()
            out = overlapped_grad_sync(g, inner_axis="w", outer_axis=None,
                                       bucket_bytes=16, stats=s)
            assert s.flush_msgs == 2  # two buckets -> two flushes
            return out

        f = _sm(body, ({"a": P(None), "b": P(None)},),
                {"a": P(None), "b": P(None)})
        out = f(grads)
        np.testing.assert_allclose(np.asarray(out["a"]), np.ones(8))


# ----------------------------------------------------------- model new terms
class TestAggregationModel:
    def test_small_messages_pack_large_direct(self):
        m = DEFAULT_MODEL
        assert m.select_aggregation(16, 8.0) == "pack"
        assert m.select_aggregation(16, 1 << 20) == "direct"

    def test_single_op_is_direct(self):
        assert DEFAULT_MODEL.select_aggregation(1, 8.0) == "direct"

    def test_crossover_in_message_rate_regime(self):
        """The pack/direct boundary sits near the injection-rate crossover
        (416 ns x link bandwidth ~ 20 KiB on v5e), as in paper Fig. 5b."""
        cross = DEFAULT_MODEL.aggregation_crossover_bytes(16)
        assert 2048 <= cross <= 128 * 1024, cross

    def test_crossover_monotone_in_fanin(self):
        m = DEFAULT_MODEL
        assert m.aggregation_crossover_bytes(64) >= m.aggregation_crossover_bytes(4)

    def test_packed_beats_direct_model_on_small(self):
        m = DEFAULT_MODEL
        assert m.p_packed_transfer(64, 8.0) < m.p_direct_transfers(64, 8.0)

    def test_put_backend_threshold(self):
        m = DEFAULT_MODEL
        assert m.select_put_backend(64.0) == "xla"
        assert m.select_put_backend(16 << 20) == "pallas"

    def test_strategist_delegates(self):
        from repro.parallel.overlap import CollectiveStrategist

        s = CollectiveStrategist()
        assert s.aggregation_plan(16, 8.0) == "pack"
        assert s.backend_plan(16, shift_eligible=False) == "xla"


# ----------------------------------------------------------------- the codec
class TestWordCodec:
    @pytest.mark.parametrize("dtype", [
        jnp.float32, jnp.int32, jnp.uint32, jnp.bool_, jnp.bfloat16,
        jnp.float16, jnp.int8, jnp.uint16,
    ])
    def test_encode_decode_roundtrip(self, dtype):
        rng = np.random.RandomState(0)
        if dtype == jnp.bool_:
            x = jnp.asarray(rng.rand(3, 5) > 0.5)
        elif jnp.dtype(dtype).kind in "iu":
            info = jnp.iinfo(dtype)
            x = jnp.asarray(
                rng.randint(int(info.min), int(info.max), size=(3, 5)), dtype)
        else:
            x = jnp.asarray(rng.randn(3, 5), dtype)
        w = plan_mod._encode(x, 1)
        assert w.dtype == jnp.uint32 and w.shape[0] == 3
        y = plan_mod._decode(w, x.shape, dtype)
        assert y.dtype == jnp.dtype(dtype)
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_wide_dtypes_split_into_words(self):
        assert plan_mod._words_per_elt(np.float64) == 2
        assert plan_mod._words_per_elt(jnp.float32) == 1
        assert plan_mod._words_per_elt(jnp.bool_) == 1
