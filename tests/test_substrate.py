"""Substrate tests: optimizer, data pipeline, checkpointing, compression,
heartbeat/straggler logic, sharding-spec fitting, HLO cost analyzer."""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.ckpt.checkpoint import CheckpointManager

from .helpers import given, settings, st
from repro.data.pipeline import DataConfig, SyntheticTokenPipeline
from repro.ft.heartbeat import HeartbeatConfig, HeartbeatMonitor
from repro.launch import hlo_cost
from repro.parallel.compression import compress_decompress, init_compression_state
from repro.parallel.sharding import fit_spec
from repro.train.optimizer import AdamWConfig, adamw_update, init_opt_state, lr_at

RNG = jax.random.PRNGKey(0)


# ---------------------------------------------------------------- optimizer
class TestAdamW:
    def test_matches_manual_reference(self):
        cfg = AdamWConfig(lr=1e-2, warmup_steps=0, weight_decay=0.0, clip_norm=1e9)
        p = {"w": jnp.asarray([[1.0, -2.0]], jnp.float32)}
        g = {"w": jnp.asarray([[0.1, 0.2]], jnp.float32)}
        st_ = init_opt_state(p)
        new_p, st2, _ = adamw_update(cfg, p, g, st_)
        m = 0.1 * np.asarray(g["w"])
        v = 0.05 * np.asarray(g["w"]) ** 2
        mhat = m / (1 - 0.9)
        vhat = v / (1 - 0.95)
        expect = np.asarray(p["w"]) - 1e-2 * mhat / (np.sqrt(vhat) + 1e-8)
        np.testing.assert_allclose(np.asarray(new_p["w"]), expect, rtol=1e-5)

    def test_clip_norm_applied(self):
        cfg = AdamWConfig(clip_norm=0.001, warmup_steps=0)
        p = {"w": jnp.ones((4,), jnp.float32)}
        g = {"w": jnp.full((4,), 100.0)}
        _, _, met = adamw_update(cfg, p, g, init_opt_state(p))
        assert float(met["grad_norm"]) == pytest.approx(200.0, rel=1e-3)

    def test_lr_schedule_warmup_and_cosine(self):
        cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=110, min_lr_frac=0.1)
        assert float(lr_at(cfg, jnp.asarray(0))) == pytest.approx(0.1)
        assert float(lr_at(cfg, jnp.asarray(9))) == pytest.approx(1.0)
        end = float(lr_at(cfg, jnp.asarray(110)))
        assert end == pytest.approx(0.1, abs=1e-2)

    def test_moments_dtype_fp32(self):
        p = {"w": jnp.ones((2, 2), jnp.bfloat16)}
        st_ = init_opt_state(p)
        assert st_.mu["w"].dtype == jnp.float32


# -------------------------------------------------------------------- data
class TestPipeline:
    def test_deterministic_and_seekable(self):
        cfg = DataConfig(vocab_size=97, seq_len=16, global_batch=4)
        p1 = SyntheticTokenPipeline(cfg)
        p2 = SyntheticTokenPipeline(cfg)
        b1, b2 = p1.batch_at(17), p2.batch_at(17)
        np.testing.assert_array_equal(np.asarray(b1["tokens"]), np.asarray(b2["tokens"]))

    def test_shards_differ_and_labels_shifted(self):
        a = SyntheticTokenPipeline(DataConfig(97, 16, 8, n_shards=2, shard_id=0)).batch_at(0)
        b = SyntheticTokenPipeline(DataConfig(97, 16, 8, n_shards=2, shard_id=1)).batch_at(0)
        assert not np.array_equal(np.asarray(a["tokens"]), np.asarray(b["tokens"]))

    @given(st.integers(0, 1000))
    @settings(max_examples=20, deadline=None)
    def test_tokens_in_range(self, step):
        cfg = DataConfig(vocab_size=50, seq_len=8, global_batch=2)
        b = SyntheticTokenPipeline(cfg).batch_at(step)
        t = np.asarray(b["tokens"])
        assert t.min() >= 0 and t.max() < 50


# -------------------------------------------------------------- checkpoint
class TestCheckpoint:
    def test_roundtrip_and_gc(self):
        tree = {"a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
                "b": {"c": jnp.ones((2,), jnp.bfloat16)}}
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, keep=2)
            for s in (1, 2, 3):
                mgr.save(s, tree, extra={"step": s}, blocking=True)
            assert mgr.list_steps() == [2, 3]
            like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
            out, extra = mgr.restore(like)
            assert extra["step"] == 3
            np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(tree["a"]))
            assert out["b"]["c"].dtype == jnp.bfloat16

    def test_no_partial_checkpoint_visible(self):
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d)
            mgr.save(1, {"a": jnp.zeros((2,))}, blocking=True)
            names = os.listdir(d)
            assert all(n.startswith("step_") for n in names), names

    def test_missing_leaf_raises(self):
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d)
            mgr.save(1, {"a": jnp.zeros((2,))}, blocking=True)
            with pytest.raises(KeyError):
                mgr.restore({"zz": jax.ShapeDtypeStruct((2,), jnp.float32)})


# ------------------------------------------------------------- compression
class TestCompression:
    def test_error_feedback_reduces_bias(self):
        """Accumulated EF-compressed sum approaches the true sum."""
        g = jax.random.normal(RNG, (256,)) * 1e-3
        state = init_compression_state({"g": g})
        total = jnp.zeros_like(g)
        for _ in range(50):
            out, state, _ = compress_decompress({"g": g}, state)
            total = total + out["g"]
        err = float(jnp.abs(total / 50 - g).max() / (jnp.abs(g).max() + 1e-12))
        assert err < 0.05, err

    def test_compression_ratio_reported(self):
        g = {"g": jnp.ones((1024,), jnp.float32)}
        _, _, met = compress_decompress(g, init_compression_state(g))
        assert met["dcn_bytes_compressed"] * 3 < met["dcn_bytes_uncompressed"]


# ---------------------------------------------------------------- heartbeat
class TestHeartbeat:
    def test_dead_node_detected(self):
        t = [0.0]
        mon = HeartbeatMonitor(3, HeartbeatConfig(timeout_s=5), clock=lambda: t[0])
        for s in range(6):
            t[0] = float(2 * s)
            mon.beat(0, s)
            mon.beat(1, s)
            if s < 2:
                mon.beat(2, s)   # node 2 stops beating at t=2
        assert mon.check_dead() == {2}
        assert mon.healthy_nodes() == [0, 1]

    def test_straggler_flagged_after_patience(self):
        t = [0.0]
        mon = HeartbeatMonitor(2, HeartbeatConfig(straggler_factor=2.0, straggler_patience=2,
                                                  timeout_s=1e9),
                               clock=lambda: t[0])
        # node 0 steps every 100s; node 1 every 250s (a true straggler)
        events = sorted(
            [(100.0 * k, 0, k) for k in range(8)]
            + [(250.0 * k, 1, k) for k in range(4)]
        )
        flagged = set()
        for when, node, step in events:
            t[0] = when
            mon.beat(node, step)
            flagged |= mon.check_stragglers()
        assert 1 in flagged and 0 not in flagged


# ----------------------------------------------------------------- sharding
class TestFitSpec:
    def _mesh(self):
        return jax.make_mesh((1,), ("model",))

    @given(dim=st.integers(1, 64))
    @settings(max_examples=30, deadline=None)
    def test_fitted_spec_always_divides(self, dim):
        import jax as _j
        mesh = _j.make_mesh((1,), ("model",))
        # synthetic mesh sizes via dict-mesh stub
        class FakeMesh:
            shape = {"model": 16, "data": 8}
        spec = fit_spec(P("model", ("data", "model")), (dim, dim * 2), FakeMesh())
        for d, entry in zip((dim, dim * 2), list(spec)):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            prod = 1
            for a in axes:
                prod *= FakeMesh.shape[a]
            assert d % prod == 0

    def test_divisible_spec_preserved(self):
        class FakeMesh:
            shape = {"model": 4, "data": 2}
        assert fit_spec(P("model", None), (8, 3), FakeMesh()) == P("model", None)
        assert fit_spec(P(("data", "model")), (8,), FakeMesh()) == P(("data", "model"))
        assert fit_spec(P("model",), (6,), FakeMesh()) == P(None)


# ---------------------------------------------------------------- hlo cost
class TestHloCost:
    def test_scan_trip_count_multiplied(self):
        def with_scan(w, x):
            def layer(h, wi):
                return h @ wi, None
            h, _ = jax.lax.scan(layer, x, w)
            return h.sum()

        w = jax.ShapeDtypeStruct((8, 64, 64), jnp.float32)
        x = jax.ShapeDtypeStruct((16, 64), jnp.float32)
        c = jax.jit(with_scan).lower(w, x).compile()
        s = hlo_cost.analyze(c.as_text())
        analytic = 8 * 2 * 16 * 64 * 64
        assert 0.9 * analytic < s.flops < 2.0 * analytic, s.flops
        # XLA's own counter must be ~1/8 of ours (loop counted once)
        xla = c.cost_analysis()["flops"]
        assert s.flops > 4 * xla

    def test_dot_flops_exact_without_loops(self):
        def f(a, b):
            return a @ b
        a = jax.ShapeDtypeStruct((32, 128), jnp.float32)
        b = jax.ShapeDtypeStruct((128, 16), jnp.float32)
        c = jax.jit(f).lower(a, b).compile()
        s = hlo_cost.analyze(c.as_text())
        assert s.flops == pytest.approx(2 * 32 * 128 * 16, rel=0.2)
