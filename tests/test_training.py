"""End-to-end training behaviors: loss decreases, checkpoint-resume
determinism (restart must replay the uninterrupted trajectory exactly),
MoE dispatch correctness vs a dense reference, serving-engine consistency.
"""

import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.data.pipeline import DataConfig, SyntheticTokenPipeline
from repro.models import build_model
from repro.models.moe import init_moe, moe_ffn
from repro.train.optimizer import AdamWConfig, init_opt_state
from repro.train.train_step import StepConfig, make_train_step
from repro.train.trainer import Trainer, TrainerConfig

RNG = jax.random.PRNGKey(0)


def _trainer(tmp, steps, model, pipe, step_fn, params):
    return Trainer(
        step_fn, params, pipe,
        TrainerConfig(total_steps=steps, ckpt_every=5, log_every=1, ckpt_dir=tmp),
        ckpt=CheckpointManager(tmp),
    )


class TestTraining:
    def _setup(self):
        cfg = get_config("smollm-360m", smoke=True)
        model = build_model(cfg)
        params = model.init(RNG)
        pipe = SyntheticTokenPipeline(DataConfig(cfg.vocab_size, 32, 4))
        step = jax.jit(make_train_step(
            model, AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20), StepConfig()))
        return model, params, pipe, step

    def test_loss_decreases(self):
        model, params, pipe, step = self._setup()
        opt = init_opt_state(params)
        losses = []
        for i in range(25):
            params, opt, m = step(params, opt, pipe.batch_at(i))
            losses.append(float(m["loss"]))
        assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.05

    def test_resume_is_bitwise_deterministic(self):
        """Kill at step 7, resume from the step-5 checkpoint, arrive at the
        same step-10 params as the uninterrupted run — the fault-tolerance
        contract (deterministic data + atomic checkpoints)."""
        model, params0, pipe, step = self._setup()
        with tempfile.TemporaryDirectory() as d1:
            t = _trainer(d1, 10, model, pipe, step, jax.tree.map(jnp.copy, params0))
            t.run()
            ref = t.params

            with tempfile.TemporaryDirectory() as d2:
                t1 = _trainer(d2, 7, model, pipe, step, jax.tree.map(jnp.copy, params0))
                t1.run()  # "crashes" after step 7 (ckpt exists at 5)
                t2 = _trainer(d2, 10, model, pipe, step, jax.tree.map(jnp.copy, params0))
                assert t2.maybe_resume()
                assert t2.step in (5, 7)  # resumed from a checkpoint
                t2.run()
                for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(t2.params)):
                    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestMoECorrectness:
    @pytest.mark.parametrize("mlp_type", ["swiglu", "gelu"])
    def test_matches_dense_reference(self, mlp_type):
        """With ample capacity, sort-based dispatch == dense per-token loop."""
        D, E, F, k = 16, 8, 32, 2
        p = init_moe(RNG, D, E, F, mlp_type, dtype=jnp.float32)
        x = jax.random.normal(jax.random.fold_in(RNG, 1), (2, 12, D), jnp.float32)
        y, met = moe_ffn(p, x, top_k=k, capacity_factor=8.0, mlp_type=mlp_type)
        assert float(met.drop_fraction) == 0.0

        # dense reference: route every token through its top-k experts
        xt = x.reshape(-1, D)
        logits = xt @ p["router"]
        probs = jax.nn.softmax(logits, -1)
        gv, ei = jax.lax.top_k(probs, k)
        gv = gv / gv.sum(-1, keepdims=True)
        ref = np.zeros_like(np.asarray(xt))
        for t in range(xt.shape[0]):
            for j in range(k):
                e = int(ei[t, j])
                h = np.asarray(xt[t]) @ np.asarray(p["experts"]["w_in"][e])
                if mlp_type == "swiglu":
                    gate = np.asarray(xt[t]) @ np.asarray(p["experts"]["w_gate"][e])
                    h = gate / (1 + np.exp(-gate)) * h
                else:
                    h = np.asarray(jax.nn.gelu(jnp.asarray(h)))
                ref[t] += float(gv[t, j]) * (h @ np.asarray(p["experts"]["w_out"][e]))
        np.testing.assert_allclose(
            np.asarray(y.reshape(-1, D)), ref, rtol=2e-4, atol=2e-4)

    def test_capacity_drops_bounded(self):
        D, E, F, k = 8, 4, 16, 2
        p = init_moe(RNG, D, E, F, dtype=jnp.float32)
        x = jax.random.normal(RNG, (1, 64, D), jnp.float32)
        _, met = moe_ffn(p, x, top_k=k, capacity_factor=0.5)
        assert 0.0 < float(met.drop_fraction) < 1.0
        assert float(met.aux_loss) > 0.0


class TestServeEngine:
    def test_engine_matches_direct_decode(self):
        """Engine output for a single request == hand-rolled prefill+decode."""
        from repro.serve.engine import Request, ServeEngine

        cfg = get_config("smollm-360m", smoke=True)
        model = build_model(cfg)
        params = model.init(RNG)
        prompt = [3, 1, 4, 1, 5]
        n_new = 6

        cache = model.init_cache(1, 64)
        logits, cache = model.prefill(params, jnp.asarray([prompt], jnp.int32), cache, None)
        want = []
        for _ in range(n_new):
            tok = jnp.argmax(logits, -1)
            want.append(int(tok[0]))
            logits, cache = model.decode_step(params, tok, cache)

        eng = ServeEngine(model, params, n_slots=2, max_seq=64)
        req = Request(rid=0, prompt=prompt, max_new=n_new)
        eng.submit(req)
        steps = eng.run_until_drained()
        assert steps >= 1                    # drained (DrainError otherwise)
        assert req.done.is_set()
        assert req.output == want, (req.output, want)
        assert eng.queue.empty() and all(eng.slot_free)

    def test_engine_donates_its_cache_and_reuses_lanes(self):
        """Both compiled programs alias the donated K/V cache, and two waves
        through one engine (each lane prefilled, recycled and written again)
        serve the tokens of a hand-rolled prefill/decode loop."""
        from repro.serve.engine import Request, ServeEngine

        cfg = get_config("smollm-360m", smoke=True)
        model = build_model(cfg)
        params = model.init(RNG)
        max_seq, n_new = 32, 5
        eng = ServeEngine(model, params, n_slots=2, max_seq=max_seq)
        kv_bytes = sum(a.nbytes for a in jax.tree.leaves(eng.cache["kv"]))
        programs = [
            eng._decode.lower(params, jnp.zeros((2,), jnp.int32), eng.cache),
            eng._prefill.lower(params, eng.cache, jnp.zeros((max_seq,), jnp.int32), 0, plen=4),
        ]
        for lowered in programs:
            assert lowered.compile().memory_analysis().alias_size_in_bytes >= kv_bytes

        def direct(prompt):
            cache = model.init_cache(1, max_seq)
            logits, cache = model.prefill(params, jnp.asarray([prompt], jnp.int32), cache, None)
            out = []
            for _ in range(n_new):
                tok = jnp.argmax(logits, -1)
                out.append(int(tok[0]))
                logits, cache = model.decode_step(params, tok, cache)
            return out

        # one prompt length per wave: the cache holds one length for all lanes
        waves = [[[3, 1, 4, 1], [2, 7, 1, 8]], [[5, 9, 2, 6, 5, 3], [5, 8, 9, 7, 9, 3]]]
        for w, prompts in enumerate(waves):
            reqs = [Request(rid=10 * w + i, prompt=p, max_new=n_new) for i, p in enumerate(prompts)]
            for r in reqs:
                eng.submit(r)
            eng.run_until_drained()
            for r in reqs:
                assert r.output == direct(r.prompt), (r.rid, r.output)
        assert eng.recycled_total == 4

    def test_engine_interleaves_requests(self):
        from repro.serve.engine import Request, ServeEngine

        cfg = get_config("smollm-360m", smoke=True)
        model = build_model(cfg)
        params = model.init(RNG)
        eng = ServeEngine(model, params, n_slots=2, max_seq=32)
        reqs = [Request(rid=i, prompt=[1 + i, 2 + i], max_new=4) for i in range(5)]
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained()
        assert all(r.done.is_set() and len(r.output) == 4 for r in reqs)
        assert eng.lock_win.total_amos > 0  # admission control exercised
        # the lock window is fully released after a drain: no leaked reader
        # counts or writer bits (the §2.3 discipline held throughout)
        assert eng.lock_win.master.v == 0
        assert all(w.v == 0 for w in eng.lock_win.local)

    def test_drain_timeout_raises_with_undrained_ids(self):
        from repro.serve.engine import DrainError, Request, ServeEngine

        eng = ServeEngine(_StubServeModel(), {}, n_slots=2, max_seq=32)
        for i in range(3):
            eng.submit(Request(rid=10 + i, prompt=[1], max_new=8))
        with pytest.raises(DrainError) as ei:
            eng.run_until_drained(max_steps=1)
        assert len(ei.value.undrained) > 0
        assert set(ei.value.undrained) <= {10, 11, 12}


class _StubServeModel:
    """Minimal deterministic Model: token t always produces (t+1) % vocab.

    Fast enough to hammer the engine's lock protocol from many threads; the
    cache tree has the same (n_slots, ...) leaf structure a real KV cache
    has, so `_prefill_impl`'s lane scatter is exercised too.
    """

    vocab = 17

    def init_cache(self, b, max_seq):
        return {"k": jnp.zeros((b, max_seq, 4)), "len": jnp.zeros((), jnp.int32)}

    def prefill(self, params, tokens, cache, _):
        last = tokens[:, -1]
        return jax.nn.one_hot((last + 1) % self.vocab, self.vocab), cache

    def decode_step(self, params, tokens, cache):
        return jax.nn.one_hot((tokens + 1) % self.vocab, self.vocab), cache


class TestServeLockDiscipline:
    """The §2.3 bugfix: lane recycling is a writer section.  The old
    `admit()` recycled an instantly-finished lane under its *shared* lock;
    `_recycle` now carries a writer-bit tripwire and every mutation path
    takes the exclusive lock."""

    def _engine(self, n_slots=3):
        from repro.serve.engine import ServeEngine

        return ServeEngine(_StubServeModel(), {}, n_slots=n_slots, max_seq=32)

    def test_recycle_under_reader_lock_raises(self):
        from repro.serve.engine import LockDisciplineError, Request

        eng = self._engine()
        req = Request(rid=0, prompt=[1], max_new=1)
        eng.slot_free[0] = False
        eng.slot_req[0] = req
        with pytest.raises(LockDisciplineError):
            eng._recycle(0)                      # no lock at all
        eng.lock.lock_shared(0)
        try:
            with pytest.raises(LockDisciplineError):
                eng._recycle(0)                  # the historical bug, exactly
        finally:
            eng.lock.unlock_shared(0)
        assert not req.done.is_set()             # the bad paths did nothing
        eng.lock.lock_exclusive(0)
        try:
            eng._recycle(0)                      # writer-locked: legal
        finally:
            eng.lock.unlock_exclusive(0)
        assert req.done.is_set() and eng.slot_free[0]
        assert eng.lock_win.master.v == 0
        assert all(w.v == 0 for w in eng.lock_win.local)

    def test_threaded_submitters_vs_scheduler(self):
        """Request threads admit (shared-lock prefills, exclusive-lock
        allocations/recycles) while a scheduler thread runs the unified
        tick.  Every request must finish exactly once with the right
        tokens, and the lock window must come back fully released — the
        locks_sim state assertions that catch a reader-locked recycle."""
        import threading

        from repro.serve.engine import Request

        eng = self._engine(n_slots=3)
        vocab = _StubServeModel.vocab
        reqs = [Request(rid=i, prompt=[(i % 13) + 1],
                        max_new=1 if i % 5 == 0 else 3)
                for i in range(24)]
        stop = threading.Event()
        errors = []

        def scheduler():
            try:
                while not stop.is_set():
                    eng.schedule()
            except Exception as e:  # pragma: no cover - surfaced by assert
                errors.append(e)

        def submitter(chunk):
            try:
                for r in chunk:
                    eng.submit(r)
                    eng.admit()   # request threads run admission themselves
            except Exception as e:  # pragma: no cover - surfaced by assert
                errors.append(e)

        sched = threading.Thread(target=scheduler)
        subs = [threading.Thread(target=submitter, args=(reqs[i::3],))
                for i in range(3)]
        sched.start()
        for t in subs:
            t.start()
        for t in subs:
            t.join(timeout=120)
        done = all(r.done.wait(timeout=120) for r in reqs)
        stop.set()
        sched.join(timeout=120)
        assert not errors, errors
        assert done
        for r in reqs:                           # exactly once, right tokens
            want = [(r.prompt[0] + 1 + j) % vocab for j in range(r.max_new)]
            assert r.output == want, (r.rid, r.output, want)
        assert eng.recycled_total == len(reqs)
        assert all(eng.slot_free)
        # lock-window state: nothing leaked, AMO traffic went through the
        # paper's protocol (fetch-add/CAS on the lock words)
        assert eng.lock_win.master.v == 0
        assert all(w.v == 0 for w in eng.lock_win.local)
        assert eng.lock_win.total_amos > 0
