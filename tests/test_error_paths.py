"""Error-path coverage for the PR 3-4 surfaces (ISSUE 5 satellite):

  * `DrainError` carries the EXACT undrained request ids (`ServeEngine`
    here; the flow/paged `DisaggEngine` variants live in
    `tests/subtests/disagg_sub.py` because they need a device mesh);
  * `LockTimeout` diagnostics name the rank HOLDING the contended writer
    lock, not just the contended word;
  * the SPMD heap surfaces double-free / share-dead violations through the
    ERRS counter, and `heap.check_errors` promotes them to the same
    `HeapError` the host path raises.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.core import locks_sim
from repro.rmem import heap


# ================================================================ DrainError
class TestDrainErrorExactRids:
    def _engine(self, n_slots=2):
        from repro.serve.engine import ServeEngine

        from .test_training import _StubServeModel

        return ServeEngine(_StubServeModel(), {}, n_slots=n_slots, max_seq=32)

    def test_zero_step_budget_reports_every_submitted_rid(self):
        from repro.serve.engine import DrainError, Request

        eng = self._engine()
        rids = [41, 7, 99]
        for rid in rids:
            eng.submit(Request(rid=rid, prompt=[1], max_new=4))
        with pytest.raises(DrainError) as ei:
            eng.run_until_drained(max_steps=0)
        assert ei.value.undrained == tuple(sorted(rids))   # exact, sorted
        assert "[7, 41, 99]" in str(ei.value)              # ids in the message

    def test_partial_progress_reports_the_remainder_exactly(self):
        from repro.serve.engine import DrainError, Request

        eng = self._engine(n_slots=1)                      # serialize lanes
        reqs = [Request(rid=i, prompt=[1], max_new=3) for i in (5, 6, 7)]
        for r in reqs:
            eng.submit(r)
        with pytest.raises(DrainError) as ei:
            eng.run_until_drained(max_steps=2)
        done = {r.rid for r in reqs if r.done.is_set()}
        assert set(ei.value.undrained) == {5, 6, 7} - done
        assert ei.value.undrained                          # something WAS left


# =============================================================== LockTimeout
class TestLockTimeoutNamesHolder:
    def test_exclusive_holder_rank_in_diagnostics(self):
        win = locks_sim.LockWindow(p=3)
        holder = locks_sim.LockOrigin(win, rank=2)
        waiter = locks_sim.LockOrigin(win, rank=0)
        holder.lock_exclusive(1)
        with pytest.raises(locks_sim.LockTimeout) as ei:
            waiter.lock_shared(1, max_retries=3)
        msg = str(ei.value)
        assert "local[1]: writer=True" in msg
        assert "held_by=rank 2, readers=" in msg           # names the offender
        holder.unlock_exclusive(1)
        # released: holder cleared, next acquisition succeeds
        assert win.holder[1] == -1
        waiter.lock_shared(1, max_retries=3)
        waiter.unlock_shared(1)

    def test_holder_updates_across_handoff(self):
        win = locks_sim.LockWindow(p=2)
        a = locks_sim.LockOrigin(win, rank=0)
        b = locks_sim.LockOrigin(win, rank=1)
        a.lock_exclusive(0)
        assert win.holder[0] == 0
        a.unlock_exclusive(0)
        b.lock_exclusive(0)
        assert win.holder[0] == 1
        with pytest.raises(locks_sim.LockTimeout) as ei:
            a.lock_exclusive(0, max_retries=3)
        assert "held_by=rank 1" in str(ei.value)
        b.unlock_exclusive(0)


# ============================================= SPMD HeapError (check_errors)
def _mesh():
    return jax.make_mesh((1,), ("w",))


def _run_pool_epochs(fn, desc, state, *extra):
    """Run `fn(local_state, *extra)` under single-device shard_map."""
    specs = heap.state_specs("w")
    f = jax.jit(shard_map(
        fn, mesh=_mesh(),
        in_specs=(specs,) + tuple(P("w", None) for _ in extra),
        out_specs=specs, check_vma=False))
    return f(state, *extra)


class TestSpmdHeapErrorSurface:
    def _alloc_one(self, desc, state):
        """Alloc one page; returns (state, the granted page id)."""
        specs = heap.state_specs("w")

        def body(st, want):
            st = heap.to_local(st)
            st, ids, _ = heap.alloc(desc, st, want[0], 1)
            return heap.to_global(st), ids[None]

        f = jax.jit(shard_map(
            body, mesh=_mesh(), in_specs=(specs, P("w", None)),
            out_specs=(specs, P("w", None, None)), check_vma=False))
        state, ids = f(state, jnp.ones((1, 1), jnp.int32))
        return state, int(np.asarray(ids)[0, 0, 0])

    def _release(self, desc, state, pid):
        def body(st, ids):
            st = heap.to_local(st)
            st, _ = heap.release(desc, st, ids[0], jnp.zeros((1,), jnp.int32))
            return heap.to_global(st)

        return _run_pool_epochs(body, desc, state,
                                jnp.full((1, 1), pid, jnp.int32))

    def test_double_free_raises_through_check_errors(self):
        desc, state = heap.pool_allocate(_mesh(), "w", 4)
        state, pid = self._alloc_one(desc, state)
        state = self._release(desc, state, pid)            # legal: 1 -> 0
        heap.check_errors(desc, state)                     # clean so far
        state = self._release(desc, state, pid)            # double free
        assert int(np.asarray(state.head)[0, heap.ERRS]) == 1
        with pytest.raises(heap.HeapError, match="rank 0: 1"):
            heap.check_errors(desc, state)
        # the violation was dropped WHOLE: conservation still holds
        cons = heap.conservation(desc, state)
        assert (cons["free_plus_live"] == 4).all()

    def test_share_dead_raises_through_check_errors(self):
        desc, state = heap.pool_allocate(_mesh(), "w", 4)

        def share_dead(st, ids):
            st = heap.to_local(st)
            st, _ = heap.ref_update(desc, st, ids[0],
                                    jnp.zeros((1,), jnp.int32),
                                    jnp.ones((1,), jnp.int32))   # +1 on dead
            return heap.to_global(st)

        state = _run_pool_epochs(share_dead, desc, state,
                                 jnp.zeros((1, 1), jnp.int32))
        assert int(np.asarray(state.head)[0, heap.ERRS]) == 1
        with pytest.raises(heap.HeapError, match="share-dead|double-free"):
            heap.check_errors(desc, state)
        assert heap.conservation(desc, state)["stack_consistent"].all()

    def test_clean_pool_passes_check_errors(self):
        desc, state = heap.pool_allocate(_mesh(), "w", 4)
        state, _ = self._alloc_one(desc, state)
        heap.check_errors(desc, state)                     # no raise


# ======================================================== epoch misuse guards
class TestEpochMisuseGuards:
    """ISSUE 8 satellite: each misuse raises `PlanError` with a message
    precise enough to act on (what was violated, on which axis, and why
    the op would be wrong) — instead of silently dropping or double-
    counting ops."""

    def test_op_recorded_after_epoch_close_raises(self):
        from repro.core.plan import AccessEpoch, PlanError

        ep = AccessEpoch("w", family="fence", p=4)
        ep.plan.flush()                    # the epoch's plan is now closed
        with pytest.raises(PlanError, match=r"fence epoch on axis 'w' "
                                            r"already closed — op recorded "
                                            r"after close\(\)"):
            ep.put_shift(jnp.zeros(3), 1)

    def test_nested_begin_plan_without_flush_raises(self):
        from repro.core.epoch import FenceEpoch
        from repro.core.plan import PlanError

        ep = FenceEpoch("w", p=4)
        pl = ep.begin_plan()
        pl.fetch_and_op(jnp.zeros(3), jnp.ones(3))   # recorded, unflushed
        with pytest.raises(PlanError, match=r"begin_plan on axis 'w': the "
                                            r"epoch's previous plan still "
                                            r"holds 1 unflushed recorded "
                                            r"op\(s\)"):
            ep.begin_plan()
        pl.flush()                         # flushing clears the guard
        assert ep.begin_plan() is not pl

    def test_double_fence_close_without_open_raises(self):
        from repro.core.epoch import FenceEpoch
        from repro.core.plan import PlanError

        ep = FenceEpoch("w", p=4)
        t = ep.open(jnp.zeros(3))
        t = ep.close(t)
        with pytest.raises(PlanError, match=r"double fence on axis 'w': "
                                            r"close\(\) called with no open "
                                            r"epoch"):
            ep.close(t)

    def test_reopening_an_open_fence_epoch_raises(self):
        from repro.core.epoch import FenceEpoch
        from repro.core.plan import PlanError

        ep = FenceEpoch("w", p=4)
        t = ep.open(jnp.zeros(3))
        with pytest.raises(PlanError, match="already open"):
            ep.open(t)
        ep.close(t)                        # still closable exactly once
