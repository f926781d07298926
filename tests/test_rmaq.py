"""rmaq tests: queue protocol invariants (host path), channel typing,
heartbeat transport, perf-model dispatch — plus the multi-device XLA/Pallas
paths and the disaggregated serving engine via subprocess subtests."""

import numpy as np
import pytest

from repro.core.perfmodel import DEFAULT_MODEL
from repro.parallel.overlap import CollectiveStrategist
from repro.rmaq.channel import ChannelError, HostChannel, Lane
from repro.rmaq.queue import HostQueueGroup, QueueError, admission_plan

from .helpers import given, run_subtest, settings, st


# ------------------------------------------------------------ admission plan
class TestAdmissionPlan:
    @given(seed=st.integers(0, 200))
    @settings(max_examples=30, deadline=None)
    def test_grants_bounded_and_rank_ordered(self, seed):
        rng = np.random.RandomState(seed)
        p, cap = rng.randint(2, 9), 16
        C = rng.randint(0, 7, size=(p, p)).astype(np.int64)
        used = rng.randint(0, cap + 1, size=p).astype(np.int64)
        grant, offset = admission_plan(C, used, cap, xp=np)
        free = cap - used
        assert (grant >= 0).all() and (grant <= C).all()
        # per target: total grants never exceed free space
        assert (grant.sum(axis=0) <= free).all()
        # rank order: r's slots start exactly after all lower ranks' grants
        for t in range(p):
            running = 0
            for r in range(p):
                if grant[r, t] > 0:
                    assert offset[r, t] == running
                running += grant[r, t]

    def test_full_target_rejects_everything(self):
        C = np.asarray([[3], [2]], np.int64)
        grant, _ = admission_plan(C, np.asarray([8], np.int64), 8, xp=np)
        assert grant.sum() == 0


# ----------------------------------------------------------------- host queue
class TestHostQueue:
    def test_capacity_must_be_power_of_two(self):
        with pytest.raises(QueueError):
            HostQueueGroup(p=2, capacity=12, item_width=1)

    def test_fifo_per_producer_exactly_once(self):
        g = HostQueueGroup(p=3, capacity=8, item_width=1)
        seen = []
        serial = 0
        for _ in range(10):
            sends = {
                r: [(0, np.asarray([100 * r + serial + i], np.float32))
                    for i in range(2)]
                for r in range(3)
            }
            serial += 2
            g.step(sends)
            seen += [float(m[0]) for m in g.drain(0)]
        assert len(seen) == len(set(seen)) == 60          # exactly once
        for r in range(3):                                 # FIFO per producer
            vals = [v for v in seen if int(v) // 100 == r]
            assert vals == sorted(vals)

    def test_wraparound_many_times_over(self):
        g = HostQueueGroup(p=2, capacity=4, item_width=1)
        for i in range(40):                                # 10x around the ring
            g.step({1: [(0, np.asarray([i], np.float32))]})
            (msg,) = g.drain(0)
            assert float(msg[0]) == i

    def test_backpressure_reject_then_retry(self):
        g = HostQueueGroup(p=2, capacity=4, item_width=1)
        flags = g.step({1: [(0, np.asarray([i], np.float32)) for i in range(6)]})
        assert flags[1] == [True] * 4 + [False] * 2        # origin-side reject
        assert g.stats(1)["dropped_by_me"] == 2
        assert [float(m[0]) for m in g.drain(0)] == [0.0, 1.0, 2.0, 3.0]
        flags = g.step({1: [(0, np.asarray([9], np.float32))]})
        assert flags[1] == [True]                          # retry succeeds

    def test_notification_count_matches_model_accounting(self):
        """Every admitted message is exactly one notification — the §6.5
        model's per-message accounting, asserted on the counter."""
        g = HostQueueGroup(p=2, capacity=8, item_width=1)
        g.step({1: [(0, np.asarray([i], np.float32)) for i in range(5)]})
        s = g.stats(0)
        assert s["notifications"] == s["enqueued"] == 5
        assert g.stats(1)["notifications"] == 0            # producers get none


# -------------------------------------------------------------------- channel
class TestHostChannel:
    def _ch(self):
        return HostChannel(
            p=2, capacity=8,
            lanes=[Lane("beat", (2,), "int32"), Lane("kv", (3,), "float32")],
        )

    def test_typed_lanes_roundtrip_and_demux(self):
        ch = self._ch()
        ch.send(1, "beat", [7, 42], tag=5, dest=0)
        ch.send(1, "kv", [1.5, 2.5, 3.5], tag=9, dest=0)
        ch.flush()
        msgs = ch.recv(0)
        assert [m["lane"] for m in msgs] == ["beat", "kv"]  # shared FIFO
        assert msgs[0]["payload"].dtype == np.int32
        assert msgs[0]["payload"].tolist() == [7, 42]
        assert msgs[0]["src"] == 1 and msgs[0]["tag"] == 5
        np.testing.assert_allclose(msgs[1]["payload"], [1.5, 2.5, 3.5])

    def test_unknown_lane_and_wide_dtype_rejected(self):
        ch = self._ch()
        with pytest.raises(ChannelError):
            ch.send(0, "nope", [1, 2], tag=0, dest=1)
        with pytest.raises(ChannelError):
            HostChannel(p=2, capacity=8, lanes=[Lane("bad", (2,), "float64")])


# ------------------------------------------------------- heartbeat transport
class TestChannelHeartbeat:
    def test_dead_node_detected_through_channel(self):
        from repro.ft.heartbeat import (ChannelHeartbeat, HeartbeatConfig,
                                        HeartbeatMonitor)

        t = [0.0]
        mon = HeartbeatMonitor(3, HeartbeatConfig(timeout_s=5),
                               clock=lambda: t[0])
        hb = ChannelHeartbeat(mon, capacity=8)
        for s in range(6):
            t[0] = float(2 * s)
            hb.beat(0, s)
            hb.beat(1, s)
            if s < 2:
                hb.beat(2, s)                      # node 2 stops beating
            hb.poll()
        assert mon.check_dead() == {2}
        assert mon.healthy_nodes() == [0, 1]
        assert hb.stats()["enqueued"] == 14        # 2 + 2 + (2 only twice)

    def test_backpressure_shows_as_staleness_not_crash(self):
        from repro.ft.heartbeat import (ChannelHeartbeat, HeartbeatConfig,
                                        HeartbeatMonitor)

        mon = HeartbeatMonitor(4, HeartbeatConfig(timeout_s=1e9))
        hb = ChannelHeartbeat(mon, capacity=2)     # tiny monitor ring
        for s in range(4):
            for node in range(4):
                hb.beat(node, s)
            hb.poll()                              # only 2 beats land per epoch
        assert hb.stats()["dropped_total"] > 0


# ------------------------------------------------------ perf model + planner
class TestQueueModel:
    def test_notified_put_is_put_plus_doorbell(self):
        m = DEFAULT_MODEL
        nb = 4096.0
        assert m.p_notified_put(nb) == pytest.approx(
            m.p_put(nb) + m.hw.sem_op_latency)

    def test_dequeue_is_local(self):
        m = DEFAULT_MODEL
        # no ICI term at all: dequeue must be cheaper than any remote op
        assert m.p_queue_dequeue(4096.0) < m.p_put(0.0)

    def test_dispatch_crossover(self):
        m = DEFAULT_MODEL
        assert m.select_dispatch(4, 256.0, 64, 32) == "queue"      # sparse
        assert m.select_dispatch(2048, 256.0, 8, 4) == "alltoall"  # dense
        # disagg KV blocks: few, large -> queue
        assert m.select_dispatch(8, 65536.0, 16, 8) == "queue"

    def test_strategist_dispatch_plan(self):
        strat = CollectiveStrategist()
        assert strat.dispatch_plan(4, 256.0, 64, 32) == "queue"
        assert strat.dispatch_plan(2048, 256.0, 8, 4) == "alltoall"

    @given(n=st.integers(1, 4096))
    @settings(max_examples=25, deadline=None)
    def test_queue_cost_monotone_in_messages(self, n):
        m = DEFAULT_MODEL
        t = m.p_queue_reserve() + n * m.p_queue_enqueue(64.0)
        t2 = m.p_queue_reserve() + (n + 1) * m.p_queue_enqueue(64.0)
        assert t2 > t


# ------------------------------------------------------- descriptor metadata
class TestQueueMetadata:
    def test_descriptor_metadata_o1(self):
        """O(1): queue metadata independent of capacity and item size (the
        ring storage is window payload, not metadata) — §2.2 preserved."""
        import jax

        from repro.rmaq import queue as rq

        mesh = jax.make_mesh((1,), ("w",))
        d1, _ = rq.queue_allocate(mesh, "w", 8, (4,))
        d2, _ = rq.queue_allocate(mesh, "w", 512, (256,))
        assert d1.metadata_nbytes() == d2.metadata_nbytes()

    def test_channel_metadata_counts_lanes_not_capacity(self):
        import jax

        from repro.rmaq import channel as rch

        mesh = jax.make_mesh((1,), ("w",))
        lanes = [rch.Lane("a", (4,)), rch.Lane("b", (2,))]
        c1, _ = rch.channel_allocate(mesh, "w", 8, lanes)
        c2, _ = rch.channel_allocate(mesh, "w", 1024, lanes)
        assert c1.metadata_nbytes() == c2.metadata_nbytes()


@pytest.mark.parametrize("name,payload", [
    ("ids", [[3, -1]]),
    ("kv", [[1e-45, -2.5]]),      # a float32 denormal payload word
])
def test_channel_cells_are_integers(name, payload):
    """Channel cells are uint32: a TPU flushes float32 denormals to zero,
    and a small int32 header word bitcast to float32 is one."""
    import jax
    import jax.numpy as jnp

    from repro.rmaq import channel as rch

    mesh = jax.make_mesh((1,), ("w",))
    ch, state = rch.channel_allocate(
        mesh, "w", 8,
        [rch.Lane("ids", (2,), jnp.int32), rch.Lane("kv", (2,), jnp.float32)])
    assert state.buf.dtype == jnp.uint32
    want = np.asarray(payload, ch.lane(name).dtype)
    msgs = ch.pack(name, jnp.asarray(want), jnp.asarray([7], jnp.int32))
    assert msgs.dtype == jnp.uint32
    hdr = np.asarray(msgs[:, :rch.HDR]).view(np.int32)
    batch = rch.RecvBatch(lane_id=jnp.asarray(hdr[:, 0]), src=jnp.asarray(hdr[:, 1]),
                          tag=jnp.asarray(hdr[:, 2]), words=msgs[:, rch.HDR:],
                          valid=jnp.asarray([True]))
    got, mask = ch.payload(batch, name)
    assert bool(mask[0]) and int(batch.tag[0]) == 7
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                  want.view(np.uint32))


# ----------------------------------------------------- multi-device subtests
def test_rmaq_spmd_xla_and_pallas_paths():
    run_subtest("rmaq_sub.py", devices=4)


def test_disaggregated_serving():
    run_subtest("disagg_sub.py", devices=4)
