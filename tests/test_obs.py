"""Observability layer (DESIGN.md §12): tracer, clock seam, metrics
registry, Chrome-trace export determinism, and the drift harness.

The two satellite contracts pinned here:

  * **Trace determinism** — the same ``(seed, schedule)`` conformance run
    exports byte-identical traces across two runs (virtual clock domain),
    including at the acceptance criterion's 256 ranks.
  * **No-op invariance** — running instrumented code with no tracer (the
    default `NullTracer`) produces exactly the same protocol results as a
    traced run: instrumentation observes, never perturbs.
"""

from __future__ import annotations

import json

import pytest

from repro.obs import trace as obs_trace
from repro.obs.export import chrome_trace, dumps_chrome_trace
from repro.obs.metrics import Histogram, MetricsRegistry, snapshot_delta
from repro.obs.trace import NULL_SPAN, NULL_TRACER, Tracer, set_tracer


@pytest.fixture(autouse=True)
def _restore_tracer():
    """Every test leaves the process-wide tracer as it found it."""
    prev = obs_trace.TRACER
    yield
    set_tracer(prev)


# ================================================================== tracer
class TestTracer:
    def test_default_is_noop(self):
        assert obs_trace.TRACER is NULL_TRACER
        assert not obs_trace.TRACER.enabled
        # the null span is a shared singleton: no allocation on hot paths
        assert obs_trace.TRACER.span("x") is NULL_SPAN
        with obs_trace.TRACER.span("x") as sp:
            sp.set(a=1)                          # absorbed silently

    def test_event_and_span_recording(self):
        tr = Tracer()
        tr.event("e.one", rank=3, n=7)
        with tr.span("s.outer", rank=1, k=2) as sp:
            tr.event("e.inner", rank=1)
            sp.set(raw=5, coalesced=1)
        assert [e["name"] for e in tr.events] == ["e.one", "e.inner", "s.outer"]
        outer = tr.named("s.outer")[0]
        assert outer["ph"] == "X"
        assert outer["args"] == {"k": 2, "raw": 5, "coalesced": 1}
        assert outer["dur"] >= 0
        assert tr.ranks() == [1, 3]
        assert len(tr.by_rank(1)) == 2

    def test_span_nesting_intervals_contain_children(self):
        tr = Tracer(clock=_TickClock())
        with tr.span("outer", rank=0):
            with tr.span("inner", rank=0):
                pass
        inner, outer = tr.named("inner")[0], tr.named("outer")[0]
        assert outer["ts"] <= inner["ts"]
        assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]

    def test_context_manager_installs_and_restores(self):
        assert obs_trace.TRACER is NULL_TRACER
        with Tracer() as tr:
            assert obs_trace.TRACER is tr
        assert obs_trace.TRACER is NULL_TRACER

    def test_clock_seam_switches_domain(self):
        tr = Tracer()
        assert tr.clock_domain == "wall_us"
        clk = _TickClock()
        tr.attach_clock(clk)
        assert tr.clock_domain == "virtual"
        clk.now = 42
        tr.event("a")
        assert tr.events[-1]["ts"] == 42
        tr.detach_clock()
        assert tr.clock_domain == "wall_us"


class _TickClock:
    """Minimal stand-in for sim.sched.VirtualClock."""

    def __init__(self):
        self.now = 0


# ============================================== snapshot schema unification
class TestSnapshotUnification:
    def test_snapshot_delta_nested_and_missing_keys(self):
        cur = {"a": 5, "nested": {"x": 3, "y": 1}, "tag": "s", "new": 2}
        prev = {"a": 2, "nested": {"x": 1}, "tag": "s"}
        assert snapshot_delta(cur, prev) == {
            "a": 3, "nested": {"x": 2, "y": 1}, "tag": "s", "new": 2}
        assert snapshot_delta(cur, None) == cur

    def test_opcounter_delta(self):
        from repro.core.rma import OpCounter

        with OpCounter() as c:
            OpCounter.record("puts", 2, axis="x")
            before = c.snapshot()
            OpCounter.record("gets", 3, axis="x")
        d = c.delta(before)
        assert d["puts"] == 0 and d["gets"] == 3
        assert d["by_axis"]["x"] == {"gets": 3, "puts": 0}
        # accepts the live object too
        assert c.delta(c)["raw_msgs"] == 0

    def test_syncstats_delta(self):
        from repro.core.epoch import SyncStats

        with SyncStats() as s:
            SyncStats.record("flush_msgs", 4)
            before = s.snapshot()
            SyncStats.record("flush_msgs", 1)
            SyncStats.record("barrier_stages", 3)
        d = s.delta(before)
        assert d["flush_msgs"] == 1 and d["barrier_stages"] == 3

    def test_planstats_snapshot_shares_schema(self):
        from repro.core.plan import PlanStats

        st = PlanStats()
        st.raw, st.coalesced, st.bytes_wire = 8, 2, 64
        snap = st.snapshot()
        # same message-count key naming as OpCounter/SyncStats (§12.3)
        assert snap["raw_msgs"] == 8 and snap["coalesced_msgs"] == 2
        st.raw += 4
        assert st.delta(snap)["raw_msgs"] == 4

    def test_fabric_delta(self):
        import numpy as np

        from repro.core.fabric import LocalFabric

        fab = LocalFabric(2)
        cells = np.zeros((2, 1), np.int64)
        fab.register("cell", cells)
        before = fab.snapshot()
        fab.put(0, 1, "cell", (0,), 7)
        fab.flush(0)
        fab.fence()
        d = fab.delta(before)
        assert d["puts"] == 1 and d["epoch"] == 1
        assert d["sync_flush_msgs"] == 1

    def test_registry_ingests_all_four_schemas(self):
        import numpy as np

        from repro.core.epoch import SyncStats
        from repro.core.fabric import LocalFabric
        from repro.core.plan import PlanStats
        from repro.core.rma import OpCounter

        reg = MetricsRegistry()
        with OpCounter() as c:
            OpCounter.record("puts", 2, axis="w")
        reg.ingest("rma", c.snapshot())
        reg.ingest("sync", SyncStats().snapshot())
        reg.ingest("plan", PlanStats().snapshot())
        fab = LocalFabric(2)
        fab.register("cell", np.zeros((2, 1), np.int64))
        fab.fence()
        reg.ingest("fabric", fab.snapshot())
        flat = reg.flat()
        assert flat["rma.puts"] == 2
        assert flat["rma.by_axis.w.puts"] == 2       # nested dicts recurse
        assert "sync.flush_msgs" in flat
        assert "plan.raw_msgs" in flat
        assert flat["fabric.epoch"] == 1
        assert "fabric.sync_barrier_stages" in flat


# ======================================================== metrics registry
class TestMetricsRegistry:
    def test_get_or_create_keyed_by_labels(self):
        reg = MetricsRegistry()
        a = reg.counter("ops", axis="x")
        b = reg.counter("ops", axis="x")
        c = reg.counter("ops", axis="y")
        assert a is b and a is not c
        a.inc(3)
        assert reg.flat() == {"ops{axis=x}": 3, "ops{axis=y}": 0}

    def test_histogram_percentiles(self):
        h = Histogram()
        for v in range(1, 101):
            h.observe(float(v))
        s = h.summary()
        assert s["count"] == 100 and s["min"] == 1.0 and s["max"] == 100.0
        assert s["p50"] == 51.0 and s["p99"] == 99.0
        assert Histogram().summary()["count"] == 0

    def test_flat_is_deterministic(self):
        reg = MetricsRegistry()
        reg.gauge("b").set(2)
        reg.gauge("a").set(1)
        reg.histogram("h").observe(5.0)
        assert list(reg.flat()) == ["a", "b", "h"]
        assert reg.flat()["h"]["count"] == 1


# ============================================= trace determinism (satellite)
class TestTraceDeterminism:
    def _traced(self, protocol, ranks, schedule, seed):
        from repro.sim.conformance import run_one

        tr = Tracer()
        report = run_one(protocol, ranks, schedule, seed, tracer=tr)
        return tr, report

    def test_byte_identical_across_replays(self):
        tr1, _ = self._traced("queue", 64, "reorder", 0)
        tr2, _ = self._traced("queue", 64, "reorder", 0)
        assert tr1.clock_domain == "virtual"       # the Scheduler attached
        b1, b2 = dumps_chrome_trace(tr1), dumps_chrome_trace(tr2)
        assert b1 == b2
        assert len(tr1.events) > 0

    def test_different_seed_different_trace(self):
        tr1, _ = self._traced("epoch", 16, "delay", 0)
        tr2, _ = self._traced("epoch", 16, "delay", 1)
        assert dumps_chrome_trace(tr1) != dumps_chrome_trace(tr2)

    def test_256_rank_trace_byte_identical_and_loadable(self):
        """The acceptance criterion: 256 ranks, virtual time, Perfetto-shaped."""
        tr1, _ = self._traced("epoch", 256, "reorder", 0)
        tr2, _ = self._traced("epoch", 256, "reorder", 0)
        b1 = dumps_chrome_trace(tr1)
        assert b1 == dumps_chrome_trace(tr2)
        doc = json.loads(b1)
        assert doc["metadata"]["clock_domain"] == "virtual"
        evs = doc["traceEvents"]
        # per-rank thread tracks plus the control track
        names = {e["args"]["name"] for e in evs if e["name"] == "thread_name"}
        assert "control" in names
        assert {f"rank {r}" for r in (0, 255)} <= names
        # every non-metadata event is a well-formed complete/instant event
        for e in evs:
            if e["ph"] == "M":
                continue
            assert e["ph"] in ("X", "i") and "ts" in e and "tid" in e

    def test_run_one_restores_previous_tracer(self):
        from repro.sim.conformance import run_one

        assert obs_trace.TRACER is NULL_TRACER
        run_one("epoch", 8, "delay", 0, tracer=Tracer())
        assert obs_trace.TRACER is NULL_TRACER

    def test_suite_exports_failing_run_traces(self, tmp_path):
        from repro.sim.conformance import run_suite

        # tear is the fault-injection schedule: the queue protocol MUST
        # fail under it, and the suite must export that run's trace
        results = run_suite(["queue"], 32, ["tear"], [0],
                            trace_dir=str(tmp_path))
        assert any(not r["ok"] for r in results)
        failing = [r for r in results if not r["ok"]]
        for r in failing:
            assert r["trace"].endswith("queue-tear-seed0.trace.json")
            doc = json.loads(open(r["trace"]).read())
            assert doc["metadata"]["clock_domain"] == "virtual"
        assert obs_trace.TRACER is NULL_TRACER     # restored after the sweep


# ================================================ no-op invariance (satellite)
class TestNoopInvariance:
    def test_untraced_equals_traced_report(self):
        from repro.sim.conformance import run_one

        plain = run_one("queue", 32, "duplicate", 3)
        traced_tr = Tracer()
        traced = run_one("queue", 32, "duplicate", 3, tracer=traced_tr)
        assert plain == traced
        assert len(traced_tr.events) > 0           # the tracer did observe

    def test_flow_report_unchanged_under_tracing(self):
        from repro.sim.conformance import run_one

        plain = run_one("flow", 16, "reorder", 1)
        traced = run_one("flow", 16, "reorder", 1, tracer=Tracer())
        assert plain == traced


# ==================================== lock timeout diagnostics (satellite)
class TestLockTimeoutDiagnostics:
    def test_wait_and_attempts_carried(self):
        from repro.core.locks_sim import LockOrigin, LockTimeout, LockWindow

        win = LockWindow(p=1)
        holder = LockOrigin(win, rank=0)
        holder.lock_exclusive(0)
        blocked = LockOrigin(win, rank=1)
        with pytest.raises(LockTimeout) as ei:
            blocked.lock_shared(0, backoff=1e-6, max_retries=3)
        e = ei.value
        assert e.attempts == 3
        assert e.wait_s > 0
        assert "after 3 retries" in str(e)
        assert "held_by=rank 0" in str(e)          # pre-existing holder info

    def test_timeout_emits_trace_event(self):
        from repro.core.locks_sim import LockOrigin, LockTimeout, LockWindow

        win = LockWindow(p=1)
        LockOrigin(win, rank=0).lock_exclusive(0)
        with Tracer() as tr:
            with pytest.raises(LockTimeout):
                LockOrigin(win, rank=1).lock_shared(0, max_retries=2)
        (ev,) = tr.named("lock.timeout")
        assert ev["args"]["attempts"] == 2
        assert ev["args"]["op"] == "lock_shared"
        assert ev["args"]["wait_us"] >= 0


# ============================================================ drift harness
class TestDriftHarness:
    def _write_benches(self, root, tamper=None):
        from repro.core.perfmodel import DEFAULT_MODEL

        k, msg_bytes = 32, 8
        packed = DEFAULT_MODEL.select_aggregation(k, float(msg_bytes)) == "pack"
        wire = 1 if packed else k
        rma_plan = {
            "k_msgs": k, "msg_bytes": msg_bytes,
            "eager": {"raw_msgs": k, "wire_transfers": k},
            "coalesced": {"raw_msgs": k, "wire_transfers": wire},
        }
        serve_flow = {
            "queue_backpressure": {
                "retry": {"wire_transfers_per_append": 2,
                          "measured_msg_rate_per_s": 1e5},
                "credit": {"wire_transfers_per_append": 2,
                           "measured_msg_rate_per_s": 2e5},
            },
            "serve_engine": {
                "retry": {"retries": 3, "msg_stats": {"wire_msgs_per_step": 2}},
                "credit": {"retries": 0, "msg_stats": {"wire_msgs_per_step": 2}},
            },
            "model": {"modeled_msg_rate_per_s": 1e6},
        }
        rmem = {"inline": {"wire_transfers_per_append": 2},
                "paged": {"wire_transfers_per_append": 2}}
        if tamper:
            tamper(rma_plan, serve_flow, rmem)
        for name, doc in (("BENCH_rma_plan.json", rma_plan),
                          ("BENCH_serve_flow.json", serve_flow),
                          ("BENCH_rmem.json", rmem)):
            (root / name).write_text(json.dumps(doc))

    def test_matching_benches_pass_the_gate(self, tmp_path):
        from repro.obs import drift

        self._write_benches(tmp_path)
        entries = drift.gate(str(tmp_path),
                             json_path=str(tmp_path / "BENCH_drift.json"))
        assert entries and not drift.violations(entries)
        doc = json.loads((tmp_path / "BENCH_drift.json").read_text())
        assert doc["violations"] == 0
        assert doc["count_tol"] == drift.COUNT_TOL
        # rate rows are informational: present but never gated
        rates = [e for e in entries if not e["gate"]]
        assert rates and all(e["tol"] == drift.RATE_TOL for e in rates)

    def test_wire_count_drift_fails_the_gate(self, tmp_path):
        from repro.obs import drift

        def tamper(rma_plan, serve_flow, rmem):
            serve_flow["serve_engine"]["credit"]["msg_stats"][
                "wire_msgs_per_step"] = 3
        self._write_benches(tmp_path, tamper)
        with pytest.raises(SystemExit, match="drift beyond tolerance"):
            drift.gate(str(tmp_path))
        bad = drift.violations(drift.collect(str(tmp_path)))
        assert [e["metric"] for e in bad] == ["engine.credit.wire_msgs_per_step"]

    def test_credit_retries_are_gated_at_zero(self, tmp_path):
        from repro.obs import drift

        def tamper(rma_plan, serve_flow, rmem):
            serve_flow["serve_engine"]["credit"]["retries"] = 1
        self._write_benches(tmp_path, tamper)
        with pytest.raises(SystemExit):
            drift.gate(str(tmp_path))

    def test_rate_drift_is_informational_only(self, tmp_path):
        from repro.obs import drift

        def tamper(rma_plan, serve_flow, rmem):
            # 10x off the model: flagged in the table, never a gate failure
            serve_flow["queue_backpressure"]["credit"][
                "measured_msg_rate_per_s"] = 1e12
        self._write_benches(tmp_path, tamper)
        entries = drift.gate(str(tmp_path))
        assert not drift.violations(entries)

    def test_table_marks_drift_rows(self, tmp_path):
        from repro.obs import drift

        def tamper(rma_plan, serve_flow, rmem):
            rmem["paged"]["wire_transfers_per_append"] = 4
        self._write_benches(tmp_path, tamper)
        table = drift.format_table(drift.collect(str(tmp_path)))
        assert "DRIFT" in table and "| info |" in table


# ========================================================== serve latency
SERVE_SPANS = (
    "serve.admit", "serve.prefill.prepare", "serve.prefill.launch",
    "serve.prefill.readback", "serve.step", "serve.decode.prepare",
    "serve.decode.launch", "serve.decode.readback", "serve.decode.emit",
    "serve.recycle",
)


def _stub_engine(n_slots=2, registry=None):
    from repro.serve.engine import ServeEngine

    from .test_training import _StubServeModel

    return ServeEngine(_StubServeModel(), {}, n_slots=n_slots, max_seq=32,
                       metrics=MetricsRegistry() if registry is None else registry)


def _serve_stub() -> int:
    """Drain three requests through a stub-model engine; returns the number
    of `schedule()` calls.  Every engine phase runs at least once."""
    from repro.serve.engine import Request

    eng = _stub_engine()
    for i in range(3):
        eng.submit(Request(rid=i, prompt=[1, 2, 3], max_new=3))
    return eng.run_until_drained()


class TestServeLatencyMetrics:
    def test_engine_ttft_tbt_histograms(self):
        """What the engine records of a drained batch: its ten spans, its
        host-gap and compile counters, and the `serve.request.*` events from
        which `obs.critpath` cuts TTFT (requests are timed by their client)."""
        from repro.serve.engine import Request

        reg = MetricsRegistry()
        eng = _stub_engine(registry=reg)
        with Tracer() as tr:
            reqs = [Request(rid=i, prompt=[1, 2], max_new=4) for i in range(3)]
            for r in reqs:
                eng.submit(r)
            steps = eng.run_until_drained()
        assert {e["name"] for e in tr.events if e["ph"] == "X"} == set(SERVE_SPANS)
        assert len(tr.named("serve.step")) == len(tr.named("serve.admit")) == steps
        # two lanes: r0 and r1 prefill, decode 3 ticks and recycle, then r2
        # prefills and decodes 3 ticks.  Each program compiles on its first
        # dispatch; every later dispatch follows a read-back while the
        # engine holds work, r2's prefill included (the queue held it).
        c = reg.flat()
        assert c["serve.compiles{program=prefill}"] == 1
        assert c["serve.compiles{program=decode}"] == 1
        assert c["serve.compile_s{program=decode}"] > 0
        assert c["serve.prefill.host_gaps"] == 2
        assert c["serve.decode.host_gaps"] == 5
        assert c["serve.prefill.host_gap_s"] > 0 and c["serve.decode.host_gap_s"] > 0
        assert [len(r.output) for r in reqs] == [4, 4, 4]
        assert len(tr.named("serve.request.submit")) == 3
        assert len(tr.named("serve.request.first_token")) == 3
        assert len(tr.named("serve.request.drain")) == 3

    def test_chrome_export_carries_serve_events(self):
        from repro.serve.engine import Request, ServeEngine

        from .test_training import _StubServeModel

        eng = ServeEngine(_StubServeModel(), {}, n_slots=1, max_seq=32)
        with Tracer() as tr:
            eng.submit(Request(rid=7, prompt=[3], max_new=2))
            eng.run_until_drained()
        doc = chrome_trace(tr)
        names = {e["name"] for e in doc["traceEvents"]}
        assert {"serve.request.submit", "serve.request.first_token",
                "serve.request.drain"} <= names
        assert doc["metadata"]["clock_domain"] == "wall_us"


# =========================================== engine host-gap / compile counters
class TestEngineCounters:
    def test_default_registry_is_the_process_wide_one(self):
        from repro.obs.metrics import REGISTRY
        from repro.serve.engine import ServeEngine

        from .test_training import _StubServeModel

        eng = ServeEngine(_StubServeModel(), {}, n_slots=1, max_seq=32)
        assert eng._decode_counters.gaps is REGISTRY.counter("serve.decode.host_gaps")
        assert eng._prefill_counters.compile_s is REGISTRY.counter(
            "serve.compile_s", program="prefill")

    def test_one_prefill_compile_per_prompt_length(self):
        from repro.serve.engine import Request

        reg = MetricsRegistry()
        eng = _stub_engine(registry=reg)
        for i in range(2):
            eng.submit(Request(rid=i, prompt=[1, 2, 3], max_new=2))
        eng.run_until_drained()
        c = reg.flat()
        assert c["serve.compiles{program=prefill}"] == 1
        assert c["serve.compiles{program=decode}"] == 1
        eng.submit(Request(rid=2, prompt=[1, 2, 3, 4, 5], max_new=2))
        eng.run_until_drained()
        c = reg.flat()
        assert c["serve.compiles{program=prefill}"] == 2
        assert c["serve.compiles{program=decode}"] == 1
        assert c["serve.compile_s{program=prefill}"] > 0

    def test_a_gap_that_ends_in_a_compile_is_not_counted(self):
        """r1's prefill (a new length) and the first decode both follow a
        read-back with work held, and both compile: neither counts a gap."""
        from repro.serve.engine import Request

        reg = MetricsRegistry()
        eng = _stub_engine(registry=reg)
        eng.submit(Request(rid=0, prompt=[1, 2], max_new=3))
        eng.submit(Request(rid=1, prompt=[1, 2, 3], max_new=3))
        tick = eng.schedule()
        assert (tick.admitted, tick.emitted) == (2, 2)
        c = reg.flat()
        assert c["serve.compiles{program=prefill}"] == 2
        assert c["serve.compiles{program=decode}"] == 1
        assert c["serve.prefill.host_gaps"] == c["serve.decode.host_gaps"] == 0
        assert c["serve.prefill.host_gap_s"] == c["serve.decode.host_gap_s"] == 0
        eng.schedule()          # a decode that follows a read-back: counted
        assert reg.flat()["serve.decode.host_gaps"] == 1

    def test_first_dispatch_after_a_drain_counts_no_gap(self):
        """Whatever the caller does between drained batches is not a host
        gap: the next batch's first dispatch counts nothing."""
        import time

        from repro.serve.engine import Request

        reg = MetricsRegistry()
        eng = _stub_engine(registry=reg)
        eng.submit(Request(rid=0, prompt=[1, 2], max_new=2))
        eng.run_until_drained()
        before = reg.flat()
        time.sleep(0.2)
        eng.submit(Request(rid=1, prompt=[1, 2], max_new=1))   # prefill only
        eng.run_until_drained()
        after = reg.flat()
        assert after == before

    def test_host_gaps_count_dispatches_after_a_readback_with_work_held(self):
        """Over waves drained one by one, every dispatch that did not compile
        counts one gap, except each later wave's first, which follows a
        drained engine; the caller's pauses between waves land in none."""
        import time

        from repro.serve.engine import Request

        reg = MetricsRegistry()
        eng = _stub_engine(registry=reg)
        calls = {"prefill": 0, "decode": 0}

        def counted(program, fn):
            def call(*a, **k):
                calls[program] += 1
                return fn(*a, **k)
            return call

        eng._prefill = counted("prefill", eng._prefill)
        eng._decode = counted("decode", eng._decode)
        waves = 3
        for w in range(waves):
            for i in range(2):
                eng.submit(Request(rid=2 * w + i, prompt=[w + 1, 2], max_new=2 + 3 * i))
            eng.run_until_drained()
            time.sleep(0.1)
        c = reg.flat()
        assert calls["prefill"] == 2 * waves and calls["decode"] == 4 * waves
        assert c["serve.prefill.host_gaps"] == (
            calls["prefill"] - c["serve.compiles{program=prefill}"] - (waves - 1))
        assert c["serve.decode.host_gaps"] == (
            calls["decode"] - c["serve.compiles{program=decode}"])
        assert c["serve.prefill.host_gap_s"] + c["serve.decode.host_gap_s"] < 0.1


# ======================================== engine spans on the profiler's clock
class TestProfiledSpans:
    def test_disabled_span_is_a_bare_annotation(self):
        import jax

        assert not obs_trace.TRACER.enabled
        sp = obs_trace.profiled_span("serve.step", rank=2, k=1)
        assert isinstance(sp, jax.profiler.TraceAnnotation)
        with sp:
            pass

    def test_recording_tracer_gets_the_span_with_its_attrs(self):
        with Tracer() as tr:
            with obs_trace.profiled_span("outer", rank=1, k=2) as sp:
                with obs_trace.profiled_span("inner", rank=1):
                    pass
                sp.set(n=3)
            with pytest.raises(ValueError, match="reserved causal attrs"):
                obs_trace.profiled_span("s", edge="1:hop")
        inner, outer = tr.named("inner")[0], tr.named("outer")[0]
        assert outer["args"] == {"k": 2, "n": 3} and outer["rank"] == 1
        assert outer["ts"] <= inner["ts"]
        assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]

    def test_engine_spans_land_in_the_profiler_trace(self, tmp_path):
        """Profile a stub-model engine as the benchmark does (no Python
        tracer): every engine span sits on the line of the thread that
        called `schedule()`, inside that thread's own annotation, with the
        prefill phases inside `serve.admit` and the decode phases inside
        `serve.step`."""
        import glob
        import os

        import jax
        from jax.profiler import ProfileData

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("test.scheduler"):
                steps = _serve_stub()
        finally:
            jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*", "*.xplane.pb"))
        lines = [ln for p in ProfileData.from_file(path).planes
                 if p.name.startswith("/host:") for ln in p.lines
                 if any(ev.name == "test.scheduler" for ev in ln.events)]
        assert len(lines) == 1
        evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
               for ev in lines[0].events]
        (lo, hi), = [(s, e) for n, s, e in evs if n == "test.scheduler"]
        spans = {n: [(s, e) for m, s, e in evs if m == n] for n in SERVE_SPANS}
        assert all(spans.values()), {n: len(v) for n, v in spans.items()}
        assert all(lo <= s <= e <= hi for v in spans.values() for s, e in v)
        assert len(spans["serve.admit"]) == len(spans["serve.step"]) == steps
        assert len(spans["serve.prefill.launch"]) == 3

        def inside(child, parent):
            return all(any(ps <= s and e <= pe for ps, pe in spans[parent])
                       for s, e in spans[child])

        for name in SERVE_SPANS:
            if name.startswith("serve.prefill."):
                assert inside(name, "serve.admit"), name
            if name.startswith("serve.decode."):
                assert inside(name, "serve.step"), name

    def test_recording_tracer_records_the_engine_spans(self):
        with Tracer() as tr:
            steps = _serve_stub()
        recorded = {e["name"] for e in tr.events if e["ph"] == "X"}
        assert recorded == set(SERVE_SPANS)
        assert len(tr.named("serve.step")) == len(tr.named("serve.admit")) == steps
        assert len(tr.named("serve.prefill.readback")) == 3
        # each request finishes once: by one recycle on either path
        assert len(tr.named("serve.request.drain")) == 3


# ===================================================== attend-step latency
class TestAttendLatencyHistogram:
    """§13 per-decode-step `serve.attend_us` rides the same exact-order-
    statistics histogram as TTFT/TBT: nearest-rank percentiles, no bucket
    error, empty-safe summaries."""

    def test_exact_nearest_rank_percentiles(self):
        reg = MetricsRegistry()
        h = reg.histogram("serve.attend_us")
        vals = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0, 8.0, 4.0, 6.0, 10.0]
        for v in vals:
            h.observe(v)
        xs = sorted(vals)
        for q in (0, 50, 90, 99, 100):
            rank = max(0, min(len(xs) - 1,
                              int(round(q / 100.0 * (len(xs) - 1)))))
            assert h.percentile(q) == xs[rank]
        s = h.summary()
        # nearest-rank on n=10: p50 -> rank round(4.5)=4, p90 -> 8, p99 -> 9
        assert s == {"count": 10, "sum": 55.0, "min": 1.0, "max": 10.0,
                     "p50": 5.0, "p90": 9.0, "p99": 10.0}

    def test_registry_get_or_create_accumulates(self):
        reg = MetricsRegistry()
        reg.histogram("serve.attend_us").observe(3.0)
        reg.histogram("serve.attend_us").observe(4.0)   # same instance
        assert reg.histogram("serve.attend_us").summary()["count"] == 2

    def test_empty_attend_histogram_is_zero_summary(self):
        s = Histogram().summary()
        assert s["count"] == 0
        assert all(s[k] == 0.0 for k in ("sum", "min", "max", "p50", "p90",
                                         "p99"))

    def test_single_observation_all_percentiles_equal(self):
        h = Histogram()
        h.observe(42.0)
        assert h.percentile(50) == h.percentile(99) == 42.0


# ============================================ disabled-span contract (§15 s1)
class TestNullSpanContract:
    def test_null_span_is_shared_and_absorbing(self):
        # one module-level singleton: every disabled span IS the same object
        assert NULL_TRACER.span("a") is NULL_TRACER.span("b") is NULL_SPAN
        sp = NULL_TRACER.span("x", rank=3, k=1)
        assert sp.set(raw=5) is sp               # chains, discards
        with sp as inner:
            assert inner is sp

    def test_null_tracer_mirrors_tracer_surface(self):
        # instrumented code never branches on tracer *type*; the two
        # tracers must expose the same callables
        for name in ("event", "span", "attach_clock", "detach_clock",
                     "enabled"):
            assert hasattr(NULL_TRACER, name), name
        NULL_TRACER.event("e", rank=0, a=1)      # all no-ops, no state
        NULL_TRACER.attach_clock(_TickClock())
        NULL_TRACER.detach_clock()

    def test_span_rejects_reserved_causal_attrs(self):
        # edge/cause are instant-event links (obs.causal): a span interval
        # has no single firing point, so the producer fails loudly
        tr = Tracer()
        with pytest.raises(ValueError, match="reserved causal attrs"):
            tr.span("s", rank=0, edge="1:hop")
        with pytest.raises(ValueError, match="reserved causal attrs"):
            tr.span("s", rank=0, cause="1:hop")
        tr.event("e", rank=0, edge="1:hop", cause="2:hop")  # events: fine
        assert tr.events[-1]["args"]["edge"] == "1:hop"

    def test_null_span_skips_validation(self):
        # the disabled path does zero work — including the reserved-attr
        # check (kwargs are never inspected when tracing is off)
        assert NULL_TRACER.span("s", edge="1:hop") is NULL_SPAN

    def test_disabled_path_cost_microbench(self):
        """Pin the zero-cost-when-off contract: the guarded disabled path
        (attribute load + falsy branch) must be far cheaper than recording.
        The 2x bound is deliberately generous — the real ratio is >10x —
        so a noisy CI runner cannot flake this, but an accidental dict
        build or lock acquisition on the disabled path still fails it."""
        import time

        n = 20_000

        def loop(tr):
            t0 = time.perf_counter()
            for _ in range(n):
                if tr.enabled:
                    tr.event("bench.op", rank=0, a=1, b=2)
            return time.perf_counter() - t0

        disabled = min(loop(NULL_TRACER) for _ in range(3))
        enabled = min(loop(Tracer()) for _ in range(3))
        assert disabled * 2 < enabled, (disabled, enabled)


# ===================================== histogram deltas + exemplars (§15 s2)
class TestHistogramSnapshotDelta:
    def test_hist_delta_summarizes_the_suffix(self):
        h = Histogram()
        h.observe(1.0)
        h.observe(5.0)
        before = {"lat": h.snapshot(), "n": 2}
        h.observe(9.0)
        h.observe(3.0)
        cur = {"lat": h.snapshot(), "n": 4}
        d = snapshot_delta(cur, before)
        assert d["n"] == 2
        # percentiles don't subtract: the delta is the summary of ONLY the
        # observations recorded between the two snapshots
        assert d["lat"]["count"] == 2
        assert d["lat"]["sum"] == 12.0
        assert d["lat"]["min"] == 3.0 and d["lat"]["max"] == 9.0

    def test_hist_delta_against_nothing_is_the_full_summary(self):
        h = Histogram()
        for v in (2.0, 4.0):
            h.observe(v)
        d = snapshot_delta({"lat": h.snapshot()}, None)
        assert d["lat"]["count"] == 2 and d["lat"]["sum"] == 6.0

    def test_empty_suffix_is_a_zero_summary(self):
        h = Histogram()
        h.observe(7.0)
        snap = {"lat": h.snapshot()}
        d = snapshot_delta({"lat": h.snapshot()}, snap)
        assert d["lat"]["count"] == 0

    def test_p99_exemplar_names_the_tail_request(self):
        h = Histogram()
        for rid, v in enumerate([10.0, 20.0, 300.0]):
            h.observe(v, exemplar=rid)
        s = h.summary()
        assert s["p99"] == 300.0
        assert s["p99_exemplar"] == 2            # the rid to go look at

    def test_exemplar_free_summary_keeps_prior_shape(self):
        h = Histogram()
        h.observe(5.0)
        assert "p99_exemplar" not in h.summary()

    def test_latest_exemplar_wins_per_value(self):
        h = Histogram()
        h.observe(9.0, exemplar=1)
        h.observe(9.0, exemplar=2)
        assert h.summary()["p99_exemplar"] == 2


# ================================== export: gzip + bounded traces (§15 s3)
class TestExportGzipAndTruncation:
    def _filled(self, n=10):
        tr = Tracer(clock=_TickClock())
        for i in range(n):
            tr.event(f"e{i}", rank=0)
        return tr

    def test_gzip_roundtrip_and_suffix(self, tmp_path):
        import gzip

        from repro.obs.export import dump_chrome_trace

        tr = self._filled(3)
        path = dump_chrome_trace(tr, str(tmp_path / "t.json"), gzipped=True)
        assert path.endswith("t.json.gz")
        raw = gzip.decompress((tmp_path / "t.json.gz").read_bytes())
        assert raw.decode() == dumps_chrome_trace(tr)

    def test_gzip_bytes_are_a_pure_function_of_the_payload(self, tmp_path):
        from repro.obs.export import dump_chrome_trace

        tr = self._filled(3)
        dump_chrome_trace(tr, str(tmp_path / "a.json"), gzipped=True)
        dump_chrome_trace(tr, str(tmp_path / "b.json"), gzipped=True)
        # mtime pinned to 0, no embedded filename: byte-identity survives
        # compression, so gzipped flight dumps still replay exactly
        assert (tmp_path / "a.json.gz").read_bytes() == \
               (tmp_path / "b.json.gz").read_bytes()

    def test_max_events_keeps_newest_with_marker(self):
        tr = self._filled(10)
        doc = chrome_trace(tr, max_events=4)
        kept = [e["name"] for e in doc["traceEvents"]
                if e["name"].startswith("e")]
        assert kept == ["e6", "e7", "e8", "e9"]  # newest survive
        (mark,) = [e for e in doc["traceEvents"]
                   if e["name"] == "trace.truncated"]
        assert mark["args"] == {"dropped": 6, "kept": 4}
        assert doc["metadata"]["dropped_events"] == 6

    def test_untruncated_trace_has_no_marker(self):
        doc = chrome_trace(self._filled(3))
        assert not [e for e in doc["traceEvents"]
                    if e["name"] == "trace.truncated"]
        assert doc["metadata"]["dropped_events"] == 0

    def test_truncation_is_logged_to_stderr(self, tmp_path, capsys):
        from repro.obs.export import dump_chrome_trace

        dump_chrome_trace(self._filled(10), str(tmp_path / "t.json"),
                          max_events=4)
        err = capsys.readouterr().err
        assert "truncated" in err and "6 oldest events cut" in err
