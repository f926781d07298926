"""The trace reduction, on interval arithmetic and on a trace recorded on
the chip (a TPU v5 lite): one traced wave of a smoke-width cell, with the
client's record of what it ran beside it."""

import json

import pytest

from bench import trace_reduce
from bench.client import DECODE_PROGRAM, PREFILL_PROGRAM
from helpers import DATA

TRACE = DATA / "smoke_decode.xplane.pb"
RECORD = DATA / "smoke_decode.json"


def test_union_clip_and_gaps():
    u = trace_reduce.union([[5, 7], [1, 3], [2, 4], [7, 8]])
    assert u == [[1, 4], [5, 8]]
    assert trace_reduce.clip(u, 2, 6) == [[2, 4], [5, 6]]
    assert trace_reduce.gaps(u, 0, 10) == [[0, 1], [4, 5], [8, 10]]
    assert trace_reduce.gaps([], 0, 3) == [[0, 3]]


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.summarize(str(TRACE))


def test_window_and_busy(summary):
    assert summary.devices == 1
    assert 0 < summary.busy_s <= summary.window_s


def test_one_module_execution_per_step(summary):
    rec = json.loads(RECORD.read_text())
    assert len(summary.module_times(DECODE_PROGRAM)) == rec["traced_ticks"]
    assert len(summary.module_times(PREFILL_PROGRAM)) == rec["traced_prefills"]
    assert all(t > 0 for t in summary.module_times(DECODE_PROGRAM))


def test_breakdown(summary):
    assert 0 < len(summary.device_ops) <= trace_reduce.TOP
    assert 0 < len(summary.idle_gaps) <= trace_reduce.TOP
    # innermost ops do not overlap, so their time fits in the busy time
    assert sum(t for _, t in summary.device_ops) <= summary.busy_s + 1e-9
    assert all(n.split("/")[0].startswith("jit_") for n, _ in summary.device_ops)
    idle = sum(t for _, t in summary.idle_gaps)
    assert idle <= summary.window_s - summary.busy_s + 1e-9


def test_per_layer_readers_read_the_trace(summary):
    """Every per-layer metric of a decode cell that reads the device trace
    reads the recorded trace beside the client's record: a share lies in
    (0, 100].  (The counter readers read the process's registry, which
    holds nothing unless an engine has run in this process.)"""
    from bench import spec
    from helpers import SMOKE_CONFIGS, recorded_run, smoke_cell

    cell = smoke_cell(SMOKE_CONFIGS[0])
    run = recorded_run(cell.config, summary)
    traced = [m for m in cell.per_layer if m["source"] == "device_trace"]
    assert traced
    for m in traced:
        value = spec.metric_reader(m["name"])(run)
        assert value is not None and value > 0, m["name"]
        if m["unit"] == "%":
            assert value <= 100, (m["name"], value)


def test_op_label():
    assert trace_reduce.op_label(
        "%fusion.160 = bf16[1920,13696]{1,0:T(8,128)(2,1)} fusion(bf16[28,4096]"
        "{1,0} %get-tuple-element.928), kind=kOutput") == "fusion.160 bf16[1920,13696] fusion"
    assert trace_reduce.op_label(
        "%while.47 = (s32[]{:T(128)}, bf16[16,1,4096]{2,0,1}) while((s32[]) %t)"
    ) == "while.47 (...) while"


def test_idle_gap_goes_to_the_deepest_host_event():
    host = [(0, 10, "bench.schedule"), (2, 6, "PjitFunction(decode_step)"),
            (2.5, 3.5, "DevicePut"), (8, 8.5, "PjitFunction(argmax)")]
    assert trace_reduce._attribute([1, 3, 5, 9, 11], host) == [
        "bench.schedule", "DevicePut", "PjitFunction(decode_step)",
        "bench.schedule", "(no host event)"]
