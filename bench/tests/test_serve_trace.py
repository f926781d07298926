"""The engine's host phases in a trace recorded on the chip (a TPU v5 lite)
from a program whose phases are profiled spans (`record_smoke_trace.py`
wrote it): the spans lie on the client's thread and line up with the
device programs, and the reduction puts idle onto them."""

import json

import pytest

from bench import trace_reduce
from bench.client import DECODE_PROGRAM, PREFILL_PROGRAM
from helpers import DATA

TRACE = DATA / "smoke_serve.xplane.pb"
RECORD = DATA / "smoke_serve.json"
SPANS = ("serve.admit", "serve.prefill.prepare", "serve.prefill.launch",
         "serve.prefill.readback", "serve.step", "serve.decode.prepare",
         "serve.decode.launch", "serve.decode.readback", "serve.decode.emit",
         "serve.recycle")


@pytest.fixture(scope="module")
def client_line():
    """The window and every event of the client thread's host line."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(str(TRACE)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                   for ev in line.events]
            window = [(s, e) for n, s, e in evs if n == trace_reduce.WINDOW_SPAN]
            if window:
                return window[0], evs
    pytest.fail(f"no {trace_reduce.WINDOW_SPAN} span in the trace")


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.summarize(str(TRACE))


def test_one_launch_span_per_device_program(client_line, summary):
    rec = json.loads(RECORD.read_text())
    (lo, hi), evs = client_line
    spans = {n: [(s, e) for m, s, e in evs if m == n and lo <= s and e <= hi]
             for n in SPANS}
    assert all(spans.values()), {n: len(v) for n, v in spans.items()}
    assert len(spans["serve.decode.launch"]) == rec["traced_ticks"]
    assert len(summary.module_times(DECODE_PROGRAM)) == rec["traced_ticks"]
    assert len(spans["serve.prefill.launch"]) == rec["traced_prefills"]
    assert len(summary.module_times(PREFILL_PROGRAM)) == rec["traced_prefills"]
    for name in SPANS:
        parent = {"serve.prefill": "serve.admit",
                  "serve.decode": "serve.step"}.get(name.rsplit(".", 1)[0])
        if parent:
            assert all(any(ps <= s and e <= pe for ps, pe in spans[parent])
                       for s, e in spans[name]), name


def test_recorded_trace_puts_idle_on_the_engine_spans(summary):
    idle = dict(summary.idle_gaps)
    serve = {n: t for n, t in idle.items() if n.startswith("serve.")}
    assert serve, summary.idle_gaps
    # bench.schedule itself is left only the call into schedule()
    assert idle.get("bench.schedule", 0.0) < 0.1 * sum(serve.values())
