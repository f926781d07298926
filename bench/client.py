"""The client loop: drives `ServeEngine` with closed waves and keeps time.

Single-threaded: `submit` a wave of `n_slots` requests, call `schedule()`
until every one has drained, then submit the next.  A token is stamped when
the `schedule()` call that produced it returns; the engine syncs on every
tick (it reads the sampled tokens back to the host), so the token is there
by then.  The window runs from the first wave's submission to the drain of
the last wave started before `seconds` ran out (with `close_on: cycle`, the
last whole cycle of prompt lengths).

Host spans `bench.client`, `bench.submit_wave` and `bench.schedule` go into
the profiler's trace when one is recording, so idle gaps on the device can
be put down to what the host was doing.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Iterator, Optional

from jax.profiler import TraceAnnotation

# Names that the program gives its two jitted step programs; the trace's
# module names start with them.
DECODE_PROGRAM = "jit_decode_step"
PREFILL_PROGRAM = "jit__prefill_impl"


@dataclasses.dataclass
class RequestRecord:
    rid: int
    wave: int
    plen: int
    max_new: int
    prompt: list
    t_submit: float
    t_first: float = 0.0
    t_last: float = 0.0
    output: list = dataclasses.field(default_factory=list)
    seen: int = 0


@dataclasses.dataclass
class Tick:
    """One decode step: its active slots all hold their new token at
    `position`; `traced` when the profiler was recording it."""
    position: int
    active: int
    traced: bool


@dataclasses.dataclass
class Prefill:
    plen: int
    traced: bool


@dataclasses.dataclass
class Window:
    requests: list
    ticks: list
    prefills: list
    t_start: float
    t_end: float
    waves: int

    @property
    def seconds(self) -> float:
        return self.t_end - self.t_start


def run_wave(engine, wave, rid0: int, traced: bool, request_cls,
             ticks: list, prefills: list) -> list:
    """Submit one wave and schedule until it has drained."""
    with TraceAnnotation("bench.submit_wave"):
        reqs = [request_cls(rid=rid0 + i, prompt=wave.prompts[i].tolist(),
                            max_new=wave.max_new[i])
                for i in range(len(wave.max_new))]
        t_sub = time.perf_counter()
        for r in reqs:
            engine.submit(r)
    recs = [RequestRecord(r.rid, wave.index, wave.plen, r.max_new, r.prompt, t_sub)
            for r in reqs]
    prefills.extend(Prefill(wave.plen, traced) for _ in reqs)
    pos = wave.plen
    while not all(r.done.is_set() for r in reqs):
        with TraceAnnotation("bench.schedule"):
            tick = engine.schedule()
        now = time.perf_counter()
        if tick.emitted:
            ticks.append(Tick(pos, tick.emitted, traced))
            pos += 1
        for r, rec in zip(reqs, recs):
            if len(r.output) > rec.seen:
                if not rec.seen:
                    rec.t_first = now
                rec.t_last = now
                rec.seen = len(r.output)
    for r, rec in zip(reqs, recs):
        rec.output = list(r.output)
    return recs


def drive(engine, waves: Iterator, seconds: float, request_cls,
          cycle_len: int, close_on: str,
          trace_hook: Optional[Callable[[int, bool], bool]] = None,
          min_waves: int = 1) -> Window:
    """Run the measured window of at least `min_waves` waves.
    `trace_hook(wave_index, start)` is called before and after each wave, so
    a traced run can start and stop the profiler on wave boundaries; before
    a wave it says whether the profiler records it."""
    requests, ticks, prefills = [], [], []
    t0 = t_end = time.perf_counter()
    deadline = t0 + seconds
    n = 0
    for wave in waves:
        if n >= min_waves and t_end >= deadline and (
                close_on == "wave" or n % cycle_len == 0):
            break
        traced = bool(trace_hook and trace_hook(n, True))
        requests += run_wave(engine, wave, len(requests), traced,
                             request_cls, ticks, prefills)
        t_end = time.perf_counter()
        if trace_hook:
            trace_hook(n, False)
        n += 1
    return Window(requests, ticks, prefills, t0, t_end, n)


def wave_summary(window: Window) -> list:
    """Per wave: prompt length, seconds from submission to drain, seconds to
    its first tokens, tokens served."""
    out = {}
    for r in window.requests:
        w = out.setdefault(r.wave, {"wave": r.wave, "plen": r.plen,
                                    "t0": r.t_submit, "t1": r.t_last,
                                    "ttft_s": r.t_first - r.t_submit, "tokens": 0})
        w["t1"] = max(w["t1"], r.t_last)
        w["tokens"] += len(r.output)
    return [{"wave": w["wave"], "plen": w["plen"],
             "seconds": round(w["t1"] - w["t0"], 4),
             "ttft_s": round(w["ttft_s"], 4), "tokens": w["tokens"]}
            for w in out.values()]
