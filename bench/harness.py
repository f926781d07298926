"""One run of one cell: set-up, the measured window, metrics and the check.

`run_cell` is what `bench/run.py` calls.  Tests call it on the CPU at smoke
width with `require_chip=False`; nothing else differs between the two.
`Session` holds what set-up builds, so that `bench/calibrate.py` can serve
many seeds through one engine.
"""

from __future__ import annotations

import dataclasses
import gc
import glob
import os
import shutil
import sys
import tempfile
import time
import types
from typing import Optional

import jax

from bench import check, client, spec, trace_reduce, traffic as traffic_mod
from bench.family import root_key
from bench.peaks import peaks_for


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Run:
    """What the metric readers read.  `family` is the configuration's
    family module, whose `prefill_work` and `decode_work` the roofline and
    MFU readers count with."""
    family: types.ModuleType
    dims: dict
    n_slots: int
    chips: int
    peaks: Optional[dict]
    setup_s: float
    window: client.Window
    trace: Optional[trace_reduce.Summary] = None


def devices_for(cell: spec.Cell, require_chip: bool) -> list:
    devs = jax.devices()
    if require_chip and (devs[0].platform == "cpu" or len(devs) < cell.chips):
        raise NoChip(f"cell {cell.name} needs {cell.chips} accelerator chip(s); "
                     f"JAX found {len(devs)} {devs[0].platform} device(s)")
    return devs[:cell.chips]


class CountCompiles:
    """Counts backend compiles and persistent-cache hits and misses while it
    is entered: no compile may fall in the window, whose every shape the
    warm-up compiled, and a second run of a cell compiles nothing."""

    def __init__(self):
        self.n = self.hits = self.misses = 0

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def __str__(self) -> str:
        return (f"{self.n} backend compiles, {self.hits} cache hits, "
                f"{self.misses} cache misses")


class Session:
    """The engine of one cell, with weights from one seed at a time."""

    def __init__(self, cell: spec.Cell):
        from repro.configs import get_config
        from repro.models import build_model
        from repro.serve.engine import Request, ServeEngine

        self.config, self.traffic = cell.config, cell.traffic
        self.dims = self.config["dims"]
        eng = self.config["engine"]
        self.n_slots, self.max_seq = eng["n_slots"], eng["max_seq"]
        arch = get_config(self.config["arch"], smoke=self.config.get("smoke", False))
        self.family = spec.family_module(self.config)
        self.family.check_widths(self.config, arch)
        self.model = build_model(arch)
        self.family.check_layout(self.model.init_shapes(), self.dims)
        traffic_mod.validate(self.traffic, self.max_seq)
        self.Request = Request
        self._make = jax.jit(lambda r: self.family.program_params(r, self.dims))
        self.engine = ServeEngine(self.model, None, n_slots=self.n_slots,
                                  max_seq=self.max_seq)

    def load(self, seed: int) -> None:
        """Make the seed's weights on the device, in one jitted call."""
        self.engine.params = None
        gc.collect()
        self.engine.params = self._make(root_key(seed))
        jax.block_until_ready(self.engine.params)

    def release(self) -> None:
        """Free the weights, so the reference has the device to itself."""
        self.engine.params = None
        gc.collect()

    def warm_up(self) -> None:
        """Compile every shape the window uses: one prefill per prompt length,
        the decode step, and the host-side ops around them."""
        for i, plen in enumerate(sorted(set(self.traffic["prompt_len_cycle"]))):
            self.engine.submit(self.Request(rid=-1 - i, prompt=[i] * plen, max_new=2))
            self.engine.run_until_drained()

    def window(self, seed: int, seconds: float, trace_hook=None,
               min_waves: int = 1) -> client.Window:
        tr = self.traffic
        waves = traffic_mod.waves(tr, self.n_slots, self.dims["vocab"],
                                  self.max_seq, seed)
        return client.drive(self.engine, waves, seconds, self.Request,
                            len(tr["prompt_len_cycle"]), tr["close_on"],
                            trace_hook=trace_hook, min_waves=min_waves)


class Tracer:
    """Starts the profiler before wave `first` and stops it after `count`
    waves; the host span `bench.client` marks the traced window."""

    def __init__(self, first: int, count: int):
        self.first, self.last = first, first + count - 1
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self._span = None

    def __call__(self, wave: int, start: bool) -> bool:
        if start and wave == self.first:
            # no Python tracer: it records every Python call, slows the
            # client loop several times over and swells the trace tenfold
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation("bench.client")
            self._span.__enter__()
        if not start and wave == self.last:
            self.stop()
        return self.first <= wave <= self.last

    def stop(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
            jax.profiler.stop_trace()

    def summary(self) -> trace_reduce.Summary:
        found = sorted(glob.glob(os.path.join(self.dir, "plugins", "profile",
                                              "*", "*.xplane.pb")))
        if not found:
            raise RuntimeError("the profiler wrote no trace")
        try:
            return trace_reduce.summarize(found[-1])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def memory_peak_bytes(devices) -> int:
    return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices))


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_process: float, require_chip: bool = True,
             peaks: Optional[dict] = None, log=None) -> dict:
    """Set up, measure, read metrics, check; returns the result object."""
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    devices = devices_for(cell, require_chip)
    dev = devices[0]
    if peaks is None:
        peaks = peaks_for(dev.device_kind)

    # ---------------------------------------------------------- set-up
    def stamp(what):
        log(f"set-up: {what} at {time.perf_counter() - t_process:.3f} s")

    stamp("devices found")
    with CountCompiles() as setup_compiles:
        s = Session(cell)
        stamp(f"engine built, {s.family.n_params(s.dims)} parameters")
        s.load(seed)
        stamp("weights made")
        s.warm_up()
    setup_s = time.perf_counter() - t_process
    log(f"setup_s {setup_s!r}; {setup_compiles}")

    # ---------------------------------------------------------- window
    tracer = Tracer(1, int(s.traffic["trace_waves"])) if trace else None
    try:
        with CountCompiles() as compiles:
            window = s.window(seed, seconds, trace_hook=tracer,
                              min_waves=1 + int(s.traffic["trace_waves"]) if trace else 1)
    finally:
        if tracer is not None:
            tracer.stop()
    mem_peak = memory_peak_bytes(devices)
    log(f"window {window.seconds!r} s, {window.waves} waves, "
        f"{len(window.requests)} requests, {compiles.n} compiles in the window")
    for w in client.wave_summary(window):
        log(" ".join(f"{k} {v}" for k, v in w.items()))

    run = Run(s.family, s.dims, s.n_slots, cell.chips, peaks, setup_s, window)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    extra = {}
    if tracer is not None:
        run.trace = tracer.summary()
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        extra["breakdown"] = {"device_ops": run.trace.device_ops,
                              "idle_gaps": run.trace.idle_gaps}

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    # ----------------------------------------------------------- check
    s.release()
    verdict = check.check_window(window.requests, s.config, s.traffic, seed,
                                 spec.reference_module(s.config), s.family,
                                 log=log)
    for name, c in verdict["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return {
        "correct": verdict["correct"],
        "attempted": len(window.requests),
        "failed": verdict["failed"],
        "metrics": metrics,
        "device": device,
        **extra,
        "compiles_in_window": compiles.n,
        "checks": verdict["checks"],
    }
