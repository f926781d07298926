"""Batched serving engine: continuous batching over a shared KV cache.

Host-side admission control uses the *paper's lock protocol* (see
`core.locks_sim`): request threads take shared locks on the cache window to
append, the scheduler takes the exclusive lock to mutate shared engine state
— a live deployment of MPI_Win_lock semantics where gang-scheduled device
code cannot express them (DESIGN.md §5.1).

Lock discipline (DESIGN.md §9.4) — every section is classified by what it
touches, not by who calls it:

  * **exclusive** — slot-table mutation: allocating a lane to a request and
    recycling a finished lane (`slot_free`/`slot_req` writes, `done.set()`).
    These are writer sections whoever runs them; the historical bug was
    `admit()` recycling an instantly-finished lane under its *shared* lock.
    `_recycle()` carries a tripwire: it refuses to run unless the window's
    writer bit is set, so a regression to reader-locked recycling fails
    loudly in the threaded stress test.
  * **shared** — per-lane cache appends (prefill into a fresh lane, decode
    appending one token per active lane): disjoint window regions, many
    readers/appenders at once.  The host-side `self.cache` *reference swap*
    is additionally guarded by a plain mutex — a real window's regions are
    physically disjoint; a Python tree reference is not, so the mutex stands
    in for that property (it is NOT part of the §2.3 protocol).

Device-side the engine runs two jitted programs: `prefill` (one sequence at
a time into its cache lane) and `decode_step` (all active lanes, one token).
Slots are fixed (static shapes); finished lanes are recycled.  Both programs
take the cache donated and update it in place, so the engine owns the only
live cache: `self.cache` is replaced by each dispatch's result, and no caller
may keep its arrays across `schedule()` (DESIGN.md §9.4).

`schedule()` is the unified scheduler tick — admit, decode, recycle — and
`run_until_drained` loops it, raising `DrainError` (with the undrained
request ids) instead of silently returning partial results when `max_steps`
is exhausted.

Host phases are spans through `obs.trace.profiled_span`, so a profiler
trace names what the host did in each device idle gap (DESIGN.md §12.1):
`serve.admit` holds `serve.prefill.prepare`/`.launch`/`.readback` per
admitted request; `serve.step` holds `serve.decode.prepare`/`.launch`/
`.readback`/`.emit`; `serve.recycle` is the exclusive recycle section on
either path.

Counters (in `obs.metrics.REGISTRY` unless the engine is handed a registry),
per program, `decode` or `prefill`:

  * ``serve.<program>.host_gap_s`` / ``.host_gaps`` — the seconds and number
    of host gaps ended by a dispatch of that program.  A host gap runs from
    the moment the engine's last device result reached the host (the return
    of the token read-back in `step()` or `admit()`) to the return of the
    next dispatch: the engine's emit and recycle, the caller's own loop
    between `schedule()` calls, the empty admission check, preparation and
    the dispatch itself.  It is counted only while the engine holds work: a
    read-back that leaves no busy lane and an empty queue opens none, so
    the time between drained batches is not a host gap.  A gap whose ending
    dispatch compiled is not counted either.
  * ``serve.compiles{program=}`` / ``serve.compile_s{program=}`` — dispatches
    that added an entry to that jitted program's cache (each new prompt
    length compiles a prefill), and their seconds: trace, lower, and compile
    or load from the persistent cache.

Expert counters, for a model whose cache carries ``experts`` (the `moe`
family): the decode program adds, in each expert layer of each tick, the
(token, expert) pairs routed to the experts held here, all routed pairs,
and the busiest held expert's pairs, over the lanes that carry a request
(``cache["lanes"]``, made each tick under the span ``serve.experts.lanes``;
free lanes are computed but not counted), to that donated device state
(`models.transformer`).  Nothing reads it inside a
decode tick: a scrape of the registry (`MetricsRegistry.flat`) reads it
back once, under the span ``serve.experts.collect``, and adds what is new
to ``serve.experts.held_pairs`` / ``.routed_pairs`` / ``.peak_pairs`` /
``.layer_steps`` (expert layers x decode ticks); the gauge
``serve.experts.held`` gives the number of experts the served weights hold,
set with the lanes.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
import weakref
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.locks_sim import WRITER_BIT, LockOrigin, LockWindow
from repro.models.registry import Model
from repro.obs import flight as obs_flight
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.trace import profiled_span


# the device's expert counters, in the order `models.moe.moe_serve` counts them
EXPERT_COUNTERS = ("held_pairs", "routed_pairs", "peak_pairs", "layer_steps")


class LockDisciplineError(RuntimeError):
    """A writer section ran without the exclusive lock (§2.3 violation)."""


class DrainError(RuntimeError):
    """`run_until_drained` exhausted `max_steps` with work still queued.

    `reasons` (optional) maps each undrained rid to why it is stuck —
    ``"credit"`` (deferred on a dry credit window), ``"pool"`` (page pool
    dry), ``"pull"`` (rendezvous descriptor published but the pull never
    completed), or ``"queue"`` (never left the pending queue)."""

    def __init__(self, message: str, undrained: tuple,
                 reasons: dict | None = None):
        detail = f"{message}; undrained request ids: {list(undrained)}"
        if reasons:
            detail += "; stall reasons: " + ", ".join(
                f"{rid}={reasons[rid]}" for rid in undrained if rid in reasons)
        super().__init__(detail)
        self.undrained = tuple(undrained)
        self.reasons = dict(reasons or {})


class ScheduleTick(NamedTuple):
    admitted: int
    emitted: int
    recycled: int


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new: int = 16
    done: threading.Event = dataclasses.field(default_factory=threading.Event)
    output: list[int] = dataclasses.field(default_factory=list)
    t_submit: float = 0.0      # wall time of submit() (TTFT reference point)


class _ProgramCounters:
    """The host-gap and compile counters of one jitted program, looked up
    once so that a dispatch costs no registry lookup."""

    __slots__ = ("jitted", "gap_s", "gaps", "compiles", "compile_s")

    def __init__(self, registry: obs_metrics.MetricsRegistry, program: str, jitted):
        self.jitted = jitted
        self.gap_s = registry.counter(f"serve.{program}.host_gap_s")
        self.gaps = registry.counter(f"serve.{program}.host_gaps")
        self.compiles = registry.counter("serve.compiles", program=program)
        self.compile_s = registry.counter("serve.compile_s", program=program)


class ServeEngine:
    def __init__(self, model: Model, params, n_slots: int = 4, max_seq: int = 256,
                 metrics: Optional[obs_metrics.MetricsRegistry] = None):
        self.model = model
        self.params = params
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.cache = model.init_cache(n_slots, max_seq)
        self.slot_free = [True] * n_slots
        # ready = prefill landed; decode must skip allocated-but-unprefilled
        # lanes (an admitting request thread may be between its exclusive
        # allocation and its shared-lock prefill when the scheduler decodes)
        self.slot_ready = [False] * n_slots
        self.slot_req: list[Optional[Request]] = [None] * n_slots
        self.slot_pos = np.zeros(n_slots, np.int32)
        self.slot_last = np.zeros(n_slots, np.int32)
        self.queue: "queue.Queue[Request]" = queue.Queue()
        # admission control: paper's RW lock over the cache window
        self.lock_win = LockWindow(p=1)
        self.lock = LockOrigin(self.lock_win, rank=0)
        # host stand-in for window-region disjointness (see module docstring)
        self._cache_mu = threading.Lock()
        self.recycled_total = 0
        self._decode = jax.jit(model.decode_step, donate_argnames="cache")
        self._prefill = jax.jit(self._prefill_impl, static_argnames=("plen",),
                                donate_argnames=("cache",))
        registry = obs_metrics.REGISTRY if metrics is None else metrics
        self._decode_counters = _ProgramCounters(registry, "decode", self._decode)
        self._prefill_counters = _ProgramCounters(registry, "prefill", self._prefill)
        # perf_counter() when the last device result reached the host, or
        # None when no host gap is open (module docstring)
        self._t_result: Optional[float] = None
        if "experts" in self.cache:
            self._expert_counters = [registry.counter(f"serve.experts.{n}")
                                     for n in EXPERT_COUNTERS]
            self._experts_held = registry.gauge("serve.experts.held")
            self._experts_seen = np.zeros(len(EXPERT_COUNTERS), np.uint32)
            me = weakref.ref(self)
            registry.collector(lambda: (e := me()) is not None and e._collect_experts())

    def _collect_experts(self) -> bool:
        """Add the device's expert counts since the last scrape to the
        registry: one read-back, outside every decode tick.  The device
        counters are uint32 and wrap; the difference wraps with them."""
        with profiled_span("serve.experts.collect"), self._cache_mu:
            now = np.asarray(self.cache["experts"])
        for counter, d in zip(self._expert_counters, (now - self._experts_seen).tolist()):
            counter.inc(int(d))
        self._experts_seen = now
        return True

    # --------------------------------------------------------- plumbing
    def _prefill_impl(self, params, cache, tokens, slot, plen):
        """Prefill one slot's lane: write K/V rows for [0, plen).  `cache`
        is donated, so the lane write updates 1/n_slots of it in place."""
        # run the model on this single sequence with a fresh single-lane cache
        lane_cache = self.model.init_cache(1, self.max_seq)
        logits, lane_cache = self.model.prefill(params, tokens[None, :plen], lane_cache, None)

        def put(full, lane):
            # lane leaves have batch dim 1 where full has n_slots
            b_axis = _batch_axis(full.shape, lane.shape)
            if b_axis is None:
                return full
            return jax.lax.dynamic_update_index_in_dim(full, lane[_take0(b_axis, lane.ndim)], slot, b_axis)

        new_cache = jax.tree.map(put, cache, lane_cache)
        new_cache["len"] = cache["len"]  # global len unused in slot mode
        return logits[0], new_cache

    def _launch(self, fn, counters: _ProgramCounters, *args, **kwargs):
        """Dispatch one device program and count what its return ends: the
        open host gap, or a compile.  Called under `_cache_mu`, which keeps
        the counters' updates from interleaving."""
        size = counters.jitted._cache_size()
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        t1 = time.perf_counter()
        if counters.jitted._cache_size() != size:
            counters.compiles.inc()
            counters.compile_s.inc(t1 - t0)
        elif self._t_result is not None:
            counters.gaps.inc()
            counters.gap_s.inc(t1 - self._t_result)
        self._t_result = None
        return out

    def _holds_work(self) -> bool:
        return not self.queue.empty() or not all(self.slot_free)

    def submit(self, req: Request) -> None:
        req.t_submit = time.perf_counter()
        tr = obs_trace.TRACER
        if tr.enabled:
            tr.event("serve.request.submit", rid=req.rid,
                     plen=len(req.prompt), max_new=req.max_new)
        self.queue.put(req)

    # ------------------------------------------------- locked state sections
    def _alloc_slot(self) -> Optional[tuple[Request, int]]:
        """Exclusive section: claim (queue head, free slot), or None."""
        with self.lock.exclusive(0):
            if self.queue.empty() or not any(self.slot_free):
                return None
            try:
                req = self.queue.get_nowait()
            except queue.Empty:
                return None
            slot = self.slot_free.index(True)
            self.slot_free[slot] = False
            self.slot_ready[slot] = False
            self.slot_req[slot] = req
            return req, slot

    def _recycle(self, slot: int) -> None:
        """Writer section: free a finished lane.  MUST run inside an
        exclusive lock epoch — asserted on the lock window itself, so a
        regression to reader-locked recycling (the historical `admit()` bug)
        raises instead of silently corrupting the slot table."""
        if not (self.lock_win.local[0].v & WRITER_BIT):
            raise LockDisciplineError(
                "lane recycle without the exclusive lock (writer bit clear)"
            )
        req = self.slot_req[slot]
        self.slot_free[slot] = True
        self.slot_ready[slot] = False
        self.slot_req[slot] = None
        if req is not None:
            self.recycled_total += 1
            tr = obs_trace.TRACER
            if tr.enabled:
                tr.event("serve.request.drain", rid=req.rid, slot=slot,
                         tokens=len(req.output))
            req.done.set()

    # ------------------------------------------------------------ steps
    def admit(self) -> int:
        """Admit queued requests into free slots.

        Slot allocation is an exclusive (writer) section; the prefill that
        appends the new lane's K/V rows runs under the shared lock, like any
        other per-lane cache append.  A request whose prefill already
        produced all requested tokens is recycled under the exclusive lock —
        the §2.3 fix: the old code mutated the slot table (and signalled
        `done`) while holding only the reader lock.
        """
        with profiled_span("serve.admit"):
            admitted = 0
            while True:
                claim = self._alloc_slot()
                if claim is None:
                    return admitted
                req, slot = claim
                self._admit_one(req, slot)
                admitted += 1

    def _admit_one(self, req: Request, slot: int) -> None:
        """Prefill one claimed lane and emit the request's first token."""
        tr = obs_trace.TRACER
        if tr.enabled:
            # seg milestones cut the TTFT interval (obs.critpath): the
            # time since the previous milestone — here, since submit —
            # is charged to the named segment
            tr.event("serve.request.admit", rid=req.rid, slot=slot,
                     seg="queue_wait")
        with self.lock.shared(0):
            plen = len(req.prompt)
            with profiled_span("serve.prefill.prepare"):
                tokens = jnp.zeros((self.max_seq,), jnp.int32).at[:plen].set(
                    jnp.asarray(req.prompt, jnp.int32)
                )
            with profiled_span("serve.prefill.launch"), self._cache_mu:
                logits, self.cache = self._launch(
                    self._prefill, self._prefill_counters,
                    self.params, self.cache, tokens, slot, plen=plen
                )
            self.slot_pos[slot] = plen
            with profiled_span("serve.prefill.readback"):
                first = int(jnp.argmax(logits))
            self._t_result = time.perf_counter()
            self.slot_last[slot] = first
            req.output.append(first)   # the prefill already produced token 1
            tr = obs_trace.TRACER
            if tr.enabled:
                ttft_us = int((time.perf_counter() - req.t_submit) * 1e6)
                tr.event("serve.request.prefill", rid=req.rid, slot=slot,
                         plen=plen, seg="prefill")
                tr.event("serve.request.first_token", rid=req.rid,
                         slot=slot, seg="host", ttft_us=ttft_us)
            if len(req.output) < req.max_new:
                # decode may pick the lane up now; an instantly-finished
                # request must never become visible to the decoder (the
                # scheduler could emit an extra token — or recycle the
                # lane before our exclusive recycle below runs)
                self.slot_ready[slot] = True
        if len(req.output) >= req.max_new:
            with profiled_span("serve.recycle"), self.lock.exclusive(0):
                self._recycle(slot)
            if not self._holds_work():
                self._t_result = None

    def step(self) -> int:
        """One decode step over all active lanes; returns #tokens emitted."""
        with profiled_span("serve.step"):
            with self.lock.shared(0):
                with profiled_span("serve.decode.prepare"):
                    active = [i for i in range(self.n_slots)
                              if not self.slot_free[i] and self.slot_ready[i]]
                    if not active:
                        return 0
                    tokens = jnp.asarray(self.slot_last, jnp.int32)
                    # the cache len is per-engine-step: use max position
                    # (static shapes); per-slot masking comes from
                    # kv_valid_len in attention
                    cache_len = jnp.asarray(int(self.slot_pos.max()), jnp.int32)
                    lanes = None
                    if "experts" in self.cache:
                        with profiled_span("serve.experts.lanes"):
                            lanes = jnp.asarray(np.isin(np.arange(self.n_slots), active))
                            ex = self.params["blocks"]["moe"]["experts"]
                            self._experts_held.set(ex["w_in"].shape[1])
                # the cache dict is copied under the mutex: an admitting
                # thread may swap `self.cache` until we hold it
                with profiled_span("serve.decode.launch"), self._cache_mu:
                    cache = dict(self.cache)
                    cache["len"] = cache_len
                    if lanes is not None:
                        cache["lanes"] = lanes
                    logits, self.cache = self._launch(
                        self._decode, self._decode_counters, self.params, tokens, cache)
                with profiled_span("serve.decode.readback"):
                    nxt = np.asarray(jnp.argmax(logits, -1))
                self._t_result = time.perf_counter()
                emitted = 0
                finished = []
                with profiled_span("serve.decode.emit"):
                    for i in active:
                        req = self.slot_req[i]
                        if req is None:            # recycled concurrently mid-step
                            continue
                        req.output.append(int(nxt[i]))
                        self.slot_last[i] = int(nxt[i])
                        self.slot_pos[i] += 1
                        emitted += 1
                        if len(req.output) >= req.max_new or self.slot_pos[i] >= self.max_seq - 1:
                            finished.append(i)
            if finished:
                with profiled_span("serve.recycle"), self.lock.exclusive(0):
                    for i in finished:
                        self._recycle(i)
                if not self._holds_work():
                    self._t_result = None
            return emitted

    def schedule(self) -> ScheduleTick:
        """One unified scheduler tick: admit, decode, recycle."""
        before = self.recycled_total
        admitted = self.admit()
        emitted = self.step()
        return ScheduleTick(admitted, emitted, self.recycled_total - before)

    def _undrained_rids(self) -> tuple:
        queued = [r.rid for r in list(self.queue.queue)]
        slotted = [r.rid for r in self.slot_req if r is not None]
        return tuple(sorted(set(queued + slotted)))

    def run_until_drained(self, max_steps: int = 10_000) -> int:
        """Schedule until queue and slots are empty; returns steps taken.

        Raises `DrainError` (with the undrained request ids) when
        `max_steps` is exhausted — partial progress is never reported as a
        drained engine.
        """
        steps = 0
        while self._holds_work():
            if steps >= max_steps:
                err = DrainError(
                    f"not drained after {max_steps} steps", self._undrained_rids()
                )
                obs_flight.on_error(err, tag="serve")
                raise err
            self.schedule()
            steps += 1
        return steps


def _batch_axis(full_shape, lane_shape) -> Optional[int]:
    """Find the axis where lane has size 1 and full has n_slots."""
    if len(full_shape) != len(lane_shape):
        return None
    for i, (f, l) in enumerate(zip(full_shape, lane_shape)):
        if l == 1 and f != 1:
            return i
        if f != l:
            return None
    return None


def _take0(axis: int, ndim: int):
    idx = [slice(None)] * ndim
    idx[axis] = 0
    return tuple(idx)
