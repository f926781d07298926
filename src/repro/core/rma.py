"""One-sided communication functions (paper §2.4) as JAX/SPMD primitives.

The paper's claim: *"communication functions map nearly directly to low-level
hardware functions — this is a major strength of RMA programming."*  On TPU
the same is true twice over:

  * **XLA path (this module)** — inside ``shard_map``, a put to a neighbor is
    ``lax.ppermute`` (which XLA lowers to a `collective-permute`, i.e. a
    one-sided ICI DMA with no receiver involvement — the exact hardware
    mechanism DMAPP exposes on Gemini).  Used by everything that runs under
    `jit` at scale.
  * **Pallas path (`repro.kernels.rma`)** — explicit
    ``pltpu.make_async_remote_copy`` with per-DMA semaphores, giving
    MPI-style *origin-controlled* timing: start ≙ MPI_Put, wait ≙
    MPI_Win_flush.  Used by the fused overlap kernels.

Since the deferred-substrate refactor (DESIGN.md §8) every function here is
a thin wrapper over a **single-op `repro.core.plan.RmaPlan`**: record one
descriptor, flush immediately.  Eager call sites keep their exact semantics
and message counts, while multi-op call sites migrate to epoch-scoped plans
(`plan.AccessEpoch`) and get op coalescing + model-guided backend dispatch
for free.

All functions here are pure and must be called inside ``shard_map`` (they use
named-axis collectives).  Ranks are positions along one mesh axis.

Accumulate (MPI_Accumulate / MPI-3 atomics) adaptation: TPU has no remote
AMOs, so we use the *slotted* protocol (each origin owns a disjoint slot at
the target, local reduction at completion) — the bufferless analogue of the
paper's free-storage-managed matching lists; see DESIGN.md §5.4.
"""

from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.obs import trace as obs_trace
from repro.obs.metrics import snapshot_delta


Array = jax.Array


def _axis_size(axis: str) -> int:
    return jax.lax.axis_size(axis)


def _plan(axis: str):
    """One single-op plan (lazy import: plan.py imports OpCounter from here)."""
    from repro.core import plan as plan_mod

    return plan_mod.RmaPlan(axis)


def rank(axis: str) -> Array:
    """This process's rank within the window axis."""
    return lax.axis_index(axis)


# --------------------------------------------------------------------- put
def put_shift(x: Array, shift: int, axis: str) -> Array:
    """Put `x` to rank (r + shift) mod p; returns what was put *into us*.

    One ICI hop for |shift|=1 on a torus axis — the common halo/ring case.
    """
    p = _plan(axis)
    h = p.put_shift(x, shift)
    p.flush()
    return h.result()


def put_perm(x: Array, perm: Sequence[tuple[int, int]], axis: str) -> Array:
    """Put along an arbitrary (src, dst) permutation — MPI_Put to any rank.

    Ranks absent as destinations receive zeros (MPI: their window region is
    simply not written).
    """
    p = _plan(axis)
    h = p.put_perm(x, perm)
    p.flush()
    return h.result()


# --------------------------------------------------------------------- get
def get_shift(x: Array, shift: int, axis: str) -> Array:
    """Get from rank (r + shift) mod p.

    A get *by* rank r from r+shift is a put *by* r+shift to r: under SPMD
    both sides run the same program so the origin-passivity is preserved at
    the target (no compute on the target's side, only its DMA engine).
    """
    p = _plan(axis)
    h = p.get_shift(x, shift)
    p.flush()
    return h.result()


def _get_index_impl(x: Array, src: Array | int, axis: str) -> Array:
    full = lax.all_gather(x, axis)  # [n, ...]
    return jax.tree.map(lambda f: lax.dynamic_index_in_dim(f, src, 0, keepdims=False), full)


def get_index(x: Array, src: Array | int, axis: str) -> Array:
    """Get rank `src`'s shard — all ranks read one rank (broadcast get)."""
    p = _plan(axis)
    h = p.all_gather(x, kind="gets")
    p.flush()
    full = h.result()
    return jax.tree.map(lambda f: lax.dynamic_index_in_dim(f, src, 0, keepdims=False), full)


def get_gather(x: Array, src_per_rank: Array, axis: str) -> Array:
    """Each rank gets the shard of rank ``src_per_rank[r]`` (gather-get)."""
    p = _plan(axis)
    h = p.all_gather(x, kind="gets")
    p.flush()
    full = h.result()
    me = lax.axis_index(axis)
    src = src_per_rank[me]
    return lax.dynamic_index_in_dim(full, src, 0, keepdims=False)


# -------------------------------------------------------------- accumulate
def accumulate_shift(
    x: Array,
    acc: Array,
    shift: int,
    axis: str,
    op: Callable[[Array, Array], Array] = jnp.add,
) -> Array:
    """MPI_Accumulate to rank r+shift with reduction `op` (slotted protocol).

    Returns the target-side accumulator updated with the one incoming
    contribution.  Element-wise atomicity holds because the slot is private
    to the origin and the reduction is applied by the owner (paper §2.4).
    """
    p = _plan(axis)
    h = p.accumulate_shift(x, acc, shift, op)
    p.flush()
    return h.result()


def accumulate_perm(
    x: Array,
    acc: Array,
    perm: Sequence[tuple[int, int]],
    axis: str,
    op: Callable[[Array, Array], Array] = jnp.add,
) -> Array:
    p = _plan(axis)
    h = p.accumulate_perm(x, acc, perm, op)
    p.flush()
    return h.result()


def accumulate_slots(
    contributions: Array,  # [k, ...] one slot per neighbor, zeros where unused
    acc: Array,
    op: Callable = jnp.add,
) -> Array:
    """Owner-side reduction over the slot buffer at epoch completion."""
    return op(acc, jnp.sum(contributions, axis=0)) if op is jnp.add else functools.reduce(
        op, [contributions[i] for i in range(contributions.shape[0])], acc
    )


def fetch_and_op(x: Array, target: Array, axis: str, op: Callable = jnp.add) -> tuple[Array, Array]:
    """MPI_Fetch_and_op on the window axis (returns old value + new target).

    TPU adaptation: no remote AMOs → implemented as a get followed by an
    owner-applied op within the same epoch (serialization is provided by the
    epoch, not a hardware lock; see DESIGN.md §5.1).  `axis` names the window
    axis whose epoch provides that serialization; it tags the per-axis AMO
    counters so complexity tests can attribute atomics to a window.  For the
    rank-ordered multi-origin variant (the queue's slot reservation) see
    `repro.rmaq.notify.fetch_and_add_ordered`.
    """
    OpCounter.record("accs", axis=axis)
    old = target
    new = op(target, x)
    return old, new


# ------------------------------------------------------------- bulk moves
def put_all_to_all(x: Array, axis: str, tiled: bool = False) -> Array:
    """Personalized all-to-all built on one-sided puts (DSDE substrate §4.2).

    `x` has leading dim p (one block destined per rank).
    """
    if tiled:  # plan a2a is untiled; tiled keeps the native lowering
        OpCounter.record("colls")
        return lax.all_to_all(x, axis, split_axis=0, concat_axis=0, tiled=True)
    p = _plan(axis)
    h = p.put_all_to_all(x, kind="colls")
    p.flush()
    return h.result()


def put_bcast(x: Array, root: int, axis: str) -> Array:
    """Root puts its value to everyone (window-wide broadcast).

    Calls the unwrapped get implementation: a broadcast is ONE collective op,
    not a collective plus a get (the double count the instrumented `get_index`
    would record).
    """
    OpCounter.record("colls")
    return _get_index_impl(x, root, axis)


# ---------------------------------------------------------- instrumentation
class OpCounter:
    """Counts one-sided ops issued while tracing — tests assert the paper's
    O(k)/O(log p) message-complexity bounds against these counters.

    Since the deferred substrate (DESIGN.md §8) the counter distinguishes
    **raw** messages (ops as recorded — what the program *meant*) from
    **coalesced** messages (wire transfers actually issued after plan
    aggregation).  Coalesced ops are attributed to their originating kind —
    a fused transfer carrying 3 puts and 1 accumulate counts puts += 3,
    accs += 1, raw_msgs += 4, coalesced_msgs += 1 — never as one `put`.
    Per-plan aggregation detail accumulates in `.plans`.
    """

    _active: list["OpCounter"] = []

    def __init__(self) -> None:
        self.puts = 0
        self.gets = 0
        self.accs = 0
        self.colls = 0
        # deferred-substrate accounting (DESIGN.md §8)
        self.raw_msgs = 0        # logical messages recorded
        self.coalesced_msgs = 0  # wire transfers actually issued
        self.plans: list[dict] = []  # per-plan aggregation stats
        # per-window-axis breakdown: {axis: {kind: count}}
        self.by_axis: dict = {}

    def __enter__(self) -> "OpCounter":
        OpCounter._active.append(self)
        return self

    def __exit__(self, *exc) -> None:
        OpCounter._active.remove(self)

    @property
    def aggregation_factor(self) -> float:
        return self.raw_msgs / self.coalesced_msgs if self.coalesced_msgs else 1.0

    def snapshot(self) -> dict:
        """Order-independent fingerprint of every counter — the unit the
        fabric diff tests compare byte-for-byte against golden traces."""
        return {
            "puts": self.puts,
            "gets": self.gets,
            "accs": self.accs,
            "colls": self.colls,
            "raw_msgs": self.raw_msgs,
            "coalesced_msgs": self.coalesced_msgs,
            "by_axis": {a: dict(sorted(k.items())) for a, k in sorted(self.by_axis.items())},
        }

    def delta(self, prev) -> dict:
        """Snapshot diff against `prev` (a snapshot dict or an OpCounter)."""
        if hasattr(prev, "snapshot"):
            prev = prev.snapshot()
        return snapshot_delta(self.snapshot(), prev)

    @classmethod
    def record(cls, kind: str, n: int = 1, axis: str | None = None) -> None:
        """Eager-path record: one logical op == one wire transfer."""
        tr = obs_trace.TRACER
        if tr.enabled:
            tr.event("rma.op", kind=kind, n=n, axis=axis or "")
        for c in cls._active:
            setattr(c, kind, getattr(c, kind) + n)
            c.raw_msgs += n
            c.coalesced_msgs += n
            if axis is not None:
                per = c.by_axis.setdefault(axis, {})
                per[kind] = per.get(kind, 0) + n

    @classmethod
    def record_plan(
        cls,
        kinds: dict[tuple[str, str], int],
        raw: int,
        coalesced: int,
        info: dict | None = None,
    ) -> None:
        """Plan-flush record: attribute each recorded op to its originating
        kind (the raw count), and account wire transfers separately."""
        tr = obs_trace.TRACER
        if tr.enabled:
            tr.event("rma.plan", raw=raw, coalesced=coalesced)
        for c in cls._active:
            for (kind, axis), n in kinds.items():
                setattr(c, kind, getattr(c, kind) + n)
                per = c.by_axis.setdefault(axis, {})
                per[kind] = per.get(kind, 0) + n
            c.raw_msgs += raw
            c.coalesced_msgs += coalesced
            if info is not None:
                c.plans.append(dict(info))
