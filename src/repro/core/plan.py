"""Deferred one-sided substrate: epoch-scoped plan recording (DESIGN.md §8).

The paper's bufferless protocols win because *synchronization*, not each
message, pays the latency: ops issued inside an access epoch only have to be
remotely complete at the closing flush (§2.3), which leaves the runtime free
to aggregate small messages — the exact property its UPC message-rate
comparison hinges on.  The eager functions in `repro.core.rma` lower every
put to its own ``ppermute`` at call time and cannot exploit this, so this
module adds the deferred layer underneath them:

  * **`RmaPlan`** *records* put/get/accumulate/fetch_and_op descriptors
    instead of issuing them.  Each record returns an `RmaHandle`; nothing
    moves until `flush()`.
  * **Coalescing** — at flush, ops with an identical collective signature
    (same axis + same permutation, or same all-to-all/all-gather shape) are
    fused into ONE wire transfer: payloads are re-expressed as uint32 words,
    concatenated, moved by a single collective, then split and decoded
    losslessly.  `PerfModel.select_aggregation` decides pack-vs-direct from
    message size, reproducing the paper's Fig. 5b message-rate crossover
    (small messages are injection-rate-bound → packing wins; large messages
    are bandwidth-bound → packing only adds copy cost).
  * **Backend dispatch** — each coalesced group is issued on a backend
    chosen by the §3 models (`choose_backend` / the strategist's
    ``backend_plan``): XLA ``ppermute``/``all_to_all``/``all_gather``, the
    Pallas `repro.kernels.rma` explicit-DMA path (uniform-shift groups on
    TPU, or forced with ``backend="interpret"`` for validation), or the
    interpret path.

`AccessEpoch` ties a plan to one of the three §2.3 synchronization families
(fence / PSCW / shared lock): `open()` performs the family's opening sync,
record methods defer ops into the plan, and `close()` flushes the plan (one
fused transfer per coalesced group) before the family's closing sync.  The
epoch's `SyncStats` then counts BOTH raw (recorded) and coalesced (wire)
messages, so the complexity tests can assert the aggregation factor.

The eager `repro.core.rma` functions are thin wrappers over single-op plans,
so every consumer of the one-sided API transparently shares this substrate.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Literal, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.obs import trace as obs_trace
from repro.obs.metrics import snapshot_delta

from .perfmodel import DEFAULT_MODEL, PerfModel
from .rma import OpCounter

Array = jax.Array


class PlanError(RuntimeError):
    pass


# --------------------------------------------------------- payload word codec
def _widen(dtype) -> tuple[Any, bool]:
    """Map a payload dtype to a >=32-bit carrier dtype.

    Returns (wide dtype, needs_value_cast).  Sub-32-bit payloads are widened
    by a value-preserving cast before bitcasting to words; 32/64-bit payloads
    bitcast directly.
    """
    dt = jnp.dtype(dtype)
    if dt == jnp.dtype(jnp.bool_):
        return jnp.dtype(jnp.uint32), True
    if dt.kind in "iu" and dt.itemsize < 4:
        return jnp.dtype(jnp.int32), True
    # fp16/bf16: numpy reports bfloat16 as kind 'V', so match by dtype
    if dt in (jnp.dtype(jnp.float16), jnp.dtype(jnp.bfloat16)):
        return jnp.dtype(jnp.float32), True
    if dt.itemsize in (4, 8):
        return dt, False
    raise PlanError(f"cannot pack payload dtype {dt}")


def _words_per_elt(dtype) -> int:
    wide, _ = _widen(dtype)
    return wide.itemsize // 4


def _encode(x: Array, lead: int) -> Array:
    """Re-express `x` as uint32 words: shape [*x.shape[:lead], -1]."""
    wide, cast = _widen(x.dtype)
    if cast:
        x = x.astype(wide)
    w = lax.bitcast_convert_type(x, jnp.uint32)
    return w.reshape(x.shape[:lead] + (-1,))


def _decode(w: Array, shape: tuple, dtype) -> Array:
    """Inverse of `_encode`: uint32 words back to the original payload."""
    dt = jnp.dtype(dtype)
    wide, cast = _widen(dt)
    if wide.itemsize == 8:
        out = lax.bitcast_convert_type(w.reshape(tuple(shape) + (2,)), wide)
    else:
        out = lax.bitcast_convert_type(w.reshape(tuple(shape)), wide)
    return out.astype(dt) if cast else out


# ------------------------------------------------------------------- handles
_UNRESOLVED = object()


class RmaHandle:
    """Deferred result of one recorded op; resolved by the plan's flush."""

    __slots__ = ("_result",)

    def __init__(self) -> None:
        self._result = _UNRESOLVED

    @property
    def resolved(self) -> bool:
        return self._result is not _UNRESOLVED

    def result(self):
        if self._result is _UNRESOLVED:
            raise PlanError("handle not resolved — flush the plan first")
        return self._result


@dataclasses.dataclass
class _RecordedOp:
    kind: Optional[str]     # puts | gets | accs | colls | None (protocol rider)
    sig: tuple              # ("ppermute", perm) | ("all_to_all",) | ("all_gather",) | ("local",)
    axis: str
    payload: Any
    handle: RmaHandle
    finalize: Callable      # delivered array -> handle result
    shift: Optional[int] = None   # set when sig is a uniform-shift ppermute
    # target byte interval [lo, hi) on the destination window; None means
    # the op's own disjoint slot of the fused buffer (the §8 layout).  Set
    # via the record methods' ``at=`` to model aliasing protocols — the
    # `analysis.ir` lowering turns this into the access IR's byte-interval.
    at: Optional[tuple] = None

    @property
    def nbytes(self) -> int:
        return int(self.payload.size) * jnp.dtype(self.payload.dtype).itemsize


@dataclasses.dataclass
class PlanStats:
    """Per-plan aggregation stats (the OpCounter ledger keeps the totals)."""

    raw: int = 0             # recorded (logical) messages
    coalesced: int = 0       # wire transfers actually issued
    groups: int = 0          # distinct collective signatures
    packed_groups: int = 0   # groups fused into one transfer
    bytes_logical: int = 0   # payload bytes as recorded
    bytes_wire: int = 0      # origin-injected bytes actually on the wire
    backends: dict = dataclasses.field(default_factory=dict)

    @property
    def aggregation_factor(self) -> float:
        return self.raw / self.coalesced if self.coalesced else 1.0

    def snapshot(self) -> dict:
        """Fingerprint in the shared ledger schema (§12): same raw/coalesced
        key naming as OpCounter/SyncStats so the metrics registry ingests it
        without an adapter."""
        return {
            "raw_msgs": self.raw,
            "coalesced_msgs": self.coalesced,
            "groups": self.groups,
            "packed_groups": self.packed_groups,
            "bytes_logical": self.bytes_logical,
            "bytes_wire": self.bytes_wire,
            "backends": dict(sorted(self.backends.items())),
        }

    def delta(self, prev) -> dict:
        """Snapshot diff against `prev` (a snapshot dict or a PlanStats)."""
        if hasattr(prev, "snapshot"):
            prev = prev.snapshot()
        return snapshot_delta(self.snapshot(), prev)


# --------------------------------------------------------- backend selection
Backend = Literal["xla", "pallas", "interpret"]


def choose_backend(
    model: PerfModel, nbytes: float, shift_eligible: bool
) -> Backend:
    """Model-guided backend dispatch (ROADMAP north star; paper §6 style).

    The Pallas explicit-DMA path only exists for uniform-shift permutations
    (the `kernels/rma` surface) and only pays off when the payload is large
    enough that origin-controlled DMA timing beats XLA's scheduled
    collective (`PerfModel.select_put_backend`); it additionally requires a
    real TPU backend — on CPU the interpret path is validation-only and the
    XLA lowering is always used unless explicitly forced.
    """
    if not shift_eligible:
        return "xla"
    if model.select_put_backend(nbytes) == "pallas" and jax.default_backend() == "tpu":
        return "pallas"
    return "xla"


def _pallas_tileable(x: Array) -> bool:
    """Whether the compiled `kernels/rma` put can carry `x` without padding."""
    return (
        x.ndim >= 2
        and x.shape[-1] % 128 == 0
        and x.shape[-2] % 8 == 0
        and jnp.dtype(x.dtype).itemsize == 4
    )


def _issue_ppermute(x: Array, axis: str, perm: tuple, shift: Optional[int],
                    backend: Backend) -> Array:
    if backend in ("pallas", "interpret") and shift is not None:
        from repro.kernels.rma import kernel as rma_kernel  # lazy: pallas import

        n = jax.lax.axis_size(axis)
        return rma_kernel.put_shift_pallas(
            x, shift, axis, n, interpret=(backend == "interpret")
        )
    return lax.ppermute(x, axis, list(perm))


# ----------------------------------------------------------------- the plan
class RmaPlan:
    """Records one-sided ops for one window axis; coalesces at flush (§8).

    All record methods must be called inside ``shard_map`` on `axis` (they
    consult the axis size); `flush()` issues every recorded op, fusing
    same-signature groups into single transfers when the §3 model (or the
    explicit ``aggregate`` override) says packing wins.
    """

    def __init__(
        self,
        axis: str,
        model: PerfModel = DEFAULT_MODEL,
        strategist: Any = None,   # optional CollectiveStrategist override
    ) -> None:
        self.axis = axis
        self.model = model
        self.strategist = strategist
        self.ops: list[_RecordedOp] = []
        self.flushed = False
        self.stats: Optional[PlanStats] = None

    # ------------------------------------------------------------ recording
    @property
    def pending(self) -> int:
        return 0 if self.flushed else len(self.ops)

    def _record(self, kind, sig, payload, finalize=None, shift=None,
                at=None) -> RmaHandle:
        if self.flushed:
            raise PlanError("plan already flushed")
        tr = obs_trace.TRACER
        if tr.enabled:
            tr.event("plan.record", axis=self.axis, kind=kind or "rider",
                     sig=sig[0])
        h = RmaHandle()
        self.ops.append(
            _RecordedOp(kind, sig, self.axis, payload, h,
                        finalize or (lambda d: d), shift=shift,
                        at=None if at is None else (int(at[0]), int(at[1])))
        )
        return h

    def _shift_perm(self, shift: int) -> tuple:
        n = jax.lax.axis_size(self.axis)
        return tuple((i, (i + shift) % n) for i in range(n))

    def put_shift(self, x: Array, shift: int, kind: str = "puts",
                  at: Optional[tuple] = None) -> RmaHandle:
        """Record: put `x` to rank (r+shift) mod p; resolves to what landed
        here.  ``at=(lo, hi)`` declares the target byte interval for the
        `analysis.ir` race lowering (default: the op's own disjoint slot)."""
        return self._record(kind, ("ppermute", self._shift_perm(shift)), x,
                            shift=shift, at=at)

    def put_perm(self, x: Array, perm: Sequence[tuple[int, int]],
                 kind: str = "puts", at: Optional[tuple] = None) -> RmaHandle:
        """Record: put along an arbitrary (src, dst) permutation."""
        return self._record(kind, ("ppermute", tuple(tuple(p) for p in perm)),
                            x, at=at)

    def get_shift(self, x: Array, shift: int) -> RmaHandle:
        """Record: get from rank (r+shift) mod p (the symmetric SPMD put)."""
        return self._record("gets", ("ppermute", self._shift_perm(-shift)), x,
                            shift=-shift)

    def accumulate_shift(self, x: Array, acc: Array, shift: int,
                         op: Callable = jnp.add) -> RmaHandle:
        """Record: slotted MPI_Accumulate to rank r+shift (owner-side `op`).

        Shares the wire with same-permutation puts — the accumulate payload
        is just another segment of the fused transfer; the reduction happens
        owner-side after delivery (§2.4 slotted protocol).
        """
        return self._record("accs", ("ppermute", self._shift_perm(shift)), x,
                            finalize=lambda inc: op(acc, inc), shift=shift)

    def accumulate_perm(self, x: Array, acc: Array,
                        perm: Sequence[tuple[int, int]],
                        op: Callable = jnp.add) -> RmaHandle:
        return self._record("accs", ("ppermute", tuple(tuple(p) for p in perm)),
                            x, finalize=lambda inc: op(acc, inc))

    def fetch_and_op(self, x: Array, target: Array,
                     op: Callable = jnp.add) -> RmaHandle:
        """Record: MPI_Fetch_and_op; resolves to (old, new).  Serialization
        is the epoch's (DESIGN.md §5.1) — no wire transfer on this path, but
        it is one AMO message for the complexity accounting."""
        return self._record("accs", ("local",), x,
                            finalize=lambda _: (target, op(target, x)))

    def put_all_to_all(self, x: Array, kind: Optional[str] = "colls") -> RmaHandle:
        """Record: personalized all-to-all (leading dim p, block b to rank b)."""
        return self._record(kind, ("all_to_all",), x)

    def all_gather(self, x: Array, kind: Optional[str] = "gets") -> RmaHandle:
        """Record: window-wide gather (a broadcast get of every rank's shard)."""
        return self._record(kind, ("all_gather",), x)

    # -------------------------------------------------------------- issuing
    def _issue_group(self, sig: tuple, ops: list[_RecordedOp], pack: bool,
                     backend: Backend) -> tuple[int, int]:
        """Issue one signature group; returns (wire transfers, wire bytes —
        origin-injected, i.e. what this rank puts on its links)."""
        axis = self.axis
        if sig[0] == "local":
            for op in ops:
                op.handle._result = op.finalize(op.payload)
            return len(ops), 0

        if not pack or len(ops) == 1:
            for op in ops:
                if sig[0] == "ppermute":
                    moved = _issue_ppermute(op.payload, axis, sig[1], op.shift,
                                            backend)
                elif sig[0] == "all_to_all":
                    moved = lax.all_to_all(op.payload, axis, split_axis=0,
                                           concat_axis=0)
                else:  # all_gather
                    moved = lax.all_gather(op.payload, axis)
                op.handle._result = op.finalize(moved)
            return len(ops), sum(op.nbytes for op in ops)

        # -- fused: encode each payload to uint32 words, move once, decode
        lead = 1 if sig[0] == "all_to_all" else 0
        segs = [_encode(op.payload, lead) for op in ops]
        widths = [s.shape[-1] for s in segs]
        packed = jnp.concatenate(segs, axis=lead)
        if sig[0] == "ppermute":
            # shift eligibility requires every segment to agree (they do —
            # same signature), so reuse the first op's shift
            moved = _issue_ppermute(packed, axis, sig[1], ops[0].shift, backend)
        elif sig[0] == "all_to_all":
            moved = lax.all_to_all(packed, axis, split_axis=0, concat_axis=0)
        else:
            moved = lax.all_gather(packed, axis)  # [p, W]

        off = 0
        p = jax.lax.axis_size(axis)
        for op, w in zip(ops, widths):
            if sig[0] == "ppermute":
                seg = lax.slice_in_dim(moved, off, off + w, axis=0)
                out = _decode(seg, op.payload.shape, op.payload.dtype)
            elif sig[0] == "all_to_all":
                seg = lax.slice_in_dim(moved, off, off + w, axis=1)
                out = _decode(seg, op.payload.shape, op.payload.dtype)
            else:
                seg = lax.slice_in_dim(moved, off, off + w, axis=1)
                out = _decode(seg, (p,) + tuple(op.payload.shape),
                              op.payload.dtype)
            op.handle._result = op.finalize(out)
            off += w
        return 1, int(packed.size) * 4

    def flush(self, aggregate: Optional[bool] = None,
              backend: str = "auto") -> PlanStats:
        """Issue every recorded op (MPI_Win_flush for the whole plan).

        aggregate: True forces packing of every fusable group, False forces
        per-op transfers, None consults `PerfModel.select_aggregation`.
        backend: "auto" consults `choose_backend` (or the strategist), else
        one of "xla" | "pallas" | "interpret" forced for every group.
        """
        tr = obs_trace.TRACER
        if not tr.enabled:
            return self._flush_impl(aggregate, backend)
        with tr.span("plan.flush", axis=self.axis, pending=len(self.ops)) as sp:
            stats = self._flush_impl(aggregate, backend)
            sp.set(raw=stats.raw, coalesced=stats.coalesced,
                   groups=stats.groups, packed_groups=stats.packed_groups,
                   bytes_wire=stats.bytes_wire)
            return stats

    def _flush_impl(self, aggregate: Optional[bool],
                    backend: str) -> PlanStats:
        if self.flushed:
            raise PlanError("plan already flushed")
        self.flushed = True
        stats = PlanStats()
        groups: dict[tuple, list[_RecordedOp]] = {}
        for op in self.ops:
            groups.setdefault((op.axis, op.sig), []).append(op)

        kinds: dict[tuple, int] = {}
        for (axis, sig), ops in groups.items():
            n = len(ops)
            group_bytes = sum(op.nbytes for op in ops)
            stats.groups += 1
            stats.bytes_logical += group_bytes

            if aggregate is None:
                pack = (
                    n > 1
                    and sig[0] != "local"
                    and self._aggregation(n, group_bytes / n) == "pack"
                )
            else:
                pack = bool(aggregate) and n > 1 and sig[0] != "local"

            be: Backend
            if backend != "auto":
                be = backend  # type: ignore[assignment]
            else:
                # auto-dispatch to the Pallas DMA path only for uniform-shift
                # groups whose payloads meet the kernel's tile contract (the
                # compiled path needs (8,128)-aligned 32-bit tiles; packed
                # word buffers are 1-D and always take the XLA lowering)
                shift_ok = (
                    sig[0] == "ppermute"
                    and not pack
                    and all(op.shift is not None for op in ops)
                    and all(_pallas_tileable(op.payload) for op in ops)
                )
                be = self._backend(group_bytes, shift_ok)

            wire, wire_bytes = self._issue_group(sig, ops, pack, be)
            stats.raw += n
            stats.coalesced += wire
            stats.bytes_wire += wire_bytes
            if pack and wire == 1 and n > 1:
                stats.packed_groups += 1
            stats.backends[be] = stats.backends.get(be, 0) + wire
            for op in ops:
                if op.kind is not None:
                    kinds[(op.kind, axis)] = kinds.get((op.kind, axis), 0) + 1

        OpCounter.record_plan(
            kinds, raw=stats.raw, coalesced=stats.coalesced,
            info={
                "axis": self.axis,
                "raw": stats.raw,
                "coalesced": stats.coalesced,
                "groups": stats.groups,
                "packed_groups": stats.packed_groups,
                "bytes_logical": stats.bytes_logical,
                "bytes_wire": stats.bytes_wire,
            },
        )
        self.stats = stats
        return stats

    # delegation points (the strategist can override the model rules)
    def _aggregation(self, n: int, msg_bytes: float) -> str:
        if self.strategist is not None:
            return self.strategist.aggregation_plan(n, msg_bytes)
        return self.model.select_aggregation(n, msg_bytes)

    def _backend(self, nbytes: float, shift_eligible: bool) -> Backend:
        if self.strategist is not None:
            return self.strategist.backend_plan(nbytes, shift_eligible)
        return choose_backend(self.model, nbytes, shift_eligible)


# ------------------------------------------------------------- access epochs
class AccessEpoch:
    """An access epoch = one §2.3 sync family wrapped around one `RmaPlan`.

    Usage (functional, inside shard_map):

        ep = AccessEpoch("x", family="fence", p=p)
        x = ep.open(x)
        h1 = ep.put_shift(a, +1)          # recorded, not issued
        h2 = ep.put_shift(b, +1)          # same wire transfer as h1
        x = ep.close(x)                   # flush (coalesced) + family sync
        a2, b2 = h1.result(), h2.result()

    `ep.sync.stats` counts raw and coalesced messages plus the family's own
    synchronization messages; `ep.plan_stats` keeps the aggregation detail.
    """

    def __init__(
        self,
        axis: str,
        family: Literal["fence", "pscw", "lock"] = "fence",
        *,
        p: Optional[int] = None,
        group: Sequence[int] = (),
        model: PerfModel = DEFAULT_MODEL,
        strategist: Any = None,
    ) -> None:
        from . import epoch as epoch_mod  # late: epoch lazily imports plan

        self.axis = axis
        self.family = family
        if family == "fence":
            if p is None:
                raise PlanError(
                    "fence epochs need the process count p — the O(log p) "
                    "sync accounting and predicted_cost depend on it"
                )
            self.sync = epoch_mod.FenceEpoch(axis, p, model)
        elif family == "pscw":
            self.sync = epoch_mod.PSCWEpoch(axis, list(group), model)
        elif family == "lock":
            self.sync = epoch_mod.SharedLockEpoch(axis, model)
        else:
            raise PlanError(f"unknown epoch family {family!r}")
        self.plan = RmaPlan(axis, model=model, strategist=strategist)
        self.plan_stats: Optional[PlanStats] = None

    # family-appropriate open/close
    def open(self, tree: Any) -> Any:
        if self.family == "fence":
            return self.sync.open(tree)
        if self.family == "pscw":
            return self.sync.start(self.sync.post(tree))
        return self.sync.lock(tree)

    def close(self, tree: Any, *, aggregate: Optional[bool] = None,
              backend: str = "auto") -> Any:
        if not self.plan.flushed:
            self.plan_stats = self.plan.flush(aggregate=aggregate, backend=backend)
            self.sync.stats.raw_msgs += self.plan_stats.raw
            self.sync.stats.coalesced_msgs += self.plan_stats.coalesced
        if self.family == "fence":
            return self.sync.close(tree)
        if self.family == "pscw":
            return self.sync.wait(self.sync.complete(tree))
        return self.sync.unlock(tree)

    # record API (delegated)
    def _rec(self) -> RmaPlan:
        # epoch-misuse guard: the closing flush already issued this epoch's
        # plan, so a late record would silently miss the epoch's sync
        if self.plan.flushed:
            raise PlanError(
                f"{self.family} epoch on axis {self.axis!r} already closed "
                "— op recorded after close() would never be synchronized "
                "by this epoch")
        return self.plan

    def put_shift(self, x, shift, kind="puts", at=None):
        return self._rec().put_shift(x, shift, kind=kind, at=at)

    def put_perm(self, x, perm, kind="puts", at=None):
        return self._rec().put_perm(x, perm, kind=kind, at=at)

    def get_shift(self, x, shift):
        return self._rec().get_shift(x, shift)

    def accumulate_shift(self, x, acc, shift, op=jnp.add):
        return self._rec().accumulate_shift(x, acc, shift, op)

    def accumulate_perm(self, x, acc, perm, op=jnp.add):
        return self._rec().accumulate_perm(x, acc, perm, op)

    def fetch_and_op(self, x, target, op=jnp.add):
        return self._rec().fetch_and_op(x, target, op)

    def put_all_to_all(self, x, kind="colls"):
        return self._rec().put_all_to_all(x, kind=kind)

    def all_gather(self, x, kind="gets"):
        return self._rec().all_gather(x, kind=kind)

    def predicted_cost(self) -> float:
        return self.sync.predicted_cost()
