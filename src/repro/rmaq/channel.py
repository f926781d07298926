"""Typed multi-lane message channels multiplexed over one queue (DESIGN.md §6.3).

A `Channel` gives the queue a *message* surface: each message is a typed
payload on a named **lane** plus a 4-word header (lane id, source rank,
user tag, payload length).  All lanes share ONE ring per rank — one
reservation counter, one notification counter, one FIFO — and the receiver
demultiplexes by lane id after `recv` (this mirrors how RAMC multiplexes
logical channels over a single notified-access region: lanes are a typing
discipline, not extra windows, so the O(1)-metadata property survives).

Headers and payloads are stored bitcast into the queue's uint32 cells, so
int32/uint32/float32 payloads round-trip exactly.  The cells are integers
on purpose: a small int32 header word bitcast to float32 is a denormal,
which TPU float ops flush to zero.  With float32 cells the disaggregated
engine stopped draining on four v5e chips.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import queue as rq

Array = jax.Array

HDR = 4  # header words: lane_id, src_rank, tag, payload_words


class ChannelError(RuntimeError):
    pass


LANE_KINDS = ("payload", "descriptor")


class Lane(NamedTuple):
    """A typed lane: fixed payload shape + 32-bit dtype.

    `kind` tags what the lane carries: ``"payload"`` lanes move the data
    itself (eager push — the ring bounds the transfer size), while
    ``"descriptor"`` lanes carry only rendezvous descriptors (page tables /
    heap extents + generation tags) whose referents the consumer pulls with
    one-sided gets (§16).  The kind changes no wire format — it lets flow
    control and the drift gates account ring traffic by class, e.g. assert
    that a pull-mode engine issues ZERO ring-payload transfers.
    """

    name: str
    shape: tuple
    dtype: Any = jnp.float32
    kind: str = "payload"


def _lane_width(lane: Lane) -> int:
    return int(np.prod(lane.shape)) if lane.shape else 1


def _lane_kind(lane) -> str:
    kind = getattr(lane, "kind", "payload")
    if kind not in LANE_KINDS:
        raise ChannelError(f"lane kind must be one of {LANE_KINDS}, got {kind!r}")
    return kind


def _check_dtype(dtype) -> None:
    if jnp.dtype(dtype).itemsize != 4:
        raise ChannelError(f"lane dtypes must be 32-bit (bitcast storage), got {dtype}")


class RecvBatch(NamedTuple):
    """Demux view of drained messages (owner-local)."""

    lane_id: Array   # [n] int32
    src: Array       # [n] int32
    tag: Array       # [n] int32
    words: Array     # [n, max_payload_words] uint32 raw payload cells
    valid: Array     # [n] bool


@dataclasses.dataclass(frozen=True)
class Channel:
    """O(1) channel metadata: the lane table + the queue descriptor."""

    lanes: tuple[Lane, ...]
    desc: rq.QueueDescriptor

    def lane_id(self, name: str) -> int:
        for i, lane in enumerate(self.lanes):
            if lane.name == name:
                return i
        raise ChannelError(f"unknown lane {name!r} (have {[l.name for l in self.lanes]})")

    def lane(self, name: str) -> Lane:
        return self.lanes[self.lane_id(name)]

    @property
    def payload_words(self) -> int:
        return self.desc.item_width - HDR

    def metadata_nbytes(self) -> int:
        return 32 * len(self.lanes) + self.desc.metadata_nbytes()

    # ------------------------------------------------------------- packing
    def pack(self, name: str, payload: Array, tag: Array) -> Array:
        """[k, *lane.shape] typed payload + [k] int32 tag -> [k, item] msgs."""
        lane = self.lane(name)
        k = payload.shape[0]
        w = _lane_width(lane)
        flat = payload.reshape(k, w)
        flat = lax.bitcast_convert_type(flat.astype(lane.dtype), jnp.uint32)
        pad = self.payload_words - w
        if pad < 0:
            raise ChannelError(f"lane {name!r} payload wider than channel item")
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
        hdr_i = jnp.stack(
            [
                jnp.full((k,), self.lane_id(name), jnp.int32),
                jnp.full((k,), 0, jnp.int32),  # src filled in send()
                tag.astype(jnp.int32),
                jnp.full((k,), w, jnp.int32),
            ],
            axis=1,
        )
        return jnp.concatenate([lax.bitcast_convert_type(hdr_i, jnp.uint32), flat], axis=1)

    def homogeneous(self) -> bool:
        """Whether every lane shares one payload shape + dtype + kind — the
        precondition for runtime (data-dependent) lane selection."""
        return len({(l.shape, jnp.dtype(l.dtype), _lane_kind(l))
                    for l in self.lanes}) == 1

    # ------------------------------------------------- send/recv (SPMD path)
    def packed(
        self, name: str, payload: Array, tag: Array, lane_id: Array | None = None
    ) -> Array:
        """Pack + stamp this rank as the source (must run inside shard_map).

        `lane_id` ([k] int32) overrides the static lane id per message —
        runtime lane selection for credit-aware multi-lane senders (`flow`).
        Only legal when the lane table is homogeneous, since the payload was
        typed/padded against lane `name`.
        """
        msgs = self.pack(name, payload, tag)
        me = lax.axis_index(self.desc.axis).astype(jnp.int32)
        hdr = lax.bitcast_convert_type(msgs[:, :HDR], jnp.int32)
        hdr = hdr.at[:, 1].set(me)
        if lane_id is not None:
            if not self.homogeneous():
                raise ChannelError(
                    "runtime lane selection needs a homogeneous lane table"
                )
            hdr = hdr.at[:, 0].set(lane_id.astype(jnp.int32))
        return jnp.concatenate(
            [lax.bitcast_convert_type(hdr, jnp.uint32), msgs[:, HDR:]], axis=1
        )

    def send(
        self,
        state: rq.QueueState,
        name: str,
        payload: Array,
        tag: Array,
        dest: Array,
    ) -> tuple[rq.QueueState, rq.EnqueueReceipt]:
        """Collective: enqueue `payload[i]` on lane `name` at rank dest[i]
        (-1 = skip).  Must run inside shard_map on the channel axis."""
        return rq.enqueue(self.desc, state, self.packed(name, payload, tag), dest)

    def recv(
        self, state: rq.QueueState, max_n: int
    ) -> tuple[rq.QueueState, RecvBatch]:
        """Owner-local drain + header decode; caller demuxes with `payload`."""
        state, items, valid = rq.dequeue(self.desc, state, max_n)
        hdr = lax.bitcast_convert_type(items[:, :HDR], jnp.int32)
        return state, RecvBatch(
            lane_id=jnp.where(valid, hdr[:, 0], -1),
            src=jnp.where(valid, hdr[:, 1], -1),
            tag=jnp.where(valid, hdr[:, 2], -1),
            words=items[:, HDR:],
            valid=valid,
        )

    def _decode_rows(self, batch: RecvBatch, lane: Lane,
                     mask: Array) -> tuple[Array, Array]:
        """Decode `batch` rows as `lane`-typed payloads, zeroing ~mask."""
        w = _lane_width(lane)
        flat = lax.bitcast_convert_type(batch.words[:, :w], lane.dtype)
        flat = jnp.where(mask[:, None], flat, jnp.zeros_like(flat))
        return flat.reshape((batch.words.shape[0],) + lane.shape), mask

    def payload(self, batch: RecvBatch, name: str) -> tuple[Array, Array]:
        """Decode lane `name`'s messages from a RecvBatch.

        Returns (typed [n, *lane.shape] payloads, [n] bool mask of which rows
        belong to this lane).  Other lanes' rows are zeroed.
        """
        mask = batch.valid & (batch.lane_id == self.lane_id(name))
        return self._decode_rows(batch, self.lane(name), mask)

    def payload_all(self, batch: RecvBatch) -> tuple[Array, Array]:
        """Decode every valid row regardless of lane — the multi-lane drain
        for engines where lanes are scheduling channels (credit domains),
        not types.  Requires a homogeneous lane table."""
        if not self.homogeneous():
            raise ChannelError("payload_all needs a homogeneous lane table")
        mask = (batch.valid & (batch.lane_id >= 0)
                & (batch.lane_id < len(self.lanes)))
        return self._decode_rows(batch, self.lanes[0], mask)


def channel_allocate(
    mesh,
    axis: str,
    capacity: int,
    lanes: Sequence[Lane],
) -> tuple[Channel, rq.QueueState]:
    """One ring per rank sized for the widest lane (+HDR header words)."""
    lanes = tuple(
        Lane(l.name, tuple(l.shape), jnp.dtype(l.dtype), _lane_kind(l))
        for l in lanes
    )
    names = [l.name for l in lanes]
    if len(set(names)) != len(names):
        raise ChannelError(f"duplicate lane names: {names}")
    for lane in lanes:
        _check_dtype(lane.dtype)
    item_w = HDR + max(_lane_width(l) for l in lanes)
    desc, state = rq.queue_allocate(mesh, axis, capacity, (item_w,), jnp.uint32)
    return Channel(lanes, desc), state


# --------------------------------------------------------------- host mirror
class HostChannel:
    """Host-side channel over `HostQueueGroup` — same header layout, same
    admission protocol; used by control-plane components (ft.heartbeat).

    `fabric` (a `core.fabric.Fabric`) is threaded through to the queue
    group: the default in-process transport keeps today's semantics, the
    sim transport runs the same protocol under chaos schedules.  `name`
    namespaces this channel's fabric regions — give each channel sharing
    one fabric a distinct name (the default suits one channel per fabric).
    """

    def __init__(self, p: int, capacity: int, lanes: Sequence[Lane], fabric=None,
                 name: str = "q"):
        self.lanes = tuple(
            Lane(l.name, tuple(l.shape), np.dtype(l.dtype), _lane_kind(l))
            for l in lanes
        )
        for lane in self.lanes:
            if np.dtype(lane.dtype).itemsize != 4:
                raise ChannelError(f"lane dtypes must be 32-bit, got {lane.dtype}")
        self.payload_words = max(
            (int(np.prod(l.shape)) if l.shape else 1) for l in self.lanes
        )
        self.group = rq.HostQueueGroup(p, capacity, HDR + self.payload_words,
                                       np.uint32, fabric=fabric, name=name)
        self._pending: dict[int, list[tuple[int, np.ndarray]]] = {}

    def _lane_id(self, name: str) -> int:
        for i, lane in enumerate(self.lanes):
            if lane.name == name:
                return i
        raise ChannelError(f"unknown lane {name!r}")

    def send(self, src: int, name: str, payload, tag: int, dest: int) -> None:
        """Stage one message; delivered at the next `flush()` epoch."""
        lid = self._lane_id(name)
        lane = self.lanes[lid]
        w = int(np.prod(lane.shape)) if lane.shape else 1
        flat = np.asarray(payload, lane.dtype).reshape(w).view(np.uint32)
        row = np.zeros(HDR + self.payload_words, np.uint32)
        row[:HDR] = np.asarray([lid, src, tag, w], np.int32).view(np.uint32)
        row[HDR : HDR + w] = flat
        self._pending.setdefault(src, []).append((dest, row))

    def flush(self) -> dict[int, list[bool]]:
        """Run one enqueue epoch over everything staged (the fence close)."""
        sends, self._pending = self._pending, {}
        return self.group.step(sends)

    def recv(self, rank: int, max_n: int | None = None) -> list[dict]:
        """Drain + demux rank's ring into decoded message dicts."""
        out = []
        for row in self.group.drain(rank, max_n):
            hdr = row[:HDR].view(np.int32)
            lane = self.lanes[int(hdr[0])]
            w = int(hdr[3])
            payload = row[HDR : HDR + w].view(lane.dtype).reshape(lane.shape or (1,))
            out.append(
                {
                    "lane": lane.name,
                    "kind": lane.kind,
                    "src": int(hdr[1]),
                    "tag": int(hdr[2]),
                    "payload": payload.copy(),
                }
            )
        return out

    def stats(self, rank: int) -> dict:
        return self.group.stats(rank)
