"""Disaggregated prefill/decode serving over rmaq channels (DESIGN.md §6.7, §9).

Modern serving separates the two inference phases onto different worker
pools: *prefill* ranks are compute-bound (process whole prompts, build the
KV cache), *decode* ranks are memory-bound (hold many KV caches, emit one
token per step).  The phase boundary is a bulk KV-cache transfer per
request — variable-size, asynchronous, many-to-many: exactly a message, not
a collective.  This engine makes `repro.rmaq` load-bearing for it:

  * the mesh axis "serve" is split into prefill ranks [0, n_prefill) and
    decode ranks [n_prefill, p);
  * each prefill rank computes a request's KV block and **sends it over a
    channel lane** to a decode rank — a notified put into the decode rank's
    MPSC ring.  Each decode rank exposes `n_lanes` homogeneous kv lanes;
    a lane is a *credit domain*, so the host scheduler can spread one
    producer's requests across (rank, lane) pairs by credit availability —
    multi-lane continuous batching;
  * decode ranks **drain their ring** each step and run attention readout
    over the received KV to emit tokens;
  * backpressure comes in two flavours (`DisaggConfig.flow`):
      - **credit** (default): `rmaq.flow` credit-based admission.  The host
        stages a request only onto a (rank, lane) whose device-held credit
        cache (`limit - sent`, returned with the engine state every step)
        covers it, so no send is ever rejected and nothing is ever replayed
        over the wire — `retries` stays 0 by construction while the wire
        cost per append is the same 2 fused transfers;
      - **reject/retry** (legacy): a send that finds the ring full is
        rejected at the origin and the host re-queues it — in *staging
        order* (a batch splice at the queue head), so simultaneous
        rejections keep their FIFO order; the old per-item `insert(0, ...)`
        reversed them.

  * **paged mode** (`DisaggConfig.paged`, DESIGN.md §10): the channel
    message carries a **page table** — (owner, page id) int32 pairs — not
    the KV payload.  Prefill ranks write *novel* KV pages directly into the
    decode ranks' `repro.rmem` page pools (one fused scatter transfer per
    step), while pages whose content hash already lives at the routed
    decoder are **shared**: a refcount bump host-side, zero payload bytes
    on the wire.  Requests are routed by consistent hash of their first
    page (prefix affinity), so the decoder's page gather is pool-local.
    For any workload with shared prompt prefixes, `bytes_wire` per admitted
    request drops below inline-payload mode at the same 2 fused wire
    transfers per channel append (`bench_rmem` is the evidence).

Under SPMD every rank executes the same jitted step with role masks (a
decode rank "computes" a zero KV block and sends to nobody; prefill ranks
drain an always-empty ring) — the standard gang-scheduled adaptation of an
asymmetric service, same trade as `core.dsde`'s slotted protocols.

The model here is a deliberately small single-head attention stack
(embedding KV producer + query readout decoder) so the engine runs
end-to-end on CPU in tests and `examples/disagg_serve.py`; the channel
mechanics — reservation, notified puts, drain, credits, backpressure — are
the production-shaped part and are independent of the model plugged in.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.kernels.common import interpret_mode
from repro.kernels.paged_attention import kernel as pattn
from repro.obs import causal as obs_causal
from repro.obs import flight as obs_flight
from repro.obs import trace as obs_trace
from repro.obs.metrics import MetricsRegistry
from repro.rmaq import channel as rch
from repro.rmaq import flow as rfl
from repro.rmaq import queue as rq
from repro.rmem import pages as rpg
from repro.serve.engine import DrainError


@dataclasses.dataclass(frozen=True)
class DisaggConfig:
    n_prefill: int = 2            # first n_prefill ranks run prefill
    block_tokens: int = 16        # prompt tokens per request (one KV block)
    d_model: int = 32
    vocab: int = 97
    queue_capacity: int = 16      # KV blocks a decode rank can hold in flight
    max_recv_per_step: int = 4    # decode drain width per step
    n_lanes: int = 2              # kv lanes (credit domains) per decode rank
    flow: bool = True             # credit-based admission vs reject/retry
    # paged remote KV-cache (DESIGN.md §10); requires flow=True
    paged: bool = False           # page-table messages + rmem page pools
    page_tokens: int = 4          # tokens per KV page (divides block_tokens)
    novel_slots: int = 2          # novel pages a prefill rank ships per step
    pool_pages: int = 32          # pages per decode-rank pool
    # decode attention path (paged mode, DESIGN.md §13): "fused" walks the
    # page table inside one Pallas kernel (2-page staging window, no packed
    # KV block); "gather" is the A/B baseline that materializes the block
    # (`rpg.gather_local`) and attends over the copy
    attend: str = "fused"
    # KV transfer protocol (DESIGN.md §16).  "eager" keeps the historical
    # behavior (sender-push; `paged` decides payload vs page-table wire
    # format).  "rendezvous" publishes a descriptor over a descriptor-kind
    # lane and the DECODER pulls the pages with one-sided gets — no payload
    # ever occupies a ring slot.  "auto" asks the perf model
    # (`select_transfer_protocol`) to pick per the configured block size
    # and `expected_reuse` fraction.
    transport: str = "eager"
    expected_reuse: float = 0.0

    def __post_init__(self) -> None:
        # fail at config time, not first engine build: these combinations
        # have no meaning and an engine would only reject them later
        if self.transport not in ("eager", "rendezvous", "auto"):
            raise ValueError(
                f"transport must be 'eager', 'rendezvous' or 'auto', "
                f"got {self.transport!r}")
        if not 0.0 <= self.expected_reuse <= 1.0:
            raise ValueError(
                f"expected_reuse must be in [0, 1], got {self.expected_reuse}")
        if self.transport != "eager":
            if self.paged:
                raise ValueError(
                    "transport= and paged=True are exclusive: paged is the "
                    "legacy eager-mode switch (use transport='auto' with "
                    "expected_reuse to let the model pick paged shipping)")
            if not self.flow:
                raise ValueError(
                    f"transport={self.transport!r} needs credit flow "
                    "control (flow=True)")

    @property
    def pages_per_block(self) -> int:
        return self.block_tokens // self.page_tokens

    @property
    def staging_pages_resident(self) -> int:
        """Peak KV pages resident in decode staging per request: the fused
        kernel's double-buffer window vs the gather path's full block."""
        if self.attend == "fused":
            return min(2, self.pages_per_block)
        return self.pages_per_block

    @property
    def staging_nbytes(self) -> int:
        return self.staging_pages_resident * self.page_nbytes

    @property
    def page_nbytes(self) -> int:
        return self.page_tokens * 2 * self.d_model * 4

    @property
    def block_nbytes(self) -> int:
        return self.block_tokens * 2 * self.d_model * 4

    @property
    def table_nbytes(self) -> int:
        return self.pages_per_block * rpg.ENTRY_WORDS * 4


def resolve_transport(cfg: DisaggConfig, model=None) -> str:
    """Resolve `cfg.transport` to a concrete protocol — "eager",
    "rendezvous", or "paged".  "auto" delegates to the §16 crossover model
    (`PerfModel.select_transfer_protocol` via `CollectiveStrategist`):
    small blocks push eagerly, the multi-MB band pulls by descriptor,
    huge or high-reuse blocks ship page tables.  Pure function of the
    config so tests can probe the selection without building an engine."""
    if cfg.transport != "auto":
        return cfg.transport
    from repro.parallel.overlap import CollectiveStrategist

    strat = CollectiveStrategist() if model is None \
        else CollectiveStrategist(model=model)
    plan = strat.transfer_plan(float(cfg.block_nbytes), cfg.pages_per_block,
                               cfg.expected_reuse)
    return str(plan["protocol"])


def _requeue_rejected(pending: list, staged: dict, sent_ok) -> int:
    """Splice this step's rejected sends back onto the head of `pending`
    in *staging order* (ascending prefill rank = the order they were popped),
    ahead of everything not yet staged.  Returns the number re-queued.

    The regression this guards: re-inserting each rejection at position 0
    while iterating the staged dict reverses the relative order of multiple
    same-step rejections, breaking request FIFO under sustained backpressure.
    """
    rejected = [staged[r] for r in sorted(staged) if not bool(sent_ok[r])]
    pending[:0] = rejected
    return len(rejected)


class DisaggEngine:
    """Host-orchestrated, device-stepped disaggregated serving engine."""

    def __init__(self, mesh, axis: str, cfg: DisaggConfig, seed: int = 0):
        self.mesh = mesh
        self.axis = axis
        self.cfg = cfg
        self.p = mesh.shape[axis]
        if not (0 < cfg.n_prefill < self.p):
            raise ValueError(f"need 0 < n_prefill < {self.p}, got {cfg.n_prefill}")
        if cfg.n_lanes < 1:
            raise ValueError(f"need n_lanes >= 1, got {cfg.n_lanes}")
        if cfg.transport not in ("eager", "rendezvous", "auto"):
            raise ValueError(
                f"transport must be 'eager', 'rendezvous' or 'auto', "
                f"got {cfg.transport!r}")
        if not 0.0 <= cfg.expected_reuse <= 1.0:
            raise ValueError(
                f"expected_reuse must be in [0, 1], got {cfg.expected_reuse}")
        if cfg.transport != "eager":
            if cfg.paged:
                raise ValueError(
                    "transport= and paged=True are exclusive: paged is the "
                    "legacy eager-mode switch (use transport='auto' with "
                    "expected_reuse to let the model pick paged shipping)")
            if not cfg.flow:
                raise ValueError(
                    f"transport={cfg.transport!r} needs credit flow control "
                    "(flow=True)")
        # resolve the configured transport to a concrete engine mode:
        # "inline" (eager payload push), "paged" (eager page-table
        # shipping), or "rendezvous" (descriptor publish + consumer pull)
        self.transport_selected = resolve_transport(cfg)
        if cfg.transport == "eager":
            self.mode = "paged" if cfg.paged else "inline"
        else:
            self.mode = {"eager": "inline", "paged": "paged",
                         "rendezvous": "rendezvous"}[self.transport_selected]
        if self.mode in ("paged", "rendezvous"):
            if not cfg.flow:
                raise ValueError("paged mode needs credit flow control (flow=True)")
            if cfg.block_tokens % cfg.page_tokens:
                raise ValueError(
                    f"page_tokens {cfg.page_tokens} must divide "
                    f"block_tokens {cfg.block_tokens}")
            if cfg.novel_slots < 1:
                raise ValueError(f"need novel_slots >= 1, got {cfg.novel_slots}")
            if cfg.pool_pages < cfg.pages_per_block:
                raise ValueError(
                    f"pool_pages {cfg.pool_pages} < pages_per_block "
                    f"{cfg.pages_per_block}: no request could ever map")
            if self.mode == "paged" and cfg.attend not in ("fused", "gather"):
                raise ValueError(
                    f"attend must be 'fused' or 'gather', got {cfg.attend!r}")
        self.n_decode = self.p - cfg.n_prefill

        key = jax.random.PRNGKey(seed)
        kk, kv, kq, ko = jax.random.split(key, 4)
        scale = 1.0 / np.sqrt(cfg.d_model)
        self.params = {
            "emb_k": jax.random.normal(kk, (cfg.vocab, cfg.d_model)) * scale,
            "emb_v": jax.random.normal(kv, (cfg.vocab, cfg.d_model)) * scale,
            "w_q": jax.random.normal(kq, (cfg.d_model,)) * scale,
            "readout": jax.random.normal(ko, (cfg.d_model, cfg.vocab)) * scale,
        }

        # n_lanes homogeneous kv lanes; lanes share the ring but are separate
        # credit domains.  Inline mode ships the KV block [bt, 2, d] itself;
        # paged mode ships the page table [pages_per_block, 2] int32 instead
        # (the §10 wire format) and moves page payloads through the pool.
        # Rendezvous mode ships the same table but as a DESCRIPTOR-kind
        # lane (§16): it names prefill-resident pages the decoder will pull,
        # so credits only ever cover descriptor-width slots.
        lane_kind = "payload"
        if self.mode == "rendezvous":
            lane_shape, lane_dtype = (cfg.pages_per_block, rpg.ENTRY_WORDS), jnp.int32
            lane_kind = "descriptor"
        elif self.mode == "paged":
            lane_shape, lane_dtype = (cfg.pages_per_block, rpg.ENTRY_WORDS), jnp.int32
        else:
            lane_shape, lane_dtype = (cfg.block_tokens, 2, cfg.d_model), jnp.float32
        lanes = [rch.Lane(f"kv{i}", lane_shape, lane_dtype, lane_kind)
                 for i in range(cfg.n_lanes)]
        if self.mode in ("paged", "rendezvous"):
            # page pools: device payload storage + the host allocator mirror
            # (free lists, refcounts, prefix index).  Paged mode's pools are
            # DECODER-owned (prefill scatters novel pages into them);
            # rendezvous pools are PREFILL-owned — pages stay at the rank
            # that computed them until the decoder pulls.
            self.pool = jax.device_put(
                jnp.zeros((self.p, cfg.pool_pages, cfg.page_tokens, 2,
                           cfg.d_model), jnp.float32),
                jax.sharding.NamedSharding(mesh, P(axis, None, None, None, None)),
            )
            owners = (list(range(cfg.n_prefill))
                      if self.mode == "rendezvous"
                      else list(range(cfg.n_prefill, self.p)))
            self.kv = rpg.PagedKVPool(
                owners=owners,
                n_pages=cfg.pool_pages,
                page_words=cfg.page_tokens * 2 * cfg.d_model,
            )
        else:
            self.pool = None
            self.kv = None
        if cfg.flow:
            self.channel, self.qstate, self.fstate = rfl.flow_allocate(
                mesh, axis, cfg.queue_capacity, lanes,
                n_producers=cfg.n_prefill,
            )
        else:
            self.channel, self.qstate = rch.channel_allocate(
                mesh, axis, cfg.queue_capacity, lanes)
            self.fstate = None
        self._attend_step = None      # set by _build_step in paged mode
        self._step = self._build_step()
        # trace-time message accounting: the KV shipping rides the queue's
        # epoch-scoped plans (DESIGN.md §8), so one abstract trace tells us
        # exactly how many raw ops coalesce into how many wire transfers
        # per engine step — the serving-side aggregation factor
        self.msg_stats = self._trace_message_stats()

        # host-side request tracking
        self._pending: list[tuple[int, np.ndarray]] = []   # (req_id, tokens)
        self._n_submitted = 0
        self._submitted_ids: set[int] = set()
        self.results: dict[int, int] = {}                  # req_id -> token
        self.retries = 0           # wire sends replayed (reject/retry only)
        self.credit_stalls = 0     # stage deferrals for want of credit (flow)
        self.lane_sends = np.zeros((self.p, cfg.n_lanes), np.int64)
        # paged-mode host scheduler state
        self._jobs: dict[int, dict] = {}         # rid -> shipping job
        self._rank_job: list = [None] * cfg.n_prefill   # prefill rank -> rid
        self._page_ready: set = set()            # (owner, page_id) scattered
        self.pool_stalls = 0       # requests deferred: pool had no free page
        self.novel_pages_shipped = 0
        self.appends = 0           # channel appends (admitted requests)
        self.ring_payload_appends = 0   # appends on payload-kind lanes
        self.descriptor_appends = 0     # appends on descriptor-kind lanes
        self.pulled_pages = 0      # pages pulled to completion (rendezvous)
        # rendezvous pull pins: rid -> [(owner, page_id, tag)] taken when the
        # descriptor is published, dropped when the token lands (or the
        # request is cancelled) — the §16 liveness protocol's host mirror
        self._pins: dict[int, list[tuple[int, int, int]]] = {}
        self.steps_run = 0
        # request-lifecycle latency ledgers (§12): TTFT = submit -> result
        # landing; TBT = engine-wide gap between consecutive result landings
        # (disaggregated decode emits one token per request here, so the
        # inter-result gap is the decode cadence, not a per-lane stream)
        self.metrics = MetricsRegistry()
        self._t_submit: dict[int, float] = {}
        self._t_staged: dict[int, float] = {}   # rid -> staging wall time
        # rid -> why it last stalled while queued ("credit" | "pool").
        # Entries are popped on EVERY terminal transition (staging, result
        # landing, cancel, DrainError) — a leaked rid would mis-attribute a
        # later request that reuses the id to a stall it never paid.
        self._stalled: dict[int, str] = {}
        self._t_last_result: float | None = None

    # ----------------------------------------------------------- device step
    def _build_step(self):
        cfg, axis, mode = self.cfg, self.axis, self.mode
        n_prefill, n_decode = cfg.n_prefill, self.n_decode
        ch = self.channel
        qspecs = rq.state_specs(axis)
        fspecs = rfl.state_specs(axis)

        def compute_kv(params, toks):
            tok_safe = jnp.clip(toks, 0, cfg.vocab - 1)
            kblk = params["emb_k"][tok_safe]               # [bt, d]
            vblk = params["emb_v"][tok_safe]               # [bt, d]
            return jnp.stack([kblk, vblk], axis=1)         # [bt, 2, d]

        def readout(params, kv_in, mask, tags):
            k_in, v_in = kv_in[:, :, 0], kv_in[:, :, 1]    # [m, bt, d]
            attn = jax.nn.softmax(
                jnp.einsum("mtd,d->mt", k_in, params["w_q"]), axis=-1
            )
            ctx = jnp.einsum("mt,mtd->md", attn, v_in)     # [m, d]
            logits = ctx @ params["readout"]               # [m, vocab]
            out_tok = jnp.where(mask, jnp.argmax(logits, -1).astype(jnp.int32), -1)
            out_req = jnp.where(mask, tags, -1)
            return out_req, out_tok

        def decode_batch(params, batch):
            kv_in, mask = ch.payload_all(batch)            # [m, bt, 2, d]
            return readout(params, kv_in, mask, batch.tag)

        if mode == "rendezvous":
            def ship_rdv(params, qstate, fstate, pool, ptab, req_id, dest,
                         lane, novel_toks, novel_slot):
                """Rendezvous step (§16): prefill writes novel KV pages into
                its OWN pool slice (owner-local, zero wire), publishes the
                descriptor (page table) over the descriptor lane, and the
                decode side — gated by its drain width, i.e. only when it is
                ready to attend — pulls the pages with one fused one-sided
                gather and attends in the same step.  No KV payload ever
                occupies a ring slot.  All per-rank [1, ...] inputs except
                pool."""
                me = jax.lax.axis_index(axis)
                qstate = rq.to_local(qstate)
                fstate = rfl.to_local(fstate)
                pool_l = pool[0]                           # [pages, pt, 2, d]
                rid = req_id[0]

                # 1. novel pages land in MY pool: owner-local writes, the
                # payload never leaves the prefill rank at publish time
                toks = jnp.clip(novel_toks[0], 0, cfg.vocab - 1)   # [S, pt]
                kv_pages = jnp.stack(
                    [params["emb_k"][toks], params["emb_v"][toks]], axis=2
                )                                          # [S, pt, 2, d]
                slot = novel_slot[0]
                n_pages = pool_l.shape[0]
                rows = jnp.where(slot >= 0, slot, n_pages)
                pool_l = (pool_l.reshape(n_pages, -1)
                          .at[rows].set(kv_pages.reshape(slot.shape[0], -1),
                                        mode="drop")
                          .reshape(pool_l.shape))

                # 2. descriptor append: the only thing that rides the ring
                is_prefill = (me < n_prefill) & (rid >= 0)
                dest_eff = jnp.where(is_prefill, dest[0], -1).astype(jnp.int32)
                qstate, fstate, receipt = rfl.send(
                    ch, qstate, fstate, "kv0",
                    ptab[0][None], rid[None], dest_eff[None], lane[0],
                )

                # 3. drain descriptors — the decoder's readiness gate
                qstate, fstate, batch = rfl.recv(
                    ch, qstate, fstate, cfg.max_recv_per_step)
                entries, mask = ch.payload_all(batch)      # [m, ppb, 2] i32

                # 4. pull: one fused get epoch against the owners' pools,
                # then attend over the pulled block immediately
                kv_pages_in = rpg.gather_pages(axis, pool_l, entries, mask)
                m = kv_pages_in.shape[0]
                kv_in = kv_pages_in.reshape(
                    m, cfg.block_tokens, 2, cfg.d_model)
                out_req, out_tok = readout(params, kv_in, mask, batch.tag)
                sent_ok = receipt.accepted[0] & is_prefill
                return (
                    rq.to_global(qstate), rfl.to_global(fstate), pool_l[None],
                    out_req[None], out_tok[None],
                    sent_ok[None], receipt.rejected[None],
                )

            pspec = P(axis, None, None, None, None)
            return jax.jit(
                shard_map(
                    ship_rdv,
                    mesh=self.mesh,
                    in_specs=(P(), qspecs, fspecs, pspec,
                              P(axis, None, None), P(axis), P(axis),
                              P(axis, None), P(axis, None, None),
                              P(axis, None)),
                    out_specs=(qspecs, fspecs, pspec,
                               P(axis, None), P(axis, None),
                               P(axis), P(axis)),
                    check_vma=False,
                )
            )

        if mode == "paged":
            def ship(params, qstate, fstate, pool, ptab, req_id, dest, lane,
                     novel_toks, novel_slot, novel_dest):
                """Paged shipping step: scatter novel KV pages into decoder
                pools, append the page TABLE over the channel, drain my
                ring.  Attention runs in the separate `_attend_step` (host-
                timed per decode step).  All per-rank [1, ...] inputs
                except pool."""
                me = jax.lax.axis_index(axis)
                qstate = rq.to_local(qstate)
                fstate = rfl.to_local(fstate)
                pool_l = pool[0]                           # [pages, pt, 2, d]
                rid = req_id[0]

                # 1. novel pages: compute their KV and write them directly
                # into the owners' pools (ONE fused scatter transfer)
                toks = jnp.clip(novel_toks[0], 0, cfg.vocab - 1)   # [S, pt]
                kv_pages = jnp.stack(
                    [params["emb_k"][toks], params["emb_v"][toks]], axis=2
                )                                          # [S, pt, 2, d]
                pool_l = rpg.scatter_pages(
                    axis, pool_l, kv_pages, novel_slot[0], novel_dest[0])

                # 2. channel append: the page table is the message payload
                is_prefill = (me < n_prefill) & (rid >= 0)
                dest_eff = jnp.where(is_prefill, dest[0], -1).astype(jnp.int32)
                qstate, fstate, receipt = rfl.send(
                    ch, qstate, fstate, "kv0",
                    ptab[0][None], rid[None], dest_eff[None], lane[0],
                )

                # 3. drain: the received page tables ARE the decode input
                qstate, fstate, batch = rfl.recv(
                    ch, qstate, fstate, cfg.max_recv_per_step)
                entries, mask = ch.payload_all(batch)      # [m, ppb, 2] i32
                sent_ok = receipt.accepted[0] & is_prefill
                return (
                    rq.to_global(qstate), rfl.to_global(fstate), pool_l[None],
                    entries[None], mask[None], batch.tag[None],
                    sent_ok[None], receipt.rejected[None],
                )

            def attend(params, pool, entries, mask, tags):
                """Paged decode attention: page table -> token, by the
                configured path.  "fused" hands the pool + id list straight
                to the paged-attention kernel (scale 1.0 = this engine's
                unscaled toy readout; the kernel's online softmax == the
                readout's dense softmax on all-valid tables); "gather"
                materializes the packed block first — the A/B baseline."""
                me = jax.lax.axis_index(axis)
                pool_l = pool[0]
                e, msk, tg = entries[0], mask[0], tags[0]
                mine = e[..., rpg.ENTRY_OWNER] == me
                ids = jnp.where(msk[:, None] & mine,
                                e[..., rpg.ENTRY_PAGE], -1)
                if cfg.attend == "gather":
                    kv_in = rpg.gather_local(pool_l, ids)  # [m, ppb, pt, 2, d]
                    m = kv_in.shape[0]
                    kv_in = kv_in.reshape(m, cfg.block_tokens, 2, cfg.d_model)
                    out_req, out_tok = readout(params, kv_in, msk, tg)
                else:
                    q = jnp.broadcast_to(
                        params["w_q"], (ids.shape[0], 1, cfg.d_model))
                    ctx = pattn.paged_attention_pallas(
                        q, pool_l, ids, scale=1.0, causal=False,
                        interpret=interpret_mode())[:, 0]  # [m, d]
                    logits = ctx @ params["readout"]       # [m, vocab]
                    out_tok = jnp.where(
                        msk, jnp.argmax(logits, -1).astype(jnp.int32), -1)
                    out_req = jnp.where(msk, tg, -1)
                return out_req[None], out_tok[None]

            pspec = P(axis, None, None, None, None)
            self._attend_step = jax.jit(
                shard_map(
                    attend,
                    mesh=self.mesh,
                    in_specs=(P(), pspec, P(axis, None, None, None),
                              P(axis, None), P(axis, None)),
                    out_specs=(P(axis, None), P(axis, None)),
                    check_vma=False,
                )
            )
            return jax.jit(
                shard_map(
                    ship,
                    mesh=self.mesh,
                    in_specs=(P(), qspecs, fspecs, pspec,
                              P(axis, None, None), P(axis), P(axis),
                              P(axis, None), P(axis, None, None),
                              P(axis, None), P(axis, None)),
                    out_specs=(qspecs, fspecs, pspec,
                               P(axis, None, None, None), P(axis, None),
                               P(axis, None), P(axis), P(axis)),
                    check_vma=False,
                )
            )

        if cfg.flow:
            def step(params, qstate, fstate, tokens, req_id, dest, lane):
                """Per-rank [1, ...] inputs: this rank's staged request
                (req_id -1 = none), its target decode rank and kv lane."""
                me = jax.lax.axis_index(axis)
                qstate = rq.to_local(qstate)
                fstate = rfl.to_local(fstate)
                toks, rid = tokens[0], req_id[0]

                is_prefill = (me < n_prefill) & (rid >= 0)
                kv_block = compute_kv(params, toks)
                dest_eff = jnp.where(is_prefill, dest[0], -1).astype(jnp.int32)
                qstate, fstate, receipt = rfl.send(
                    ch, qstate, fstate, "kv0",
                    kv_block[None], rid[None], dest_eff[None], lane[0],
                )
                qstate, fstate, batch = rfl.recv(
                    ch, qstate, fstate, cfg.max_recv_per_step)
                out_req, out_tok = decode_batch(params, batch)
                sent_ok = receipt.accepted[0] & is_prefill
                return (
                    rq.to_global(qstate), rfl.to_global(fstate),
                    out_req[None], out_tok[None], sent_ok[None],
                    receipt.rejected[None],
                )

            return jax.jit(
                shard_map(
                    step,
                    mesh=self.mesh,
                    in_specs=(P(), qspecs, fspecs, P(axis, None), P(axis),
                              P(axis), P(axis, None)),
                    out_specs=(qspecs, fspecs, P(axis, None), P(axis, None),
                               P(axis), P(axis)),
                    check_vma=False,
                )
            )

        def step(params, qstate, tokens, req_id, dest, lane):
            me = jax.lax.axis_index(axis)
            qstate = rq.to_local(qstate)
            toks, rid = tokens[0], req_id[0]

            is_prefill = (me < n_prefill) & (rid >= 0)
            kv_block = compute_kv(params, toks)
            dest_eff = jnp.where(is_prefill, dest[0], -1).astype(jnp.int32)
            msgs = ch.packed("kv0", kv_block[None], rid[None], lane_id=lane[0])
            qstate, receipt = rq.enqueue(ch.desc, qstate, msgs, dest_eff[None])
            qstate, batch = ch.recv(qstate, cfg.max_recv_per_step)
            out_req, out_tok = decode_batch(params, batch)
            sent_ok = receipt.accepted[0] & is_prefill
            return (
                rq.to_global(qstate),
                out_req[None], out_tok[None], sent_ok[None],
            )

        return jax.jit(
            shard_map(
                step,
                mesh=self.mesh,
                in_specs=(P(), qspecs, P(axis, None), P(axis), P(axis),
                          P(axis, None)),
                out_specs=(qspecs, P(axis, None), P(axis, None), P(axis)),
                check_vma=False,
            )
        )

    def _trace_message_stats(self) -> dict:
        """Abstractly trace one engine step under an `OpCounter` and report
        the raw vs coalesced (wire) message counts of the KV-shipping path."""
        from repro.core.rma import OpCounter

        cfg = self.cfg
        if self.mode in ("paged", "rendezvous"):
            state = (self.params, self.qstate, self.fstate, self.pool)
        elif self.fstate is None:
            state = (self.params, self.qstate)
        else:
            state = (self.params, self.qstate, self.fstate)
        like = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)
        req_id = jax.ShapeDtypeStruct((self.p,), jnp.int32)
        dest = jax.ShapeDtypeStruct((self.p,), jnp.int32)
        lane = jax.ShapeDtypeStruct((self.p, 1), jnp.int32)
        if self.mode in ("paged", "rendezvous"):
            ptab = jax.ShapeDtypeStruct(
                (self.p, cfg.pages_per_block, rpg.ENTRY_WORDS), jnp.int32)
            novel_toks = jax.ShapeDtypeStruct(
                (self.p, cfg.novel_slots, cfg.page_tokens), jnp.int32)
            novel_i = jax.ShapeDtypeStruct((self.p, cfg.novel_slots), jnp.int32)
            if self.mode == "rendezvous":
                args = like + (ptab, req_id, dest, lane, novel_toks, novel_i)
            else:
                args = like + (ptab, req_id, dest, lane, novel_toks, novel_i,
                               novel_i)
        else:
            tokens = jax.ShapeDtypeStruct((self.p, cfg.block_tokens), jnp.int32)
            args = like + (tokens, req_id, dest, lane)
        with OpCounter() as c:
            self._step.lower(*args)
        bytes_wire = sum(pl.get("bytes_wire", 0) for pl in c.plans)
        return {
            "raw_msgs_per_step": c.raw_msgs,
            "wire_msgs_per_step": c.coalesced_msgs,
            "aggregation_factor": c.aggregation_factor,
            "puts": c.puts,
            "gets": c.gets,
            "accs": c.accs,
            "bytes_wire_per_step": bytes_wire,
            "plans": [dict(pl) for pl in c.plans],
        }

    # ------------------------------------------------------------ host side
    def submit(self, req_id: int, tokens) -> None:
        toks = np.asarray(tokens, np.int32)
        if toks.shape != (self.cfg.block_tokens,):
            raise ValueError(f"prompt must be [{self.cfg.block_tokens}] tokens")
        self._pending.append((req_id, toks))
        self._n_submitted += 1
        self._submitted_ids.add(int(req_id))
        self._t_submit[int(req_id)] = time.perf_counter()
        tr = obs_trace.TRACER
        if tr.enabled:
            tr.event("serve.request.submit", rid=int(req_id),
                     plen=int(toks.shape[0]))

    def _observe_result(self, rid: int, rank: int = 0) -> None:
        """Land one decoded result in the latency ledgers: per-request TTFT
        and the engine-wide inter-result gap (TBT).  `rank` is the decode
        rank that produced the token — the consumer end of the request's
        KV edge, which closes the cross-rank causal DAG (obs.causal)."""
        now = time.perf_counter()
        # a result landing is a terminal transition: drop any recorded stall
        # reason even when the submit timestamp is already gone (the old
        # discard sat inside the t0 branch and leaked rids whose ledger
        # entry was consumed elsewhere — a later request reusing the id then
        # inherited credit_stall/page_alloc attribution it never paid)
        self._stalled.pop(rid, None)
        t0 = self._t_submit.pop(rid, None)
        if t0 is not None:
            ttft_us = (now - t0) * 1e6
            self.metrics.histogram("serve.ttft_us").observe(ttft_us,
                                                            exemplar=rid)
            t_staged = self._t_staged.pop(rid, None)
            wire_seg = "kv_pull" if self.mode == "rendezvous" else "kv_wire"
            if t_staged is not None:
                self.metrics.histogram(f"seg.{wire_seg}_us").observe(
                    (now - t_staged) * 1e6)
            tr = obs_trace.TRACER
            if tr.enabled:
                tr.event("serve.request.decode", rid=rid, rank=rank,
                         cause=obs_causal.edge(rid, "kv"), seg=wire_seg)
                tr.event("serve.request.first_token", rid=rid, rank=rank,
                         seg="attend", ttft_us=int(ttft_us))
        if self._t_last_result is not None:
            self.metrics.histogram("serve.tbt_us").observe(
                (now - self._t_last_result) * 1e6)
        self._t_last_result = now

    def serve_metrics(self) -> dict:
        """Request-latency summaries (§12): TTFT and TBT in microseconds,
        plus the per-decode-step attention latency (paged mode; empty
        summary otherwise)."""
        return {
            "ttft_us": self.metrics.histogram("serve.ttft_us").summary(),
            "tbt_us": self.metrics.histogram("serve.tbt_us").summary(),
            "attend_us": self.metrics.histogram("serve.attend_us").summary(),
            "seg.queue_wait_us":
                self.metrics.histogram("seg.queue_wait_us").summary(),
            "seg.kv_wire_us":
                self.metrics.histogram("seg.kv_wire_us").summary(),
            "seg.kv_pull_us":
                self.metrics.histogram("seg.kv_pull_us").summary(),
        }

    def _host_credits(self) -> np.ndarray:
        """[p(producer), p(target), L] credits the device-side caches hold —
        read back from the returned flow state, so host admission mirrors
        the device protocol exactly (same one-epoch refresh staleness)."""
        limit = np.asarray(self.fstate.limit).astype(np.int64)
        sent = np.asarray(self.fstate.sent).astype(np.int64)
        return limit - sent

    def _select_lane(self, credits: np.ndarray, r: int,
                     targets=None) -> tuple[int, int] | None:
        """Credit-aware lane selection for producer r: the (decode rank,
        lane) with the most available credit, ties broken toward the least
        historically loaded lane (continuous batching spreads work instead
        of camping on the first lane); None when every lane is dry (the
        request stays pending — no wire traffic, nothing to retry).
        `targets` restricts the candidate decode ranks (paged mode routes
        by prefix affinity, so the destination is fixed)."""
        best, best_key = None, None
        if targets is None:
            targets = range(self.cfg.n_prefill, self.p)
        for t in targets:
            for ln in range(self.cfg.n_lanes):
                c = credits[r, t, ln]
                if c < 1:
                    continue
                key = (c, -self.lane_sends[t, ln])
                if best_key is None or key > best_key:
                    best, best_key = (t, ln), key
        return best

    # ------------------------------------------------------- paged host side
    def _map_request(self, rid: int, toks: np.ndarray):
        """Build a shipping job: acquire (or share) every page of the
        request at its routed decoder.  None when the pool is dry — every
        acquisition is rolled back and the request waits for releases."""
        cfg = self.cfg
        pages_toks = rpg.split_pages(toks, cfg.page_tokens)
        dest = self.kv.route(rpg.page_key(pages_toks[0]))
        entries, novel = [], []
        hits0, miss0 = self.kv.hits, self.kv.misses
        for ptoks in pages_toks:
            res = self.kv.acquire(dest, rpg.page_key(ptoks))
            if res is None:
                for ref in entries:
                    self.kv.release_ref(ref)
                # rolled-back acquisitions are not real traffic: keep the
                # hit/miss stats (the BENCH_rmem evidence) truthful
                self.kv.hits, self.kv.misses = hits0, miss0
                self.pool_stalls += 1
                return None
            ref, shared = res
            entries.append(ref)
            if not shared:
                novel.append((ref.page_id, ptoks))
        self.kv.table_set(rid, entries)
        return {"rid": rid, "dest": dest, "entries": entries,
                "novel": novel, "next": 0}

    def _paged_step(self) -> int:
        """One paged engine step: ship novel pages, append page tables for
        requests whose pages are all resident, drain + decode, release the
        pages of finished requests."""
        cfg, p = self.cfg, self.p
        S, ppb = cfg.novel_slots, cfg.pages_per_block
        ptab = np.full((p, ppb, rpg.ENTRY_WORDS), -1, np.int32)
        req_id = np.full((p,), -1, np.int32)
        dest = np.full((p,), -1, np.int32)
        lane = np.zeros((p, 1), np.int32)
        novel_toks = np.full((p, S, cfg.page_tokens), -1, np.int32)
        novel_slot = np.full((p, S), -1, np.int32)
        novel_dest = np.full((p, S), -1, np.int32)

        budget = self._host_credits()
        appended: dict[int, int] = {}
        pool_dry = False       # one dry probe per step, not one per idle rank
        for r in range(cfg.n_prefill):
            if self._rank_job[r] is None and self._pending and not pool_dry:
                rid, toks = self._pending.pop(0)
                job = self._map_request(rid, toks)
                if job is None:
                    self._pending.insert(0, (rid, toks))   # pool dry: wait
                    self._stalled[int(rid)] = "pool"
                    tr = obs_trace.TRACER
                    if tr.enabled:
                        tr.event("serve.request.pool_stall", rank=r,
                                 rid=int(rid), seg="queue_wait")
                    pool_dry = True
                    continue
                self._jobs[rid] = job
                self._rank_job[r] = rid
                now = time.perf_counter()
                self._t_staged[int(rid)] = now
                self.metrics.histogram("seg.queue_wait_us").observe(
                    (now - self._t_submit.get(int(rid), now)) * 1e6)
                tr = obs_trace.TRACER
                if tr.enabled:
                    # time since submit was queue wait, unless the request
                    # sat out a dry pool — then it waited on page releases
                    tr.event("serve.request.page_alloc", rank=r,
                             rid=int(rid), pages=len(job["entries"]),
                             seg=("page_alloc"
                                  if self._stalled.get(int(rid)) == "pool"
                                  else "queue_wait"))
            if self._rank_job[r] is None:
                continue
            job = self._jobs[self._rank_job[r]]
            # ship up to novel_slots of the job's unshipped novel pages;
            # a staged page is resident from this step on (the scatter
            # precedes every drain in program order)
            n_stage = min(S, len(job["novel"]) - job["next"])
            for s in range(n_stage):
                pid, ptoks = job["novel"][job["next"] + s]
                novel_toks[r, s] = ptoks
                novel_slot[r, s] = pid
                novel_dest[r, s] = job["dest"]
                self._page_ready.add((job["dest"], pid))
            job["next"] += n_stage
            self.novel_pages_shipped += n_stage
            if n_stage:
                tr = obs_trace.TRACER
                if tr.enabled:
                    tr.event("serve.request.kv_transfer", rank=r,
                             rid=int(job["rid"]), dst=int(job["dest"]),
                             pages=int(n_stage),
                             nbytes=int(n_stage) * cfg.page_nbytes)
            # append once every page (own novels AND shared pages shipped
            # by other jobs) is resident, and a lane credit is available
            resident = all((ref.owner, ref.page_id) in self._page_ready
                           for ref in job["entries"])
            if job["next"] < len(job["novel"]) or not resident:
                continue
            t = job["dest"]
            sel = self._select_lane(budget, r, targets=(t,))
            if sel is None:
                self.credit_stalls += 1
                self._stalled[int(job["rid"])] = "credit"
                tr = obs_trace.TRACER
                if tr.enabled:
                    tr.event("serve.request.credit_stall", rank=r,
                             rid=int(job["rid"]), seg="host")
                continue
            _, ln = sel
            ptab[r] = self.kv.table_entries(job["rid"])
            req_id[r], dest[r], lane[r, 0] = job["rid"], t, ln
            budget[r, t, ln] -= 1
            self.lane_sends[t, ln] += 1
            self.appends += 1
            self.ring_payload_appends += 1
            appended[r] = job["rid"]
            tr = obs_trace.TRACER
            if tr.enabled:
                # the append (page-table message) is what wakes the decoder:
                # it carries the request's KV edge in paged mode
                tr.event("serve.request.append", rank=r, rid=int(job["rid"]),
                         dst=int(t), lane=int(ln),
                         seg=("credit_stall"
                              if self._stalled.get(int(job["rid"])) == "credit"
                              else "host"),
                         edge=obs_causal.edge(int(job["rid"]), "kv"))
            # the stall (if any) is paid for and attributed: clear it so a
            # later reuse of the rid starts clean
            self._stalled.pop(int(job["rid"]), None)

        (self.qstate, self.fstate, self.pool, entries, mask, tags, sent_ok,
         rejected) = self._step(
            self.params, self.qstate, self.fstate, self.pool,
            jnp.asarray(ptab), jnp.asarray(req_id), jnp.asarray(dest),
            jnp.asarray(lane), jnp.asarray(novel_toks),
            jnp.asarray(novel_slot), jnp.asarray(novel_dest),
        )
        self.steps_run += 1
        if int(np.asarray(rejected).sum()):
            raise RuntimeError(
                "credit conservation violated: a credited paged append was "
                "rejected at the ring")
        sent_ok = np.asarray(sent_ok)
        for r, rid in appended.items():
            if not bool(sent_ok[r]):
                raise RuntimeError(f"credited paged append not delivered: {rid}")
            self._rank_job[r] = None        # the prefill rank frees up
            del self._jobs[rid]

        # decode attention, host-timed per step: the fused-vs-gather A/B
        # lever lives entirely inside this call (DESIGN.md §13)
        t0 = time.perf_counter()
        out_req, out_tok = self._attend_step(
            self.params, self.pool, entries, mask, tags)
        out_req, out_tok = np.asarray(out_req), np.asarray(out_tok)
        attend_us = (time.perf_counter() - t0) * 1e6
        self.metrics.histogram("serve.attend_us").observe(attend_us)
        tr = obs_trace.TRACER
        if tr.enabled:
            tr.event("serve.decode.attend", us=int(attend_us),
                     path=cfg.attend, staging_pages=cfg.staging_pages_resident)
        emitted = 0
        for rr in range(cfg.n_prefill, p):
            for rid, tok in zip(out_req[rr], out_tok[rr]):
                # a cancelled rid may still deliver a stale token; counting
                # it toward the drain quota would end run_until_drained with
                # a LIVE request still in flight
                if rid >= 0 and int(rid) in self._submitted_ids:
                    self.results[int(rid)] = int(tok)
                    self._observe_result(int(rid), rank=rr)
                    for ref in self.kv.table_release(int(rid)):
                        self._page_ready.discard((ref.owner, ref.page_id))
                    emitted += 1
        return emitted

    def _map_request_rdv(self, rid: int, toks: np.ndarray, owner: int):
        """Rendezvous shipping job: acquire (or share) every page of the
        request in the PREFILL rank's own pool — the pages never move at
        publish time.  None when the pool is dry (rolled back, request
        waits for pull completions to release pages)."""
        cfg = self.cfg
        pages_toks = rpg.split_pages(toks, cfg.page_tokens)
        entries, novel = [], []
        hits0, miss0 = self.kv.hits, self.kv.misses
        for ptoks in pages_toks:
            res = self.kv.acquire(owner, rpg.page_key(ptoks))
            if res is None:
                for ref in entries:
                    self.kv.release_ref(ref)
                self.kv.hits, self.kv.misses = hits0, miss0
                self.pool_stalls += 1
                return None
            ref, shared = res
            entries.append(ref)
            if not shared:
                novel.append((ref.page_id, ptoks))
        self.kv.table_set(rid, entries)
        return {"rid": rid, "owner": owner, "entries": entries,
                "novel": novel, "next": 0}

    def _rendezvous_step(self) -> int:
        """One rendezvous engine step (§16): stage novel pages into the
        prefill ranks' own pools, publish descriptors for requests whose
        pages are all resident (pinning every named page so it stays live
        for the pull), run the device step — descriptor ring + fused pull
        + attend — and release pins when tokens land."""
        cfg, p = self.cfg, self.p
        S, ppb = cfg.novel_slots, cfg.pages_per_block
        ptab = np.full((p, ppb, rpg.ENTRY_WORDS), -1, np.int32)
        req_id = np.full((p,), -1, np.int32)
        dest = np.full((p,), -1, np.int32)
        lane = np.zeros((p, 1), np.int32)
        novel_toks = np.full((p, S, cfg.page_tokens), -1, np.int32)
        novel_slot = np.full((p, S), -1, np.int32)

        budget = self._host_credits()
        appended: dict[int, int] = {}
        pool_dry = False
        for r in range(cfg.n_prefill):
            if self._rank_job[r] is None and self._pending and not pool_dry:
                rid, toks = self._pending.pop(0)
                job = self._map_request_rdv(rid, toks, r)
                if job is None:
                    self._pending.insert(0, (rid, toks))   # pool dry: wait
                    self._stalled[int(rid)] = "pool"
                    tr = obs_trace.TRACER
                    if tr.enabled:
                        tr.event("serve.request.pool_stall", rank=r,
                                 rid=int(rid), seg="queue_wait")
                    pool_dry = True
                    continue
                self._jobs[rid] = job
                self._rank_job[r] = rid
                now = time.perf_counter()
                self._t_staged[int(rid)] = now
                self.metrics.histogram("seg.queue_wait_us").observe(
                    (now - self._t_submit.get(int(rid), now)) * 1e6)
                tr = obs_trace.TRACER
                if tr.enabled:
                    tr.event("serve.request.page_alloc", rank=r,
                             rid=int(rid), pages=len(job["entries"]),
                             seg=("page_alloc"
                                  if self._stalled.get(int(rid)) == "pool"
                                  else "queue_wait"))
            if self._rank_job[r] is None:
                continue
            job = self._jobs[self._rank_job[r]]
            # stage up to novel_slots of the job's unwritten novel pages
            # into MY pool (owner-local device writes, zero wire traffic)
            n_stage = min(S, len(job["novel"]) - job["next"])
            for s in range(n_stage):
                pid, ptoks = job["novel"][job["next"] + s]
                novel_toks[r, s] = ptoks
                novel_slot[r, s] = pid
                self._page_ready.add((r, pid))
            job["next"] += n_stage
            self.novel_pages_shipped += n_stage
            # publish once every page (own novels AND shared pages written
            # by earlier jobs at this rank) is resident, and a descriptor
            # credit is available toward some decode rank
            resident = all((ref.owner, ref.page_id) in self._page_ready
                           for ref in job["entries"])
            if job["next"] < len(job["novel"]) or not resident:
                continue
            sel = self._select_lane(budget, r)
            if sel is None:
                self.credit_stalls += 1
                self._stalled[int(job["rid"])] = "credit"
                tr = obs_trace.TRACER
                if tr.enabled:
                    tr.event("serve.request.credit_stall", rank=r,
                             rid=int(job["rid"]), seg="host")
                continue
            t, ln = sel
            # pin every named page before the descriptor goes out: the
            # puller's refcount bump (heap.pin, an AMO against the owner's
            # ref bank) keeps the source pages live until the pull epoch
            # completes — a concurrent release can free nothing we named
            rid_j = int(job["rid"])
            pins = [(ref.owner, ref.page_id,
                     self.kv.pools[ref.owner].pin(ref.page_id, origin=t))
                    for ref in job["entries"]]
            self._pins[rid_j] = pins
            ptab[r] = self.kv.table_entries(rid_j)
            req_id[r], dest[r], lane[r, 0] = rid_j, t, ln
            budget[r, t, ln] -= 1
            self.lane_sends[t, ln] += 1
            self.appends += 1
            self.descriptor_appends += 1
            appended[r] = rid_j
            tr = obs_trace.TRACER
            if tr.enabled:
                # the descriptor append carries the request's KV edge: it is
                # what licenses the decoder's pull
                tr.event("serve.request.publish", rank=r, rid=rid_j,
                         dst=int(t), lane=int(ln),
                         nbytes=cfg.table_nbytes,
                         seg=("credit_stall"
                              if self._stalled.get(rid_j) == "credit"
                              else "host"),
                         edge=obs_causal.edge(rid_j, "kv"))
            self._stalled.pop(rid_j, None)   # stall paid + attributed

        (self.qstate, self.fstate, self.pool, out_req, out_tok, sent_ok,
         rejected) = self._step(
            self.params, self.qstate, self.fstate, self.pool,
            jnp.asarray(ptab), jnp.asarray(req_id), jnp.asarray(dest),
            jnp.asarray(lane), jnp.asarray(novel_toks),
            jnp.asarray(novel_slot),
        )
        self.steps_run += 1
        if int(np.asarray(rejected).sum()):
            raise RuntimeError(
                "credit conservation violated: a credited descriptor append "
                "was rejected at the ring")
        sent_ok = np.asarray(sent_ok)
        for r, rid in appended.items():
            if not bool(sent_ok[r]):
                raise RuntimeError(
                    f"credited descriptor append not delivered: {rid}")
            self._rank_job[r] = None        # the prefill rank frees up
            del self._jobs[rid]

        out_req, out_tok = np.asarray(out_req), np.asarray(out_tok)
        emitted = 0
        for rr in range(cfg.n_prefill, p):
            for rid, tok in zip(out_req[rr], out_tok[rr]):
                # a cancelled rid may still deliver a stale token — its pins
                # and table are already rolled back, and the token must not
                # count toward the drain quota (a live request could still
                # be in flight behind it)
                if rid >= 0 and int(rid) in self._submitted_ids:
                    self.results[int(rid)] = int(tok)
                    self._observe_result(int(rid), rank=rr)
                    # pull complete: drop the pull pins, then the table refs
                    for owner, pid, tag in self._pins.pop(int(rid), []):
                        self.kv.pools[owner].unpin(pid, tag, origin=rr)
                        self.pulled_pages += 1
                    if int(rid) in self.kv.page_tables:
                        for ref in self.kv.table_release(int(rid)):
                            self._page_ready.discard((ref.owner, ref.page_id))
                    emitted += 1
        return emitted

    def cancel(self, rid: int) -> bool:
        """Abort a request host-side — the "puller dies before flush" path.
        Rolls back everything the request holds: pull pins (if the
        descriptor was already published), page-table refs, queue slots,
        ledger entries.  Refcount conservation is the contract: after a
        cancel the pages a dead pull named are reclaimable (no leak), which
        `tests/test_rendezvous` asserts via pool conservation.  True if the
        rid was known."""
        rid = int(rid)
        known = False
        job = self._jobs.pop(rid, None)
        if job is not None:
            known = True
            for r, j in enumerate(self._rank_job):
                if j == rid:
                    self._rank_job[r] = None
        for owner, pid, tag in self._pins.pop(rid, []):
            self.kv.pools[owner].unpin(pid, tag, origin=owner)
            known = True
        if self.kv is not None and rid in self.kv.page_tables:
            for ref in self.kv.table_release(rid):
                self._page_ready.discard((ref.owner, ref.page_id))
            known = True
        before = len(self._pending)
        self._pending = [x for x in self._pending if int(x[0]) != rid]
        known = known or len(self._pending) != before
        if rid in self._submitted_ids and rid not in self.results:
            self._submitted_ids.discard(rid)
            self._n_submitted -= 1
        self._t_submit.pop(rid, None)
        self._t_staged.pop(rid, None)
        self._stalled.pop(rid, None)
        tr = obs_trace.TRACER
        if tr.enabled:
            tr.event("serve.request.cancel", rid=rid)
        return known

    def step(self) -> int:
        """One engine step: assign pending requests to prefill ranks, run the
        jitted SPMD step, collect decode outputs.  Returns #tokens emitted."""
        if self.mode == "rendezvous":
            return self._rendezvous_step()
        if self.cfg.paged:
            return self._paged_step()
        cfg, p = self.cfg, self.p
        tokens = np.full((p, cfg.block_tokens), -1, np.int32)
        req_id = np.full((p,), -1, np.int32)
        dest = np.full((p,), -1, np.int32)
        lane = np.zeros((p, 1), np.int32)
        staged: dict[int, tuple[int, np.ndarray]] = {}

        if cfg.flow:
            credits = self._host_credits()
            budget = credits.copy()
            for r in range(cfg.n_prefill):
                if not self._pending:
                    break
                sel = self._select_lane(budget, r)
                if sel is None:
                    self.credit_stalls += 1
                    rid_wait = int(self._pending[0][0])
                    self._stalled[rid_wait] = "credit"
                    tr = obs_trace.TRACER
                    if tr.enabled:
                        # milestone: time up to this stall was pure queue
                        # wait; the eventual staging charges credit_stall
                        tr.event("serve.request.credit_stall", rank=r,
                                 rid=rid_wait, seg="queue_wait")
                    continue               # r idles this step; request waits
                t, ln = sel
                rid, toks = self._pending.pop(0)
                tokens[r], req_id[r], dest[r], lane[r, 0] = toks, rid, t, ln
                staged[r] = (rid, toks)
                budget[r, t, ln] -= 1
                self.lane_sends[t, ln] += 1
                now = time.perf_counter()
                self._t_staged[int(rid)] = now
                self.metrics.histogram("seg.queue_wait_us").observe(
                    (now - self._t_submit.get(int(rid), now)) * 1e6)
                tr = obs_trace.TRACER
                if tr.enabled:
                    # the producer end of the request's KV edge: the decode
                    # side stamps cause=edge(rid, "kv") when the token lands
                    tr.event("serve.request.kv_transfer", rank=r, rid=int(rid),
                             dst=int(t), lane=int(ln),
                             nbytes=cfg.block_nbytes,
                             seg=("credit_stall"
                                  if self._stalled.get(int(rid)) == "credit"
                                  else "queue_wait"),
                             edge=obs_causal.edge(int(rid), "kv"))
                self._stalled.pop(int(rid), None)   # stall paid + attributed
                self.ring_payload_appends += 1
        else:
            # legacy: round-robin by request id, single implicit lane
            for r in range(cfg.n_prefill):
                if self._pending:
                    rid, toks = self._pending.pop(0)
                    tokens[r], req_id[r] = toks, rid
                    dest[r] = cfg.n_prefill + max(rid, 0) % self.n_decode
                    staged[r] = (rid, toks)

        if cfg.flow:
            (self.qstate, self.fstate, out_req, out_tok, sent_ok,
             rejected) = self._step(
                self.params, self.qstate, self.fstate,
                jnp.asarray(tokens), jnp.asarray(req_id),
                jnp.asarray(dest), jnp.asarray(lane),
            )
            if int(np.asarray(rejected).sum()):
                raise RuntimeError(
                    "credit conservation violated: a credited send was "
                    "rejected at the ring (mixed credited/uncredited "
                    "producers on one channel?)"
                )
            sent_ok = np.asarray(sent_ok)
            # a credit-admitted send is never rejected: nothing to re-queue
            lost = [staged[r] for r in sorted(staged) if not bool(sent_ok[r])]
            if lost:
                raise RuntimeError(f"credited sends not delivered: {lost}")
        else:
            self.qstate, out_req, out_tok, sent_ok = self._step(
                self.params, self.qstate,
                jnp.asarray(tokens), jnp.asarray(req_id),
                jnp.asarray(dest), jnp.asarray(lane),
            )
            sent_ok = np.asarray(sent_ok)
            # backpressure: rejected sends go back to the head of the queue
            # in staging order (FIFO-preserving batch splice)
            self.retries += _requeue_rejected(self._pending, staged, sent_ok)

        self.steps_run += 1
        out_req, out_tok = np.asarray(out_req), np.asarray(out_tok)
        emitted = 0
        for r in range(cfg.n_prefill, p):
            for rid, tok in zip(out_req[r], out_tok[r]):
                # cancelled rids may still emit; see _rendezvous_step
                if rid >= 0 and int(rid) in self._submitted_ids:
                    self.results[int(rid)] = int(tok)
                    self._observe_result(int(rid), rank=r)
                    emitted += 1
        return emitted

    def run_until_drained(self, max_steps: int = 1000) -> dict[int, int]:
        """Step until every submitted request has a result — including
        requests already in flight inside the decode rings.  Raises
        `DrainError` with the undrained request ids if `max_steps` is
        exhausted; partial results are never reported as drained."""
        steps = 0
        while len(self.results) < self._n_submitted:
            if steps >= max_steps:
                undrained = sorted(self._submitted_ids - set(self.results))
                # each undrained rid carries why it is stuck: a published
                # descriptor whose pull never completed ("pull"), a recorded
                # credit/pool stall, or plain queue residence.  The ledger
                # is cleared here — DrainError is a terminal transition too
                # (the _stalled leak regression).
                reasons = {}
                for rid in undrained:
                    if rid in self._pins:
                        reasons[rid] = "pull"
                    elif rid in self._stalled:
                        reasons[rid] = self._stalled[rid]
                    else:
                        reasons[rid] = "queue"
                self._stalled.clear()
                err = DrainError(
                    f"not drained after {max_steps} steps", tuple(undrained),
                    reasons=reasons,
                )
                obs_flight.on_error(err, tag="disagg")
                raise err
            self.step()
            steps += 1
        return self.results

    # ----------------------------------------------------------- reference
    def reference(self, tokens) -> int:
        """Single-host oracle: what the disaggregated path must produce."""
        toks = jnp.clip(jnp.asarray(tokens, jnp.int32), 0, self.cfg.vocab - 1)
        k = self.params["emb_k"][toks]
        v = self.params["emb_v"][toks]
        attn = jax.nn.softmax(k @ self.params["w_q"])
        logits = (attn @ v) @ self.params["readout"]
        return int(jnp.argmax(logits))

    def queue_stats(self) -> dict:
        return {k: np.asarray(v) for k, v in rq.stats(self.qstate).items()}

    def paged_stats(self) -> dict:
        """Paged-mode instrumentation: prefix sharing, page traffic, and the
        effective payload bytes a request costs on the wire — the §10
        evidence that prefix reuse cuts bytes_wire per admitted request.

        `effective_payload_bytes` counts what actually needed moving:
        one page-table message per append plus one page put per NOVEL page
        (shared pages cost zero payload).  `wire_bytes_total` is the §8
        plan ledger's origin-injected bytes accumulated over the steps the
        workload actually ran (dense epochs: every staged-or-not slot pays,
        like all this engine's accounting).
        """
        if self.mode != "paged":
            return {}
        ks = self.kv.stats()
        return {
            "attend_path": self.cfg.attend,
            "pages_per_block": self.cfg.pages_per_block,
            "staging_pages_resident": self.cfg.staging_pages_resident,
            "staging_bytes_per_decode": self.cfg.staging_nbytes,
            "appends": self.appends,
            "steps": self.steps_run,
            "novel_pages_shipped": self.novel_pages_shipped,
            "prefix_hits": ks["hits"],
            "prefix_hit_rate": ks["hit_rate"],
            "pool_stalls": self.pool_stalls,
            "effective_payload_bytes": (
                self.appends * self.cfg.table_nbytes
                + self.novel_pages_shipped * self.cfg.page_nbytes
            ),
            "wire_bytes_total": self.steps_run
            * self.msg_stats["bytes_wire_per_step"],
            "pool_conservation_ok": self.kv.conservation()["ok"],
        }

    def rendezvous_stats(self) -> dict:
        """Rendezvous-mode instrumentation (§16): descriptor-lane traffic vs
        the pull path.  The headline invariant is `ring_payload_appends == 0`
        — the ring moves descriptors only; every KV byte travels as a
        one-sided get issued by the decoder when it is ready to attend.
        """
        if self.mode != "rendezvous":
            return {}
        ks = self.kv.stats()
        return {
            "transport_selected": self.transport_selected,
            "descriptor_appends": self.descriptor_appends,
            "ring_payload_appends": self.ring_payload_appends,
            "descriptor_bytes": self.descriptor_appends * self.cfg.table_nbytes,
            "pulled_pages": self.pulled_pages,
            "pulled_bytes": self.pulled_pages * self.cfg.page_nbytes,
            "pool_stalls": self.pool_stalls,
            "prefix_hits": ks["hits"],
            "prefix_hit_rate": ks["hit_rate"],
            "pins_outstanding": sum(len(v) for v in self._pins.values()),
            "pool_conservation_ok": self.kv.conservation()["ok"],
            "wire_msgs_per_step": self.msg_stats["wire_msgs_per_step"],
            "wire_bytes_per_step": self.msg_stats["bytes_wire_per_step"],
        }

    def flow_stats(self) -> dict:
        """Credit-path instrumentation (flow mode only)."""
        if self.fstate is None:
            return {}
        cons = rfl.conservation(self.channel, self.qstate, self.fstate)
        return {
            "credit_stalls": self.credit_stalls,
            "retries": self.retries,
            "lane_sends": self.lane_sends.copy(),
            "conservation_ok": bool(
                (cons["granted_minus_head"] == cons["capacity"]).all()
                and (cons["outstanding_plus_occupancy"] == cons["capacity"]).all()
            ),
        }
