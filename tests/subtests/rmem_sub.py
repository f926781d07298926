"""Multi-device page pool (DESIGN.md §10): rank-ordered alloc epochs from
8 concurrent origins, the conservation invariant (free + live == capacity,
stack/meta set consistency) under concurrent alloc/free traffic, ABA
generation tags across free/realloc, zero-marginal-wire piggybacked
allocation, the fused page scatter, and the paged_gather kernel vs its
oracle."""
import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.core import plan as plan_mod
from repro.core.rma import OpCounter
from repro.kernels.paged_gather import ops as pg_ops, ref as pg_ref
from repro.rmem import heap, pages

N = len(jax.devices())
mesh = jax.make_mesh((N,), ("x",))
sm = functools.partial(shard_map, mesh=mesh, check_vma=False)
failures = []


def check(name, ok):
    print(("PASS" if ok else "FAIL"), name)
    if not ok:
        failures.append(name)


N_PAGES, KMAX, PW = 32, 4, 2
desc, state0 = heap.pool_allocate(mesh, "x", N_PAGES, (PW,))
specs = heap.state_specs("x", 1)


def conserved(st):
    c = heap.conservation(desc, st)
    return ((c["free_plus_live"] == N_PAGES).all()
            and c["stack_consistent"].all())


# ---- 1. one alloc epoch, all 8 ranks hammering every target -------------
def alloc_step(s, want):
    s = heap.to_local(s)
    s, ids, granted = heap.alloc(desc, s, want[0], kmax=KMAX)
    return heap.to_global(s), ids[None], granted[None]


f_alloc = jax.jit(sm(alloc_step, in_specs=(specs, P("x", None)),
                     out_specs=(specs, P("x", None, None), P("x", None))))

want = np.full((N, N), 2, np.int32)          # 2 pages from EVERY target
with OpCounter() as c:
    st, ids, granted = f_alloc(state0, jnp.asarray(want))
ids, granted = np.asarray(ids), np.asarray(granted)
check("alloc epoch = 1 fused wire transfer", c.coalesced_msgs == 1)
check("fetch-and-op charged as AMO (accs=1 gets=1)",
      c.by_axis["x"] == {"gets": 1, "accs": 1})
check("every request granted (demand 16 <= capacity 32)",
      (granted == 2).all())
for t in range(N):
    got = ids[:, t, :].reshape(-1)
    got = got[got >= 0]
    if len(set(got.tolist())) != got.size:
        check(f"ids unique per target {t}", False)
        break
else:
    check("rank-ordered grants are disjoint (unique ids per target)", True)
check("conservation after concurrent alloc", conserved(st))

# ---- 2. release returns pages; refcount +1 defers the free --------------
flat_owner = np.repeat(np.arange(N, dtype=np.int32), KMAX)[None].repeat(N, 0)


def rel_step(s, ids_in, owner):
    s = heap.to_local(s)
    flat = ids_in[0].reshape(-1)
    s, nfreed = heap.release(desc, s, flat,
                             jnp.where(flat >= 0, owner[0], -1))
    return heap.to_global(s), nfreed[None]


f_rel = jax.jit(sm(rel_step,
                   in_specs=(specs, P("x", None, None), P("x", None)),
                   out_specs=(specs, P("x"))))


def share_step(s, ids_in, owner, delta):
    s = heap.to_local(s)
    flat = ids_in[0].reshape(-1)
    s, nfreed = heap.ref_update(desc, s, flat,
                                jnp.where(flat >= 0, owner[0], -1), delta[0])
    return heap.to_global(s), nfreed[None]


f_share = jax.jit(sm(share_step,
                     in_specs=(specs, P("x", None, None), P("x", None),
                               P("x", None)),
                     out_specs=(specs, P("x"))))

delta_p1 = np.ones((N, N * KMAX), np.int32)
st, nf = f_share(st, jnp.asarray(ids), jnp.asarray(flat_owner),
                 jnp.asarray(delta_p1))                    # share: ref 1 -> 2
check("share epoch frees nothing", int(np.asarray(nf).sum()) == 0)
st, nf1 = f_rel(st, jnp.asarray(ids), jnp.asarray(flat_owner))  # ref 2 -> 1
check("first release keeps shared pages live",
      int(np.asarray(nf1).sum()) == 0 and conserved(st))
gen_before = np.asarray(st.meta)[..., heap.GEN].copy()
st, nf2 = f_rel(st, jnp.asarray(ids), jnp.asarray(flat_owner))  # ref 1 -> 0
check("second release frees all pages",      # 2 pages x N producers x N targets
      int(np.asarray(nf2).sum()) == 2 * N * N)
cons = heap.conservation(desc, st)
check("conservation after concurrent free",
      (cons["free"] == N_PAGES).all() and conserved(st))
gen_after = np.asarray(st.meta)[..., heap.GEN]
freed_rows = gen_after != gen_before
check("free bumps the ABA generation of exactly the freed pages",
      int(freed_rows.sum()) == 2 * N * N)

# a +1 addressed to a DEAD page (a stale ref used after free — the ABA
# hazard) must not resurrect it while its id sits in the free stack: the
# delta is dropped whole and surfaced through the ERRS head counter (the
# SPMD analogue of HostPagePool's HeapError)
st_bad, _ = f_share(st, jnp.asarray(ids), jnp.asarray(flat_owner),
                    jnp.asarray(delta_p1))
cons_bad = heap.conservation(desc, st_bad)
check("dead-page delta dropped (no resurrection)",
      (cons_bad["live"] == 0).all() and (cons_bad["free"] == N_PAGES).all()
      and cons_bad["stack_consistent"].all())
check("protocol violation surfaced in ERRS counter",
      (cons_bad["protocol_errors"] > 0).all()
      and (heap.conservation(desc, st)["protocol_errors"] == 0).all())

# ---- 3. ABA: a tag cached before free/realloc must not validate ---------
tag_cached = np.asarray(st.meta)[0, :, heap.GEN][np.asarray(ids)[0, 0, 0]]
st2, ids2, _ = f_alloc(st, jnp.asarray(want))              # realloc everything


def tag_step(s, idv, genv):
    s = heap.to_local(s)
    return heap.tag_valid(s, idv[0], genv[0])[None]


f_tag = jax.jit(sm(tag_step, in_specs=(specs, P("x", None), P("x", None)),
                   out_specs=P("x", None)))
pid = int(np.asarray(ids)[0, 0, 0])
idv = np.full((N, 1), pid, np.int32)
stale = np.full((N, 1), int(tag_cached) - 1, np.uint32)    # pre-free tag
fresh = np.asarray(st2.meta)[:, pid, heap.GEN][:, None]
ok_stale = np.asarray(f_tag(st2, jnp.asarray(idv), jnp.asarray(stale)))
ok_fresh = np.asarray(f_tag(st2, jnp.asarray(idv), jnp.asarray(fresh)))
check("stale (pre-free) tag invalid after realloc", not ok_stale.any())
check("fresh tag valid", ok_fresh.all())

# ---- 4. random concurrent alloc/free traffic: conservation every epoch --
rng = np.random.RandomState(0)
st = state0
held: list[tuple[int, int]] = []       # (owner, page_id) live pages, host view
for epoch in range(6):
    w = rng.randint(0, 3, size=(N, N)).astype(np.int32)
    st, ids_e, _ = f_alloc(st, jnp.asarray(w))
    ids_e = np.asarray(ids_e)
    for r in range(N):
        for t in range(N):
            held.extend((t, int(i)) for i in ids_e[r, t] if i >= 0)
    # free a random half of what is held, from all ranks concurrently
    rng.shuffle(held)
    n_rel = len(held) // 2
    rel, held = held[:n_rel], held[n_rel:]
    rel_ids = np.full((N, N * KMAX), -1, np.int32)
    rel_own = np.full((N, N * KMAX), -1, np.int32)
    for j, (t, i) in enumerate(rel):
        rel_ids[j % N, j // N] = i
        rel_own[j % N, j // N] = t
    st, _ = f_rel(st, jnp.asarray(rel_ids.reshape(N, N, KMAX)),
                  jnp.asarray(rel_own))
    if not conserved(st):
        check(f"conservation under random alloc/free (epoch {epoch})", False)
        break
else:
    check("conservation under random concurrent alloc/free", True)
c2 = heap.conservation(desc, st)
check("host live census matches device meta",
      int(c2["live"].sum()) == len(held))

# ---- 5. piggyback: alloc rides an existing epoch's fused gather ---------
def piggy_step(s, want, other):
    s = heap.to_local(s)
    pl = plan_mod.RmaPlan("x")
    h_other = pl.all_gather(other[0], kind="gets")   # the host epoch's own op
    handles = heap.alloc_record(pl, s, want[0])
    pl.flush(aggregate=True)
    s, ids, granted = heap.alloc_apply(desc, s, KMAX, handles)
    return heap.to_global(s), ids[None], h_other.result()[None]


f_piggy = jax.jit(sm(piggy_step,
                     in_specs=(specs, P("x", None), P("x", None)),
                     out_specs=(specs, P("x", None, None), P("x", None, None))))
other = np.arange(N * 4, dtype=np.int32).reshape(N, 4)
with OpCounter() as c:
    st3, ids3, oth = f_piggy(state0, jnp.asarray(want), jnp.asarray(other))
check("piggybacked alloc: still ONE fused wire transfer",
      c.coalesced_msgs == 1 and c.raw_msgs == 4)
check("rider data intact", (np.asarray(oth)[0] == other).all())
check("piggybacked grants land", (np.asarray(ids3)[:, 0, :2] >= 0).all())

# ---- 6. fused page scatter + owner-local gather -------------------------
S = 3


def scatter_step(pool, payload, slot, dest, gather_ids):
    pool = pages.scatter_pages("x", pool[0], payload[0], slot[0], dest[0])
    out = pages.gather_local(pool, gather_ids[0])
    return pool[None], out[None]


f_scatter = jax.jit(sm(
    scatter_step,
    in_specs=(P("x", None, None), P("x", None, None), P("x", None),
              P("x", None), P("x", None)),
    out_specs=(P("x", None, None), P("x", None, None))))

pool0 = np.zeros((N, N_PAGES, PW), np.float32)
payload = rng.rand(N, S, PW).astype(np.float32)
slot = np.tile(np.asarray([[5, 9, 17]], np.int32), (N, 1))
dest = np.full((N, S), -1, np.int32)
dest[0] = [1, 1, 2]                       # rank 0 writes into pools 1 and 2
dest[3] = [-1, 2, 2]                      # rank 3 writes two pages into 2
slot[3] = [0, 2, 30]
gather_ids = np.full((N, S), -1, np.int32)
gather_ids[1] = [5, 9, -1]
gather_ids[2] = [17, 2, 30]
with OpCounter() as c:
    pool1, got = f_scatter(jnp.asarray(pool0), jnp.asarray(payload),
                           jnp.asarray(slot), jnp.asarray(dest),
                           jnp.asarray(gather_ids))
got = np.asarray(got)
check("scatter = 1 fused wire transfer (payload + slots)",
      c.coalesced_msgs == 1 and c.raw_msgs == 2)
check("pages landed at their owner slots",
      np.allclose(got[1, 0], payload[0, 0]) and
      np.allclose(got[1, 1], payload[0, 1]) and
      np.allclose(got[2, 0], payload[0, 2]) and
      np.allclose(got[2, 1], payload[3, 1]) and
      np.allclose(got[2, 2], payload[3, 2]))
check("masked gather rows stay zero", (got[1, 2] == 0).all())
check("invalid dest dropped (nobody wrote rank 0's pool)",
      np.allclose(np.asarray(pool1)[0], 0))

# ---- 7. paged_gather kernel vs oracle -----------------------------------
pool = jnp.asarray(rng.rand(N, 16, 8).astype(np.float32))
idsk = jnp.asarray(rng.randint(0, 16, size=(N, 5)).astype(np.int32))
for shift in (1, 3):
    out_k = pg_ops.paged_gather(pool, idsk, shift, mesh, "x")
    f_ref = jax.jit(sm(
        lambda b, i, s=shift: pg_ref.paged_gather_ref(b[0], i[0], s, "x")[None],
        in_specs=(P("x", None, None), P("x", None)),
        out_specs=P("x", None, None)))
    out_r = f_ref(pool, idsk)
    check(f"paged_gather kernel == oracle (shift={shift})",
          bool(jnp.allclose(out_k, out_r)))
# the oracle really reads the NEIGHBOR's pool
manual = np.asarray(pool)[(np.arange(N) + 1) % N][
    np.arange(N)[:, None], np.asarray(idsk)]
check("paged_gather semantics (shift=1 reads rank r+1)",
      np.allclose(np.asarray(pg_ops.paged_gather(pool, idsk, 1, mesh, "x")),
                  manual))

sys.exit(1 if failures else 0)
