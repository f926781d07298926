#!/usr/bin/env python3
"""Chip smoke check: drive the serving path once on a TPU and check it.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four chips, one process

One chip (default): SmolLM-360M at its published width, random weights
from `--seed`, wrapped in `ServeEngine` as `repro.launch.serve` wraps it
(4 slots, max_seq 512).  Eight requests are served in two waves of four,
each with a 128-token prompt and 32 new tokens.  Every emitted token is
checked against the model's own full forward pass on the chip.

Four chips (`--four-chips`): a shared-prefix prompt set through
`DisaggEngine` on a ("serve",) mesh of the four devices (2 prefill + 2
decode ranks) in each transfer mode: eager inline, paged with the fused
Pallas attend, and rendezvous.  Tokens must equal `DisaggEngine.reference`,
credit and page-pool conservation must hold, and the queue state and page
pool must hold one shard on each device.  Then the compiled
`put_shift_pallas` must equal `lax.ppermute` bit for bit on a 1 MiB payload.

With no TPU the script exits non-zero before any model code runs.  The last
line of stdout is one JSON object naming the device, printed only when every
check passed.  Times printed here are smoke output, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# Largest admitted gap, in logits, between a row's maximum and the logit of
# the token the engine emitted there.  Sized from this check on the CPU at
# smoke width: with float32 weights the worst gap is 0.0017 (one token in
# 256 off the argmax, because the decode cache stores K/V in bf16 while the
# full forward keeps them in float32); with bf16 weights every token is the
# argmax.  At full width the logits of the random model reach about 2.5,
# where one bf16 step is 2**-6, and the chip reduces in another order for
# one-token decode than for the full forward.  Eight bf16 steps admit that
# rounding over 32 layers; a token from a wrong position or a wrong cache
# row sits about a whole logit (0.6) or more below the maximum.
GAP_TOL = 8 * 2.0**-6

N_SLOTS, MAX_SEQ = 4, 512
PROMPT_LEN, MAX_NEW, WAVES = 128, 32, 2

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_seconds = [0.0]


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _on_duration(event: str, duration: float, **_) -> None:
    if event == BACKEND_COMPILE_EVENT:
        _compile_seconds[0] += duration


# ------------------------------------------------------------- one chip
def serve_and_check(cfg, seed: int = 0, log=print) -> dict:
    """Serve WAVES x N_SLOTS requests through `ServeEngine` and check every
    emitted token against `Model.forward_logits` over prompt + output[:-1].

    Every request of a wave has the same prompt length, so all slots of a
    wave sit at one position.  Returns a report; raises `SmokeFailure` when
    a token is further than `GAP_TOL` from its row's maximum."""
    from repro.models import build_model
    from repro.serve.engine import Request, ServeEngine

    key = jax.random.PRNGKey(seed)
    model = build_model(cfg)
    params = model.init(key)
    engine = ServeEngine(model, params, n_slots=N_SLOTS, max_seq=MAX_SEQ)
    n_req = WAVES * N_SLOTS
    prompts = np.asarray(jax.random.randint(
        jax.random.fold_in(key, 1), (n_req, PROMPT_LEN), 0, cfg.vocab_size))

    reqs, wave_s, compile_s = [], [], []
    for w in range(WAVES):
        wave = [Request(rid=w * N_SLOTS + i,
                        prompt=prompts[w * N_SLOTS + i].tolist(),
                        max_new=MAX_NEW) for i in range(N_SLOTS)]
        c0, t0 = _compile_seconds[0], time.perf_counter()
        for r in wave:
            engine.submit(r)
        engine.run_until_drained()
        wave_s.append(time.perf_counter() - t0)
        compile_s.append(_compile_seconds[0] - c0)
        reqs += wave
    for r in reqs:
        _check(r.done.is_set() and len(r.output) == MAX_NEW,
               f"request {r.rid} emitted {len(r.output)} of {MAX_NEW} tokens")

    @jax.jit
    def gaps(params, seqs, out):
        logits = model.forward_logits(params, {"tokens": seqs}).logits
        logits = logits[:, PROMPT_LEN - 1:].astype(jnp.float32)
        got = jnp.take_along_axis(logits, out[..., None], axis=-1)[..., 0]
        return logits.max(-1) - got, jnp.argmax(logits, -1)

    seqs = np.asarray([r.prompt + r.output[:-1] for r in reqs], np.int32)
    out = np.asarray([r.output for r in reqs], np.int32)
    gap, best = (np.asarray(a) for a in gaps(params, seqs, out))
    for r, g, b, o in zip(reqs, gap, best, out):
        log(f"request {r.rid}: {MAX_NEW} tokens, {int((b == o).sum())} equal "
            f"the full-forward argmax, worst gap {float(g.max())!r}")
    worst = float(gap.max())
    _check(worst <= GAP_TOL,
           f"worst gap {worst!r} exceeds the bf16 tolerance {GAP_TOL!r}")
    return {
        "requests": n_req,
        "tokens_equal_argmax": int((best == out).sum()),
        "tokens": int(out.size),
        "worst_gap": worst,
        "wave_seconds": wave_s,
        "wave_compile_seconds": compile_s,
    }


def one_chip(seed: int) -> None:
    from repro.configs import get_config

    cfg = get_config("smollm-360m")
    print(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads / {cfg.n_kv_heads} kv heads, head_dim "
          f"{cfg.hd}, vocab {cfg.vocab_size}; random weights from seed {seed}")
    print(f"one prompt length per wave ({PROMPT_LEN} tokens): every slot of a "
          "wave sits at the same position, so the single cache length of "
          "mixed-length decode (ROADMAP Reach 1) can neither hide nor fake "
          "a result here")
    rep = serve_and_check(cfg, seed)
    print(f"all {rep['requests']} requests pass the full-forward token check: "
          f"{rep['tokens_equal_argmax']}/{rep['tokens']} tokens equal the "
          f"argmax, worst gap {rep['worst_gap']!r} <= tolerance {GAP_TOL!r}")
    print(f"first wave: {rep['wave_compile_seconds'][0]!r} s of backend "
          f"compile, {rep['wave_seconds'][0]!r} s wall")
    print(f"second wave wall time (smoke timing, not a benchmark number): "
          f"{rep['wave_seconds'][1]!r} s")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")


# ---------------------------------------------------------- four chips
def _one_shard_per_device(tree, devices) -> bool:
    """Every leaf is split into distinct slices, one on each device."""
    want = {d.id for d in devices}
    return all(len(leaf.addressable_shards) == len(devices)
               and {s.device.id for s in leaf.addressable_shards} == want
               and len({str(s.index) for s in leaf.addressable_shards})
               == len(devices)
               for leaf in jax.tree.leaves(tree))


def disagg_phase(mesh, seed: int) -> None:
    from repro.serve.disagg import DisaggConfig, DisaggEngine

    devices = list(mesh.devices.flat)
    rng = np.random.RandomState(seed)
    vocab, bt, n_req = 97, 16, 12
    prefix = rng.randint(0, vocab, size=bt // 2)
    prompts = {i: np.concatenate([prefix, rng.randint(0, vocab, size=bt // 2)])
               for i in range(n_req)}
    # the toy model is float32; hold every path, the reference included, to
    # float32 matmuls so the comparison is of the transport alone
    with jax.default_matmul_precision("float32"):
        for mode in ("inline", "paged", "rendezvous"):
            cfg = DisaggConfig(
                n_prefill=2, block_tokens=bt, d_model=32, vocab=vocab,
                queue_capacity=16, max_recv_per_step=4, n_lanes=2,
                flow=True, paged=(mode == "paged"), page_tokens=4,
                novel_slots=2, pool_pages=48,
                transport="rendezvous" if mode == "rendezvous" else "eager")
            eng = DisaggEngine(mesh, "serve", cfg, seed=seed)
            _check(eng.mode == mode, f"engine built mode {eng.mode}, not {mode}")
            for rid, toks in prompts.items():
                eng.submit(rid, toks)
            res = eng.run_until_drained()
            bad = [rid for rid, toks in prompts.items()
                   if res.get(rid) != eng.reference(toks)]
            _check(not bad, f"[{mode}] tokens differ from the reference: {bad}")
            _check(eng.flow_stats()["conservation_ok"],
                   f"[{mode}] credit conservation broken")
            extra = ""
            if mode == "paged":
                ps = eng.paged_stats()
                _check(ps["pool_conservation_ok"],
                       "[paged] page-pool conservation broken")
                extra = f", prefix hits {ps['prefix_hits']}"
            if mode == "rendezvous":
                rs = eng.rendezvous_stats()
                _check(rs["pool_conservation_ok"],
                       "[rendezvous] page-pool conservation broken")
                _check(rs["ring_payload_appends"] == 0,
                       "[rendezvous] payload went through the ring")
                extra = (f", ring_payload_appends 0, "
                         f"{rs['pulled_pages']} pages pulled")
            state = {"qstate": eng.qstate, "fstate": eng.fstate}
            if eng.pool is not None:
                state["pool"] = eng.pool
            _check(_one_shard_per_device(state, devices),
                   f"[{mode}] state does not hold one shard per device")
            print(f"[{mode}] {len(res)}/{n_req} tokens == reference; credit "
                  f"conservation OK{', pool conservation OK' if eng.pool is not None else ''}"
                  f"{extra}; {' '.join(sorted(state))} hold one shard on each of "
                  f"{len(devices)} devices; {eng.steps_run} steps")


def put_phase(mesh) -> None:
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import plan as plan_mod
    from repro.core.perfmodel import PerfModel
    from repro.kernels.common import interpret_mode
    from repro.kernels.rma.kernel import put_shift_pallas

    n = mesh.shape["serve"]
    rows, cols = 1024, 256                    # 1 MiB of f32 per device
    nbytes = rows * cols * 4
    backend = plan_mod.choose_backend(PerfModel(), nbytes, shift_eligible=True)
    _check(backend == "pallas", f"the plan picks {backend} for {nbytes} B")
    spec = P("serve", None)

    def sm(f):
        return jax.jit(shard_map(f, mesh=mesh, in_specs=spec, out_specs=spec,
                                 check_vma=False))

    put = sm(lambda x: put_shift_pallas(x, 1, "serve", n,
                                        interpret=interpret_mode()))
    ref = sm(lambda x: jax.lax.ppermute(
        x, "serve", [(i, (i + 1) % n) for i in range(n)]))
    x = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(3), (n * rows, cols), jnp.float32),
        NamedSharding(mesh, spec))
    compiled = put.lower(x).compile()
    _check("tpu_custom_call" in compiled.as_text(),
           "compiled put has no tpu_custom_call")
    y, y_ref = np.asarray(compiled(x)), np.asarray(ref(x))
    _check(np.array_equal(y.view(np.uint32), y_ref.view(np.uint32)),
           "put_shift_pallas differs from lax.ppermute")
    print(f"put_shift_pallas ({nbytes} B per device, plan backend {backend}) "
          f"== lax.ppermute bit for bit; compiled program holds tpu_custom_call")


def four_chips(seed: int) -> None:
    devices = jax.devices()
    _check(len(devices) == 4, f"--four-chips needs 4 devices, found {len(devices)}")
    mesh = jax.make_mesh((4,), ("serve",))
    print("mesh ('serve',): ranks 0-1 prefill, 2-3 decode")
    disagg_phase(mesh, seed)
    put_phase(mesh)


# ----------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the cross-chip phase on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform} devices); "
              "nothing to check", file=sys.stderr)
        return 1

    from repro.kernels.common import interpret_mode
    from repro.launch.compile_cache import enable_compile_cache

    print(f"device: {dev.device_kind} x{len(devices)} ({dev.platform}), "
          f"jax {jax.__version__}")
    print(f"compile cache: {enable_compile_cache()}")
    print(f"interpret_mode(): {interpret_mode()}")
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    try:
        _check(not interpret_mode(), "Pallas kernels would run interpreted")
        if args.four_chips:
            four_chips(args.seed)
        else:
            one_chip(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
