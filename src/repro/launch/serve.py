"""Batched serving launcher: continuous batching over the ServeEngine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-moe-30b-a3b --smoke \
        --requests 8 --max-new 12
"""

from __future__ import annotations

import argparse
import time

import jax

from repro.configs import ARCH_IDS, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serve.engine import Request, ServeEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    if cfg.family in ("audio",):
        raise SystemExit("serve demo targets decoder-only archs; see examples/ for enc-dec")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, n_slots=args.slots, max_seq=args.max_seq)

    rng = jax.random.PRNGKey(7)
    reqs = []
    for i in range(args.requests):
        plen = 4 + (i % 5)
        prompt = jax.random.randint(jax.random.fold_in(rng, i), (plen,), 0,
                                    cfg.vocab_size).tolist()
        req = Request(rid=i, prompt=prompt, max_new=args.max_new)
        reqs.append(req)
        engine.submit(req)

    t0 = time.perf_counter()
    engine.run_until_drained()
    dt = time.perf_counter() - t0
    total_tokens = sum(len(r.output) for r in reqs)
    for r in reqs[:4]:
        print(f"req {r.rid}: prompt[{len(r.prompt)}] -> {r.output}")
    print(f"{len(reqs)} requests, {total_tokens} tokens in {dt:.2f}s "
          f"({total_tokens/dt:.1f} tok/s, {args.slots} slots, "
          f"lock AMOs={engine.lock_win.total_amos})")
    assert all(r.done.is_set() for r in reqs)


if __name__ == "__main__":
    main()
