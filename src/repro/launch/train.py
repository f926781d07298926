"""End-to-end training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --smoke \
        --steps 50 --batch 4 --seq 128

On real hardware this binds the production mesh; on this container it runs
the reduced config on the local device(s) — the same Trainer/pipeline/ckpt
stack either way (mesh size is the only difference, by construction).
"""

from __future__ import annotations

import argparse

import jax

from repro.configs import ARCH_IDS, get_config
from repro.ckpt.checkpoint import CheckpointManager
from repro.data.pipeline import DataConfig, SyntheticTokenPipeline
from repro.ft.heartbeat import HeartbeatMonitor
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import StepConfig, make_train_step
from repro.train.trainer import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n_params = model.param_count()
    print(f"arch={cfg.name} params={n_params/1e6:.2f}M devices={len(jax.devices())}")

    pipeline = SyntheticTokenPipeline(
        DataConfig(cfg.vocab_size, args.seq, args.batch)
    )
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 1),
                          total_steps=args.steps)
    step = jax.jit(make_train_step(model, opt_cfg,
                                   StepConfig(n_microbatches=args.microbatches)))

    trainer = Trainer(
        step, params, pipeline,
        TrainerConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                      log_every=max(args.steps // 20, 1), ckpt_dir=args.ckpt_dir),
        monitor=HeartbeatMonitor(1),
        ckpt=CheckpointManager(args.ckpt_dir),
    )
    if args.resume and trainer.maybe_resume():
        print(f"resumed at step {trainer.step}")

    history = trainer.run(on_step=lambda r: print(
        f"step {r['step']:5d}  loss {r['loss']:.4f}  gnorm {r['grad_norm']:.3f}  "
        f"{r['dt_s']*1e3:.0f} ms"))
    first, last = history[0]["loss"], history[-1]["loss"]
    print(f"loss: {first:.4f} -> {last:.4f} "
          f"({'improved' if last < first else 'NO IMPROVEMENT'})")


if __name__ == "__main__":
    main()
