import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: builds the
production mesh from 512 placeholder host devices, attaches the sharding
policy to abstract params/optimizer/batch (ShapeDtypeStruct only — nothing
is allocated), AOT-compiles the jitted step, and records memory analysis,
XLA cost analysis, and the loop-aware HLO cost summary (repro.launch.
hlo_cost) for the roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen3-moe-30b-a3b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, get_config, shape_applicable
from repro.launch import hlo_cost
from repro.launch.mesh import chips_in, make_production_mesh
from repro.models.registry import build_model
from repro.parallel.sharding import ShardingPolicy, _dp, fit_spec
from repro.train.optimizer import AdamWConfig, init_opt_state
from repro.train.train_step import StepConfig, make_prefill_step, make_serve_step, make_train_step


def _sharded_sds(tree, spec_tree, mesh):
    return jax.tree.map(
        lambda s, p: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, fit_spec(p, s.shape, mesh))
        ),
        tree, spec_tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )


def _batch_specs(batch_sds, policy):
    dp = _dp(policy.mesh)

    def spec(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name in ("tokens", "labels"):
            return P(dp, None)
        if name in ("frames", "patches"):
            return P(dp, None, None)
        if name == "token":
            return P(dp)
        return P()

    return jax.tree_util.tree_map_with_path(spec, batch_sds)


def _cache_specs(cache_sds, policy, cfg):
    dp = _dp(policy.mesh)
    kv = policy.kv_cache_spec(cfg.n_kv_heads)     # [B, Hkv, hd, S]

    def spec(path, leaf):
        keys = [p.key if hasattr(p, "key") else str(p) for p in path]
        nd = len(leaf.shape)
        if "kv" in keys:                          # [L, B, Hkv, hd, S]
            return P(None, *kv)
        if "enc_out" in keys:                     # [B, S, D]
            return P(dp, None, None)
        if "len" in keys:
            return P()
        if "mamba" in keys:                       # [n_p, n_m, B, ...model-sharded]
            if keys[-1] == "h":                   # [n_p,n_m,B,di,N]
                return P(None, None, dp, "model", None)
            return P(None, None, dp, None, "model")  # conv [n_p,n_m,B,W-1,di]
        if "mlstm" in keys:                       # C [n_p,P-1,B,nh,dh,dh] / n / m
            pads = (None,) * (nd - 2)
            if keys[-1] == "C":
                return P(None, None, dp, "model", None, None)
            if keys[-1] == "n":
                return P(None, None, dp, "model", None)
            return P(None, None, dp, "model")     # m
        if "slstm" in keys:                       # [n_p, B, D]
            if nd == 3:
                return P(None, dp, "model")
            return P(*((None,) * (nd - 2)), dp, "model")
        return P()

    return jax.tree_util.tree_map_with_path(spec, cache_sds)


def make_policy(mesh, cfg, shape) -> ShardingPolicy:
    tp = mesh.shape.get("model", 1)
    return ShardingPolicy(
        mesh=mesh,
        seq_parallel=False,
        kv_seq_shard=(shape.name == "long_500k") or cfg.n_kv_heads < tp,
        fsdp=True,
    )


def run_cell(arch: str, shape_name: str, mesh, mesh_name: str,
             opt_overrides: dict | None = None) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name, "chips": chips_in(mesh)}
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = reason
        return rec

    model = build_model(cfg)
    policy = make_policy(mesh, cfg, shape)
    if opt_overrides:
        policy = dataclasses.replace(policy, **{k: v for k, v in opt_overrides.items()
                                                if hasattr(policy, k)})

    params_sds = model.init_shapes()
    pspecs = policy.tree_specs(params_sds)
    params_sds = _sharded_sds(params_sds, pspecs, mesh)
    inputs = model.input_specs(shape)

    t0 = time.time()
    if shape.kind == "train":
        opt_sds = jax.eval_shape(init_opt_state, params_sds)
        opt_specs = type(opt_sds)(P(), pspecs, pspecs)
        opt_sds = _sharded_sds(opt_sds, opt_specs, mesh)
        batch_sds = _sharded_sds(inputs, _batch_specs(inputs, policy), mesh)
        n_micro = (opt_overrides or {}).get("n_microbatches", 1)
        step = make_train_step(model, AdamWConfig(), StepConfig(n_microbatches=n_micro), policy)
        args = (params_sds, opt_sds, batch_sds)
    elif shape.kind == "prefill":
        batch_sds = _sharded_sds(inputs, _batch_specs(inputs, policy), mesh)
        step = make_prefill_step(model, policy)
        args = (params_sds, batch_sds)
    else:  # decode
        token_sds = _sharded_sds({"token": inputs["token"]}, _batch_specs({"token": inputs["token"]}, policy), mesh)["token"]
        cache_sds = _sharded_sds(inputs["cache"], _cache_specs(inputs["cache"], policy, cfg), mesh)
        step = make_serve_step(model, policy)
        args = (params_sds, token_sds, cache_sds)

    with mesh:
        lowered = jax.jit(step).lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = hlo_cost.analyze(compiled.as_text())

    rec.update(
        status="ok",
        kind=shape.kind,
        t_lower_s=round(t_lower, 2),
        t_compile_s=round(t_compile, 2),
        # per-device memory (bytes)
        mem_args=getattr(ma, "argument_size_in_bytes", 0),
        mem_out=getattr(ma, "output_size_in_bytes", 0),
        mem_temp=getattr(ma, "temp_size_in_bytes", 0),
        # XLA cost_analysis (per device; loop bodies counted ONCE — see hlo_*)
        xla_flops=float(ca.get("flops", 0.0)),
        xla_bytes=float(ca.get("bytes accessed", 0.0)),
        # loop-aware analysis (per device)
        hlo_flops=hlo.flops,
        hlo_bytes=hlo.hbm_bytes,
        coll_bytes=hlo.collective_bytes,
        coll_by_kind=hlo.collective_bytes_by_kind(),
        coll_by_group={str(k): v for k, v in hlo.collective_bytes_by_group_size().items()},
        hlo_warnings=hlo.warnings[:5],
        n_params=model.param_count(),
        n_active_params=cfg.n_active_params(),
    )
    return rec


def pspecs_as_tree(pspecs):
    return pspecs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCH_IDS + [None])
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    archs = ARCH_IDS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = 0
    for multi in meshes:
        mesh = make_production_mesh(multi_pod=multi)
        mname = "pod2x16x16" if multi else "pod16x16"
        for arch in archs:
            for shape in shapes:
                tag = f"{arch}_{shape}_{mname}"
                try:
                    rec = run_cell(arch, shape, mesh, mname)
                except Exception as e:  # noqa: BLE001 — a failing cell is a bug, record it
                    rec = {"arch": arch, "shape": shape, "mesh": mname,
                           "status": "FAILED", "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc()[-2000:]}
                    failures += 1
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(rec, f, indent=1)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    extra = (f"lower={rec['t_lower_s']}s compile={rec['t_compile_s']}s "
                             f"flops/dev={rec['hlo_flops']:.3e} coll/dev={rec['coll_bytes']:.3e}B "
                             f"temp={rec['mem_temp']/2**30:.2f}GiB")
                elif status == "FAILED":
                    extra = rec["error"][:160]
                print(f"[{status:7s}] {tag} {extra}", flush=True)
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
