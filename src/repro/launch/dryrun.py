import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh).

The two lines above MUST stay the first statements in this module — jax
locks the device count on first init, and the production meshes need 512
placeholder host devices. Nothing here allocates tensors: inputs are
ShapeDtypeStructs, params/opt/cache shapes come from jax.eval_shape.

Usage:
  python -m repro.launch.dryrun --arch phi3-mini-3.8b --shape train_4k
  python -m repro.launch.dryrun --all --mesh both --out experiments/dryrun
"""
import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config, get_shape
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding import (
    STRATEGIES,
    batch_sharding,
    cache_sharding,
    param_sharding,
    replicated,
    strategy_batch_axes,
)
from repro.launch.specs import (
    cache_len_for,
    cache_specs,
    input_specs,
    params_specs,
)
from repro.launch.steps import (
    default_optimizer,
    make_prefill_step,
    make_serve_step,
    make_train_step,
)
from repro.models.sharding_ctx import activation_axes
from repro.launch.mesh import batch_axes


def mirror_sharding(state_specs, p_shard, mesh):
    """Sharding for optimizer state: m/v/mu mirror the param tree."""
    flat_p = dict(jax.tree_util.tree_flatten_with_path(p_shard)[0])

    def one(path, leaf):
        sub = path[1:] if len(path) > 1 else path
        if path and getattr(path[0], "key", None) in ("m", "v", "mu"):
            hit = flat_p.get(tuple(sub))
            if hit is not None:
                return hit
        return replicated(mesh)

    return jax.tree_util.tree_map_with_path(one, state_specs)


def lower_one(arch: str, shape_name: str, multi_pod: bool,
              strategy: str = "baseline", serve_dtype=None):
    cfg = get_config(arch)
    if serve_dtype is not None:
        cfg = cfg.with_(param_dtype=serve_dtype)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    p_specs = params_specs(cfg)
    p_shard = param_sharding(cfg, p_specs, mesh, strategy)
    batch = input_specs(cfg, shape)
    b_shard = batch_sharding(cfg, batch, mesh, strategy)
    # fsdp: no TP anywhere. ep_fsdp: no TP on activations, but the MoE
    # dispatch still reshards experts over `model` (role used by moe_apply).
    act_model = None if strategy == "fsdp" else "model"

    # jax.set_mesh makes bare-PartitionSpec sharding constraints
    # resolvable in-trace
    with jax.set_mesh(mesh), activation_axes(
            batch=strategy_batch_axes(mesh, strategy), model=act_model,
            gather_weights=(strategy in ("fsdp", "ep_fsdp"))):
        if shape.mode == "train":
            opt = default_optimizer()
            o_specs = jax.eval_shape(opt.init, p_specs)
            o_shard = mirror_sharding(o_specs, p_shard, mesh)
            step = make_train_step(cfg, opt)
            lowered = jax.jit(step, in_shardings=(p_shard, o_shard, b_shard)
                              ).lower(p_specs, o_specs, batch)
        elif shape.mode == "prefill":
            step = make_prefill_step(cfg)
            lowered = jax.jit(step, in_shardings=(p_shard, b_shard)
                              ).lower(p_specs, batch)
        else:  # decode
            ring = bool(shape.sliding_window) and cfg.attn_kind != "none"
            c_specs = cache_specs(cfg, shape)
            c_shard = cache_sharding(cfg, c_specs, mesh)
            idx = jax.ShapeDtypeStruct((), jnp.int32)
            step = make_serve_step(cfg, ring=ring)
            lowered = jax.jit(step, in_shardings=(p_shard, b_shard, c_shard,
                                                  replicated(mesh))
                              ).lower(p_specs, batch, c_specs, idx)
    return cfg, shape, mesh, lowered


def run_one(arch: str, shape_name: str, multi_pod: bool, verbose=True,
            strategy: str = "baseline", serve_dtype=None):
    t0 = time.time()
    cfg, shape, mesh, lowered = lower_one(arch, shape_name, multi_pod,
                                          strategy, serve_dtype)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    cost = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        cost = dict(ca) if ca else {}
    except Exception as e:  # pragma: no cover
        cost = {"error": str(e)}
    mem = None
    mem_str = ""
    try:
        mem = compiled.memory_analysis()
        mem_str = str(mem)
    except Exception as e:  # pragma: no cover
        mem_str = f"memory_analysis failed: {e}"

    hlo = compiled.as_text()
    report = rl.analyze(cfg, shape, tuple(mesh.devices.shape), hlo, cost, mem)
    rec = {
        "arch": arch, "shape": shape_name, "strategy": strategy,
        "mesh": list(mesh.devices.shape), "multi_pod": multi_pod,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory_analysis": mem_str,
        "cost_flops": report.ca_flops, "cost_bytes": report.ca_bytes,
        "hlo_dot_flops_per_dev": report.hlo_flops_per_dev,
        "analytic_bytes_per_dev": report.analytic_bytes_per_dev,
        "collective_bytes_per_dev": report.collective_bytes_per_dev,
        "collective_by_type": report.collective_by_type,
        "t_compute": report.t_compute, "t_memory": report.t_memory,
        "t_collective": report.t_collective, "dominant": report.dominant,
        "model_flops_total": report.model_flops_total,
        "useful_ratio": report.useful_ratio,
    }
    if verbose:
        print(f"== {arch} x {shape_name} x mesh{rec['mesh']} [{strategy}] ==")
        print(f"   lower {t_lower:.1f}s compile {t_compile:.1f}s")
        print(f"   memory_analysis: {mem_str[:300]}")
        print(f"   cost_analysis: flops={report.ca_flops:.3e} "
              f"bytes={report.ca_bytes:.3e}")
        print(f"   roofline: compute={report.t_compute:.3e}s "
              f"memory={report.t_memory:.3e}s "
              f"collective={report.t_collective:.3e}s "
              f"-> dominant={report.dominant} useful={report.useful_ratio:.2f}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), default=None)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES), default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--strategy", choices=list(STRATEGIES), default="baseline")
    ap.add_argument("--serve-dtype", choices=["f32", "bf16"], default=None)
    ap.add_argument("--out", default=None, help="append JSONL records here")
    args = ap.parse_args()
    serve_dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16,
                   None: None}[args.serve_dtype]

    archs = list(ARCH_IDS) if args.all or not args.arch else [args.arch]
    shapes = list(INPUT_SHAPES) if args.all or not args.shape else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    rec = run_one(arch, shape, mp, strategy=args.strategy,
                                  serve_dtype=serve_dtype)
                    if args.out:
                        with open(args.out, "a") as f:
                            f.write(json.dumps(rec) + "\n")
                except Exception:
                    failures.append((arch, shape, mp))
                    traceback.print_exc()
    if failures:
        print("FAILURES:", failures)
        sys.exit(1)
    print(f"dry-run OK: {len(archs)*len(shapes)*len(meshes)} combinations")


if __name__ == "__main__":
    main()
