"""Selection-path scaling sweep: host argsort vs jitted top_k vs Pallas vs
the sharded engine.

Times one full selection step of the round engine — predicted round cost
(Eq. 1's ``power(i)`` input) + scores + exploration + state update — over
synthetic populations from 10k to 4M clients, on four legs:

  host     the original eager path (eager ``predicted_round_cost_pct`` +
           ``select_host``: jnp scores pulled to host, two full
           ``np.argsort`` over the population)
  jit      the PR-1 device-resident path (one jitted function fusing the
           cost model with ``select_device``'s ``jax.lax.top_k``)
  pallas   the same fused step dispatching exploitation to the fused
           ``topk_reward`` Pallas kernel (interpret mode off-TPU, so its
           CPU number only proves the kernel logic)
  sharded  the sharded round engine (``--devices D`` virtual CPU devices
           via ``--xla_force_host_platform_device_count``): population
           sharded over a `clients` mesh, per-shard top-k + global merge,
           and the round-invariant per-client cost table hoisted to engine
           setup (``round_cost_table``) instead of recomputed in-step —
           both effects together carry the speedup over the jit leg

Device counts are baked into the process at jax init, so the sharded leg
runs in its own invocation and MERGES its rows into an existing output:

  PYTHONPATH=src python -m benchmarks.selection_scale                # 1-dev legs
  PYTHONPATH=src python -m benchmarks.selection_scale --devices 8    # sharded

Writes ``BENCH_selection.json`` and prints one row per (N, leg). Every
write also stamps each row with ``auto_engine`` — the engine the unified
``repro.federated.run_rounds`` dispatcher would pick for that
(N, device_count) — plus the cutover rule, so the engine-selection table
in ``docs/architecture.md`` is regenerable from this file
(``--annotate`` refreshes the stamps without re-timing anything).
"""
from __future__ import annotations

import os

from repro.host_devices import force_host_device_count_from_argv

force_host_device_count_from_argv()  # must precede the first jax import

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import EnergyModel, SelectorConfig, SelectorState, \
    make_population
from repro.core.selection import _device_select, make_sharded_select_step, \
    select_host
from repro.federated.simulation import ENGINE_CUTOVER_N, _round_cost, \
    predicted_round_cost_pct, resolve_engine, round_cost_table

DEFAULT_SIZES = (10_000, 65_536, 262_144, 1_048_576, 4_194_304)
# the simulated device workload (ResNet-34-class update, ~500 local epochs)
MODEL_BYTES, LOCAL_STEPS, BATCH = 85e6, 1600, 20


def _synth_pop(key, n: int):
    pop = make_population(key, n)
    ks = jax.random.split(jax.random.fold_in(key, 1), 3)
    return pop.replace(
        stat_util=jax.random.uniform(ks[0], (n,)) * 10,
        explored=jax.random.bernoulli(ks[1], 0.7, (n,)),
        dropped=jax.random.bernoulli(ks[2], 0.05, (n,)),
    )


def _time_ms(fn, reps: int) -> float:
    fn()  # warmup (compile)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    # best-of-reps: the standard noise-resistant microbenchmark estimate
    # (this container shares its host, so means/medians absorb neighbours)
    return float(np.min(ts)) * 1e3


def sweep(sizes, k: int, reps: int, pallas_reps: int, skip_pallas: bool):
    cfg = SelectorConfig(kind="eafl", k=k)
    em = EnergyModel()
    interpret = jax.default_backend() != "tpu"
    rows = []
    for n in sizes:
        key = jax.random.PRNGKey(n)
        pop = _synth_pop(key, n)
        state = SelectorState.create(cfg)

        def host_step():
            pred = predicted_round_cost_pct(pop, em, MODEL_BYTES,
                                            LOCAL_STEPS, BATCH)
            return select_host(key, cfg, state, pop, pred)

        host_ms = _time_ms(host_step, reps)

        def make_jit_step(use_pallas):
            @jax.jit
            def step(key, state, pop):
                _t, cost = _round_cost(pop, em, MODEL_BYTES, LOCAL_STEPS,
                                       BATCH, None)
                return _device_select(key, cfg, state, pop, cost,
                                      use_pallas, interpret)

            return lambda: jax.block_until_ready(step(key, state, pop)[:2])

        jit_ms = _time_ms(make_jit_step(False), reps)
        row = {"n": n, "k": k, "host_ms": round(host_ms, 3),
               "jit_ms": round(jit_ms, 3),
               "speedup_jit_vs_host": round(host_ms / jit_ms, 1)}
        if not skip_pallas:
            row["pallas_ms"] = round(_time_ms(make_jit_step(True),
                                              pallas_reps), 3)
            row["pallas_interpret"] = interpret
        rows.append(row)
        print(",".join(f"{k_}={v}" for k_, v in row.items()), flush=True)
    return rows


def sweep_sharded(sizes, k: int, reps: int, devices=None):
    """The sharded leg: one selection step of the sharded engine over all
    visible devices, population pre-sharded and the static cost table
    hoisted to setup (it is round-invariant — see ``round_cost_table``)."""
    from repro.core.clients import pad_population
    from repro.launch.mesh import make_client_mesh
    from repro.launch.sharding import population_sharding

    cfg = SelectorConfig(kind="eafl", k=k)
    em = EnergyModel()
    # pass the requested count through: make_client_mesh raises a clear
    # error if the pre-jax-import XLA flag didn't take (e.g. an existing
    # host_platform_device_count in XLA_FLAGS) instead of silently timing
    # a 1-shard "sharded" leg
    mesh = make_client_mesh(devices)
    n_dev = mesh.shape["clients"]
    shard = population_sharding(mesh)
    rows = []
    for n in sizes:
        key = jax.random.PRNGKey(n)
        pop = jax.device_put(pad_population(_synth_pop(key, n), n_dev),
                             shard)
        _t, cost = round_cost_table(pop, em, MODEL_BYTES, LOCAL_STEPS,
                                    BATCH, sharding=shard)
        state = SelectorState.create(cfg).canonical()
        step = make_sharded_select_step(cfg, mesh, n)
        fn = lambda: jax.block_until_ready(step(key, state, pop, cost)[:2])
        row = {"n": n, "k": k, "device_count": n_dev,
               "sharded_ms": round(_time_ms(fn, reps), 3)}
        rows.append(row)
        print(",".join(f"{k_}={v}" for k_, v in row.items()), flush=True)
    return rows


def _annotate_dispatch(result):
    """Record, per row, the engine `repro.federated.run_rounds` would have
    auto-picked for that (N, device_count) — so the docs' cutover claim is
    regenerable from this file instead of hand-maintained. Rows measured
    without a sharded leg resolve against device_count=1 (always the
    scanned engine)."""
    for row in result.get("rows", []):
        row["auto_engine"] = resolve_engine(
            row["n"], row.get("device_count", 1), mode="auto")
    result["dispatch"] = {
        "cutover_n": ENGINE_CUTOVER_N,
        "rule": "sharded iff device_count > 1 and n >= cutover_n "
                "(async twins follow the same placement rule)",
    }
    return result


def _merge_sharded(out_path: str, sharded_rows, n_dev: int, k: int):
    """Fold sharded rows into an existing result file (matching on n/k);
    purely additive so pre-sharded readers keep working."""
    result = {"backend": jax.default_backend(), "k": k,
              "workload": {"model_bytes": MODEL_BYTES,
                           "local_steps": LOCAL_STEPS, "batch": BATCH},
              "rows": []}
    if os.path.exists(out_path):
        with open(out_path) as f:
            result = json.load(f)
    by_n = {(r["n"], r.get("k")): r for r in result.get("rows", [])}
    for srow in sharded_rows:
        row = by_n.get((srow["n"], srow["k"]))
        if row is None:
            result.setdefault("rows", []).append(srow)
            row = srow
        else:
            row.update(srow)
        if "jit_ms" in row and "sharded_ms" in row:
            row["speedup_sharded_vs_jit"] = round(
                row["jit_ms"] / row["sharded_ms"], 1)
    result["sharded"] = {"device_count": n_dev, "hoisted_cost_table": True,
                         "mesh_axis": "clients"}
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", type=int, nargs="+", default=DEFAULT_SIZES)
    ap.add_argument("--k", type=int, default=100)
    ap.add_argument("--reps", type=int, default=9)
    ap.add_argument("--pallas-reps", type=int, default=3,
                    help="interpret mode is slow on CPU; time fewer reps")
    ap.add_argument("--skip-pallas", action="store_true")
    ap.add_argument("--devices", type=int, default=None,
                    help="virtual CPU device count; >1 runs ONLY the "
                         "sharded leg and merges its rows into --out")
    ap.add_argument("--fast", action="store_true",
                    help="small sizes only (CI smoke)")
    ap.add_argument("--annotate", action="store_true",
                    help="no timing: re-read --out and (re)write the "
                         "dispatcher annotations (auto_engine per row + "
                         "the cutover rule)")
    ap.add_argument("--out", default="BENCH_selection.json")
    args = ap.parse_args()

    if args.annotate:
        with open(args.out) as f:
            result = _annotate_dispatch(json.load(f))
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2)
        print(f"annotated {args.out} (cutover_n={ENGINE_CUTOVER_N})")
        return

    sizes = (10_000, 65_536) if args.fast else args.sizes
    if args.devices and args.devices > 1:
        rows = sweep_sharded(sizes, args.k, args.reps, args.devices)
        result = _merge_sharded(args.out, rows, args.devices, args.k)
    else:
        rows = sweep(sizes, args.k, args.reps, args.pallas_reps,
                     args.skip_pallas)
        result = {"backend": jax.default_backend(), "k": args.k,
                  "reps": args.reps,
                  "workload": {"model_bytes": MODEL_BYTES,
                               "local_steps": LOCAL_STEPS, "batch": BATCH},
                  "rows": rows}
        if os.path.exists(args.out):
            # merge, don't clobber: keep sharded fields for re-measured
            # sizes and whole rows for sizes this (possibly --fast) run
            # didn't cover, so a smoke run can't erase the full sweep
            with open(args.out) as f:
                prev = json.load(f)
            by_n = {(r["n"], r.get("k")): r for r in prev.get("rows", [])}
            for row in rows:
                old = by_n.pop((row["n"], row["k"]), {})
                for f_ in ("sharded_ms", "device_count"):
                    if f_ in old:
                        row[f_] = old[f_]
                if "jit_ms" in row and "sharded_ms" in row:
                    row["speedup_sharded_vs_jit"] = round(
                        row["jit_ms"] / row["sharded_ms"], 1)
            result["rows"] = sorted(rows + list(by_n.values()),
                                    key=lambda r: (r["n"], r.get("k") or 0))
            if "sharded" in prev:
                result["sharded"] = prev["sharded"]
    result = _annotate_dispatch(result)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
