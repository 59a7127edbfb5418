from __future__ import annotations

from repro.host_devices import force_host_device_count_from_argv

force_host_device_count_from_argv()  # must precede the first jax import

"""The paper's evaluation (Sec. 5): EAFL vs Oort vs Random.

One experiment produces every figure: Fig 3a test accuracy, Fig 3b train
loss, Fig 3c Jain's fairness, Fig 4a cumulative battery dropouts, Fig 4b
round duration. The simulated device workload matches the paper (ResNet-34
scale: 85 MB model updates, ~500 local epochs), the learned proxy is the
small ResNet on the non-IID synthetic speech task.

``--mode async`` runs the same three selectors under the FedBuff-style
buffered-asynchronous server instead of the synchronous barrier (knobs:
``--buffer-size``, ``--max-concurrency``, ``--staleness-power``), emitting
the same dropout / fairness / accuracy-vs-wall-clock curves plus a
time-to-accuracy summary, so sync and async runs are directly comparable.
The default ``--mode auto`` goes through the repo's unified dispatcher
(``repro.federated.resolve_aggregation``): setting an async-only knob is
the async opt-in, otherwise the run is synchronous.

``--bench-out FILE`` switches to the training-engine throughput bench
instead: the same eafl workload at population scale (default 10k clients,
K=100) through the host reference loop, the fused device-resident scan
(``run_fl_scanned``) and — when more than one device is visible
(``--devices N`` forges virtual CPU devices) — the sharded twin, stamping
wall-clock rounds/s, speedups over host, and (simulated) time-to-accuracy
per engine. Combined with ``--mode async`` (or any async knob) the bench
covers the FedBuff family instead — host event loop vs
``run_fl_async_scanned`` vs ``run_fl_async_sharded`` — and the two
families merge under the ``"modes"`` key of one json
(``BENCH_training.json`` carries both).

Run standalone for the full-scale version:
  PYTHONPATH=src python -m benchmarks.fl_comparison --rounds 150 --clients 200
  PYTHONPATH=src python -m benchmarks.fl_comparison --buffer-size 5   # async
  PYTHONPATH=src python -m benchmarks.fl_comparison \
      --bench-out BENCH_training.json --devices 8      # engine throughput
"""
import argparse
import json
import os
from typing import Dict, Optional

from repro.configs.paper_resnet_speech import reduced
from repro.core import SelectorConfig
from repro.federated import FLConfig, FLHistory, resolve_aggregation, run_fl

# the paper's setup (Sec. 5): K=10, lr=0.05, B=20, f=0.25, YoGi
PAPER_SCALE = dict(
    k=10, f=0.25, client_lr=0.05, batch_size=20, server_opt="yogi",
    sim_model_bytes=85e6,      # ResNet-34-class update
    sim_local_steps=1600,      # ~500 epochs over 64 samples at B=20
)


def make_config(kind: str, rounds: int, clients: int, seed: int = 0,
                fast: bool = False,
                buffer_size: Optional[int] = None,
                max_concurrency: Optional[int] = None,
                staleness_power: float = 0.5,
                energy_budget_j: Optional[float] = None) -> FLConfig:
    scale = dict(PAPER_SCALE)
    sel = SelectorConfig(kind=kind, k=scale.pop("k"), f=scale.pop("f"),
                         pacer_t0=1500.0, pacer_delta=300.0)
    return FLConfig(
        selector=sel,
        n_clients=clients,
        rounds=rounds,
        local_steps=6 if fast else 10,
        samples_per_client=48 if fast else 64,
        eval_every=5,
        eval_samples=280 if fast else 560,
        model=reduced(),
        input_hw=16,
        init_battery_low=25.0,
        init_battery_high=95.0,
        seed=seed,
        client_lr=scale.pop("client_lr"),
        batch_size=scale.pop("batch_size"),
        server_opt=scale.pop("server_opt"),
        buffer_size=buffer_size,
        max_concurrency=max_concurrency,
        staleness_power=staleness_power,
        energy_budget_j=energy_budget_j,
        **scale,
    )


def run_comparison(rounds: int, clients: int, seed: int = 0,
                   fast: bool = False, verbose: bool = False,
                   mode: str = "auto", **async_kw) -> Dict[str, FLHistory]:
    out = {}
    for kind in ("eafl", "oort", "random"):
        cfg = make_config(kind, rounds, clients, seed, fast, **async_kw)
        out[kind] = run_fl(cfg, verbose=verbose, mode=mode)
    return out


def time_to_accuracy(h: FLHistory, target: float) -> Optional[float]:
    """Wall hours until test accuracy first reaches ``target`` (None if it
    never does) — the async-vs-sync headline metric."""
    for wall, acc in zip(h.wall_hours, h.test_acc):
        if acc >= target:
            return wall
    return None


def summarize(results: Dict[str, FLHistory],
              acc_target: Optional[float] = None,
              energy_budget_j: Optional[float] = None,
              ) -> Dict[str, Dict[str, float]]:
    if acc_target is None:
        # default target: 90% of the best final accuracy across selectors
        acc_target = 0.9 * max(h.test_acc[-1] for h in results.values())
    s = {}
    for kind, h in results.items():
        n = len(h.round)
        s[kind] = {
            "final_acc": h.test_acc[-1],
            "final_loss": h.train_loss[-1],
            "cum_dropouts": h.cum_dropouts[-1],
            "fairness": h.fairness[-1],
            "mean_round_s": sum(h.round_duration) / n,
            "mean_participation": sum(h.participation) / n,
            "wall_hours": h.wall_hours[-1],
            "acc_target": acc_target,
            "hours_to_target": time_to_accuracy(h, acc_target),
            "energy_spent_j": h.energy_spent_j[-1],
        }
        if energy_budget_j is not None:
            s[kind]["energy_budget_j"] = energy_budget_j
            s[kind]["budget_exhausted_round"] = h.budget_exhausted_round
    return s


def run_training_bench(clients: int, k: int, rounds: int, seed: int,
                       out: str,
                       checkpoint_every: Optional[int] = None,
                       mode: str = "sync",
                       buffer_size: Optional[int] = None,
                       max_concurrency: Optional[int] = None,
                       staleness_power: float = 0.5) -> None:
    """Throughput bench for the training engines (host loop / fused scan /
    sharded scan) on one eafl workload.

    ``mode="async"`` benches the FedBuff family instead — the host event
    loop vs ``run_fl_async_scanned`` vs ``run_fl_async_sharded`` — on a
    buffered regime (default ``buffer_size=k//2, max_concurrency=k``).
    One invocation benches one mode; the payloads merge under a
    ``"modes"`` key in the output json, so running ``--mode sync`` then
    ``--mode async`` against the same file stamps both families.

    Protocol: the fused engines get one warm run (their jitted R-round
    program is cached per config, so the timed run measures pure
    execution); the host loop is timed cold because re-tracing its
    per-round jits on every invocation IS part of its dispatch cost — the
    fused engines exist to amortize exactly that. All engines produce
    parity-level-identical trajectories (tests/test_training_engines.py),
    so the simulated time-to-accuracy is engine-independent and rounds/s
    is the whole story.

    ``checkpoint_every=N`` adds the elastic leg per engine: the same run
    snapshotting its carry every N rounds (amortized save cost = the
    wall-clock delta over the plain run / snapshots written) and a
    restore timed by resuming the final snapshot (zero rounds left — the
    measured time IS the load/rebuild cost), both stamped into the
    json."""
    import dataclasses
    import tempfile
    import time

    import jax

    from repro.federated.server import run_fl_scanned, run_fl_sharded

    # light local workload: at K=100 the vmapped cohort SGD + delta stack
    # is identical work for every engine (Amdahl), so the bench keeps it
    # small to expose what the engines actually differ in — per-round
    # host dispatch, transfers and the host loop's per-invocation re-jit
    cfg = FLConfig(
        selector=SelectorConfig(kind="eafl", k=k, f=0.25,
                                pacer_t0=1500.0, pacer_delta=300.0),
        n_clients=clients, rounds=rounds, local_steps=1, batch_size=4,
        samples_per_client=4, eval_every=rounds,
        eval_samples=140, model=reduced(), input_hw=16, seed=seed,
        init_battery_low=25.0, init_battery_high=95.0,
        sim_model_bytes=85e6, sim_local_steps=1600)

    async_knobs = {}
    if mode == "async":
        from repro.federated.async_server import (run_fl_async,
                                                  run_fl_async_scanned,
                                                  run_fl_async_sharded)
        async_knobs = {
            "buffer_size": buffer_size or max(1, k // 2),
            "max_concurrency": max_concurrency or k,
            "staleness_power": staleness_power,
        }
        cfg = dataclasses.replace(cfg, **async_knobs)
        engines = {
            "host": (run_fl_async, False),
            "scanned": (run_fl_async_scanned, True),
        }
        if jax.device_count() > 1:
            engines["sharded"] = (run_fl_async_sharded, True)
    else:
        engines = {
            "host": (lambda c: run_fl(c, engine="host"), False),
            "scanned": (run_fl_scanned, True),
        }
        if jax.device_count() > 1:
            engines["sharded"] = (run_fl_sharded, True)

    results, hists = {}, {}
    for name, (fn, warm) in engines.items():
        if warm:
            fn(cfg)
        t0 = time.perf_counter()
        h = fn(cfg)
        dt = time.perf_counter() - t0
        n = len(h.round)
        hists[name] = h
        results[name] = {
            "rounds": n, "wall_s": dt, "rounds_per_s": n / dt,
            "final_acc": h.test_acc[-1], "sim_wall_hours": h.wall_hours[-1],
            "energy_spent_j": h.energy_spent_j[-1],
        }
        print(f"{name:8s} {n} rounds in {dt:7.2f}s  "
              f"-> {n / dt:7.3f} rounds/s  acc={h.test_acc[-1]:.3f}")

        if checkpoint_every:
            with tempfile.TemporaryDirectory() as tmp:
                path = os.path.join(tmp, "ck_{round}.msgpack")
                ecfg = dataclasses.replace(
                    cfg, checkpoint_path=path,
                    checkpoint_every=checkpoint_every)
                if warm:  # same protocol: compile the segmented scans once
                    fn(ecfg)
                t0 = time.perf_counter()
                fn(ecfg)
                dt_ck = time.perf_counter() - t0
                saved = [r for r in range(1, rounds + 1)
                         if r % checkpoint_every == 0 or r == rounds]
                final = path.format(round=saved[-1])
                t0 = time.perf_counter()
                fn(dataclasses.replace(cfg, resume_from=final))
                dt_rs = time.perf_counter() - t0
                results[name].update({
                    "checkpoint_every": checkpoint_every,
                    "snapshots": len(saved),
                    "ckpt_wall_s": dt_ck,
                    "save_cost_s": max(dt_ck - dt, 0.0) / len(saved),
                    "snapshot_bytes": os.path.getsize(final),
                    "restore_wall_s": dt_rs,
                })
                print(f"{'':8s} elastic: {len(saved)} snapshots "
                      f"({results[name]['snapshot_bytes'] / 1e6:.1f} MB) "
                      f"save~{results[name]['save_cost_s'] * 1e3:.0f} ms "
                      f"restore {dt_rs * 1e3:.0f} ms")

    target = 0.9 * max(r["final_acc"] for r in results.values())
    hhost = results["host"]
    for name, h in hists.items():
        # simulated hours to target — engine-independent up to float
        # tolerance (trajectory parity), recorded per engine as a check
        results[name]["sim_hours_to_target"] = time_to_accuracy(h, target)
        results[name]["speedup_vs_host"] = (results[name]["rounds_per_s"]
                                            / hhost["rounds_per_s"])
    ident = {
        "bench": "training_engines", "clients": clients, "k": k,
        "rounds": rounds, "seed": seed, "devices": jax.device_count(),
        "checkpoint_every": checkpoint_every,
    }
    entry = {"acc_target": target, "engines": results, **async_knobs}
    payload = dict(ident)
    if os.path.exists(out):
        # merge with an existing bench of the same shape so sync + async
        # invocations stamp one json; any identity mismatch starts over
        try:
            with open(out) as f:
                prior = json.load(f)
            if all(prior.get(k) == v for k, v in ident.items()):
                payload = prior
        except (OSError, ValueError):
            pass
    payload.setdefault("modes", {})[mode] = entry
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
    for name, r in results.items():
        if name != "host":
            print(f"{name} speedup vs host: {r['speedup_vs_host']:.2f}x")
    print(f"wrote {out}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=150,
                    help="rounds (sync) / server aggregations (async)")
    ap.add_argument("--clients", type=int, default=200)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mode", choices=["auto", "sync", "async"],
                    default="auto",
                    help="auto = async iff an async knob is set "
                         "(the unified dispatcher's rule)")
    ap.add_argument("--buffer-size", type=int, default=None,
                    help="async: aggregate every N arrivals (default k)")
    ap.add_argument("--max-concurrency", type=int, default=None,
                    help="async: in-flight client cap (default k)")
    ap.add_argument("--staleness-power", type=float, default=None,
                    help="async: delta damping 1/(1+staleness)**p "
                         "(default 0.5; async-only, so passing it under "
                         "--mode auto opts the run into async)")
    ap.add_argument("--acc-target", type=float, default=None,
                    help="time-to-accuracy target (default: 0.9x best final)")
    ap.add_argument("--energy-budget-j", type=float, default=None,
                    help="fleet energy budget in joules: the ledger gate "
                         "stops admitting cohorts when the remaining "
                         "budget can't cover the predicted round cost "
                         "(benchmarks/budget_sweep.py sweeps this)")
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--out", default="experiments/fl_comparison.json")
    ap.add_argument("--bench-out", default=None, metavar="FILE",
                    help="run the training-engine throughput bench (host "
                         "vs fused vs sharded) and write its json here "
                         "instead of the selector comparison")
    ap.add_argument("--bench-clients", type=int, default=10000,
                    help="bench population size (default 10k)")
    ap.add_argument("--bench-k", type=int, default=100,
                    help="bench cohort size (default 100)")
    ap.add_argument("--bench-rounds", type=int, default=8)
    ap.add_argument("--checkpoint-every", type=int, default=None,
                    metavar="N",
                    help="bench: add the elastic leg — snapshot the "
                         "engine carry every N rounds and stamp the "
                         "save/restore cost into the json")
    ap.add_argument("--devices", type=int, default=None,
                    help="virtual CPU device count for the bench's "
                         "sharded leg (set before jax init)")
    args = ap.parse_args()

    if args.bench_out is not None:
        bench_mode = resolve_aggregation(args.mode, args.buffer_size,
                                         args.max_concurrency)
        if args.staleness_power is not None:
            bench_mode = "async"
        run_training_bench(args.bench_clients, args.bench_k,
                           args.bench_rounds, args.seed, args.bench_out,
                           checkpoint_every=args.checkpoint_every,
                           mode=bench_mode,
                           buffer_size=args.buffer_size,
                           max_concurrency=args.max_concurrency,
                           staleness_power=(
                               0.5 if args.staleness_power is None
                               else args.staleness_power))
        return
    if args.checkpoint_every is not None:
        ap.error("--checkpoint-every is a bench knob (use with "
                 "--bench-out); the comparison runs un-checkpointed")

    # resolve once so the emitted json records what actually ran; every
    # async-only CLI knob is an async opt-in under --mode auto (and an
    # error under a forced --mode sync — never silently dropped)
    if args.mode == "sync":
        dropped = [f for f, v in (("--buffer-size", args.buffer_size),
                                  ("--max-concurrency",
                                   args.max_concurrency),
                                  ("--staleness-power",
                                   args.staleness_power))
                   if v is not None]
        if dropped:
            ap.error(f"async-only knob(s) {'/'.join(dropped)} have no "
                     f"effect with --mode sync")
    mode = resolve_aggregation(args.mode, args.buffer_size,
                               args.max_concurrency)
    if args.staleness_power is not None:
        mode = "async"
    async_kw = {}
    if mode == "async":
        async_kw = dict(buffer_size=args.buffer_size,
                        max_concurrency=args.max_concurrency,
                        staleness_power=(0.5 if args.staleness_power is None
                                         else args.staleness_power))
    results = run_comparison(args.rounds, args.clients, args.seed,
                             fast=args.fast, verbose=True, mode=mode,
                             energy_budget_j=args.energy_budget_j,
                             **async_kw)
    summary = summarize(results, args.acc_target,
                        energy_budget_j=args.energy_budget_j)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"mode": mode, "summary": summary,
                   "history": {k: h.as_dict() for k, h in results.items()},
                   "rounds": args.rounds, "clients": args.clients,
                   "seed": args.seed,
                   "energy_budget_j": args.energy_budget_j, **async_kw}, f)
    for kind, s in summary.items():
        print(f"{kind:7s} " + " ".join(
            f"{k}={v:.3f}" if isinstance(v, float) else f"{k}={v}"
            for k, v in s.items()))
    e, o = summary["eafl"], summary["oort"]
    if e["cum_dropouts"]:
        print(f"dropout ratio oort/eafl = "
              f"{o['cum_dropouts'] / max(e['cum_dropouts'], 1):.2f}x "
              f"(paper: up to 2.45x)")
    print(f"accuracy delta eafl-oort = "
          f"{e['final_acc'] - o['final_acc']:+.3f}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
