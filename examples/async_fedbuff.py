"""Buffered-asynchronous FL (FedBuff-style) vs the synchronous barrier.

Two demonstrations on the paper's battery-powered task:

  1. PARITY — with ``buffer_size == max_concurrency == k`` and staleness
     damping off, the event-stepped async engine reproduces the sync
     scanned engine's selection/battery/dropout trajectory exactly (the
     device-resident cores are the same fused computation).
  2. ASYNC WINS — with a small buffer and extra concurrency, the server
     aggregates as soon as ``buffer_size`` updates arrive instead of
     waiting for the slowest selected client, so wall-clock per update
     drops and slow/low-energy clients still contribute (staleness-damped)
     instead of being abandoned at a deadline. The async leg goes through
     the ``run_fl`` dispatcher, which auto-resolves the device-resident
     FedBuff engine (``run_fl_async_scanned``, or the sharded twin on a
     multi-device host) — the host event loop is only the parity oracle.

  PYTHONPATH=src python examples/async_fedbuff.py [--aggregations 20]
"""
import argparse
import sys

sys.path.insert(0, "src")

import jax
import numpy as np

from repro.configs.paper_resnet_speech import reduced
from repro.core import (EnergyModel, SelectorConfig, SelectorState,
                        make_population)
from repro.federated import FLConfig, run_fl, run_rounds


def parity_demo(rounds: int = 10, n: int = 200, k: int = 10):
    """Both engines through the unified `run_rounds` front door, forcing
    one engine per leg (mode="scanned" / "async-scanned"); on a host with
    >1 device and a fleet-sized population the same call with mode left on
    "auto" would dispatch to the sharded twins instead — index-for-index
    identically."""
    key = jax.random.PRNGKey(0)
    cfg = SelectorConfig(kind="eafl", k=k)
    em = EnergyModel()
    pop = make_population(jax.random.fold_in(key, 1), n,
                          init_battery_low=15.0, init_battery_high=90.0)
    pop = pop.replace(stat_util=jax.random.uniform(
        jax.random.fold_in(key, 2), (n,)) * 10)
    krun = jax.random.fold_in(key, 3)
    _, _, sync = run_rounds(krun, cfg, pop, SelectorState.create(cfg),
                            em, 85e6, 400, 20, rounds, mode="scanned")
    _, _, asyn = run_rounds(krun, cfg, pop, SelectorState.create(cfg),
                            em, 85e6, 400, 20, rounds, mode="async-scanned",
                            buffer_size=k, max_concurrency=k,
                            staleness_power=0.0)
    same_sel = np.array_equal(np.asarray(sync["selected"]),
                              np.asarray(asyn["selected"]))
    same_dur = np.allclose(np.asarray(sync["round_duration"]),
                           np.asarray(asyn["round_duration"]), rtol=1e-6)
    print(f"[parity] {sync['engine']} vs {asyn['engine']} "
          f"(buffer=concurrency=k, damping off) -> "
          f"selection identical: {same_sel}, durations match: {same_dur}")
    assert same_sel and same_dur


def fl_config(kind: str, aggregations: int, **kw) -> FLConfig:
    base = dict(
        selector=SelectorConfig(kind=kind, k=8),
        n_clients=60, rounds=aggregations, local_steps=6, batch_size=10,
        samples_per_client=48, eval_every=5, eval_samples=280,
        model=reduced(), input_hw=16,
        sim_model_bytes=85e6, sim_local_steps=1600,
        init_battery_low=8.0, init_battery_high=60.0)
    base.update(kw)
    return FLConfig(**base)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--aggregations", type=int, default=20,
                    help="server updates for each leg")
    ap.add_argument("--kind", default="eafl",
                    choices=["eafl", "oort", "random"])
    ap.add_argument("--buffer-size", type=int, default=3)
    ap.add_argument("--max-concurrency", type=int, default=12)
    args = ap.parse_args()

    parity_demo()

    # run_fl's default mode="auto" resolves per config: no async knobs ->
    # the synchronous barrier; buffer_size/max_concurrency set -> FedBuff
    # on the device-resident engine (engine="auto" upgrades async runs to
    # the event scan with the in-carry snapshot ring)
    h_sync = run_fl(fl_config(args.kind, args.aggregations))
    h_async = run_fl(fl_config(args.kind, args.aggregations,
                               buffer_size=args.buffer_size,
                               max_concurrency=args.max_concurrency))
    for name, h in (("sync", h_sync), ("async", h_async)):
        print(f"[{name:5s}] {args.aggregations} server updates in "
              f"{h.wall_hours[-1]:.2f}h wall "
              f"(mean {3600*h.wall_hours[-1]/len(h.round):.0f}s/update)  "
              f"acc={h.test_acc[-1]:.3f} dropouts={h.cum_dropouts[-1]} "
              f"fairness={h.fairness[-1]:.3f}")
    speed = h_sync.wall_hours[-1] / max(h_async.wall_hours[-1], 1e-9)
    print(f"[async] buffer={args.buffer_size} "
          f"concurrency={args.max_concurrency}: {speed:.2f}x faster "
          f"wall-clock per server update than the synchronous barrier")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
