"""Ablation: the Eq. 1 trade-off weight f (paper Sec. 3.1 "different
weights to each function in the utility definition").

f=1 -> pure Oort (time-to-accuracy); f=0 -> pure battery. The paper picks
f=0.25. Sweep f and record accuracy / dropouts / round duration / joules
drawn (optionally under a fleet energy budget: ``--energy-budget-j``).

  PYTHONPATH=src python -m benchmarks.f_sweep [--rounds 40] [--clients 80]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os

from benchmarks.fl_comparison import make_config
from repro.federated import run_fl


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=40)
    ap.add_argument("--clients", type=int, default=80)
    ap.add_argument("--energy-budget-j", type=float, default=None,
                    help="fleet budget in joules (default unmetered)")
    ap.add_argument("--out", default="experiments/f_sweep.json")
    args = ap.parse_args()

    results = {}
    for f in (0.0, 0.25, 0.5, 0.75, 1.0):
        cfg = make_config("eafl", args.rounds, args.clients, fast=True,
                          energy_budget_j=args.energy_budget_j)
        cfg.selector = dataclasses.replace(cfg.selector, f=f)
        h = run_fl(cfg)
        results[f] = {
            "final_acc": h.test_acc[-1],
            "cum_dropouts": h.cum_dropouts[-1],
            "mean_round_s": sum(h.round_duration) / len(h.round_duration),
            "fairness": h.fairness[-1],
            "energy_spent_j": h.energy_spent_j[-1],
        }
        if args.energy_budget_j is not None:
            results[f]["energy_budget_j"] = args.energy_budget_j
            results[f]["budget_exhausted_round"] = h.budget_exhausted_round
        print(f"f={f:4.2f} acc={h.test_acc[-1]:.3f} "
              f"drop={h.cum_dropouts[-1]:3d} "
              f"round={results[f]['mean_round_s']:.0f}s "
              f"fair={h.fairness[-1]:.3f} "
              f"J={h.energy_spent_j[-1]:.0f}", flush=True)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    json.dump(results, open(args.out, "w"), indent=1)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
