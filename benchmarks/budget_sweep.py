"""Fleet energy-budget sweep: the Pareto frontier of budget x policy.

Every row is one full training run of a (policy, budget) cell. Policies
are the fixed knob arms (``fixed-k{K}`` for each ``--arm-ks`` entry) plus
the online UCB controller over the same arms
(:mod:`repro.federated.controller`); budgets are ``none`` (unmetered)
plus ``--budget-fracs`` fractions of the *largest unmetered spend* across
policies, so the sweep self-scales to whatever workload ``--fast``/
``--clients``/``--rounds`` produce. Each row stamps total joules drawn,
final accuracy, simulated hours to the shared accuracy target, Jain's
fairness and the round the budget gate first refused a cohort; rows that
no other row beats on (energy, time-to-accuracy, fairness) get
``pareto: true`` — the frontier the paper's energy/accuracy trade-off
story lives on.

  PYTHONPATH=src python -m benchmarks.budget_sweep --fast --rounds 12
  PYTHONPATH=src python -m benchmarks.budget_sweep \
      --clients 12 --rounds 5 --arm-ks 2,4 --out /tmp/b.json   # CI smoke
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
from typing import Dict, List, Optional, Tuple

from benchmarks.fl_comparison import make_config, time_to_accuracy
from repro.federated import run_fl
from repro.federated.controller import Arm, ControllerConfig


def _policy_cfg(policy: str, arm_ks: Tuple[int, ...], args,
                budget: Optional[float]):
    cfg = make_config("eafl", args.rounds, args.clients, args.seed,
                      fast=args.fast)
    if policy.startswith("fixed-k"):
        cfg.selector = dataclasses.replace(cfg.selector,
                                           k=int(policy[len("fixed-k"):]))
    else:
        cfg.controller = ControllerConfig(
            arms=tuple(Arm(k=K) for K in arm_ks))
    cfg.energy_budget_j = budget
    return cfg


def _row(policy: str, budget: Optional[float], hist) -> Dict:
    return {
        "policy": policy,
        "budget_j": budget,
        "energy_spent_j": hist.energy_spent_j[-1],
        "final_acc": hist.test_acc[-1],
        "fairness": hist.fairness[-1],
        "budget_exhausted_round": hist.budget_exhausted_round,
        "controller_arm": hist.controller_arm or None,
    }


def pareto_flags(rows: List[Dict]) -> None:
    """Mark rows no other row weakly beats on every axis (and strictly
    on one): energy down, hours-to-target down, fairness up. A run that
    never reaches the target can still be frontier-cheap, so ``None``
    hours rank behind every real time rather than disqualifying."""
    def axes(r):
        h = r["hours_to_target"]
        return (r["energy_spent_j"],
                float("inf") if h is None else h,
                -r["fairness"])

    for r in rows:
        a = axes(r)
        r["pareto"] = not any(
            all(b[i] <= a[i] for i in range(3))
            and any(b[i] < a[i] for i in range(3))
            for other in rows if other is not r
            for b in (axes(other),))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--clients", type=int, default=60)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--arm-ks", default="4,10",
                    help="comma-separated cohort sizes: one fixed policy "
                         "each, plus the controller arm set")
    ap.add_argument("--budget-fracs", default="0.35,0.6,0.85",
                    help="budgets as fractions of the largest unmetered "
                         "spend (an unmetered row always runs too)")
    ap.add_argument("--acc-target", type=float, default=None,
                    help="hours-to-accuracy target (default: 0.9x best "
                         "final accuracy across all rows)")
    ap.add_argument("--out", default="BENCH_budget.json")
    args = ap.parse_args()

    arm_ks = tuple(int(x) for x in args.arm_ks.split(","))
    fracs = tuple(float(x) for x in args.budget_fracs.split(","))
    policies = [f"fixed-k{K}" for K in arm_ks] + ["controller"]

    # unmetered pass first: it anchors the budget scale
    rows, hists = [], []
    for policy in policies:
        h = run_fl(_policy_cfg(policy, arm_ks, args, None))
        rows.append(_row(policy, None, h))
        hists.append(h)
        print(f"{policy:12s} budget=none  J={h.energy_spent_j[-1]:9.0f} "
              f"acc={h.test_acc[-1]:.3f}", flush=True)

    anchor_j = max(r["energy_spent_j"] for r in rows)
    budgets = [round(f * anchor_j, 1) for f in fracs]
    for budget in budgets:
        for policy in policies:
            h = run_fl(_policy_cfg(policy, arm_ks, args, budget))
            rows.append(_row(policy, budget, h))
            hists.append(h)
            ex = h.budget_exhausted_round
            print(f"{policy:12s} budget={budget:9.0f} "
                  f"J={h.energy_spent_j[-1]:9.0f} "
                  f"acc={h.test_acc[-1]:.3f} "
                  f"exhausted={'-' if ex is None else ex}", flush=True)

    target = (args.acc_target if args.acc_target is not None
              else 0.9 * max(r["final_acc"] for r in rows))
    for r, h in zip(rows, hists):
        r["hours_to_target"] = time_to_accuracy(h, target)
    pareto_flags(rows)

    payload = {
        "bench": "budget_sweep", "clients": args.clients,
        "rounds": args.rounds, "seed": args.seed, "fast": args.fast,
        "arm_ks": list(arm_ks), "budget_fracs": list(fracs),
        "anchor_j": anchor_j, "acc_target": target, "rows": rows,
    }
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=1)
    frontier = [(r["policy"], r["budget_j"]) for r in rows if r["pareto"]]
    print(f"pareto frontier: {frontier}")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
