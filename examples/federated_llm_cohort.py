"""Federated fine-tuning of an assigned LLM architecture with EAFL selection.

Bridges the two halves of the framework: the EAFL energy-aware selector
decides WHICH simulated edge clients contribute, and the datacenter cohort
step (the same train_step the multi-pod dry-run lowers) trains on their
pooled token batches. Reduced arch, CPU-sized.

  PYTHONPATH=src python examples/federated_llm_cohort.py [--arch olmo-1b]
"""
import argparse
import sys

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_reduced
from repro.core import (EnergyModel, SelectorConfig, SelectorState,
                        make_population, select, stat_utility)
from repro.data import lm_batch
from repro.federated import predicted_round_cost_pct, simulate_round
from repro.launch.steps import default_optimizer, make_train_step
from repro.models import init_params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--k", type=int, default=4)
    args = ap.parse_args()

    cfg = get_reduced(args.arch)
    key = jax.random.PRNGKey(0)
    pop = make_population(key, 64, init_battery_low=20.0)
    sel_cfg = SelectorConfig(kind="eafl", k=args.k, f=0.25)
    sel_state = SelectorState.create(sel_cfg)
    energy = EnergyModel()
    n_params = sum(x.size for x in jax.tree.leaves(
        init_params(jax.random.PRNGKey(1), cfg)))
    model_bytes = n_params * 4.0

    params = init_params(jax.random.fold_in(key, 1), cfg)
    opt = default_optimizer(lr=5e-3)
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(cfg, opt))

    stat = np.zeros((64,), np.float32)
    for rnd in range(1, args.rounds + 1):
        ksel = jax.random.fold_in(key, 100 + rnd)
        pred = predicted_round_cost_pct(pop, energy, model_bytes, 4, 8)
        chosen, sel_state = select(ksel, sel_cfg, sel_state, pop, pred)
        pop, outcome = simulate_round(pop, chosen, energy, model_bytes, 4, 8,
                                      rnd)
        ok = chosen[outcome.succeeded]
        if len(ok) == 0:
            continue
        # each successful client contributes a shard of the cohort batch
        batch = lm_batch(jax.random.fold_in(key, 200 + rnd), cfg,
                         batch=2 * len(ok), seq_len=64)
        params, opt_state, loss, _ = step(params, opt_state, batch)
        stat[ok] = float(loss) * np.asarray(pop.n_samples)[ok]
        pop = pop.replace(stat_util=jnp.asarray(stat))
        print(f"round {rnd}: clients={ok.tolist()} loss={float(loss):.4f} "
              f"mean_battery={float(pop.battery_pct.mean()):.1f}% "
              f"dropped={int(pop.dropped.sum())}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
