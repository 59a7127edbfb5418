"""Plain reference of selection-only rounds over a fleet: the semantics of
``repro.federated.run_rounds`` (predicted cost, EAFL selection, battery and
dropout simulation), one jitted round at a time in a Python loop.

``replay`` follows a chain of calls from the benchmark's own fleet, with
the keys the harness gave each call, and returns each call's per-round
outputs and the fleet after it. Nothing the program made is read.
``check_supported`` refuses any setting the harness would give the
program that this reference does not implement.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from reference.eafl_round import (Selector, cast_fleet, round_cost, select,
                                  selector_state, simulate)

# run_rounds' synchronous engines share one trajectory; no deadline,
# faults, upload codec or async knobs
RUN_ROUNDS_KWARGS = {"mode": ("auto", "sync", "scanned", "sharded")}
ENERGY_MODEL = ("busy_fraction",)


def check_supported(selector: dict, run_rounds_kwargs: dict,
                    energy_model: dict) -> None:
    """Raises ValueError for a setting this reference does not implement."""
    bad = []
    if selector.get("kind") != "eafl":
        bad.append(f"selector kind {selector.get('kind')!r}")
    bad += [f"selector.{k}" for k in sorted(set(selector)
                                            - set(Selector._fields))]
    for k, v in run_rounds_kwargs.items():
        if v not in RUN_ROUNDS_KWARGS.get(k, ()):
            bad.append(f"run_rounds({k}={v!r})")
    bad += [f"EnergyModel.{k}" for k in sorted(set(energy_model)
                                               - set(ENERGY_MODEL))]
    if bad:
        raise ValueError("reference fleet_rounds does not implement "
                         + ", ".join(bad))


@partial(jax.jit, static_argnames=("sel", "work", "busy_fraction"))
def _round(key, st, fleet, sel: Selector, work: tuple, busy_fraction: float):
    model_bytes, local_steps, batch_size = work
    dt = fleet["battery_pct"].dtype
    t_total, cost = round_cost(fleet, model_bytes, local_steps, batch_size,
                               model_bytes, dt)
    idx, chosen, st = select(key, sel, st, fleet, cost)
    new, out = simulate(fleet, idx, chosen, t_total, cost, st["round"],
                        busy_fraction)
    return new, st, out


def replay(fleet: Dict[str, jnp.ndarray], sel: Selector, work: tuple,
           busy_fraction: float, calls: Sequence[Tuple[jnp.ndarray, int]],
           dtype=jnp.float32) -> List[Tuple[Dict[str, np.ndarray], Dict[str, np.ndarray]]]:
    """``calls``: ``(key, rounds)`` per call, in order. Each call scans the
    keys ``split(key, rounds)``. Returns ``[(trajectory, fleet_after)]``
    as host arrays, the trajectory stacked over rounds."""
    fleet = cast_fleet(fleet, dtype)
    st = selector_state(sel, dtype)
    results = []
    for key, rounds in calls:
        outs = []
        for key_r in jax.random.split(key, rounds):
            fleet, st, out = _round(key_r, st, fleet, sel, work,
                                    busy_fraction)
            outs.append(out)
        traj = {k: np.stack([np.asarray(o[k]) for o in outs]) for k in outs[0]}
        results.append((traj, jax.device_get(fleet)))
    return results
