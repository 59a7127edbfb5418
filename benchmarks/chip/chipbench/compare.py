"""The comparison that decides ``correct``: what the timed path produced
against the plain reference, reduced to a few numbers, each held to the
limit ``limits/<workload>.json`` gives it (with the readings the limit was
set from). A run is correct when every number is at or under its limit.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

# training: history fields of the selection/energy simulation
SIM_FIELDS = ("mean_battery", "round_duration", "energy_spent_j",
              "participation", "fairness", "cum_dropouts")
# selection rounds: per-round outputs compared exactly, and by relative gap
EXACT_ROUND = ("selected", "chosen", "succeeded", "new_dropouts",
               "total_dropped")
FLOAT_ROUND = ("round_duration", "energy_spent_pct", "energy_spent_j",
               "mean_battery")
EXACT_FLEET = ("dropped", "explored", "last_round", "times_selected")
FLOAT_FLEET = ("battery_pct", "last_duration")


def _rel(a, b) -> float:
    """Worst gap relative to the reference's magnitude, or absolute where
    that is under 1 (a battery near 0, a share)."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    if a.shape != b.shape:
        return float("inf")
    if a.size == 0:
        return 0.0
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1.0)))


def _check(name: str, value: float, limits: dict) -> dict:
    return {"name": name, "value": float(value),
            "limit": float(limits["numbers"][name]["limit"])}


def training(got: Dict[str, list], ref: Dict[str, list], rounds: int,
             limits: dict) -> List[dict]:
    """``train_loss``: the worst relative gap of train_loss over the
    compared rounds (data, initial weights, selection and local SGD in
    round 1; the aggregated update and the server step through the loss
    they start each later round from). ``sim``: the worst relative gap of
    the simulation's history fields in every compared round."""
    g = {k: list(got[k][:rounds]) for k in ref}
    r = {k: list(ref[k][:rounds]) for k in ref}
    loss = (_rel(g["train_loss"], r["train_loss"])
            if len(g["train_loss"]) == rounds else float("inf"))
    sim = max(_rel(g[f], r[f]) for f in SIM_FIELDS)
    return [_check("train_loss", loss, limits), _check("sim", sim, limits)]


def rounds(got: Sequence[tuple], ref: Sequence[tuple], limits: dict
           ) -> List[dict]:
    """``got``/``ref``: per checked call, ``(trajectory, fleet_after)``.
    ``mismatches``: slots whose pick or outcome differs, rounds whose
    dropout counts differ, and clients whose integer or flag state
    differs after a call, all counted. ``fleet_gap``: the worst relative
    gap of a client's battery or last duration after a call. ``stats_gap``:
    the worst relative gap of a round's duration, energy or mean battery.
    """
    mismatches = 0
    fleet_gap = stats_gap = 0.0
    for (gt, gf), (rt, rf) in zip(got, ref):
        chosen = np.asarray(rt["chosen"])
        for f in EXACT_ROUND:
            a, b = np.asarray(gt[f]), np.asarray(rt[f])
            if a.shape != b.shape:
                mismatches += b.size
            elif f == "selected":
                mismatches += int(np.sum((a != b) & chosen))
            else:
                mismatches += int(np.sum(a != b))
        for f in EXACT_FLEET:
            mismatches += int(np.sum(np.asarray(gf[f]) != np.asarray(rf[f])))
        for f in FLOAT_FLEET:
            fleet_gap = max(fleet_gap, _rel(gf[f], rf[f]))
        for f in FLOAT_ROUND:
            stats_gap = max(stats_gap, _rel(gt[f], rt[f]))
    return [_check("mismatches", mismatches, limits),
            _check("fleet_gap", fleet_gap, limits),
            _check("stats_gap", stats_gap, limits)]


def correct(checks: List[dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks)
