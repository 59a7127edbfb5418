"""Plain reference of one EAFL round: predicted cost, client selection and
the battery/dropout simulation (EAFL Sec. 4, on Oort's
exploration/exploitation skeleton).

Straight ``jax.numpy`` over dicts of per-client arrays, written from the
paper's equations and the program's documented semantics; it imports
nothing of the program. ``dtype`` is the float type of the arithmetic:
float32 as the configurations state it, or a lower one for the control.

Randomness follows the program's stream definition, which is part of the
semantics a run is held to: exploration ranks unexplored clients by
``bits(key) >> 9`` (Gumbel top-k in integer form) and ties go to the lower
index, as ``lax.top_k`` breaks them.
"""
from __future__ import annotations

from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

from reference.energy_tables import (BATTERY_MAH, BUSY_POWER_W, COMM_A,
                                     COMM_B, IDLE_POWER_W, NOMINAL_VOLTAGE,
                                     PERF_PER_W, POWER_W)

FLOAT_FIELDS = ("down_mbps", "up_mbps", "battery_pct", "stat_util",
                "last_duration")
# the device mix a training experiment's fleet is drawn with (the
# program's documented default; run_fl takes no other): AI-Benchmark
# categories high/mid/low-end 25/45/30%, MobiPerf WiFi 60% and 3G 40%
DEVICE_MIX = {"category_probs": (0.25, 0.45, 0.30), "wifi_prob": 0.6}


class Selector(NamedTuple):
    """Oort/EAFL selector settings (defaults of the paper's setup)."""
    kind: str = "eafl"
    k: int = 100
    f: float = 0.25
    alpha: float = 2.0
    epsilon0: float = 0.9
    epsilon_decay: float = 0.98
    epsilon_min: float = 0.2
    ucb_c: float = 0.1
    pacer_t0: float = 120.0
    pacer_delta: float = 30.0
    pacer_max: float = 1800.0


def population(key, n: int, category_probs, wifi_prob: float,
               battery_low: float, battery_high: float,
               samples_per_client: int) -> Dict[str, jnp.ndarray]:
    """A fleet at the start of a study: AI-Benchmark device categories,
    MobiPerf-like WiFi/3G bandwidths (log-normal), uniform battery."""
    ks = jax.random.split(key, 6)
    category = jax.random.choice(ks[0], 3, (n,), p=jnp.array(
        category_probs)).astype(jnp.int32)
    network = (jax.random.uniform(ks[1], (n,)) > wifi_prob).astype(jnp.int32)
    down = jnp.where(network == 0, 40.0, 6.0) * jnp.exp(
        0.6 * jax.random.normal(ks[2], (n,)))
    up = jnp.where(network == 0, 15.0, 2.0) * jnp.exp(
        0.6 * jax.random.normal(ks[3], (n,)))
    return {
        "category": category, "network": network, "down_mbps": down,
        "up_mbps": up,
        "battery_pct": jax.random.uniform(ks[4], (n,), minval=battery_low,
                                          maxval=battery_high),
        "stat_util": jnp.zeros((n,), jnp.float32),
        "last_duration": jnp.full((n,), 1.0, jnp.float32),
        "explored": jnp.zeros((n,), bool),
        "last_round": jnp.zeros((n,), jnp.int32),
        "times_selected": jnp.zeros((n,), jnp.int32),
        "dropped": jnp.zeros((n,), bool),
        "n_samples": jnp.full((n,), samples_per_client, jnp.int32),
    }


def selector_state(sel: Selector, dtype=jnp.float32):
    return {"round": jnp.int32(0), "epsilon": jnp.asarray(sel.epsilon0, dtype),
            "pacer_T": jnp.asarray(sel.pacer_t0, dtype),
            "util_ema": jnp.asarray(0.0, dtype)}


def cast_fleet(fleet: Dict[str, jnp.ndarray], dtype) -> Dict[str, jnp.ndarray]:
    return {k: (v.astype(dtype) if k in FLOAT_FIELDS else v)
            for k, v in fleet.items()}


def _battery_wh(cat, dtype):
    return jnp.asarray(BATTERY_MAH, dtype)[cat] * NOMINAL_VOLTAGE / 1000.0


def round_cost(fleet, model_bytes: float, local_steps: int, batch_size: int,
               up_bytes: float, dtype=jnp.float32):
    """Per-client round seconds (download + compute + upload) and the
    battery % a participant pays for them."""
    cat, net = fleet["category"], fleet["network"]
    t_down = model_bytes * 8 / (fleet["down_mbps"] * 1e6)
    t_up = up_bytes * 8 / (fleet["up_mbps"] * 1e6)
    sps = jnp.asarray(PERF_PER_W, dtype)[cat] * jnp.asarray(POWER_W, dtype)[cat]
    t_comp = local_steps * batch_size / sps
    t_total = t_down + t_comp + t_up
    e_wh = jnp.asarray(POWER_W, dtype)[cat] * t_comp / 3600.0
    comp = 100.0 * e_wh / _battery_wh(cat, dtype)
    a, b = jnp.asarray(COMM_A, dtype), jnp.asarray(COMM_B, dtype)
    down = a[net, 0] * (t_down / 3600.0) + b[net, 0]
    up = a[net, 1] * (t_up / 3600.0) + b[net, 1]
    comm = jnp.maximum(down, 0.0) + jnp.maximum(up, 0.0)
    return t_total, comp + comm


def rank_bits(key, n: int):
    return (jax.random.bits(key, (n,), jnp.uint32) >> 9).astype(jnp.float32)


def _minmax(x, valid):
    lo = jnp.min(jnp.where(valid, x, jnp.inf))
    hi = jnp.max(jnp.where(valid, x, -jnp.inf))
    rng = jnp.maximum(hi - lo, 1e-9)
    return jnp.where(valid, (x - lo) / rng, 0.0)


def select(key, sel: Selector, st, fleet, cost):
    """Returns ``(idx (k,), chosen (k,), new_state)``: exploit slots first,
    then exploration slots; only ``chosen`` slots are picks."""
    n = fleet["battery_pct"].shape[0]
    k = min(sel.k, n)
    rnd = st["round"] + 1
    battery = fleet["battery_pct"]
    valid = (~fleet["dropped"]) & (battery > 0.0)
    k_eff = jnp.minimum(k, jnp.sum(valid)).astype(jnp.int32)
    slots = jnp.arange(k)
    explored = fleet["explored"] & valid
    unexplored = valid & ~explored

    # Oort utility (Eq. 2): stat utility x (T / t_i)^alpha when slower than T
    t_i, T = fleet["last_duration"], st["pacer_T"]
    ratio = jnp.maximum(T, 1e-9) / jnp.maximum(t_i, 1e-9)
    pen = jnp.square(ratio) if sel.alpha == 2.0 else jnp.power(ratio, sel.alpha)
    util = fleet["stat_util"] * jnp.where(t_i > T, pen, 1.0)
    # EAFL power(i): battery left after the coming round
    power = jnp.maximum(battery - cost, 0.0)
    t_f = jnp.asarray(rnd, jnp.float32).astype(battery.dtype)
    ucb = sel.ucb_c * jnp.sqrt(
        jnp.log(t_f + 1.0) / jnp.maximum(rnd - fleet["last_round"], 1))
    mask = valid & explored

    n_unexp = jnp.sum(unexplored).astype(jnp.int32)
    n_avail = jnp.sum(mask).astype(jnp.int32)
    n_explore = jnp.minimum(
        jnp.round(st["epsilon"] * k_eff).astype(jnp.int32), n_unexp)
    n_exploit = jnp.minimum(k_eff - n_explore, n_avail)
    n_explore = jnp.minimum(k_eff - n_exploit, n_unexp)

    # Eq. 1 over min-max normalised utility and power
    reward = sel.f * _minmax(util, valid) + (1.0 - sel.f) * _minmax(power, valid)
    score = jnp.where(mask, reward * (1.0 + ucb), -jnp.inf)
    _, exploit_idx = jax.lax.top_k(score, k)
    g = jnp.where(unexplored, rank_bits(key, n), -1.0)
    _, explore_idx = jax.lax.top_k(g, k)
    idx = jnp.where(slots < n_exploit, exploit_idx,
                    explore_idx[jnp.clip(slots - n_exploit, 0, k - 1)])
    chosen = slots < (n_exploit + n_explore)

    # epsilon decay, pacer and utility EMA on the chosen clients
    any_pick = k_eff > 0
    sel_util = jnp.sum(jnp.where(chosen, fleet["stat_util"][idx], 0.0)) \
        / jnp.maximum(jnp.sum(chosen), 1)
    eps, pacer, ema = st["epsilon"], st["pacer_T"], st["util_ema"]
    new_eps = jnp.where(any_pick,
                        jnp.maximum(sel.epsilon_min, eps * sel.epsilon_decay),
                        eps)
    slow = (ema > 0.0) & (sel_util < 0.95 * ema)
    new_pacer = jnp.where(any_pick & slow,
                          jnp.minimum(sel.pacer_max, pacer + sel.pacer_delta),
                          pacer)
    new_ema = jnp.where(any_pick, 0.9 * ema + 0.1 * sel_util, ema)
    return idx.astype(jnp.int32), chosen, {
        "round": rnd, "epsilon": new_eps.astype(eps.dtype),
        "pacer_T": new_pacer.astype(pacer.dtype),
        "util_ema": new_ema.astype(ema.dtype)}


def simulate(fleet, idx, chosen, t_total, cost, rnd, busy_fraction: float):
    """Debit the cohort, drain everyone else, drop the clients whose battery
    runs out. Returns ``(new_fleet, per-round outputs)``."""
    n = fleet["battery_pct"].shape[0]
    dt = fleet["battery_pct"].dtype
    sel = jnp.zeros((n,), bool).at[jnp.where(chosen, idx, n)].set(
        True, mode="drop")
    battery = fleet["battery_pct"]
    after = battery - jnp.where(sel, cost, 0.0)
    succeeded = sel & ~(after <= 0.0)
    max_succ = jnp.max(jnp.where(succeeded, t_total, -jnp.inf))
    max_sel = jnp.max(jnp.where(sel, t_total, -jnp.inf))
    duration = jnp.where(jnp.any(succeeded), max_succ, max_sel)
    duration = jnp.where(jnp.any(sel), duration, 0.0)
    cat = fleet["category"]
    p = IDLE_POWER_W * (1.0 - busy_fraction) + BUSY_POWER_W * busy_fraction
    e_wh = p * duration / 3600.0
    idle = battery - 100.0 * e_wh / _battery_wh(cat, dt)
    new_battery = jnp.clip(jnp.where(sel, after, idle), 0.0, 100.0)
    dropped = fleet["dropped"] | (new_battery <= 0.0)
    new = dict(fleet)
    new.update(
        battery_pct=new_battery, dropped=dropped,
        explored=fleet["explored"] | sel,
        last_duration=jnp.where(sel, t_total, fleet["last_duration"]),
        last_round=jnp.where(sel, jnp.asarray(rnd, jnp.int32),
                             fleet["last_round"]),
        times_selected=fleet["times_selected"] + sel.astype(jnp.int32))
    out = {
        "selected": idx, "chosen": chosen,
        "succeeded": succeeded[idx] & chosen,
        "round_duration": duration.astype(dt),
        "new_dropouts": jnp.sum(dropped & ~fleet["dropped"]).astype(jnp.int32),
        "energy_spent_pct": jnp.sum(jnp.where(sel, cost, 0.0)),
        "energy_spent_j": jnp.sum(jnp.where(
            sel, cost * _battery_wh(cat, dt) * 36.0, 0.0)),
        "mean_battery": jnp.mean(new_battery),
        "total_dropped": jnp.sum(dropped).astype(jnp.int32),
    }
    return new, out
