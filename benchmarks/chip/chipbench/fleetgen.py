"""The benchmark's own fleet generator.

The AI-Benchmark/MobiPerf device mix that
``repro.core.clients.make_population`` draws (three device categories,
WiFi or 3G with log-normal bandwidths, uniform initial battery; the copy
in ``reference.eafl_round.population``), plus the mid-run state a
selection study starts from: a share of the fleet already explored, with
an observed statistical utility. The fleet is the input of
a selection cell, so the benchmark makes it from the seed, on the device,
in one jitted call, and hands the same arrays to the program and to the
plain reference.
"""
from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

from reference.eafl_round import population


@partial(jax.jit, static_argnames=("n", "spec"))
def _make(key, n: int, spec: tuple) -> Dict[str, jnp.ndarray]:
    s = dict(spec)
    kpop, kmid = jax.random.split(key)
    fleet = population(kpop, n, s["category_probs"], s["wifi_prob"],
                       s["init_battery_low"], s["init_battery_high"],
                       s["samples_per_client"])
    ku, ke = jax.random.split(kmid)
    fleet["stat_util"] = jax.random.uniform(ku, (n,)) * s["stat_util_max"]
    fleet["explored"] = jax.random.bernoulli(ke, s["explored_frac"], (n,))
    return fleet


KEYS = ("n_clients", "category_probs", "wifi_prob", "init_battery_low",
        "init_battery_high", "samples_per_client", "explored_frac",
        "stat_util_max")


def make_fleet(seed31: int, fleet: dict) -> Dict[str, jnp.ndarray]:
    """The fleet a ``fleet`` configuration describes, from a 31-bit seed.
    Every key is needed and no other is taken: a setting the generator
    ignored would reach neither the program nor the reference."""
    if set(fleet) != set(KEYS):
        raise ValueError(f"a fleet has exactly the keys {KEYS}; got "
                         f"{sorted(fleet)}")
    spec = tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v)
        for k, v in fleet.items() if k != "n_clients"))
    return _make(jax.random.PRNGKey(seed31), int(fleet["n_clients"]), spec)
