"""The general traffic generator: one driver per public front door.

A traffic mix (``traffic/<mix>.json``) names its front door, what one
call in the window is, and the plain reference that checks it; the
configuration gives the sizes. A driver makes the call's inputs from the
run's seed, passes the configuration's and the mix's settings to the
program as data (every key a field or keyword of the program's own, so
an unknown one fails), makes the call inside a ``TraceAnnotation`` span
of its own name, keeps what the comparison needs of the calls it checks,
and after the window hands those to the reference (``check``). The
window drives nothing but ``run_fl`` and ``run_rounds``.
"""
from __future__ import annotations

import dataclasses
import gc
from typing import Dict, List

import jax
import numpy as np

from chipbench import compare, work
from chipbench.fleetgen import make_fleet
from chipbench.manifest import module


def derive_seed(seed: int, *path: int) -> int:
    """A 31-bit seed for ``PRNGKey`` from the run's seed (any whole number
    up to 64 bits and beyond) and a path such as the call index. JAX keys
    made from a Python int keep only its low 32 bits, so larger seeds
    would collide."""
    if seed < 0:
        raise ValueError(f"--seed must be a whole number >= 0, got {seed}")
    ss = np.random.SeedSequence(entropy=seed, spawn_key=tuple(path))
    return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


def span(name: str):
    return jax.profiler.TraceAnnotation(name)


class FLDriver:
    """``run_fl``: each call is one whole experiment (set-up, scan and
    history) with a seed of its own, like the runs of a policy sweep.

    The program's ``FLConfig`` is its defaults, replaced by the model
    family's fields, the mix's ``fl_config`` and its ``selector``; the
    mix's ``run_fl`` entry gives the call's keywords. The plain reference
    the mix names gets the same settings and refuses, at set-up, any it
    does not implement."""

    front_door = "run_fl"

    def __init__(self, cell, seed: int):
        from repro.core import SelectorConfig
        from repro.federated import FLConfig

        c, t = cell.config, cell.traffic
        self.model, self.seed = c, seed
        self.family = module("families", c["family"])
        self.selector = dict(t["selector"])
        self.fl = dict(t["fl_config"])
        self.kwargs = dict(t.get("run_fl", {}))
        self.reference = module("reference", t["reference"])
        self.reference.check_supported(c, self.selector, self.fl,
                                       self.kwargs)
        self.rounds_per_call = int(t["rounds_per_call"])
        self.check_rounds = int(t["reference_rounds"])
        self.base = dataclasses.replace(
            FLConfig(selector=SelectorConfig(**self.selector)),
            rounds=self.rounds_per_call,
            **self.family.program_fields(c), **self.fl)
        self.kept: Dict[int, dict] = {}
        self.engine_used = self.kwargs.get("engine", "auto")

    def call_seed(self, c: int) -> int:
        return derive_seed(self.seed, c)

    def call(self, c: int):
        from repro.federated import run_fl

        cfg = dataclasses.replace(self.base, seed=self.call_seed(c))
        with span("bench.run_fl"):
            hist = run_fl(cfg, **self.kwargs)
        if c == 0:
            self.kept[c] = hist.as_dict()
        return hist

    def check(self, limits: dict, variants=(None,)) -> Dict[str, List[dict]]:
        """Compare the set-up experiment's first rounds with the plain
        reference run from the same seed. Each of ``variants`` other than
        None (the program) puts the reference in the program's place at
        a lower precision (``high``) or with a planted fault."""
        gc.collect()

        def ref(**kw):
            return self.reference.run(self.model, self.selector, self.fl,
                                      self.call_seed(0), self.check_rounds,
                                      **kw)

        want = ref()
        out = {}
        for v in variants:
            if v is None:
                got = self.kept[0]
            elif v in self.reference.PRECISIONS:
                got = ref(precision=v)
            else:
                got = ref(fault=v)
            out[v] = compare.training(got, want, self.check_rounds, limits)
        return out

    def work_counts(self) -> dict:
        return {"flops_per_call": work.training_flops_per_experiment(
            self.family, self.model, self.selector["k"], self.fl,
            self.rounds_per_call)}


class RoundsDriver:
    """``run_rounds``: chained calls of ``rounds_per_call`` selection-only
    rounds. The fleet and the selector state carry from call to call; call
    ``c`` uses the key ``fold_in(PRNGKey(seed), c)``.

    The configuration gives the fleet, the simulated device workload and
    the energy model; the mix gives the selector and, in its
    ``run_rounds`` entry, the call's other keywords. The plain reference
    the mix names gets the same settings and refuses, at set-up, any it
    does not implement."""

    front_door = "run_rounds"

    def __init__(self, cell, seed: int):
        from repro.core import (ClientPopulation, EnergyModel,
                                SelectorConfig, SelectorState)

        c, t = cell.config, cell.traffic
        self.fleet = c["fleet"]
        self.selector = dict(t["selector"])
        self.kwargs = dict(t.get("run_rounds", {}))
        self.energy = dict(c["energy_model"])
        self.reference = module("reference", t["reference"])
        self.reference.check_supported(self.selector, self.kwargs,
                                       self.energy)
        dw = c["device_workload"]
        self.work = {"model_bytes": float(dw["model_bytes"]),
                     "local_steps": int(dw["local_steps"]),
                     "batch_size": int(dw["batch_size"])}
        self.seed31 = derive_seed(seed)
        self.rounds_per_call = int(t["rounds_per_call"])
        self.checked = tuple(range(int(t["checked_calls"])))
        self.sel = SelectorConfig(**self.selector)
        self.em = EnergyModel(**self.energy)
        self.key0 = jax.random.PRNGKey(self.seed31)
        self.pop = ClientPopulation(**make_fleet(self.seed31, self.fleet))
        self.state = SelectorState.create(self.sel)
        self.kept: Dict[int, tuple] = {}
        self.engine_used = None

    def call(self, c: int):
        from repro.federated import run_rounds

        key = jax.random.fold_in(self.key0, c)
        with span("bench.run_rounds"):
            pop, st, traj = run_rounds(key, self.sel, self.pop, self.state,
                                       self.em, rounds=self.rounds_per_call,
                                       **self.work, **self.kwargs)
        self.engine_used = traj.pop("engine")
        with span("bench.device_get"):
            traj = jax.device_get(traj)
        self.pop, self.state = pop, st
        if c in self.checked:
            self.kept[c] = (traj, pop)
        return traj

    def work_counts(self) -> dict:
        return {"bytes_per_round": work.selection_bytes_per_round(
            work.POPULATION_LEAF_BYTES, self.fleet["n_clients"])}

    def check(self, limits: dict, variants=(None,)) -> Dict[str, List[dict]]:
        """Replay the checked calls with the plain reference from the
        benchmark's own fleet and compare every round and the fleet after
        each call. A variant other than None (the program) names a float
        type the reference then runs in, in the program's place."""
        import jax.numpy as jnp
        from reference.eafl_round import Selector

        got = [(traj, {f: np.asarray(getattr(pop, f)) for f in
                       ("battery_pct", "last_duration", "dropped",
                        "explored", "last_round", "times_selected")})
               for traj, pop in (self.kept[c] for c in self.checked
                                 if c in self.kept)]
        self.kept.clear()
        self.pop = None
        gc.collect()
        sel = Selector(**self.selector)
        work_ = (self.work["model_bytes"], self.work["local_steps"],
                 self.work["batch_size"])
        calls = [(jax.random.fold_in(self.key0, c), self.rounds_per_call)
                 for c in self.checked]

        def replay(dtype):
            return self.reference.replay(
                make_fleet(self.seed31, self.fleet), sel, work_,
                self.energy["busy_fraction"], calls, dtype)

        ref = replay(jnp.float32)
        return {v: compare.rounds(got if v is None else replay(
                    jnp.dtype(v)), ref, limits) for v in variants}


DRIVERS = {d.front_door: d for d in (FLDriver, RoundsDriver)}


def make_driver(cell, seed: int):
    door = cell.traffic["front_door"]
    if door not in DRIVERS:
        raise KeyError(f"traffic front door {door!r}; the window drives "
                       f"only {sorted(DRIVERS)}")
    return DRIVERS[door](cell, seed)
