"""Event-driven round simulation: timing, energy, battery, dropouts.

Mirrors the paper's FedScale-style simulator: per-round wall time is derived
from each selected learner's download + compute + upload latency (device and
network profiles); battery is debited with the Sec. 4.2 energy models; a
client whose battery hits zero mid-round DROPS OUT — it fails the round and
becomes unavailable (the paper's central failure mode). Unselected devices
drain at the idle/busy mix rate over the round's wall time.

The core is device-resident: :func:`simulate_round_device` is a pure
traced jnp function over a selection *mask*, fused with the cost model so
prediction (Eq. 1's ``power(i)``) and debit share one computation.
:func:`make_round_engine` composes predicted-cost → selection → simulation
into a single traced step, and :func:`run_rounds_scanned` advances it for R
rounds under ``jax.lax.scan`` — training stays decoupled via the
selected-indices trajectory the scan emits. :func:`simulate_round` keeps
the original index-list host API on top of the same fused core.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.checkpoint import (CarryCheckpointer, load_engine_checkpoint,
                              segment_bounds)
from repro.core.clients import ClientPopulation, pad_population, round_times
from repro.core.energy import EnergyModel, pct_to_joules
from repro.core.selection import (
    SelectorConfig,
    SelectorState,
    _auto_pallas,
    _device_select,
    _merge_topk,
    _rank_bits,
    _shard_select,
    _slot_gather,
)
from repro.federated.faults import (N_FAULT_STREAMS, FaultConfig, apply_faults,
                                    fault_streams, faults_for_round)
from repro.kernels.topk_select import PickTally, pick_tally


@dataclass
class RoundOutcome:
    selected: np.ndarray          # (K,) indices
    succeeded: np.ndarray         # (K,) bool — finished with battery left
    durations: np.ndarray         # (K,) seconds (per selected client)
    round_duration: float         # wall seconds for the round
    new_dropouts: int             # clients that ran out of battery this round
    energy_spent_pct: float       # total battery % spent by participants
    retries: int = 0              # upload re-attempts across the cohort
    corrupt: Optional[np.ndarray] = None  # (K,) bool — delta is poisoned
    energy_spent_j: float = 0.0   # joules debited by this round's cohort
    admitted: bool = True         # False when the budget gate refused the round
    spent_after_j: float = 0.0    # cumulative fleet joules after this round


class DeviceRoundOutcome(NamedTuple):
    """Traced per-round outputs (full-population masks, device-resident)."""

    sel_mask: jnp.ndarray         # (N,) bool, selected this round
    succeeded: jnp.ndarray        # (N,) bool, selected & finished
    durations: jnp.ndarray        # (N,) f32, per-client total round seconds
    cost_pct: jnp.ndarray         # (N,) f32, battery %% a participant pays
    round_duration: jnp.ndarray   # f32 scalar, wall seconds
    new_dropouts: jnp.ndarray     # i32 scalar
    energy_spent_pct: jnp.ndarray  # f32 scalar
    energy_spent_j: jnp.ndarray   # f32 scalar, cohort joules this round


class BudgetLedger(NamedTuple):
    """Fleet-wide cumulative-energy ledger riding in the engine carry.

    ``spent_j`` accumulates the joules every admitted cohort debits (the
    same f32 chain on every engine, so host/scanned stay bitwise equal);
    ``exhausted_round`` records the first 1-based round the budget gate
    refused a cohort (0 = never). Checkpoint/resume parity follows from
    the ledger living in the carry, exactly like the PR 7 RNG chain.
    """

    spent_j: jnp.ndarray          # f32 scalar, cumulative joules debited
    exhausted_round: jnp.ndarray  # i32 scalar, first refused round (0=never)

    @classmethod
    def create(cls) -> "BudgetLedger":
        return cls(spent_j=jnp.float32(0.0),
                   exhausted_round=jnp.int32(0))


def cohort_energy_j(pop: ClientPopulation, sel_mask: jnp.ndarray,
                    cost_pct: jnp.ndarray,
                    axis_name: Optional[str] = None) -> jnp.ndarray:
    """Joules the masked cohort would debit at ``cost_pct`` battery-%.

    This is the single expression shared by the budget gate's prediction
    and :func:`simulate_round_device`'s debit — using one computation for
    both is what makes "spent never exceeds budget" exact rather than
    approximate."""
    return _asum(jnp.where(sel_mask, pct_to_joules(pop.category, cost_pct),
                           0.0), axis_name)


def budget_gate(sel_mask: jnp.ndarray, round_j: jnp.ndarray,
                ledger: BudgetLedger, energy_budget_j: Optional[float],
                rnd, axis_name: Optional[str] = None,
                ) -> Tuple[jnp.ndarray, jnp.ndarray, BudgetLedger]:
    """All-or-nothing cohort admission against the remaining budget.

    Returns ``(sel_mask', admit, ledger')`` where ``sel_mask'`` is zeroed
    when the predicted cohort debit ``round_j`` does not fit, and
    ``ledger'`` stamps ``exhausted_round`` on the first refusal. A refused
    round is inert (no battery movement, no stat updates) but the run
    continues: a later, cheaper cohort may still fit — the paper's fleet
    keeps training as long as any admissible cohort remains. When
    ``energy_budget_j`` is None the gate is the identity.
    """
    if energy_budget_j is None:
        return sel_mask, jnp.bool_(True), ledger
    admit = ledger.spent_j + round_j <= jnp.float32(energy_budget_j)
    refused = _aany(sel_mask, axis_name) & ~admit
    exhausted = jnp.where((ledger.exhausted_round == 0) & refused,
                          jnp.asarray(rnd, jnp.int32),
                          ledger.exhausted_round)
    return (sel_mask & admit, admit,
            ledger._replace(exhausted_round=exhausted))


def _round_cost(pop: ClientPopulation, energy_model: EnergyModel,
                model_bytes: float, local_steps: int, batch_size: int,
                up_bytes: Optional[float]):
    """Shared fused computation of per-client round time + battery cost."""
    t = round_times(pop, model_bytes, local_steps, batch_size, up_bytes)
    cost = energy_model.round_cost_pct(pop.category, pop.network,
                                       t["comp"], t["down"], t["up"])
    return t["total"], cost


def predicted_round_cost_pct(pop: ClientPopulation, energy_model: EnergyModel,
                             model_bytes: float, local_steps: int,
                             batch_size: int,
                             up_bytes: float = None) -> jnp.ndarray:
    """battery_used(i) for Eq. 1's power(i) — identical model to the debit."""
    return _round_cost(pop, energy_model, model_bytes, local_steps,
                       batch_size, up_bytes)[1]


def _picks_out(tally: PickTally, axis_name: Optional[str] = None
               ) -> Dict[str, jnp.ndarray]:
    """A step's Pallas top-k pick counts as trajectory entries, summed
    over the shards of ``axis_name`` (zero where ``lax.top_k`` ran)."""
    picks, slots = tally.picks, jnp.int32(tally.slots)
    if axis_name is not None and tally.slots:
        picks = jax.lax.psum(picks, axis_name)
        slots = jax.lax.psum(slots, axis_name)
    return {"topk_picks": picks, "topk_pick_slots": slots}


def _asum(x, axis_name):
    s = jnp.sum(x)
    return jax.lax.psum(s, axis_name) if axis_name else s


def _amax(x, axis_name):
    m = jnp.max(x)
    return jax.lax.pmax(m, axis_name) if axis_name else m


def _aany(x, axis_name):
    a = jnp.any(x)
    if axis_name:
        a = jax.lax.pmax(a.astype(jnp.int32), axis_name) > 0
    return a


@jax.named_scope("energy_sim")
def simulate_round_device(pop: ClientPopulation, sel_mask: jnp.ndarray,
                          t_total: jnp.ndarray, cost: jnp.ndarray,
                          rnd, energy_model: EnergyModel,
                          deadline_s: Optional[float] = None,
                          axis_name: Optional[str] = None,
                          busy_mask: Optional[jnp.ndarray] = None,
                          fail_mask: Optional[jnp.ndarray] = None,
                          ) -> Tuple[ClientPopulation, DeviceRoundOutcome]:
    """Pure traced round state update over a (N,) selection mask.

    With ``axis_name`` the same body runs shard-local under ``shard_map``:
    per-client updates are elementwise (bitwise identical to the unsharded
    run) and the scalar reductions go through psum/pmax collectives (max is
    exactly associative, so durations match bitwise too; summed stats may
    differ in the last ulp from the single-device reduction order).

    ``fail_mask`` marks clients whose upload is lost to an injected crash
    fault (``repro.federated.faults``): they fail the round like a battery
    death — energy is still debited, the round does not count as a success
    — but they do not drop out unless their battery actually ran dry.
    """
    battery_after = pop.battery_pct - jnp.where(sel_mask, cost, 0.0)
    ran_out = sel_mask & (battery_after <= 0.0)
    # NOTE: `is not None`, not truthiness — deadline_s=0.0 is a real (if
    # degenerate) deadline that nobody can meet, not "no deadline".
    missed_deadline = (sel_mask & (t_total > deadline_s)
                       if deadline_s is not None
                       else jnp.zeros_like(sel_mask))
    succeeded = sel_mask & ~ran_out & ~missed_deadline
    if fail_mask is not None:
        succeeded = succeeded & ~fail_mask

    # round wall time: slowest successful participant (or deadline)
    any_sel = _aany(sel_mask, axis_name)
    max_succ = _amax(jnp.where(succeeded, t_total, -jnp.inf), axis_name)
    max_sel = _amax(jnp.where(sel_mask, t_total, -jnp.inf), axis_name)
    fallback = (jnp.float32(deadline_s) if deadline_s is not None
                else max_sel)
    duration = jnp.where(_aany(succeeded, axis_name), max_succ, fallback)
    if deadline_s is not None:
        duration = jnp.minimum(duration, jnp.float32(deadline_s))
    duration = jnp.where(any_sel, duration, 0.0)

    # unselected (and dropped-out mid-round) devices drain at idle/busy
    # rate; `busy_mask` marks clients that are mid-computation for the whole
    # window (the async engine's still-in-flight clients) — they pay their
    # full round cost at completion instead of idling here
    idle_cost = energy_model.idle_cost_pct(pop.category, duration)
    if busy_mask is None:
        idle = pop.battery_pct - idle_cost
    else:
        idle = jnp.where(busy_mask, pop.battery_pct,
                         pop.battery_pct - idle_cost)
    battery_new = jnp.clip(
        jnp.where(sel_mask, battery_after, idle),
        0.0, 100.0)

    was_dropped = pop.dropped
    dropped_new = was_dropped | (battery_new <= 0.0)
    new_dropouts = _asum(dropped_new & ~was_dropped,
                         axis_name).astype(jnp.int32)

    new_pop = pop.replace(
        battery_pct=battery_new,
        dropped=dropped_new,
        explored=pop.explored | sel_mask,
        last_duration=jnp.where(sel_mask, t_total, pop.last_duration),
        last_round=jnp.where(sel_mask, jnp.asarray(rnd, jnp.int32),
                             pop.last_round),
        times_selected=pop.times_selected + sel_mask.astype(jnp.int32),
    )
    outcome = DeviceRoundOutcome(
        sel_mask=sel_mask,
        succeeded=succeeded,
        durations=t_total,
        cost_pct=cost,
        round_duration=duration.astype(jnp.float32),
        new_dropouts=new_dropouts,
        energy_spent_pct=_asum(jnp.where(sel_mask, cost, 0.0), axis_name),
        energy_spent_j=cohort_energy_j(pop, sel_mask, cost, axis_name),
    )
    return new_pop, outcome


@partial(jax.jit, static_argnames=("energy_model", "model_bytes",
                                   "local_steps", "batch_size", "deadline_s",
                                   "up_bytes", "faults", "energy_budget_j"))
def _simulate_round_jit(pop, sel_mask, rnd, energy_model, model_bytes,
                        local_steps, batch_size, deadline_s, up_bytes,
                        faults, energy_budget_j, ledger):
    t_total, cost = _round_cost(pop, energy_model, model_bytes, local_steps,
                                batch_size, up_bytes)
    t_eff, cost_eff, draw = faults_for_round(faults, rnd, t_total, cost)
    # the gate predicts the cohort debit on the fault-*modified* cost so
    # retry surcharges are charged against the budget, then the admitted
    # cohort's debit is the same expression over the same mask — spent can
    # never exceed the budget, bitwise
    round_j = cohort_energy_j(pop, sel_mask, cost_eff)
    sel_mask, admit, ledger = budget_gate(sel_mask, round_j, ledger,
                                          energy_budget_j, rnd)
    new_pop, dev = simulate_round_device(
        pop, sel_mask, t_eff, cost_eff, rnd, energy_model, deadline_s,
        fail_mask=None if draw is None else draw.fail)
    ledger = ledger._replace(spent_j=ledger.spent_j + dev.energy_spent_j)
    if draw is None:
        retries = jnp.int32(0)
        corrupt = jnp.zeros((pop.n,), bool)
    else:
        retries = jnp.sum(jnp.where(sel_mask, draw.retries, 0)) \
            .astype(jnp.int32)
        corrupt = draw.corrupt
    return new_pop, dev, retries, corrupt, admit, ledger


def simulate_round(pop: ClientPopulation, selected: np.ndarray,
                   energy_model: EnergyModel, model_bytes: float,
                   local_steps: int, batch_size: int, rnd: int,
                   deadline_s: Optional[float] = None,
                   up_bytes: float = None, *,
                   faults: Optional[FaultConfig] = None,
                   energy_budget_j: Optional[float] = None,
                   spent_j: float = 0.0):
    """Returns (new_pop, RoundOutcome). Host facade over the fused core.

    With ``faults`` the round's deterministic fault draws (keyed on
    ``(faults.seed, rnd, client)`` only) are folded in: stragglers/retries
    lengthen ``durations``, retries surcharge the battery debit, crashed
    uploads fail the round, and ``RoundOutcome.corrupt`` flags the
    survivors whose delta the server must quarantine.

    With ``energy_budget_j`` the fleet budget gate runs before the round:
    ``spent_j`` is the cumulative joules debited so far (feed back
    ``outcome.spent_after_j`` — it round-trips the device f32 ledger
    exactly, keeping the host loop bitwise-equal to the fused engines);
    when the predicted cohort debit does not fit, the whole round is
    refused (``outcome.admitted`` False, nothing simulated, no battery
    movement). Energy accounting flows regardless of whether a budget is
    set."""
    selected = np.asarray(selected)
    sel_mask = np.zeros((pop.n,), bool)
    sel_mask[selected] = True
    ledger = BudgetLedger(spent_j=jnp.float32(spent_j),
                          exhausted_round=jnp.int32(0))
    new_pop, dev, retries, corrupt, admit, ledger = _simulate_round_jit(
        pop, jnp.asarray(sel_mask), jnp.asarray(rnd, jnp.int32),
        energy_model, float(model_bytes), int(local_steps), int(batch_size),
        None if deadline_s is None else float(deadline_s),
        None if up_bytes is None else float(up_bytes),
        faults,
        None if energy_budget_j is None else float(energy_budget_j),
        ledger)
    outcome = RoundOutcome(
        selected=selected,
        succeeded=np.asarray(dev.succeeded)[selected],
        durations=np.asarray(dev.durations)[selected],
        round_duration=float(dev.round_duration),
        new_dropouts=int(dev.new_dropouts),
        energy_spent_pct=float(dev.energy_spent_pct),
        retries=int(retries),
        corrupt=np.asarray(corrupt)[selected],
        energy_spent_j=float(dev.energy_spent_j),
        admitted=bool(admit),
        spent_after_j=float(ledger.spent_j),
    )
    return new_pop, outcome


def make_round_engine(sel_cfg: SelectorConfig, energy_model: EnergyModel,
                      model_bytes: float, local_steps: int, batch_size: int,
                      deadline_s: Optional[float] = None,
                      up_bytes: Optional[float] = None,
                      use_pallas: bool = False, interpret: bool = False,
                      faults: Optional[FaultConfig] = None):
    """One fused traced round step: predicted cost → selection → simulation.

    Returns ``step(key, pop, sel_state) -> (pop, sel_state, idx, chosen,
    DeviceRoundOutcome)`` suitable for ``jax.jit`` or as a ``lax.scan``
    body. Training is *not* dispatched here — callers gather the selected
    indices and run training between steps (or not at all).

    With ``faults``, selection still scores on the *clean* predicted cost
    (Eq. 1's power(i) is a forecast — the selector cannot see transient
    faults coming) while the simulation runs on the fault-modified
    durations/costs, and the step returns two extra trailing outputs:
    ``retries`` (i32 scalar, cohort-total upload re-attempts) and
    ``corrupt`` ((N,) bool poisoned-delta flags).
    """

    def step(key, pop: ClientPopulation, sel_state: SelectorState):
        t_total, cost = _round_cost(pop, energy_model, model_bytes,
                                    local_steps, batch_size, up_bytes)
        idx, chosen, sel_state = _device_select(
            key, sel_cfg, sel_state, pop, cost, use_pallas, interpret)
        # scatter chosen slots into a population mask (unchosen slots are
        # routed to index N and dropped)
        sel_mask = jnp.zeros((pop.n,), bool).at[
            jnp.where(chosen, idx, pop.n)].set(True, mode="drop")
        # post-selection sel_state.round is the 1-based round number every
        # engine agrees on — the fault draws key off it
        t_eff, cost_eff, draw = faults_for_round(faults, sel_state.round,
                                                 t_total, cost)
        pop, dev = simulate_round_device(
            pop, sel_mask, t_eff, cost_eff, sel_state.round, energy_model,
            deadline_s, fail_mask=None if draw is None else draw.fail)
        if draw is None:
            return pop, sel_state, idx, chosen, dev
        retries = jnp.sum(jnp.where(sel_mask, draw.retries, 0)) \
            .astype(jnp.int32)
        return pop, sel_state, idx, chosen, dev, retries, draw.corrupt

    return step


@functools.lru_cache(maxsize=32)
def _scanned_runner(sel_cfg: SelectorConfig, energy_model: EnergyModel,
                    model_bytes: float, local_steps: int, batch_size: int,
                    deadline_s: Optional[float], up_bytes: Optional[float],
                    use_pallas: bool, interpret: bool,
                    faults: Optional[FaultConfig]):
    """Cached jitted scan over a caller-supplied (R, 2) key array (all
    config args hashable statics), so repeated calls with the same config
    reuse one compilation per distinct R. Scanning explicit key rows (the
    prefix-stable ``split(key, rounds)`` stream) instead of splitting
    inside the jit is what makes segmented/elastic runs bitwise identical
    to one uninterrupted scan: a resumed run replays the exact same keys.
    """
    step = make_round_engine(sel_cfg, energy_model, model_bytes,
                             local_steps, batch_size, deadline_s,
                             up_bytes, use_pallas, interpret, faults)
    faulty = faults is not None and faults.active

    def scan_step(carry, key_r):
        pop, st = carry
        with pick_tally() as tally:
            if faulty:
                pop, st, idx, chosen, dev, retries, corrupt = step(key_r,
                                                                   pop, st)
            else:
                pop, st, idx, chosen, dev = step(key_r, pop, st)
                retries = jnp.int32(0)
                corrupt = jnp.zeros((pop.n,), bool)
        out = {
            "selected": idx,
            "chosen": chosen,
            "succeeded": dev.succeeded[idx] & chosen,
            "round_duration": dev.round_duration,
            "new_dropouts": dev.new_dropouts,
            "energy_spent_pct": dev.energy_spent_pct,
            "energy_spent_j": dev.energy_spent_j,
            "mean_battery": jnp.mean(pop.battery_pct),
            "total_dropped": jnp.sum(pop.dropped).astype(jnp.int32),
            "retries": retries,
            "corrupt": corrupt[idx] & chosen,
            **_picks_out(tally),
        }
        return (pop, st), out

    @jax.jit
    def run(keys, pop, st):
        return jax.lax.scan(scan_step, (pop, st), keys)

    return run


# ------------------------------------------------- elastic run plumbing
# Shared by the four run_* engines: segment the scan at checkpoint
# boundaries, snapshot the full carry atomically, splice trajectory parts
# back together, and identify checkpoints so a resume refuses a snapshot
# from a different run. Restart-parity contract: because each engine scans
# an explicit prefix-stable key array and the carry hands off exactly at
# segment boundaries, `resume_from` a round-r snapshot is bitwise identical
# to the uninterrupted run (async engines: identical up to the documented
# psum scalar tolerance of their sharded twins).


def _engine_meta(family: str, sel_cfg: SelectorConfig, n: int, rounds: int,
                 deadline_s, faults: Optional[FaultConfig],
                 **extra) -> Dict[str, Any]:
    meta = {
        "family": family,
        "n_clients": int(n),
        "rounds": int(rounds),
        "kind": sel_cfg.kind,
        "k": int(sel_cfg.k),
        "deadline_s": None if deadline_s is None else float(deadline_s),
        "faults": None if faults is None else dataclasses.asdict(faults),
    }
    meta.update(extra)
    return meta


def _concat_traj(parts: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Concatenate per-segment trajectory dicts along the round axis."""
    if len(parts) == 1:
        return dict(parts[0])
    return {k: np.concatenate([np.asarray(p[k]) for p in parts], axis=0)
            for k in parts[0]}


def _make_checkpointer(checkpoint_path: Optional[str],
                       checkpoint_every: Optional[int], rounds: int,
                       meta: Dict[str, Any]):
    """Validate + normalise the elastic knobs into a CarryCheckpointer
    (or None). ``checkpoint_path`` alone means final-snapshot-only."""
    if checkpoint_every is not None and not checkpoint_path:
        raise ValueError("checkpoint_every is set but checkpoint_path is "
                         "not — there is nowhere to write snapshots")
    if not checkpoint_path:
        return None
    every = checkpoint_every if checkpoint_every is not None else rounds
    return CarryCheckpointer(checkpoint_path, every, rounds, meta)


def run_rounds_scanned(key, sel_cfg: SelectorConfig, pop: ClientPopulation,
                       sel_state: SelectorState, energy_model: EnergyModel,
                       model_bytes: float, local_steps: int, batch_size: int,
                       rounds: int,
                       deadline_s: Optional[float] = None,
                       up_bytes: Optional[float] = None,
                       use_pallas: Optional[bool] = None,
                       interpret: Optional[bool] = None,
                       faults: Optional[FaultConfig] = None,
                       checkpoint_every: Optional[int] = None,
                       checkpoint_path: Optional[str] = None,
                       resume_from: Optional[str] = None,
                       ) -> Tuple[ClientPopulation, SelectorState,
                                  Dict[str, jnp.ndarray]]:
    """Advance selection + energy + battery state for ``rounds`` rounds
    inside one ``jax.lax.scan`` — the single-device fast path (no mesh;
    the whole population lives on the default device).

    Returns ``(final_pop, final_state, trajectory)`` where the trajectory
    holds per-round arrays: ``selected (R,k)``, ``chosen (R,k)``,
    ``succeeded (R,k)`` (per selected slot), ``round_duration (R,)``,
    ``new_dropouts (R,)``, ``energy_spent_pct (R,)``, ``mean_battery (R,)``,
    ``total_dropped (R,)``, plus the fault-injection bookkeeping
    ``retries (R,)`` and ``corrupt (R,k)`` (all-zero unless ``faults`` is
    active), and ``topk_picks (R,)`` / ``topk_pick_slots (R,)``: the serial
    picks the round's Pallas top-k calls made and what the unpruned kernel
    would make (zero where ``lax.top_k`` runs; every engine carries them).

    Elasticity: ``checkpoint_path`` (+ ``checkpoint_every`` rounds, default
    final-only) atomically snapshots the full scan carry + trajectory
    (``repro.checkpoint``); ``resume_from`` restores such a snapshot and
    continues mid-trajectory. Because the scan consumes the prefix-stable
    ``split(key, rounds)`` stream as explicit rows, a resumed run is
    bitwise identical to the uninterrupted one (``tests/test_elastic.py``).

    Equivalence contract: matches the per-round host loop (``select`` +
    ``simulate_round``) within float tolerance
    (``tests/test_round_engine.py``), and is the index-for-index parity
    reference for :func:`run_rounds_sharded` and (via the ``buffer_size ==
    max_concurrency == k, staleness_power=0`` limit)
    :func:`run_async_scanned`. Prefer the :func:`run_rounds` front door
    unless you need this engine specifically.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    run = _scanned_runner(
        sel_cfg, energy_model, float(model_bytes), int(local_steps),
        int(batch_size),
        None if deadline_s is None else float(deadline_s),
        None if up_bytes is None else float(up_bytes),
        _auto_pallas(pop.n, use_pallas), interpret, faults)
    keys = jax.random.split(key, rounds)
    st = sel_state.canonical()
    if checkpoint_path is None and resume_from is None:
        if checkpoint_every is not None:
            raise ValueError("checkpoint_every is set but checkpoint_path "
                             "is not — there is nowhere to write snapshots")
        (pop, st), traj = run(keys, pop, st)
        return pop, st, traj

    meta = _engine_meta("sync", sel_cfg, pop.n, rounds, deadline_s, faults)
    start, parts = 0, []
    if resume_from is not None:
        start, state, data, _ = load_engine_checkpoint(
            resume_from, {"pop": pop, "st": st}, expect_meta=meta)
        pop, st = state["pop"], state["st"]
        if data.get("traj"):
            parts.append(data["traj"])
    ck = _make_checkpointer(checkpoint_path, checkpoint_every, rounds, meta)
    for a, b in segment_bounds(start, rounds,
                               ck.every if ck is not None else None):
        (pop, st), traj = run(keys[a:b], pop, st)
        parts.append(jax.tree.map(np.asarray, traj))
        if ck is not None and ck.due(b):
            ck.save(b, {"pop": pop, "st": st},
                    {"traj": _concat_traj(parts)})
    return pop, st, _concat_traj(parts)


# ------------------------------------------------------------------ sharded
# Round engine over a 1-D `clients` mesh: the population pytree is sharded
# on its leading (client) dimension, selection runs per-shard candidate
# generation + a global (k * n_shards -> k) merge (see
# ``selection._shard_select``), and the battery/dropout simulation stays
# fully shard-local with only the (k,) selected indices and scalar round
# stats reassembled via collectives. The static per-client cost table
# (round time + battery debit) depends only on immutable population fields
# (category, network, bandwidths), so it is computed ONCE at engine setup
# and carried as a sharded constant instead of being recomputed every round
# — on CPU meshes that hoist is most of the measured speedup
# (BENCH_selection.json).

def _shard_round_step(key, sel_state, pop, t_total, cost, bits, *,
                      sel_cfg, energy_model, deadline_s, use_pallas,
                      interpret, axis_name, n_real,
                      faults=None, streams=None,
                      energy_budget_j=None, ledger=None):
    """Shard-local round step (selection -> simulation) for shard_map.

    With ``faults`` + ``streams`` (the round's globally generated,
    spec-sharded ``(n_loc, N_FAULT_STREAMS)`` uniforms — generated *outside*
    the shard_map so every shard sees its own slice of the one global
    stream), selection scores on the clean cost while the simulation runs
    on the fault-modified durations/costs, exactly like the single-device
    engine; ``apply_faults`` is elementwise, so the per-client outcomes are
    bitwise identical to the unsharded run.
    """
    n_loc = cost.shape[0]
    base = (jax.lax.axis_index(axis_name) * n_loc).astype(jnp.int32)
    idx, chosen, sel_state = _shard_select(
        key, sel_state, pop, cost, bits, cfg=sel_cfg, axis_name=axis_name,
        n_real=n_real, use_pallas=use_pallas, interpret=interpret)
    # scatter the shard-owned chosen slots into the local population mask
    # (foreign/unchosen slots route to index n_loc and are dropped)
    own = chosen & (idx >= base) & (idx < base + n_loc)
    sel_mask = jnp.zeros((n_loc,), bool).at[
        jnp.where(own, idx - base, n_loc)].set(True, mode="drop")
    if faults is not None and streams is not None:
        t_sim, cost_sim, draw = apply_faults(
            faults, t_total, cost,
            tuple(streams[:, j] for j in range(N_FAULT_STREAMS)))
        fail_mask = draw.fail
    else:
        t_sim, cost_sim, draw, fail_mask = t_total, cost, None, None
    if ledger is not None:
        # predicted cohort debit on the fault-modified cost, globally
        # reduced — admit/refuse is a replicated decision across shards
        round_j = cohort_energy_j(pop, sel_mask, cost_sim, axis_name)
        sel_mask, admit, ledger = budget_gate(sel_mask, round_j, ledger,
                                              energy_budget_j,
                                              sel_state.round, axis_name)
    else:
        admit = jnp.bool_(True)
    pop, dev = simulate_round_device(pop, sel_mask, t_sim, cost_sim,
                                     sel_state.round, energy_model,
                                     deadline_s, axis_name=axis_name,
                                     fail_mask=fail_mask)
    if ledger is not None:
        ledger = ledger._replace(spent_j=ledger.spent_j + dev.energy_spent_j)
    # per-slot success for the trajectory: one shard owns each slot
    succ_sel = _slot_gather(dev.succeeded, idx, chosen, base, axis_name) > 0
    if draw is None:
        retries = jnp.int32(0)
        corrupt_sel = jnp.zeros(idx.shape, bool)
    else:
        # integer psums are exact, so both match the host engine bitwise
        retries = jax.lax.psum(
            jnp.sum(jnp.where(sel_mask, draw.retries, 0)),
            axis_name).astype(jnp.int32)
        corrupt_sel = (_slot_gather_i32(draw.corrupt, idx, chosen, base,
                                        axis_name) > 0) & chosen
    return (pop, sel_state, idx, chosen, succ_sel, dev, retries,
            corrupt_sel, admit, ledger)


@functools.lru_cache(maxsize=16)
def _sharded_scanned_runner(sel_cfg: SelectorConfig,
                            energy_model: EnergyModel,
                            deadline_s: Optional[float],
                            use_pallas: bool, interpret: bool,
                            mesh, n_real: int, axis_name: str,
                            faults: Optional[FaultConfig]):
    """Cached jitted sharded scan over a caller-supplied (R, 2) key array.
    The hoisted cost table is a run argument (not a static), so one
    compilation serves any population with the same shape/config."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    n_shards = mesh.shape[axis_name]
    n_padded = n_real + (-n_real) % n_shards
    n_pad = n_padded - n_real
    spec = P(axis_name)
    faulty = faults is not None and faults.active

    def body(key_r, st, pop, t_total, cost, bits, streams=None):
        with pick_tally() as tally:
            (pop, st, idx, chosen, succ_sel, dev, retries, corrupt_sel,
             _admit, _ledger) = _shard_round_step(
                key_r, st, pop, t_total, cost, bits, sel_cfg=sel_cfg,
                energy_model=energy_model, deadline_s=deadline_s,
                use_pallas=use_pallas, interpret=interpret,
                axis_name=axis_name, n_real=n_real,
                faults=faults if faulty else None, streams=streams)
        out = {
            "selected": idx,
            "chosen": chosen,
            "succeeded": succ_sel,
            "round_duration": dev.round_duration,
            "new_dropouts": dev.new_dropouts,
            "energy_spent_pct": dev.energy_spent_pct,
            "energy_spent_j": dev.energy_spent_j,
            "mean_battery": _asum(pop.battery_pct, axis_name) / n_real,
            "total_dropped": (_asum(pop.dropped, axis_name)
                              .astype(jnp.int32) - n_pad),
            "retries": retries,
            "corrupt": corrupt_sel,
            **_picks_out(tally, axis_name),
        }
        return pop, st, out

    stream_specs = (spec,) if faulty else ()
    smapped = jax.shard_map(body, mesh=mesh,
                            in_specs=(P(), P(), spec, spec, spec, spec)
                            + stream_specs,
                            out_specs=(spec, P(), P()),
                            check_vma=False)

    @jax.jit
    def run(keys, pop, st, t_total, cost):
        def scan_step(carry, key_r):
            pop, st = carry
            # prefix-stable sharded rank bits (partitionable threefry):
            # the first n_real values equal the single-device stream
            bits = jax.lax.with_sharding_constraint(
                _rank_bits(key_r, n_padded), NamedSharding(mesh, spec))
            args = (key_r, st, pop, t_total, cost, bits)
            if faulty:
                # fault streams are global + prefix-stable like the rank
                # bits: generated at n_padded outside the shard_map, keyed
                # on the post-selection round number (pre-select carry + 1)
                streams = jnp.stack(
                    fault_streams(faults, st.round + 1, n_padded), axis=-1)
                args += (jax.lax.with_sharding_constraint(
                    streams, NamedSharding(mesh, spec)),)
            pop, st, out = smapped(*args)
            return (pop, st), out

        return jax.lax.scan(scan_step, (pop, st), keys)

    return run


def round_cost_table(pop: ClientPopulation, energy_model: EnergyModel,
                     model_bytes: float, local_steps: int, batch_size: int,
                     up_bytes: Optional[float] = None, sharding=None):
    """Precompute the round-invariant per-client (round time, battery cost)
    table. Both depend only on static population fields, so the sharded
    engine computes them once at setup instead of once per round."""
    fn = lambda p: _round_cost(p, energy_model, float(model_bytes),
                               int(local_steps), int(batch_size),
                               None if up_bytes is None else float(up_bytes))
    if sharding is not None:
        return jax.jit(fn, out_shardings=(sharding, sharding))(pop)
    return jax.jit(fn)(pop)


# ------------------------------------------------------------------- async
# FedBuff-style buffered-asynchronous engine (Nguyen et al., AISTATS'22;
# the ROADMAP's async open item). Every selected client finishes at its own
# event-clock time `t_start + t_total(i)` instead of a synchronous barrier;
# the server aggregates whenever `buffer_size` completions have arrived,
# damping each delta by 1/(1+staleness)**staleness_power, and immediately
# refills the freed concurrency slots from the same selector kinds the sync
# engine uses. One scan step == one server aggregation:
#
#   flush:  pop the `buffer_size` earliest completions off the per-client
#           event clock, debit battery / dropouts via the SAME fused
#           simulate_round_device core (arrival offsets play the role of
#           round times; still-in-flight clients are exempt from the idle
#           drain), advance the server clock to the last arrival, bump the
#           server version;
#   refill: select `buffer_size` replacements (in-flight clients are masked
#           out of the candidate set) and start their event clocks at the
#           new server time.
#
# In the limit buffer_size == max_concurrency == k with staleness_power=0
# every flush completes exactly the cohort the previous refill started, so
# the engine reproduces run_rounds_scanned's selection/battery/dropout
# trajectory (tested in tests/test_async_engine.py).


def _async_knobs(sel_cfg: SelectorConfig, buffer_size: Optional[int],
                 max_concurrency: Optional[int]):
    """Normalise + validate the FedBuff knobs (shared by the scanned and
    sharded async engines so their defaults/validation cannot drift).

    Returns ``(buffer_size, max_concurrency, fill_cfg, refill_cfg)`` where
    ``fill_cfg``/``refill_cfg`` are the selector configs used to prime the
    concurrency slots (k = max_concurrency) and to refill after each flush
    (k = buffer_size)."""
    import dataclasses as _dc

    buffer_size = sel_cfg.k if buffer_size is None else int(buffer_size)
    max_concurrency = (sel_cfg.k if max_concurrency is None
                       else int(max_concurrency))
    if buffer_size < 1:
        raise ValueError("buffer_size must be >= 1")
    if max_concurrency < buffer_size:
        raise ValueError("max_concurrency must be >= buffer_size "
                         f"({max_concurrency} < {buffer_size})")
    fill_cfg = _dc.replace(sel_cfg, k=max_concurrency)
    refill_cfg = _dc.replace(sel_cfg, k=buffer_size)
    return buffer_size, max_concurrency, fill_cfg, refill_cfg


class AsyncEventState(NamedTuple):
    """Device-resident event bookkeeping for the buffered-async engine.

    The per-client leaves (``t_done``, ``start_version``) are (N,) arrays
    that live wherever the population lives: on one device for the
    scanned engine, or sharded over the `clients` mesh axis for
    :func:`run_async_sharded` / :func:`make_sharded_async_engine` (the
    scalars stay replicated). Both engines advance the same state
    transition, so the event trajectory is engine-independent.

    ``t_done`` holds each in-flight client's *remaining* seconds measured
    from the last aggregation point (+inf when idle), not an absolute
    clock: offsets are what every consumer needs (flush ordering, wall
    advance, deadline, last_duration), and keeping them relative avoids the
    ``(clock + t) - clock != t`` float drift an absolute event clock would
    leak into the sync-parity limit. Each flush advances ``server_clock``
    by the aggregation's wall time and re-bases the survivors' offsets.
    """

    t_done: jnp.ndarray          # (N,) f32 remaining seconds; +inf when idle
    start_version: jnp.ndarray   # (N,) i32 server version when started
    server_clock: jnp.ndarray    # f32 scalar, absolute seconds
    server_version: jnp.ndarray  # i32 scalar, aggregations so far
    spent_j: jnp.ndarray         # f32 scalar, cumulative fleet joules debited
    exhausted_round: jnp.ndarray  # i32 scalar, first budget-refused agg (0=no)

    @classmethod
    def create(cls, n: int) -> "AsyncEventState":
        return cls(t_done=jnp.full((n,), jnp.inf, jnp.float32),
                   start_version=jnp.zeros((n,), jnp.int32),
                   server_clock=jnp.float32(0.0),
                   server_version=jnp.int32(0),
                   spent_j=jnp.float32(0.0),
                   exhausted_round=jnp.int32(0))

    @property
    def in_flight(self) -> jnp.ndarray:
        return jnp.isfinite(self.t_done)


def _start_clients(astate: AsyncEventState, idx, chosen,
                   t_total) -> AsyncEventState:
    """Arm the event clock for the chosen slots (idx into the population).
    Started clients launch at the current aggregation point, so their
    remaining time is exactly their round time."""
    n = astate.t_done.shape[0]
    tgt = jnp.where(chosen, idx, n)
    t_done = astate.t_done.at[tgt].set(t_total[idx], mode="drop")
    start_v = astate.start_version.at[tgt].set(astate.server_version,
                                               mode="drop")
    return astate._replace(t_done=t_done, start_version=start_v)


def make_async_round_engine(sel_cfg: SelectorConfig,
                            energy_model: EnergyModel,
                            model_bytes: float, local_steps: int,
                            batch_size: int,
                            buffer_size: Optional[int] = None,
                            max_concurrency: Optional[int] = None,
                            staleness_power: float = 0.5,
                            deadline_s: Optional[float] = None,
                            up_bytes: Optional[float] = None,
                            use_pallas: bool = False,
                            interpret: bool = False,
                            energy_budget_j: Optional[float] = None):
    """Traced FedBuff event engine, single-device (the sharded twin is
    :func:`make_sharded_async_engine`): returns ``(init_fill, step)``.

    ``energy_budget_j`` arms the fleet budget gate on the *start* side:
    a fill/refill batch is admitted all-or-nothing only when the already
    spent joules (``astate.spent_j``, debited at completion) plus the
    committed cost of every in-flight client plus the batch's predicted
    cost still fit — the committed term is what guarantees the eventual
    debits can never overshoot the budget even though async charges at
    completion time. Accounting (``astate.spent_j``) accumulates whether
    or not a budget is set.

    ``init_fill(key, pop, sel_state, astate)`` primes ``max_concurrency``
    concurrency slots (no battery is debited — debits happen at completion)
    and returns ``(sel_state, astate, idx, chosen)``.

    ``step(key, pop, sel_state, astate, do_refill)`` performs one
    flush-then-refill event step and returns ``(pop, sel_state, astate,
    flush, refill)`` where ``flush`` is a dict with the completion batch
    (``completed``/``comp_chosen``/``succeeded``/``staleness``/
    ``agg_weight``/``round_duration``/``new_dropouts``/
    ``energy_spent_pct``) and ``refill`` is ``(idx, chosen)`` for the
    freshly started clients. ``do_refill=False`` flushes without starting
    (or advancing selector state for) new clients — the final step of a
    fixed-length run.

    ``deadline_s`` is a *reporting* deadline: an arrival more than
    ``deadline_s`` seconds after the previous aggregation is abandoned
    (it still pays its round energy), mirroring the sync engine's
    per-round deadline semantics.
    """
    buffer_size, max_concurrency, fill_cfg, refill_cfg = _async_knobs(
        sel_cfg, buffer_size, max_concurrency)

    def _select(key, cfg, sel_state, pop, cost, astate):
        # in-flight clients must not be re-selected: mask them out of the
        # candidate set through the `dropped` channel (selection-only copy)
        sel_pop = pop.replace(dropped=pop.dropped | astate.in_flight)
        return _device_select(key, cfg, sel_state, sel_pop, cost,
                              use_pallas, interpret)

    def _admit_batch(astate, pop, cost, idx, chosen, rnd):
        """All-or-nothing budget admission for a fill/refill batch: spent
        + in-flight commitments + batch prediction must fit. Returns the
        gated ``chosen`` and the astate with ``exhausted_round`` stamped
        on the first refusal."""
        if energy_budget_j is None:
            return chosen, astate
        cost_j = pct_to_joules(pop.category, cost)
        committed = jnp.sum(jnp.where(astate.in_flight, cost_j, 0.0))
        batch_j = jnp.sum(jnp.where(chosen, cost_j[idx], 0.0))
        admit = (astate.spent_j + committed + batch_j
                 <= jnp.float32(energy_budget_j))
        refused = jnp.any(chosen) & ~admit
        exhausted = jnp.where((astate.exhausted_round == 0) & refused,
                              jnp.asarray(rnd, jnp.int32),
                              astate.exhausted_round)
        return chosen & admit, astate._replace(exhausted_round=exhausted)

    def init_fill(key, pop: ClientPopulation, sel_state: SelectorState,
                  astate: AsyncEventState):
        t_total, cost = _round_cost(pop, energy_model, model_bytes,
                                    local_steps, batch_size, up_bytes)
        idx, chosen, sel_state = _select(key, fill_cfg, sel_state, pop,
                                         cost, astate)
        chosen, astate = _admit_batch(astate, pop, cost, idx, chosen,
                                      astate.server_version + 1)
        astate = _start_clients(astate, idx, chosen, t_total)
        return sel_state, astate, idx, chosen

    def step(key, pop: ClientPopulation, sel_state: SelectorState,
             astate: AsyncEventState, do_refill):
        n = pop.n
        t_total, cost = _round_cost(pop, energy_model, model_bytes,
                                    local_steps, batch_size, up_bytes)

        # ---- flush: the buffer_size earliest arrivals ------------------
        in_flight = astate.in_flight
        n_if = jnp.sum(in_flight).astype(jnp.int32)
        _, cidx = jax.lax.top_k(jnp.where(in_flight, -astate.t_done,
                                          -jnp.inf), buffer_size)
        cidx = cidx.astype(jnp.int32)
        comp_chosen = jnp.arange(buffer_size) < jnp.minimum(buffer_size,
                                                            n_if)
        comp_mask = jnp.zeros((n,), bool).at[
            jnp.where(comp_chosen, cidx, n)].set(True, mode="drop")

        # remaining-time offsets from the previous aggregation point play
        # the role of the sync engine's per-round times: the slowest
        # successful arrival advances the wall clock, the deadline abandons
        # late arrivals, and last_duration records the observed offset
        busy = in_flight & ~comp_mask
        rnd = astate.server_version + 1
        pop, dev = simulate_round_device(pop, comp_mask, astate.t_done,
                                         cost, rnd, energy_model,
                                         deadline_s, busy_mask=busy)

        staleness = jnp.maximum(
            astate.server_version - astate.start_version[cidx], 0)
        succeeded = dev.succeeded[cidx] & comp_chosen
        agg_weight = jnp.where(
            succeeded,
            (1.0 + staleness.astype(jnp.float32)) ** (-staleness_power),
            0.0)

        # re-base survivors to the new aggregation point. Clamp at 0: when
        # a whole flush fails (battery deaths) under a loose deadline_s the
        # duration falls back to the deadline, which can overshoot a busy
        # survivor's remaining time — the server outwaited it, so it
        # arrives at offset 0 next flush (never negative, which would run
        # the clock backwards and turn idle drain into a battery credit).
        # inf - duration stays inf for idle slots.
        any_comp = n_if > 0
        astate = astate._replace(
            t_done=jnp.where(comp_mask, jnp.inf,
                             jnp.maximum(astate.t_done
                                         - dev.round_duration, 0.0)),
            server_clock=astate.server_clock + dev.round_duration,
            server_version=astate.server_version
            + any_comp.astype(jnp.int32),
            spent_j=astate.spent_j + dev.energy_spent_j)

        flush = {
            "completed": cidx,
            "comp_chosen": comp_chosen,
            "succeeded": succeeded,
            "staleness": jnp.where(comp_chosen, staleness, 0),
            "agg_weight": agg_weight,
            "round_duration": dev.round_duration,
            "new_dropouts": dev.new_dropouts,
            "energy_spent_pct": dev.energy_spent_pct,
            "energy_spent_j": dev.energy_spent_j,
        }

        # ---- refill the freed slots ------------------------------------
        ridx, rchosen, new_sel_state = _select(key, refill_cfg, sel_state,
                                               pop, cost, astate)
        rchosen = rchosen & do_refill
        rchosen, astate = _admit_batch(astate, pop, cost, ridx, rchosen,
                                       astate.server_version + 1)
        sel_state = jax.tree.map(lambda new, old: jnp.where(do_refill, new,
                                                            old),
                                 new_sel_state, sel_state.canonical())
        astate = _start_clients(astate, ridx, rchosen, t_total)
        return pop, sel_state, astate, flush, (ridx, rchosen)

    return init_fill, step


@functools.lru_cache(maxsize=32)
def _async_scanned_runner(sel_cfg: SelectorConfig, energy_model: EnergyModel,
                          model_bytes: float, local_steps: int,
                          batch_size: int, buffer_size: Optional[int],
                          max_concurrency: Optional[int],
                          staleness_power: float,
                          deadline_s: Optional[float],
                          up_bytes: Optional[float],
                          use_pallas: bool, interpret: bool):
    """Cached jitted async runner pair (event-stepped twin of
    :func:`_scanned_runner`): ``fill(key0, pop, st)`` primes the pipe,
    ``seg(xs, pop, st, astate)`` scans a slice of the aggregation stream.
    Splitting fill from scan lets elastic runs checkpoint/resume the event
    carry between segments; the fill-prepend trajectory postprocess lives
    in :func:`run_async_scanned` after the segments are spliced."""
    init_fill, step = make_async_round_engine(
        sel_cfg, energy_model, model_bytes, local_steps, batch_size,
        buffer_size, max_concurrency, staleness_power, deadline_s,
        up_bytes, use_pallas, interpret)

    def scan_step(carry, xs):
        pop, st, astate = carry
        with pick_tally() as tally:
            pop, st, astate, flush, (ridx, rchosen) = step(
                xs["key"], pop, st, astate, xs["refill"])
        out = {
            **flush,
            "selected": ridx,
            "chosen": rchosen,
            "server_clock": astate.server_clock,
            "n_inflight": jnp.sum(astate.in_flight).astype(jnp.int32),
            "mean_battery": jnp.mean(pop.battery_pct),
            "total_dropped": jnp.sum(pop.dropped).astype(jnp.int32),
            "budget_spent_j": astate.spent_j,
            "budget_exhausted": astate.exhausted_round,
            **_picks_out(tally),
        }
        return (pop, st, astate), out

    @jax.jit
    def fill(key0, pop, st):
        astate = AsyncEventState.create(pop.n)
        st, astate, idx0, chosen0 = init_fill(key0, pop, st, astate)
        return st, astate, idx0, chosen0

    @jax.jit
    def seg(xs, pop, st, astate):
        return jax.lax.scan(scan_step, (pop, st, astate), xs)

    return fill, seg


def _async_xs(key, rounds: int):
    """The async engines' per-aggregation scan inputs: the sync engine
    draws selection keys as split(key, rounds)[r] for round r — reuse the
    exact same stream (keys[0] primes the pipe, keys[r] refills after
    flush r) so the parity limit reproduces the sync selection trajectory
    key-for-key. The last flush refills nothing: a fixed-length run is
    over, and skipping the call keeps the selector-state trajectory
    identical to ``rounds`` synchronous selections."""
    keys = jax.random.split(key, rounds)
    xs = {
        "key": jnp.concatenate([keys[1:], keys[-1:]]),
        "refill": jnp.arange(rounds) < rounds - 1,
    }
    return keys[0], xs


def _async_fill_prepend(traj, idx0, chosen0, b: int):
    """Selection trajectory aligned with the sync engine: row r is the
    cohort *started* for aggregation r+1 (initial fill + refills). The
    fill row is truncated to the refill width; the full
    (max_concurrency,) fill is also kept for replay/debugging. Returns
    a new dict — the caller's trajectory is never mutated."""
    traj = dict(traj)
    traj["fill_selected"] = idx0
    traj["fill_chosen"] = chosen0
    traj["selected"] = jnp.concatenate([jnp.asarray(idx0)[None, :b],
                                        jnp.asarray(traj["selected"])[:-1]])
    traj["chosen"] = jnp.concatenate([jnp.asarray(chosen0)[None, :b],
                                      jnp.asarray(traj["chosen"])[:-1]])
    return traj


def run_async_scanned(key, sel_cfg: SelectorConfig, pop: ClientPopulation,
                      sel_state: SelectorState, energy_model: EnergyModel,
                      model_bytes: float, local_steps: int, batch_size: int,
                      rounds: int,
                      buffer_size: Optional[int] = None,
                      max_concurrency: Optional[int] = None,
                      staleness_power: float = 0.5,
                      deadline_s: Optional[float] = None,
                      up_bytes: Optional[float] = None,
                      use_pallas: Optional[bool] = None,
                      interpret: Optional[bool] = None,
                      faults: Optional[FaultConfig] = None,
                      checkpoint_every: Optional[int] = None,
                      checkpoint_path: Optional[str] = None,
                      resume_from: Optional[str] = None,
                      ) -> Tuple[ClientPopulation, SelectorState,
                                 Dict[str, jnp.ndarray]]:
    """FedBuff-style asynchronous twin of :func:`run_rounds_scanned`:
    ``rounds`` server aggregations advanced inside one event-stepped
    ``jax.lax.scan``, single-device (no mesh — for fleet-scale populations
    use :func:`run_async_sharded`, index-for-index identical over a
    `clients` mesh, or let :func:`run_rounds` pick).

    The trajectory holds, per aggregation: the completion batch
    (``completed (R,B)``, ``comp_chosen``, ``succeeded``, ``staleness``,
    ``agg_weight`` — the 1/(1+s)**p damping factors, 0 for failed slots),
    the refilled cohort (``selected (R,B)``/``chosen``, aligned so row r is
    the cohort started for aggregation r+1 — in the parity limit identical
    to the sync trajectory), wall stats (``round_duration`` — seconds
    between consecutive aggregations, ``server_clock``), and the same
    dropout/battery fields as the sync scan. ``n_inflight`` tracks
    concurrency (never exceeds ``max_concurrency``).

    In the parity limit ``buffer_size == max_concurrency == sel_cfg.k``
    with ``staleness_power=0.0`` this reproduces the sync engine's
    selection/battery/dropout trajectory within float tolerance. Note the
    first row of ``selected``/``chosen`` is the initial fill truncated to
    ``buffer_size`` slots — equal to the full fill in the parity limit.

    Elasticity (``checkpoint_path``/``checkpoint_every``/``resume_from``)
    snapshots the full event carry — population, selector state, and
    :class:`AsyncEventState` (in-flight clocks + versions) — between
    aggregations; a resumed run replays the identical key stream and is
    bitwise identical to the uninterrupted one. ``faults`` is rejected:
    the event engine's completion ordering has no well-defined round
    boundary for per-round fault draws (use the sync engines).
    """
    if faults is not None and faults.active:
        raise ValueError(
            "fault injection is not supported by the async event engines "
            "(no per-round fault boundary); use the sync engines")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    fill, seg = _async_scanned_runner(
        sel_cfg, energy_model, float(model_bytes), int(local_steps),
        int(batch_size),
        None if buffer_size is None else int(buffer_size),
        None if max_concurrency is None else int(max_concurrency),
        float(staleness_power),
        None if deadline_s is None else float(deadline_s),
        None if up_bytes is None else float(up_bytes),
        _auto_pallas(pop.n, use_pallas), interpret)
    b = sel_cfg.k if buffer_size is None else int(buffer_size)
    key0, xs = _async_xs(key, rounds)
    st = sel_state.canonical()
    if checkpoint_path is None and resume_from is None:
        if checkpoint_every is not None:
            raise ValueError("checkpoint_every is set but checkpoint_path "
                             "is not — there is nowhere to write snapshots")
        st, astate, idx0, chosen0 = fill(key0, pop, st)
        (pop, st, astate), traj = seg(xs, pop, st, astate)
        traj = _async_fill_prepend(traj, idx0, chosen0, b)
        traj["final_event_state"] = astate
        return pop, st, traj

    meta = _engine_meta(
        "async", sel_cfg, pop.n, rounds, deadline_s, faults,
        buffer_size=b,
        max_concurrency=(sel_cfg.k if max_concurrency is None
                         else int(max_concurrency)),
        staleness_power=float(staleness_power))
    start, parts = 0, []
    if resume_from is not None:
        templates = {"pop": pop, "st": st,
                     "astate": AsyncEventState.create(pop.n)}
        start, state, data, _ = load_engine_checkpoint(
            resume_from, templates, expect_meta=meta)
        pop, st, astate = state["pop"], state["st"], state["astate"]
        idx0, chosen0 = data["fill_selected"], data["fill_chosen"]
        if data.get("traj"):
            parts.append(data["traj"])
    else:
        st, astate, idx0, chosen0 = fill(key0, pop, st)
    ck = _make_checkpointer(checkpoint_path, checkpoint_every, rounds, meta)
    for a, e in segment_bounds(start, rounds,
                               ck.every if ck is not None else None):
        xs_seg = {k2: v[a:e] for k2, v in xs.items()}
        (pop, st, astate), traj = seg(xs_seg, pop, st, astate)
        parts.append(jax.tree.map(np.asarray, traj))
        if ck is not None and ck.due(e):
            ck.save(e, {"pop": pop, "st": st, "astate": astate},
                    {"traj": _concat_traj(parts),
                     "fill_selected": np.asarray(idx0),
                     "fill_chosen": np.asarray(chosen0)})
    traj = _async_fill_prepend(_concat_traj(parts), idx0, chosen0, b)
    traj["final_event_state"] = astate
    return pop, st, traj


def run_rounds_sharded(key, sel_cfg: SelectorConfig, pop: ClientPopulation,
                       sel_state: SelectorState, energy_model: EnergyModel,
                       model_bytes: float, local_steps: int, batch_size: int,
                       rounds: int,
                       deadline_s: Optional[float] = None,
                       up_bytes: Optional[float] = None,
                       use_pallas: Optional[bool] = None,
                       interpret: Optional[bool] = None,
                       mesh=None, n_shards: Optional[int] = None,
                       faults: Optional[FaultConfig] = None,
                       checkpoint_every: Optional[int] = None,
                       checkpoint_path: Optional[str] = None,
                       resume_from: Optional[str] = None,
                       ) -> Tuple[ClientPopulation, SelectorState,
                                  Dict[str, jnp.ndarray]]:
    """Sharded twin of :func:`run_rounds_scanned` over a 1-D `clients`
    mesh (``mesh``/``n_shards``, default: all visible devices).

    Pads the population to a multiple of the mesh size (pad clients are
    dead and never selected), shards it with the hoisted cost table, and
    scans fully sharded. Parity contract: the selection trajectory
    (``selected``/``chosen``) is index-for-index identical to
    :func:`run_rounds_scanned` on the same key (verified under 1/2/8
    virtual devices by ``repro.launch.sharded_check``); summed stats
    (``energy_spent_pct``, ``mean_battery``) match within float
    reduction-order tolerance. The returned population is trimmed back to
    the real client count. Worth it above ~:data:`ENGINE_CUTOVER_N`
    clients — below that, collective latency dominates and
    :func:`run_rounds` picks the single-device engine instead.

    Elasticity (``checkpoint_path`` / ``checkpoint_every`` /
    ``resume_from``) works exactly like the scanned engine's, and
    snapshots store the population *trimmed to the real client count* —
    pad clients provably never leave their initial dead state, so a
    checkpoint written under one device count resumes under any other
    (including by the single-device engine: both share the ``"sync"``
    checkpoint family).
    """
    from repro.launch.mesh import make_client_mesh
    from repro.launch.sharding import population_sharding

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if mesh is None:
        mesh = make_client_mesh(n_shards)
    axis_name = mesh.axis_names[0]
    n_real = pop.n
    shard = population_sharding(mesh, axis_name)
    n_dev = mesh.shape[axis_name]

    def pad_put(p):
        return jax.device_put(pad_population(p, n_dev), shard)

    def trim(p):
        return (jax.tree.map(lambda x: x[:n_real], p)
                if p.n != n_real else p)

    padded = pad_put(pop)
    t_total, cost = round_cost_table(padded, energy_model, model_bytes,
                                     local_steps, batch_size, up_bytes,
                                     sharding=shard)
    run = _sharded_scanned_runner(
        sel_cfg, energy_model,
        None if deadline_s is None else float(deadline_s),
        _auto_pallas(n_real, use_pallas), interpret, mesh, n_real,
        axis_name, faults)
    keys = jax.random.split(key, rounds)
    st = sel_state.canonical()
    if checkpoint_path is None and resume_from is None:
        if checkpoint_every is not None:
            raise ValueError("checkpoint_every is set but checkpoint_path "
                             "is not — there is nowhere to write snapshots")
        (fpop, st), traj = run(keys, padded, st, t_total, cost)
        return trim(fpop), st, traj

    # same meta family as the scanned engine: sync checkpoints are
    # engine- and device-count-portable (trimmed populations)
    meta = _engine_meta("sync", sel_cfg, n_real, rounds, deadline_s, faults)
    start, parts = 0, []
    if resume_from is not None:
        start, state, data, _ = load_engine_checkpoint(
            resume_from, {"pop": pop, "st": st}, expect_meta=meta)
        padded, st = pad_put(state["pop"]), state["st"]
        if data.get("traj"):
            parts.append(data["traj"])
    ck = _make_checkpointer(checkpoint_path, checkpoint_every, rounds, meta)
    fpop = padded
    for a, b in segment_bounds(start, rounds,
                               ck.every if ck is not None else None):
        (fpop, st), traj = run(keys[a:b], fpop, st, t_total, cost)
        parts.append(jax.tree.map(np.asarray, traj))
        if ck is not None and ck.due(b):
            ck.save(b, {"pop": trim(fpop), "st": st},
                    {"traj": _concat_traj(parts)})
    return trim(fpop), st, _concat_traj(parts)


# ----------------------------------------------------------- sharded async
# The FedBuff event engine over the same 1-D `clients` mesh as the sync
# sharded engine: AsyncEventState's per-client leaves (event clocks,
# in-flight versions) stay shard-resident next to the population, the
# flush's buffer_size-earliest-arrivals pick runs as the same two-level
# tournament `_shard_select` uses (per-shard top-k of -t_done -> all-gather
# -> tiny global top-k, tie-identical to single-device lax.top_k), and the
# battery/dropout debit reuses `simulate_round_device` with psum/pmax
# collectives. Everything the single-device async step computes per client
# is elementwise, and every cross-shard reduction is either exactly
# associative (pmax durations, pmin/pmax norm stats) or a one-owner-per-slot
# psum gather, so the trajectory is index-for-index identical to
# `run_async_scanned` (checked under 1/2/8 virtual devices by
# `repro.launch.sharded_check --async`).


def _slot_gather_i32(x_loc, idx, mask, base, axis_name: str):
    """Integer twin of ``selection._slot_gather``: one shard owns each of
    the (k,) global ``idx`` slots, so a psum of int32 reassembles the
    replicated values exactly (no float round-trip for version counters)."""
    n_loc = x_loc.shape[0]
    in_range = mask & (idx >= base) & (idx < base + n_loc)
    loc = jnp.clip(idx - base, 0, n_loc - 1)
    vals = jnp.where(in_range, x_loc[loc].astype(jnp.int32), 0)
    return jax.lax.psum(vals, axis_name)


def _start_clients_shard(astate: AsyncEventState, idx, chosen, t_total,
                         base) -> AsyncEventState:
    """Shard-local :func:`_start_clients`: arm the event clocks of the
    chosen slots this shard owns (global ``idx``, local ``t_total``)."""
    n_loc = t_total.shape[0]
    loc = jnp.clip(idx - base, 0, n_loc - 1)
    own = chosen & (idx >= base) & (idx < base + n_loc)
    tgt = jnp.where(own, loc, n_loc)
    t_done = astate.t_done.at[tgt].set(t_total[loc], mode="drop")
    start_v = astate.start_version.at[tgt].set(astate.server_version,
                                               mode="drop")
    return astate._replace(t_done=t_done, start_version=start_v)


def _shard_admit_batch(astate, pop, cost, idx, chosen, rnd,
                       energy_budget_j, base, axis_name):
    """Sharded twin of the scanned engine's ``_admit_batch``: spent +
    in-flight commitments + batch prediction must fit, all-or-nothing.
    The commitment psum and the one-owner-per-slot batch psum make the
    admit decision replicated across shards."""
    if energy_budget_j is None:
        return chosen, astate
    n_loc = cost.shape[0]
    cost_j = pct_to_joules(pop.category, cost)
    committed = _asum(jnp.where(astate.in_flight, cost_j, 0.0), axis_name)
    own = chosen & (idx >= base) & (idx < base + n_loc)
    loc = jnp.clip(idx - base, 0, n_loc - 1)
    batch_j = _asum(jnp.where(own, cost_j[loc], 0.0), axis_name)
    admit = (astate.spent_j + committed + batch_j
             <= jnp.float32(energy_budget_j))
    refused = jnp.any(chosen) & ~admit
    exhausted = jnp.where((astate.exhausted_round == 0) & refused,
                          jnp.asarray(rnd, jnp.int32),
                          astate.exhausted_round)
    return chosen & admit, astate._replace(exhausted_round=exhausted)


def _shard_async_fill(key, sel_state, astate, pop, t_total, cost, bits, *,
                      fill_cfg, axis_name, n_real, use_pallas, interpret,
                      energy_budget_j=None):
    """Shard-local initial fill: prime ``max_concurrency`` slots (no debit
    — debits happen at completion), twin of the scanned ``init_fill``."""
    n_loc = cost.shape[0]
    base = (jax.lax.axis_index(axis_name) * n_loc).astype(jnp.int32)
    sel_pop = pop.replace(dropped=pop.dropped | astate.in_flight)
    idx, chosen, sel_state = _shard_select(
        key, sel_state, sel_pop, cost, bits, cfg=fill_cfg,
        axis_name=axis_name, n_real=n_real, use_pallas=use_pallas,
        interpret=interpret)
    chosen, astate = _shard_admit_batch(astate, pop, cost, idx, chosen,
                                        astate.server_version + 1,
                                        energy_budget_j, base, axis_name)
    astate = _start_clients_shard(astate, idx, chosen, t_total, base)
    return sel_state, astate, idx, chosen


def _shard_async_step(key, sel_state, astate, pop, t_total, cost, bits,
                      do_refill, *, refill_cfg, buffer_size: int,
                      staleness_power: float, energy_model, deadline_s,
                      axis_name, n_real: int, n_pad: int, use_pallas,
                      interpret, energy_budget_j=None):
    """Shard-local flush-then-refill event step (call under ``shard_map``).

    Mirrors the scanned engine's ``step`` operation-for-operation: the
    per-client arithmetic is elementwise on this shard's slice (bitwise
    identical to the unsharded run), and the only cross-shard traffic is
    the flush/refill candidate merges, the one-owner-per-slot gathers for
    staleness/success, and the scalar psum/pmax round stats. ``stats``
    also holds the refill's Pallas top-k pick counts, summed over shards.
    """
    n_loc = cost.shape[0]
    base = (jax.lax.axis_index(axis_name) * n_loc).astype(jnp.int32)

    # ---- flush: the buffer_size earliest arrivals, two-level merge -----
    in_flight = astate.in_flight
    n_if = jax.lax.psum(jnp.sum(in_flight), axis_name).astype(jnp.int32)
    b_loc = min(buffer_size, n_loc)
    g = jnp.where(in_flight, -astate.t_done, -jnp.inf)
    cidx = _merge_topk(g, buffer_size, b_loc, base, axis_name) \
        .astype(jnp.int32)
    comp_chosen = jnp.arange(buffer_size) < jnp.minimum(buffer_size, n_if)
    own = comp_chosen & (cidx >= base) & (cidx < base + n_loc)
    comp_mask = jnp.zeros((n_loc,), bool).at[
        jnp.where(own, cidx - base, n_loc)].set(True, mode="drop")

    busy = in_flight & ~comp_mask
    rnd = astate.server_version + 1
    pop, dev = simulate_round_device(pop, comp_mask, astate.t_done, cost,
                                     rnd, energy_model, deadline_s,
                                     axis_name=axis_name, busy_mask=busy)

    start_v = _slot_gather_i32(astate.start_version, cidx, comp_chosen,
                               base, axis_name)
    staleness = jnp.maximum(astate.server_version - start_v, 0)
    succeeded = (_slot_gather(dev.succeeded, cidx, comp_chosen, base,
                              axis_name) > 0) & comp_chosen
    agg_weight = jnp.where(
        succeeded,
        (1.0 + staleness.astype(jnp.float32)) ** (-staleness_power),
        0.0)

    # re-base survivors to the new aggregation point (see the scanned
    # engine for the clamp-at-0 rationale); round_duration is already the
    # global pmax, so the rebase is bitwise identical across engines
    any_comp = n_if > 0
    astate = astate._replace(
        t_done=jnp.where(comp_mask, jnp.inf,
                         jnp.maximum(astate.t_done
                                     - dev.round_duration, 0.0)),
        server_clock=astate.server_clock + dev.round_duration,
        server_version=astate.server_version + any_comp.astype(jnp.int32),
        spent_j=astate.spent_j + dev.energy_spent_j)

    flush = {
        "completed": cidx,
        "comp_chosen": comp_chosen,
        "succeeded": succeeded,
        "staleness": jnp.where(comp_chosen, staleness, 0),
        "agg_weight": agg_weight,
        "round_duration": dev.round_duration,
        "new_dropouts": dev.new_dropouts,
        "energy_spent_pct": dev.energy_spent_pct,
        "energy_spent_j": dev.energy_spent_j,
    }

    # ---- refill the freed slots ----------------------------------------
    sel_pop = pop.replace(dropped=pop.dropped | astate.in_flight)
    with pick_tally() as tally:
        ridx, rchosen, new_sel_state = _shard_select(
            key, sel_state, sel_pop, cost, bits, cfg=refill_cfg,
            axis_name=axis_name, n_real=n_real, use_pallas=use_pallas,
            interpret=interpret)
    rchosen = rchosen & do_refill
    rchosen, astate = _shard_admit_batch(astate, pop, cost, ridx, rchosen,
                                         astate.server_version + 1,
                                         energy_budget_j, base, axis_name)
    sel_state = jax.tree.map(lambda new, old: jnp.where(do_refill, new,
                                                        old),
                             new_sel_state, sel_state)
    astate = _start_clients_shard(astate, ridx, rchosen, t_total, base)

    stats = {
        "n_inflight": (jax.lax.psum(jnp.sum(astate.in_flight), axis_name)
                       .astype(jnp.int32)),
        "mean_battery": _asum(pop.battery_pct, axis_name) / n_real,
        "total_dropped": (_asum(pop.dropped, axis_name)
                          .astype(jnp.int32) - n_pad),
        "budget_spent_j": astate.spent_j,
        "budget_exhausted": astate.exhausted_round,
        **_picks_out(tally, axis_name),
    }
    return pop, sel_state, astate, flush, (ridx, rchosen), stats


def make_sharded_async_engine(sel_cfg: SelectorConfig,
                              energy_model: EnergyModel,
                              mesh, n_real: int,
                              buffer_size: Optional[int] = None,
                              max_concurrency: Optional[int] = None,
                              staleness_power: float = 0.5,
                              deadline_s: Optional[float] = None,
                              use_pallas: bool = False,
                              interpret: bool = False,
                              axis_name: Optional[str] = None,
                              energy_budget_j: Optional[float] = None):
    """Sharded twin of :func:`make_async_round_engine` over a 1-D `clients`
    mesh: returns ``(init_fill, step)`` operating on a population (and
    :class:`AsyncEventState`) padded to the mesh size and sharded over
    ``axis_name``, with the round-invariant cost table hoisted to the
    caller (:func:`round_cost_table`) instead of recomputed per event.

    ``init_fill(key, pop, sel_state, astate, t_total, cost)`` and
    ``step(key, pop, sel_state, astate, t_total, cost, do_refill)`` have
    the scanned engine's contracts plus a trailing per-step ``stats`` dict
    (``n_inflight`` / ``mean_battery`` / ``total_dropped`` via psum);
    outputs are index-for-index identical to the single-device engine on
    the unpadded population (pad clients are dead and never selected).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    if axis_name is None:
        axis_name = mesh.axis_names[0]
    buffer_size, max_concurrency, fill_cfg, refill_cfg = _async_knobs(
        sel_cfg, buffer_size, max_concurrency)
    n_shards = mesh.shape[axis_name]
    n_padded = n_real + (-n_real) % n_shards
    n_pad = n_padded - n_real
    spec = P(axis_name)
    astate_spec = AsyncEventState(t_done=spec, start_version=spec,
                                  server_clock=P(), server_version=P(),
                                  spent_j=P(), exhausted_round=P())

    fill_body = jax.shard_map(
        partial(_shard_async_fill, fill_cfg=fill_cfg, axis_name=axis_name,
                n_real=n_real, use_pallas=use_pallas, interpret=interpret,
                energy_budget_j=energy_budget_j),
        mesh=mesh,
        in_specs=(P(), P(), astate_spec, spec, spec, spec, spec),
        out_specs=(P(), astate_spec, P(), P()),
        check_vma=False)
    step_body = jax.shard_map(
        partial(_shard_async_step, refill_cfg=refill_cfg,
                buffer_size=buffer_size, staleness_power=staleness_power,
                energy_model=energy_model, deadline_s=deadline_s,
                axis_name=axis_name, n_real=n_real, n_pad=n_pad,
                use_pallas=use_pallas, interpret=interpret,
                energy_budget_j=energy_budget_j),
        mesh=mesh,
        in_specs=(P(), P(), astate_spec, spec, spec, spec, spec, P()),
        out_specs=(spec, P(), astate_spec, P(), P(), P()),
        check_vma=False)

    def _bits(key):
        # prefix-stable sharded rank bits (partitionable threefry): the
        # first n_real values equal the single-device stream
        return jax.lax.with_sharding_constraint(
            _rank_bits(key, n_padded), NamedSharding(mesh, spec))

    def init_fill(key, pop, sel_state, astate, t_total, cost):
        return fill_body(key, sel_state, astate, pop, t_total, cost,
                         _bits(key))

    def step(key, pop, sel_state, astate, t_total, cost, do_refill):
        pop, sel_state, astate, flush, refill, stats = step_body(
            key, sel_state, astate, pop, t_total, cost, _bits(key),
            do_refill)
        return pop, sel_state, astate, flush, refill, stats

    return init_fill, step


@functools.lru_cache(maxsize=16)
def _sharded_async_runner(sel_cfg: SelectorConfig, energy_model: EnergyModel,
                          buffer_size: Optional[int],
                          max_concurrency: Optional[int],
                          staleness_power: float,
                          deadline_s: Optional[float],
                          use_pallas: bool, interpret: bool,
                          mesh, n_real: int, axis_name: str):
    """Cached jitted sharded async runner pair (event-stepped twin of
    :func:`_sharded_scanned_runner`; key/trajectory layout identical to
    :func:`_async_scanned_runner`): ``fill`` primes the pipe, ``seg``
    scans a slice of the aggregation stream — same split as the scanned
    async runner, for the same elastic reasons."""
    init_fill, step = make_sharded_async_engine(
        sel_cfg, energy_model, mesh, n_real, buffer_size, max_concurrency,
        staleness_power, deadline_s, use_pallas, interpret, axis_name)
    n_shards = mesh.shape[axis_name]
    n_padded = n_real + (-n_real) % n_shards

    @jax.jit
    def fill(key0, pop, st, t_total, cost):
        # same key stream as the scanned async runner (and therefore the
        # sync engines): keys[0] primes the pipe, keys[r] refills flush r
        astate = AsyncEventState.create(n_padded)
        return init_fill(key0, pop, st, astate, t_total, cost)

    @jax.jit
    def seg(xs, pop, st, astate, t_total, cost):
        def scan_step(carry, x):
            pop, st, astate = carry
            pop, st, astate, flush, (ridx, rchosen), stats = step(
                x["key"], pop, st, astate, t_total, cost, x["refill"])
            out = {
                **flush,
                "selected": ridx,
                "chosen": rchosen,
                "server_clock": astate.server_clock,
                **stats,
            }
            return (pop, st, astate), out

        return jax.lax.scan(scan_step, (pop, st, astate), xs)

    return fill, seg


def _pad_astate(astate: AsyncEventState, n_padded: int) -> AsyncEventState:
    """Re-pad a trimmed :class:`AsyncEventState` to the mesh width. Pad
    slots get the initial idle values (+inf clock, version 0) — pad
    clients are dead, never selected, never started, so these provably
    never change over a run; a trimmed snapshot loses nothing."""
    pad = n_padded - astate.t_done.shape[0]
    if pad <= 0:
        return astate
    return astate._replace(
        t_done=jnp.concatenate(
            [astate.t_done, jnp.full((pad,), jnp.inf, jnp.float32)]),
        start_version=jnp.concatenate(
            [astate.start_version, jnp.zeros((pad,), jnp.int32)]))


def run_async_sharded(key, sel_cfg: SelectorConfig, pop: ClientPopulation,
                      sel_state: SelectorState, energy_model: EnergyModel,
                      model_bytes: float, local_steps: int, batch_size: int,
                      rounds: int,
                      buffer_size: Optional[int] = None,
                      max_concurrency: Optional[int] = None,
                      staleness_power: float = 0.5,
                      deadline_s: Optional[float] = None,
                      up_bytes: Optional[float] = None,
                      use_pallas: Optional[bool] = None,
                      interpret: Optional[bool] = None,
                      mesh=None, n_shards: Optional[int] = None,
                      faults: Optional[FaultConfig] = None,
                      checkpoint_every: Optional[int] = None,
                      checkpoint_path: Optional[str] = None,
                      resume_from: Optional[str] = None,
                      ) -> Tuple[ClientPopulation, SelectorState,
                                 Dict[str, jnp.ndarray]]:
    """Sharded twin of :func:`run_async_scanned` over a 1-D `clients` mesh
    — the FedBuff event engine without the single-device bottleneck.

    Expects (or builds, via ``mesh``/``n_shards``) a 1-D ``clients`` mesh;
    the population is padded to the mesh size (pad clients are dead, never
    selected, never in flight), sharded with the hoisted round-invariant
    cost table, and the whole flush/refill event scan runs sharded.

    Parity contract: the trajectory — selection, completion order,
    staleness, damping weights, wall clock — is index-for-index identical
    to :func:`run_async_scanned` on the same key (per-client arithmetic is
    elementwise on shards, durations merge via exactly-associative pmax,
    slot gathers have one owner per slot); summed scalar stats
    (``energy_spent_pct``, ``mean_battery``) match within float
    reduction-order tolerance. Verified under 1/2/8 virtual devices by
    ``repro.launch.sharded_check``. The returned population and
    ``final_event_state`` are trimmed back to the real client count.

    Elasticity works like :func:`run_async_scanned`'s; snapshots store the
    population *and* the event state trimmed to the real client count (pad
    slots provably stay at their initial idle values), so an ``"async"``
    checkpoint resumes under any device count — including by the
    single-device async engine. ``faults`` is rejected (see there).
    """
    from repro.launch.mesh import make_client_mesh
    from repro.launch.sharding import population_sharding
    from jax.sharding import NamedSharding, PartitionSpec as P

    if faults is not None and faults.active:
        raise ValueError(
            "fault injection is not supported by the async event engines "
            "(no per-round fault boundary); use the sync engines")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if mesh is None:
        mesh = make_client_mesh(n_shards)
    axis_name = mesh.axis_names[0]
    n_real = pop.n
    shard = population_sharding(mesh, axis_name)
    n_dev = mesh.shape[axis_name]
    n_padded = n_real + (-n_real) % n_dev
    padded = jax.device_put(pad_population(pop, n_dev), shard)
    t_total, cost = round_cost_table(padded, energy_model, model_bytes,
                                     local_steps, batch_size, up_bytes,
                                     sharding=shard)
    fill, seg = _sharded_async_runner(
        sel_cfg, energy_model,
        None if buffer_size is None else int(buffer_size),
        None if max_concurrency is None else int(max_concurrency),
        float(staleness_power),
        None if deadline_s is None else float(deadline_s),
        _auto_pallas(n_real, use_pallas), interpret, mesh, n_real,
        axis_name)
    b = sel_cfg.k if buffer_size is None else int(buffer_size)

    def trim_pop(p):
        return (jax.tree.map(lambda x: x[:n_real], p)
                if p.n != n_real else p)

    def trim_astate(a):
        if a.t_done.shape[0] == n_real:
            return a
        return a._replace(t_done=a.t_done[:n_real],
                          start_version=a.start_version[:n_real])

    key0, xs = _async_xs(key, rounds)
    st = sel_state.canonical()
    if checkpoint_path is None and resume_from is None:
        if checkpoint_every is not None:
            raise ValueError("checkpoint_every is set but checkpoint_path "
                             "is not — there is nowhere to write snapshots")
        st, astate, idx0, chosen0 = fill(key0, padded, st, t_total, cost)
        (fpop, st, astate), traj = seg(xs, padded, st, astate, t_total,
                                       cost)
        traj = _async_fill_prepend(traj, idx0, chosen0, b)
        traj["final_event_state"] = trim_astate(astate)
        return trim_pop(fpop), st, traj

    meta = _engine_meta(
        "async", sel_cfg, n_real, rounds, deadline_s, faults,
        buffer_size=b,
        max_concurrency=(sel_cfg.k if max_concurrency is None
                         else int(max_concurrency)),
        staleness_power=float(staleness_power))
    start, parts = 0, []
    if resume_from is not None:
        templates = {"pop": pop, "st": st,
                     "astate": AsyncEventState.create(n_real)}
        start, state, data, _ = load_engine_checkpoint(
            resume_from, templates, expect_meta=meta)
        padded = jax.device_put(pad_population(state["pop"], n_dev), shard)
        st = state["st"]
        astate = jax.device_put(
            _pad_astate(state["astate"], n_padded),
            AsyncEventState(t_done=shard, start_version=shard,
                            server_clock=NamedSharding(mesh, P()),
                            server_version=NamedSharding(mesh, P()),
                            spent_j=NamedSharding(mesh, P()),
                            exhausted_round=NamedSharding(mesh, P())))
        idx0, chosen0 = data["fill_selected"], data["fill_chosen"]
        if data.get("traj"):
            parts.append(data["traj"])
    else:
        st, astate, idx0, chosen0 = fill(key0, padded, st, t_total, cost)
    ck = _make_checkpointer(checkpoint_path, checkpoint_every, rounds, meta)
    fpop = padded
    for a, e in segment_bounds(start, rounds,
                               ck.every if ck is not None else None):
        xs_seg = {k2: v[a:e] for k2, v in xs.items()}
        (fpop, st, astate), traj = seg(xs_seg, fpop, st, astate, t_total,
                                       cost)
        parts.append(jax.tree.map(np.asarray, traj))
        if ck is not None and ck.due(e):
            ck.save(e, {"pop": trim_pop(fpop), "st": st,
                        "astate": trim_astate(astate)},
                    {"traj": _concat_traj(parts),
                     "fill_selected": np.asarray(idx0),
                     "fill_chosen": np.asarray(chosen0)})
    traj = _async_fill_prepend(_concat_traj(parts), idx0, chosen0, b)
    traj["final_event_state"] = trim_astate(astate)
    return trim_pop(fpop), st, traj


# -------------------------------------------------------------- dispatcher
# One front door over the four round engines. The measured boundary comes
# from BENCH_selection.json (PR 2/3): below ~262k clients the sharded
# step's collective latency dominates its per-shard win
# (speedup_sharded_vs_jit 0.3-0.5), above it the sharded engine pulls
# ahead (1.1x at 262k, 2.6x at 4.2M on 8 virtual CPU devices). Because
# every engine pair is index-for-index identical on the same key,
# switching engines at the boundary is free.

#: Population size at/above which a multi-device host dispatches to the
#: sharded engines (the measured ~256k cutover; override per call).
ENGINE_CUTOVER_N = 262_144

SYNC_ENGINES = ("scanned", "sharded")
ASYNC_ENGINES = ("async-scanned", "async-sharded")
ENGINES = SYNC_ENGINES + ASYNC_ENGINES

#: Training engines behind the ``run_fl`` front door: the reference host
#: Python round loop, the fused device-resident scan
#: (``run_fl_scanned`` / ``run_fl_async_scanned``), and the
#: `clients`-mesh shard_map twin (``run_fl_sharded`` /
#: ``run_fl_async_sharded``). All three names exist in BOTH aggregation
#: families.
TRAIN_ENGINES = ("host", "scanned", "sharded")


def resolve_train_engine(n: int, device_count: Optional[int] = None, *,
                         mode: str = "sync", engine: str = "auto",
                         cutover_n: Optional[int] = None) -> str:
    """Pick the *training* engine for ``run_fl``.

    Mirrors :func:`resolve_engine`'s placement logic for the end-to-end
    training loop. An explicit ``engine`` name passes through — every
    name in :data:`TRAIN_ENGINES` is legal in both aggregation families
    (the async family folds FedBuff local SGD into the event scan via the
    in-carry snapshot ring, ``run_fl_async_scanned`` /
    ``run_fl_async_sharded``).

    ``"auto"`` resolves per family: the sync family keeps the reference
    host loop (the trajectory every test and plot was calibrated on),
    which callers upgrade to the fused engines explicitly or via
    benchmarks; the async family picks the device-resident engines
    (``"sharded"`` on a multi-device host, else ``"scanned"``) — the host
    event loop there is the slow reference implementation, kept as the
    parity oracle and reachable via ``engine="host"``. Engines in a
    family produce the same trajectory within float tolerance
    (``tests/test_training_engines.py``,
    ``tests/test_async_training_engines.py``), so the pick is purely a
    performance decision.
    """
    if engine == "auto":
        if mode != "async":
            return "host"
        if device_count is None:
            device_count = jax.device_count()
        return "sharded" if device_count > 1 else "scanned"
    if engine not in TRAIN_ENGINES:
        raise ValueError(f"unknown training engine {engine!r}; expected "
                         f"'auto' or one of {TRAIN_ENGINES}")
    return engine


def resolve_aggregation(mode: str, buffer_size: Optional[int] = None,
                        max_concurrency: Optional[int] = None) -> str:
    """Resolve a user-facing mode string to ``"sync"`` or ``"async"``.

    ``mode="auto"`` picks ``"async"`` exactly when an async-only knob
    (``buffer_size`` / ``max_concurrency``) is set — the knobs have no
    synchronous meaning, so setting one IS the async opt-in. Explicit
    ``"sync"``/``"async"`` pass through; engine names map to their family.
    """
    if mode in ("sync", "async"):
        return mode
    if mode in SYNC_ENGINES:
        return "sync"
    if mode in ASYNC_ENGINES:
        return "async"
    if mode == "auto":
        return ("async" if buffer_size is not None
                or max_concurrency is not None else "sync")
    raise ValueError(f"unknown mode {mode!r}; expected 'auto', 'sync', "
                     f"'async', or one of {ENGINES}")


def resolve_engine(n: int, device_count: Optional[int] = None, *,
                   mode: str = "auto",
                   buffer_size: Optional[int] = None,
                   max_concurrency: Optional[int] = None,
                   cutover_n: Optional[int] = None) -> str:
    """Pick the round engine for a population of ``n`` clients.

    Two orthogonal decisions:

    - **family** (sync vs async) from ``mode`` and the async knobs, via
      :func:`resolve_aggregation` (``mode`` may also force one of the four
      engine names directly, which short-circuits everything);
    - **placement** (single-device scan vs `clients`-mesh shard_map):
      sharded iff ``device_count > 1`` and ``n >= cutover_n`` (default
      :data:`ENGINE_CUTOVER_N`, the measured ~256k boundary where the
      sharded step starts beating the single-device jit step —
      ``BENCH_selection.json``).

    Returns one of ``"scanned" | "sharded" | "async-scanned" |
    "async-sharded"``. All four produce index-identical trajectories in
    their overlap (see ``docs/architecture.md``), so the pick is purely a
    performance decision.
    """
    if mode in ENGINES:
        return mode
    family = resolve_aggregation(mode, buffer_size, max_concurrency)
    if device_count is None:
        device_count = jax.device_count()
    if cutover_n is None:
        cutover_n = ENGINE_CUTOVER_N
    sharded = device_count > 1 and n >= cutover_n
    if family == "async":
        return "async-sharded" if sharded else "async-scanned"
    return "sharded" if sharded else "scanned"


@spans.span("run_rounds")
def run_rounds(key, sel_cfg: SelectorConfig, pop: ClientPopulation,
               sel_state: SelectorState, energy_model: EnergyModel,
               model_bytes: float, local_steps: int, batch_size: int,
               rounds: int, *,
               mode: str = "auto",
               deadline_s: Optional[float] = None,
               up_bytes: Optional[float] = None,
               use_pallas: Optional[bool] = None,
               interpret: Optional[bool] = None,
               buffer_size: Optional[int] = None,
               max_concurrency: Optional[int] = None,
               staleness_power: float = 0.5,
               mesh=None, n_shards: Optional[int] = None,
               cutover_n: Optional[int] = None,
               faults: Optional[FaultConfig] = None,
               checkpoint_every: Optional[int] = None,
               checkpoint_path: Optional[str] = None,
               resume_from: Optional[str] = None,
               ) -> Tuple[ClientPopulation, SelectorState, Dict]:
    """Unified front door over the four round engines.

    Dispatches among :func:`run_rounds_scanned`, :func:`run_rounds_sharded`,
    :func:`run_async_scanned` and :func:`run_async_sharded` via
    :func:`resolve_engine`: ``mode`` picks the family (``"auto"`` infers
    async from ``buffer_size``/``max_concurrency``; ``"sync"``/``"async"``
    force a family; one of the four engine names forces that engine), and
    population size vs ``cutover_n`` on a multi-device host picks
    single-device vs sharded. Passing ``mesh``/``n_shards`` explicitly
    upgrades an auto-resolved single-device engine to its sharded twin on
    that mesh.

    All engines in a family return the same trajectory layout, and the
    sync/async families coincide in the ``buffer_size == max_concurrency
    == k, staleness_power=0`` limit, so every dispatch decision is
    behavior-preserving on the same key (the parity contracts of the
    underlying engines). The chosen engine name is recorded in the
    returned trajectory as ``traj["engine"]``, and the span ``run_rounds``
    counts the trajectory's ``topk.picks`` and ``topk.pick_slots`` (the
    async engines' initial fill is not a trajectory row and not counted)
    without waiting for the device.

    Elasticity + faults pass through to every engine: ``faults`` injects
    deterministic seed-driven transient client faults (sync engines only),
    ``checkpoint_path``/``checkpoint_every`` snapshot the engine carry
    atomically, and ``resume_from`` restores a snapshot mid-trajectory
    with restart parity. Checkpoints carry a family tag (``"sync"`` /
    ``"async"``), not an engine name — the trimmed-population format is
    engine- and device-count-portable within a family.
    """
    if mesh is not None:
        device_count = mesh.shape[mesh.axis_names[0]]
    elif n_shards is not None:
        device_count = n_shards
    else:
        device_count = jax.device_count()
    engine = resolve_engine(pop.n, device_count, mode=mode,
                            buffer_size=buffer_size,
                            max_concurrency=max_concurrency,
                            cutover_n=cutover_n)
    if mesh is not None or n_shards is not None:
        if mode in ("scanned", "async-scanned"):
            # a forced engine name always wins — don't silently override
            # it with the mesh, and don't silently ignore the mesh either
            raise ValueError(
                f"mode={mode!r} forces a single-device engine but "
                f"mesh/n_shards was passed; drop one of the two")
        # a family-level mode with an explicit mesh: use the mesh
        engine = {"scanned": "sharded",
                  "async-scanned": "async-sharded"}.get(engine, engine)
    if engine in SYNC_ENGINES and (buffer_size is not None
                                   or max_concurrency is not None):
        raise ValueError(
            f"async knobs (buffer_size/max_concurrency) with the "
            f"synchronous {engine!r} engine; use mode='async' or drop "
            f"the knobs")

    common = dict(deadline_s=deadline_s, up_bytes=up_bytes,
                  use_pallas=use_pallas, interpret=interpret,
                  faults=faults, checkpoint_every=checkpoint_every,
                  checkpoint_path=checkpoint_path, resume_from=resume_from)
    async_kw = dict(buffer_size=buffer_size,
                    max_concurrency=max_concurrency,
                    staleness_power=staleness_power)
    args = (key, sel_cfg, pop, sel_state, energy_model, model_bytes,
            local_steps, batch_size, rounds)
    if engine == "scanned":
        fpop, st, traj = run_rounds_scanned(*args, **common)
    elif engine == "sharded":
        fpop, st, traj = run_rounds_sharded(*args, **common, mesh=mesh,
                                            n_shards=n_shards)
    elif engine == "async-scanned":
        fpop, st, traj = run_async_scanned(*args, **common, **async_kw)
    else:
        fpop, st, traj = run_async_sharded(*args, **common, **async_kw,
                                           mesh=mesh, n_shards=n_shards)
    spans.count("topk.picks", traj["topk_picks"])
    spans.count("topk.pick_slots", traj["topk_pick_slots"])
    traj["engine"] = engine
    return fpop, st, traj
